"""Pallas TPU kernel: rolling-hash boundary bitmap for content-defined
chunking (the paper's POS-Tree hot-spot — §4.3.3 reports the rolling hash
as 20% of tree-build cost; Table 4 shows it dominating Put latency).

TPU adaptation (DESIGN.md §3): the byte-serial CDC scan is re-derived as a
data-parallel computation.  With G_m = rotr(h(b_m), m mod 32),

    P_i = XOR_{j=0..k-1} rotl(h(b_{i-j}), j) = rotl(S_i ^ S_{i-k}, i mod 32)

where S is the running prefix-XOR of G.  Per block the prefix-XOR is a
log2-depth doubling scan along the lane axis — 13 vector ops instead of a
48-deep serial window — and h() is the murmur32 finalizer evaluated
arithmetically (no table gather, which the TPU VPU hates).

Layout: the wrapper reshapes the stream into overlapping rows of
ROW_LEN = HALO + ROW_STRIDE bytes (HALO covers the window so each row is
self-contained; both constants are multiples of 32 so ``pos mod 32`` is a
pure function of the lane index).  The kernel processes SUBLANES=8 rows per
grid step as a (8, ROW_LEN) u32 tile in VMEM — one boundary flag per
payload byte.

Validated bit for bit against ref.boundary_bitmap_ref: in interpret mode
by tests/test_kernels.py, compiled on a TPU by chip_smoke.py.
tests/test_tpu_compile.py compiles it for a v5e chip at 8 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import obs
from . import interpret

ROW_STRIDE = 4992          # payload bytes per row (multiple of 32 and 128)
HALO = 128                 # front halo >= window (multiple of 32)
ROW_LEN = HALO + ROW_STRIDE
SUBLANES = 8               # rows per grid step

_GOLD = 0x9E3779B9
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35


def _mix32(x):
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(_M2)
    return x ^ (x >> jnp.uint32(16))


def _h_byte(b, seed: int):
    """h(byte) == rolling.byte_table(seed)[byte], computed arithmetically."""
    return _mix32(b + jnp.uint32((seed * 0x9E3779B9) & 0xFFFFFFFF))


def _rotl_v(x, r):
    """rotl by per-element amounts r in [0, 32)."""
    return (x << r) | (x >> ((jnp.uint32(32) - r) & jnp.uint32(31)))


def _rotr_v(x, r):
    return (x >> r) | (x << ((jnp.uint32(32) - r) & jnp.uint32(31)))


def _chunker_kernel(x_ref, out_ref, *, window: int, q: int, seed: int):
    x = x_ref[...].astype(jnp.uint32)          # (SUBLANES, ROW_LEN) bytes
    lane = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    g = _rotr_v(_h_byte(x, seed), lane & jnp.uint32(31))
    # prefix-XOR along lanes: log2 doubling scan
    s = g
    shift = 1
    while shift < ROW_LEN:
        shifted = jnp.pad(s, ((0, 0), (shift, 0)))[:, :ROW_LEN]
        s = s ^ shifted
        shift *= 2
    # windowed XOR: W_i = S_i ^ S_{i-window}
    s_k = jnp.pad(s, ((0, 0), (window, 0)))[:, :ROW_LEN]
    w = s ^ s_k
    p = _rotl_v(w, lane & jnp.uint32(31))
    hit = (p & jnp.uint32((1 << q) - 1)) == 0
    out_ref[...] = hit[:, HALO:].astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("window", "q", "seed",
                                             "interpret"))
def _run(rows, *, window: int, q: int, seed: int, interpret: bool):
    nrows = rows.shape[0]
    grid = nrows // SUBLANES
    return pl.pallas_call(
        functools.partial(_chunker_kernel, window=window, q=q, seed=seed),
        grid=(grid,),
        in_specs=[pl.BlockSpec((SUBLANES, ROW_LEN), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, ROW_STRIDE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nrows, ROW_STRIDE), jnp.uint8),
        interpret=interpret,
    )(rows)


def boundary_bitmap_pallas(data: np.ndarray, window: int, q: int,
                           seed: int = 0xF0B) -> np.ndarray:
    """Drop-in replacement for rolling.boundary_bitmap."""
    assert window <= HALO, f"window {window} exceeds kernel halo {HALO}"
    data = np.asarray(data, dtype=np.uint8)
    n = data.shape[0]
    if n == 0:
        return np.zeros(0, dtype=bool)
    with obs.trace("kernel.chunker"):
        nrows = max(1, -(-n // ROW_STRIDE))
        nrows = -(-nrows // SUBLANES) * SUBLANES   # pad rows to sublane multiple
        padded = np.zeros(nrows * ROW_STRIDE + HALO, dtype=np.uint8)
        padded[HALO:HALO + n] = data
        # overlapping rows: row r covers padded[r*STRIDE : r*STRIDE + ROW_LEN)
        rows = np.lib.stride_tricks.sliding_window_view(
            padded, ROW_LEN)[::ROW_STRIDE]
        out = np.asarray(_run(rows, window=window, q=q, seed=seed,
                              interpret=interpret()))
    obs.inc("kernel_launches", labels={"kernel": "chunker"})
    obs.inc("kernel_bytes", n, labels={"kernel": "chunker"})
    bitmap = out.reshape(-1)[:n].astype(bool)
    bitmap[:window - 1] = False               # no full window yet
    return bitmap
