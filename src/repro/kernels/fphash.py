"""Pallas TPU kernel: 256-bit content hash for the dedup path
(DESIGN.md §3: SHA-256's bit-level structure is hostile to the TPU VPU;
the paper explicitly allows alternative hash functions for cids).

Sponge over u32 words: the state is one native (8, 128) u32 vreg tile;
each 4 KB block is absorbed by XOR and diffused with FP_ROUNDS rounds of
{multiply by odd constant, xor-rotate, lane-roll add, sublane-roll add} —
all elementwise or roll ops the VPU executes natively.  The length is
injected last, the lanes are folded and the 8 sublane words finalized.

One kernel hashes a whole batch of chunks (the storage engine commits a
value's chunks with one put_many, so a value is one launch per block-count
bucket).  Grid = (chunk, block); TPU grids iterate serially with the last
axis fastest, so the VMEM state accumulator is re-seeded at each chunk's
block 0, absorbs only that chunk's own blocks and finalizes at its last
real block.  Layout is tile-native throughout: words arrive as
(chunks, blocks*8, 128) u32 so each grid step reads one (8, 128) tile;
the chunk lengths ride in SMEM via scalar prefetch; digests leave packed
128 chunks per (8, 128) output tile (chunk c -> tile c // 128, lane
c % 128, one digest word per sublane).

Bit-for-bit identical to ref.fphash_ref (the numpy oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from . import interpret
from .ref import FP_BLOCK_WORDS, FP_ROUNDS, FP_STATE, fp_init_state

_GOLD = 0x9E3779B9
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35

_SUB, _LANES = FP_STATE
_BLOCK_BYTES = FP_BLOCK_WORDS * 4
# rows per launch: bounds the SMEM length table and the set of compiled
# batch shapes (powers of two up to this)
_MAX_ROWS = 4096


def _mix32(x):
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(_M2)
    return x ^ (x >> jnp.uint32(16))


def _rotr(x, r: int):
    r &= 31
    if r == 0:
        return x
    return (x >> jnp.uint32(r)) | (x << jnp.uint32(32 - r))


def _round(state):
    state = state * jnp.uint32(_GOLD)
    state = state ^ _rotr(state, 13)
    state = state + jnp.roll(state, 1, axis=1)
    state = state ^ _rotr(state, 7)
    state = state + jnp.roll(state, 1, axis=0)
    return state


def _nblocks(length):
    """Absorbed 4 KB blocks for a `length`-byte input (empty input: one)."""
    return jnp.maximum(1, (length + (_BLOCK_BYTES - 1)) // _BLOCK_BYTES)


def _fphash_kernel(len_ref, words_ref, init_ref, out_ref, state_ref):
    i = pl.program_id(0)
    b = pl.program_id(1)
    length = len_ref[i]
    nb = _nblocks(length)

    @pl.when(b == 0)
    def _init():
        state_ref[...] = init_ref[...]

    @pl.when(b < nb)
    def _absorb():
        state = state_ref[...] ^ words_ref[...]
        for _ in range(FP_ROUNDS):
            state = _round(state)
        state_ref[...] = state

    @pl.when(b == nb - 1)
    def _finalize():
        st = state_ref[...] ^ length.astype(jnp.uint32)
        st = _round(_round(st))
        shift = _LANES // 2
        while shift >= 1:   # xor-reduce the lanes: each lane ends with the total
            st = st ^ jnp.roll(st, shift, axis=1)
            shift //= 2
        sub = jax.lax.broadcasted_iota(jnp.uint32, FP_STATE, 0)
        digest = _mix32(st ^ (sub * jnp.uint32(_GOLD)))
        lane = jax.lax.broadcasted_iota(jnp.int32, FP_STATE, 1)
        out_ref[...] = jnp.where(lane == i % _LANES, digest, out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run(lengths, words, init, *, interpret: bool):
    """lengths: int32[n] (byte length mod 2**32); words: u32[n, blocks*8,
    128]; returns u32[ceil(n / 128), 8, 128] lane-packed digests."""
    nchunks, rows, _ = words.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nchunks, rows // _SUB),
        in_specs=[
            # steps past a chunk's last block re-map onto it: the
            # pipeline skips the copy, so padding is never read
            pl.BlockSpec((pl.Squeezed(), _SUB, _LANES),
                         lambda i, b, lens: (
                             i, jnp.minimum(b, _nblocks(lens[i]) - 1), 0)),
            pl.BlockSpec(FP_STATE, lambda i, b, lens: (0, 0)),
        ],
        out_specs=pl.BlockSpec((pl.Squeezed(), _SUB, _LANES),
                               lambda i, b, lens: (i // _LANES, 0, 0)),
        scratch_shapes=[pltpu.VMEM(FP_STATE, jnp.uint32)],
    )
    return pl.pallas_call(
        _fphash_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (pl.cdiv(nchunks, _LANES), _SUB, _LANES), jnp.uint32),
        interpret=interpret,
    )(lengths, words, init)


def _pow2(x: int) -> int:
    return 1 << max(0, x - 1).bit_length()


def _count_blocks(blobs: list[bytes]) -> list[int]:
    return [max(1, -(-len(b) // _BLOCK_BYTES)) for b in blobs]


def fphash_many_kernel(blobs, *, interpret: bool = False) -> list[bytes]:
    """The Pallas path of ``fphash_many``.  Rows are bucketed by pow2
    block count so one outlier chunk cannot force every row to its width
    (memory stays O(input bytes), not O(n x max)); a bucket launches in
    slices of at most _MAX_ROWS rows, padded to a power of two, bounding
    jit retraces to O(log^2) shape buckets.  The kernel stops at each
    chunk's own last block, so padding never enters a digest.
    ``interpret=True`` runs the same kernel body in the Pallas
    interpreter (how the CPU tests check it)."""
    blobs = [bytes(b) for b in blobs]
    buckets: dict[int, list[int]] = {}
    for i, nb in enumerate(_count_blocks(blobs)):
        buckets.setdefault(_pow2(nb), []).append(i)
    init = jnp.asarray(fp_init_state(), dtype=jnp.uint32)
    out: list[bytes | None] = [None] * len(blobs)
    for maxnb, idx in buckets.items():
        for s in range(0, len(idx), _MAX_ROWS):
            part = idx[s:s + _MAX_ROWS]
            with obs.trace("kernel.fphash"):
                n_pad = _pow2(len(part))
                buf = np.zeros((n_pad, maxnb * _BLOCK_BYTES), dtype=np.uint8)
                lens = np.zeros(n_pad, dtype=np.uint32)   # pad rows: empty
                for r, i in enumerate(part):
                    buf[r, :len(blobs[i])] = np.frombuffer(blobs[i],
                                                           dtype=np.uint8)
                    lens[r] = len(blobs[i]) & 0xFFFFFFFF
                words = buf.view("<u4").reshape(n_pad, maxnb * _SUB, _LANES)
                res = np.asarray(_run(lens.view(np.int32), words, init,
                                      interpret=interpret))
                obs.inc("kernel_launches", labels={"kernel": "fphash"})
                obs.inc("kernel_bytes", int(lens.sum()),
                        labels={"kernel": "fphash"})
                # tile g, sublane w, lane l -> word w of chunk g*128 + l
                digests = res.transpose(0, 2, 1).reshape(-1, _SUB)
                digests = digests[:len(part)].astype("<u4")
                for r, i in enumerate(part):
                    out[i] = digests[r].tobytes()
    return out  # type: ignore[return-value]


# ----------------------------------------------------------- host sponge
#
# Off-TPU, pl.pallas_call(interpret=True) is a correctness oracle, not a
# perf path (~100x slower than hashlib).  The batched entry point instead
# runs the same sponge as a *vectorized numpy* computation — one array op
# sweep per block index across every chunk of the bucket — bit-for-bit
# identical to the kernel (asserted by the conformance tests), so cids are
# stable across hosts and TPUs.

_GOLD_NP = np.uint32(_GOLD)


def _host_rotr(x: np.ndarray, r: int) -> np.ndarray:
    r &= 31
    if r == 0:
        return x
    return (x >> np.uint32(r)) | (x << np.uint32(32 - r))


def _host_round(state: np.ndarray) -> np.ndarray:
    state = state * _GOLD_NP
    state = state ^ _host_rotr(state, 13)
    state = state + np.roll(state, 1, axis=-1)
    state = state ^ _host_rotr(state, 7)
    state = state + np.roll(state, 1, axis=-2)
    return state


def _host_mix32(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(_M1)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(_M2)
    return x ^ (x >> np.uint32(16))


def fphash_many_host(blobs) -> list[bytes]:
    """The vectorized numpy sponge: ``fphash_many``'s CPU path, and the
    host reference the chip smoke holds the kernel to."""
    blobs = [bytes(b) for b in blobs]
    out: list[bytes | None] = [None] * len(blobs)
    buckets: dict[int, list[int]] = {}
    for i, nb in enumerate(_count_blocks(blobs)):
        buckets.setdefault(nb, []).append(i)
    init = np.asarray(fp_init_state(), dtype=np.uint32)
    with np.errstate(over="ignore"):
        for nb, idx in buckets.items():
            m = len(idx)
            buf = np.zeros((m, nb * _BLOCK_BYTES), dtype=np.uint8)
            for r, i in enumerate(idx):
                buf[r, :len(blobs[i])] = np.frombuffer(blobs[i],
                                                       dtype=np.uint8)
            words = buf.view("<u4").astype(np.uint32).reshape(
                (m, nb) + FP_STATE)
            state = np.broadcast_to(init, (m,) + FP_STATE)
            for b in range(nb):
                state = state ^ words[:, b]
                for _ in range(FP_ROUNDS):
                    state = _host_round(state)
            lens = np.asarray([len(blobs[i]) & 0xFFFFFFFF for i in idx],
                              dtype=np.uint32)
            state = state ^ lens[:, None, None]
            state = _host_round(_host_round(state))
            folded = np.bitwise_xor.reduce(state, axis=-1)
            folded = _host_mix32(
                folded ^ (np.arange(8, dtype=np.uint32)[None, :] * _GOLD_NP))
            res = folded.astype("<u4")
            for r, i in enumerate(idx):
                out[i] = res[r].tobytes()
    return out  # type: ignore[return-value]


def fphash_many(blobs) -> list[bytes]:
    """Vectorized cid path behind ``core.hashing.content_hash_many``:
    hash a batch of byte strings with one kernel launch per block-count
    bucket of up to 4,096 chunks (a value of up to ~16 MB of 4 KB chunks
    is one launch per bucket).  On a TPU this is always the compiled
    kernel; without one, the vectorized numpy sponge — digests are
    identical either way."""
    blobs = list(blobs)
    if not blobs:
        return []
    if interpret():
        return fphash_many_host(blobs)
    return fphash_many_kernel(blobs)


def fphash(data: bytes) -> bytes:
    """256-bit content hash of `data` (the Pallas dedup-path cid)."""
    return fphash_many([data])[0]
