"""Pallas kernel oracle sweeps: shapes x dtypes x params vs ref.py."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st

from repro.kernels.chunker import boundary_bitmap_pallas
from repro.kernels.fphash import (fphash, fphash_many, fphash_many_host,
                                  fphash_many_kernel)
from repro.kernels.ops import use_pallas_chunker
from repro.kernels.ref import boundary_bitmap_ref, fphash_ref


@pytest.mark.parametrize("n", [1, 47, 48, 255, 4991, 4992, 4993, 39936,
                               100_001])
@pytest.mark.parametrize("wq", [(48, 12), (16, 8), (128, 10), (4, 4)])
def test_chunker_matches_ref(n, wq, rng):
    w, q = wq
    data = rng.integers(0, 256, n, dtype=np.uint8)
    got = boundary_bitmap_pallas(data, w, q)
    want = boundary_bitmap_ref(data, w, q)
    np.testing.assert_array_equal(got, want)


@given(st.binary(min_size=0, max_size=3000), st.sampled_from([8, 16, 48]))
@settings(max_examples=20, deadline=None)
def test_chunker_property(data, w):
    arr = np.frombuffer(data, dtype=np.uint8)
    got = boundary_bitmap_pallas(arr, w, 6)
    want = boundary_bitmap_ref(arr, w, 6)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [0, 1, 31, 4095, 4096, 4097, 12288, 65536])
def test_fphash_matches_ref(n, rng):
    """The batched Pallas kernel body (interpret mode), the singular and
    batched entry points and the numpy oracle agree bit for bit."""
    data = rng.bytes(n)
    want = fphash_ref(data)
    assert fphash_many_kernel([data], interpret=True) == [want]
    assert fphash(data) == fphash_many([data])[0] == want


def test_fphash_kernel_batch_spans_output_tiles(rng):
    """More than 128 chunks of mixed block counts in one call: digests
    pack 128 chunks per output tile, several buckets launch."""
    blobs = [rng.bytes(int(n)) for n in rng.integers(0, 3 * 4096, 300)]
    assert fphash_many_kernel(blobs, interpret=True) == \
        fphash_many_host(blobs)


def test_fphash_avalanche(rng):
    d = bytearray(rng.bytes(5000))
    h0 = fphash(bytes(d))
    d[2500] ^= 1
    h1 = fphash(bytes(d))
    assert h0 != h1
    diff = bin(int.from_bytes(h0, "little")
               ^ int.from_bytes(h1, "little")).count("1")
    assert 64 < diff < 192       # ~half the 256 bits flip


def test_engine_identical_trees_with_pallas(rng):
    """Flipping the storage engine to the Pallas chunker must not change
    any root cid (same boundaries bit-for-bit)."""
    from repro.core import ChunkParams, ChunkStore, POSTree
    data = rng.integers(0, 256, 150_000, dtype=np.uint8)
    s = ChunkStore()
    t_np = POSTree.build_bytes(s, data, ChunkParams())
    use_pallas_chunker(True)
    try:
        t_pl = POSTree.build_bytes(s, data, ChunkParams())
    finally:
        use_pallas_chunker(False)
    assert t_np.root_cid == t_pl.root_cid
