"""The plain reference agrees with the program's host path (the rolling
hash, the numpy fphash sponge, a map's POS-Tree root, its meta chunk and
a membership proof), at sizes that cross every boundary rule: forced
splits, oversized elements, multi-level indexes."""
import numpy as np
import pytest

from fbbench import reference as ref


@pytest.fixture
def host_fphash():
    from repro.core import hashing
    from repro.kernels.fphash import fphash_many_host
    from repro.kernels.ref import fphash_ref
    hashing.set_default_hash(fphash_ref, fphash_many_host)
    yield
    hashing.use_sha256()


@pytest.mark.parametrize("n", [0, 1, 47, 48, 100, 5000, 70_000, 300_000])
def test_pattern_bitmap_is_the_rolling_hash(n):
    from repro.core import rolling
    data = np.random.default_rng(n).integers(0, 256, n, dtype=np.uint8)
    # small blocks, so the running XOR is carried across many of them
    got = ref.pattern_bitmap(data, block=4096)
    assert np.array_equal(got, rolling.boundary_bitmap(data, 48, 12))


def test_fphash_is_the_kernels_oracle():
    from repro.kernels.ref import fphash_ref
    rng = np.random.default_rng(1)
    blobs = [rng.bytes(n) for n in (0, 1, 4095, 4096, 4097, 9000, 32768,
                                    40000)]
    assert ref.fphash_many(blobs) == [fphash_ref(b) for b in blobs]


def test_map_root_and_proof_match_the_engine(host_fphash):
    from repro.core import FMap, ForkBase
    from repro.storage import MemoryBackend
    rng = np.random.default_rng(3)
    items = {rng.bytes(20): rng.bytes(100) for _ in range(20_000)}
    items[b"\0" * 20] = bytes(40_000)          # larger than a forced split
    db = ForkBase(MemoryBackend())
    db.put(b"state", FMap(items))
    root = db.get(b"state").obj.data
    tree = ref.map_tree(sorted(items.items()))
    assert tree.root == root
    assert ref.decode_meta(db.store.get(db.get(b"state").uid))["data"] == root
    for key in sorted(items)[::4999]:
        p = db.prove_member(b"state", item_key=key)
        assert ref.check_map_proof(root, list(p.nodes), p.leaf,
                                   key) == items[key]
        forged = p.leaf[:-1] + bytes([p.leaf[-1] ^ 1])
        assert ref.check_map_proof(root, list(p.nodes), forged, key) is None


def test_folded_versions_chain_by_their_meta_chunks(host_fphash):
    """What the ledger check reads of each block: the meta chunk hashes
    to the version's uid and names its kind, key, root, depth, parent
    and context."""
    from repro.core import ForkBase
    from repro.live import EpochPolicy
    from repro.storage import MemoryBackend
    rng = np.random.default_rng(4)
    db = ForkBase(MemoryBackend())
    table = db.live(b"state", policy=EpochPolicy(max_dirty_keys=None,
                                                 max_dirty_bytes=None))
    model = {}
    uids: list[bytes] = []
    for depth in range(3):
        for _ in range(500 if depth == 0 else 20):
            k, v = rng.bytes(20), rng.bytes(100)
            table.put(k, v)
            model[k] = v
        context = b"genesis" if depth == 0 else b"block %d" % depth
        db.commit_epoch(context=context)
        head = db.get(b"state")
        raw = db.store.get(head.uid)
        meta = ref.decode_meta(raw)
        assert ref.fphash(raw) == head.uid
        assert (meta["kind"], meta["key"], meta["depth"], meta["context"]) \
            == (ref.MAP, b"state", depth, context)
        assert meta["bases"] == tuple(uids[-1:])
        assert meta["data"] == ref.map_tree(sorted(model.items())).root
        uids.append(head.uid)
