"""POS-Tree property tests: the load-bearing invariant is
equal content <=> identical root cid, independent of edit history."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st

from repro.core import chunk as ck
from repro.core.chunker import ChunkParams
from repro.core.chunkstore import ChunkStore
from repro.core.postree import POSTree
from repro.errors import TamperedChunk

P8 = ChunkParams(q=8)


def build_map(store, items, params=P8):
    items = sorted(items.items())
    els = [ck.pack_kv(k, v) for k, v in items]
    return POSTree.build_elements(store, ck.MAP, els,
                                  [k for k, _ in items], params)


# ------------------------------------------------------------ determinism

@given(st.binary(min_size=0, max_size=20_000))
@settings(max_examples=20, deadline=None)
def test_blob_content_determinism(data):
    s = ChunkStore()
    t1 = POSTree.build_bytes(s, data, P8)
    t2 = POSTree.build_bytes(s, bytes(data), P8)
    assert t1.root_cid == t2.root_cid
    assert t1.read_bytes(0, len(data)) == data


@given(st.dictionaries(st.binary(min_size=1, max_size=12),
                       st.binary(max_size=40), max_size=200))
@settings(max_examples=20, deadline=None)
def test_map_content_determinism(items):
    s = ChunkStore()
    t1 = build_map(s, items)
    t2 = build_map(s, dict(reversed(list(items.items()))))
    assert t1.root_cid == t2.root_cid


# --------------------------------------- incremental commit == full rebuild

@given(st.binary(min_size=1, max_size=8000),
       st.lists(st.tuples(st.integers(0, 7999), st.integers(0, 200),
                          st.binary(max_size=100)), min_size=1, max_size=4))
@settings(max_examples=25, deadline=None)
def test_blob_splice_equals_rebuild(data, edits):
    s = ChunkStore()
    tree = POSTree.build_bytes(s, data, P8)
    cur = data
    for start, dlen, rep in edits:
        start = min(start, len(cur))
        end = min(start + dlen, len(cur))
        tree.splice_bytes([(start, end, rep)])
        cur = cur[:start] + rep + cur[end:]
        ref = POSTree.build_bytes(s, cur, P8)
        assert tree.root_cid == ref.root_cid
        assert tree.read_bytes(0, tree.total_count) == cur


@given(st.dictionaries(st.binary(min_size=1, max_size=10),
                       st.binary(max_size=30), min_size=1, max_size=150),
       st.lists(st.tuples(st.binary(min_size=1, max_size=10),
                          st.one_of(st.none(), st.binary(max_size=30))),
                min_size=1, max_size=6))
@settings(max_examples=25, deadline=None)
def test_map_edits_equal_rebuild(items, ops):
    """Random set/delete sequences: incremental tree == fresh build,
    regardless of operation order (order-independence of the final state).
    Exercises FMap overlay batching + splice_elements."""
    from repro.core.types import FMap
    s = ChunkStore()
    m = FMap(items, params=P8)
    m.commit(s)
    state = dict(items)
    for k, v in ops:
        if v is None:
            m.delete(k)
            state.pop(k, None)
        else:
            m.set(k, v)
            state[k] = v
    m.commit(s)
    ref = build_map(s, state)
    assert m.tree.root_cid == ref.root_cid


# ----------------------------------------------------------------- dedup

def test_dedup_across_versions(rng):
    s = ChunkStore()
    data = rng.integers(0, 256, 200_000, dtype=np.uint8)
    t1 = POSTree.build_bytes(s, data, P8)
    phys0 = s.stats.physical_bytes
    d2 = data.copy()
    d2[1000:1010] = 0
    t2 = POSTree.build_bytes(s, d2, P8)
    added = s.stats.physical_bytes - phys0
    assert added < 0.05 * phys0, f"dedup failed: {added}/{phys0}"
    shared = t1.node_cids() & t2.node_cids()
    assert len(shared) > 0.8 * len(t1.node_cids())


def test_cross_object_dedup(rng):
    """The paper's point vs Decibel: dedup works ACROSS objects."""
    s = ChunkStore()
    base = rng.integers(0, 256, 100_000, dtype=np.uint8)
    POSTree.build_bytes(s, base, P8)
    phys0 = s.stats.physical_bytes
    other = np.concatenate([rng.integers(0, 256, 512, dtype=np.uint8), base])
    POSTree.build_bytes(s, other, P8)   # a *different* object, shared tail
    added = s.stats.physical_bytes - phys0
    assert added < 0.1 * phys0


# ------------------------------------------------------------------ diff

def test_diff_keys_precision(rng):
    s = ChunkStore()
    items = {f"k{i:05d}".encode(): rng.bytes(20) for i in range(3000)}
    t1 = build_map(s, items)
    items2 = dict(items)
    items2[b"k00777"] = b"CHANGED"
    items2[b"knew"] = b"ADDED"
    del items2[b"k01234"]
    t2 = build_map(s, items2)
    a, r, c = t2.diff_keys(t1)
    assert a == [b"knew"] and r == [b"k01234"] and c == [b"k00777"]


def test_lookup_paths(rng):
    s = ChunkStore()
    items = {f"k{i:05d}".encode(): rng.bytes(16) for i in range(2000)}
    t = build_map(s, items)
    assert t.descend_key(b"k00500") == items[b"k00500"]
    found, j, li, gi = t.find_key(b"k01999")
    assert found and t.get_item(gi) == (b"k01999", items[b"k01999"])
    t2 = POSTree.from_root(s, ck.MAP, t.root_cid, P8)
    assert t2.root_cid == t.root_cid
    assert t2.descend_key(b"k00001") == items[b"k00001"]


def test_tamper_evidence(rng):
    s = ChunkStore(verify=True)
    data = rng.integers(0, 256, 50_000, dtype=np.uint8)
    t = POSTree.build_bytes(s, data, P8)
    cid = t.levels[0][3].cid
    s._data[cid] = b"\x03tampered!"          # corrupt a stored chunk
    with pytest.raises(TamperedChunk):
        s.get(cid)
