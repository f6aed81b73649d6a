"""POS-Tree property tests: the load-bearing invariant is
equal content <=> identical root cid, independent of edit history."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core import chunk as ck
from repro.core import postree
from repro.core.chunker import ChunkParams
from repro.core.chunkstore import ChunkStore
from repro.core.postree import POSTree
from repro.errors import TamperedChunk

P8 = ChunkParams(q=8)
P7 = ChunkParams(q=7)   # ~128 B leaves: hundreds of leaves at test sizes
REBUILDS = "postree_leaf_index_rebuilds_total"


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

def assert_leaf_index(tree):
    """The leaf cumulative counts and max keys that splices keep current
    equal a fresh recomputation.  The counts rise strictly (every leaf of a
    non-empty tree holds an item): the resync's binary search needs it."""
    leaves = tree.levels[0]
    assert tree._cum is not None or tree.total_count == 0
    if tree._cum is not None:
        assert np.array_equal(tree._cum, np.cumsum([e.count for e in leaves]))
        assert np.all(np.diff(tree._cum) > 0)
    if tree._keycache is not None:
        assert tree._keycache == [e.key for e in leaves]


@pytest.mark.parametrize("pad", [0, 32_000], ids=["drawn", "multi_leaf"])
@given(st.binary(min_size=1, max_size=8000),
       st.lists(st.tuples(st.integers(0, 7999), st.integers(0, 200),
                          st.binary(max_size=100)), min_size=1, max_size=4))
@settings(max_examples=25, deadline=None)
def test_blob_splice_equals_rebuild(pad, data, edits):
    """``multi_leaf`` wraps the drawn bytes in seeded random ones, so the
    blob spans hundreds of leaves and the edits land all over it."""
    if pad:
        fill = np.random.default_rng(len(data)).bytes(pad)
        data = fill + data + fill
    s = ChunkStore()
    tree = POSTree.build_bytes(s, data, P8)
    cur = data
    for start, dlen, rep in edits:
        start = min(start * (1 + pad // 4000), len(cur))
        end = min(start + dlen, len(cur))
        tree.splice_bytes([(start, end, rep)])
        cur = cur[:start] + rep + cur[end:]
        ref = POSTree.build_bytes(s, cur, P8)
        assert tree.root_cid == ref.root_cid
        assert tree.read_bytes(0, tree.total_count) == cur
        assert_leaf_index(tree)


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


# ------------------------------ leaf bookkeeping kept across many clusters

def _element(kind, item):
    """(serialized element, sort key) of one model item."""
    if kind == ck.MAP:
        return ck.pack_kv(*item), item[0]
    return ck.pack_lv(item), (item if kind == ck.SET else None)


def _build(store, kind, items):
    els, keys = zip(*(_element(kind, it) for it in items))
    return POSTree.build_elements(store, kind, list(els), list(keys), P7)


class _Items:
    """Model items of one kind.  Keys open with a distinct numeric prefix,
    so a key extended by any suffix still sorts before the next one."""

    def __init__(self, kind, rng):
        self.kind, self.rng, self.fresh = kind, rng, 0

    def value(self):
        return self.rng.bytes(int(self.rng.integers(8, 40)))

    def make(self, key):
        if self.kind == ck.MAP:
            return key, self.value()
        return key if self.kind == ck.SET else self.value()

    def initial(self, n):
        return [self.make(b"k%07d" % (4 * i)
                          + self.rng.bytes(int(self.rng.integers(4, 24))))
                for i in range(n)]

    def changed(self, item):
        """The item with a new value (a new key for a set)."""
        if self.kind == ck.MAP:
            return item[0], self.value()
        return item + b"~" if self.kind == ck.SET else self.value()

    def before(self, items, i):
        """A new item that sorts between items[i - 1] and items[i]."""
        self.fresh += 1
        if i == 0:
            return self.make(b"a%07d" % self.fresh)
        if i == len(items):
            return self.make(b"z%07d" % self.fresh)
        prev = items[i - 1]
        return self.make((prev[0] if self.kind == ck.MAP else prev) + b"+")


def _splice(tree, kind, items, ops):
    """Apply model ops [(start, end, new items)] (sorted, non-overlapping)
    to ``tree`` in ONE splice_elements call, and to ``items``."""
    edits = []
    for s, e, new in ops:
        els = [_element(kind, it) for it in new]
        edits.append((s, e, [el for el, _ in els],
                      [k for _, k in els] if kind != ck.LIST else None))
    tree.splice_elements(edits)
    for s, e, new in reversed(ops):
        items[s:e] = new


def _grow_ops(tree, gen, items):
    """Update the last item of leaves followed by a leaf shorter than the
    rolling window: no old boundary after such an edit lies a window past
    it within the first re-chunked span, so the resync must widen."""
    cum, n_leaves = tree._cum, len(tree.levels[0])
    ops = []
    for j in range(1, n_leaves - 4):
        if ops and j - ops[-1][2] < 12:
            continue                    # one cluster per edit
        if len(tree._leaf_payload(j + 1)) < P7.window:
            at = int(cum[j]) - 1
            ops.append((at, at + 1, j))
    return [(s, e, [gen.changed(items[s])]) for s, e, _ in ops]


@pytest.mark.parametrize("kind", [ck.MAP, ck.SET, ck.LIST],
                         ids=["map", "set", "list"])
def test_scattered_splices_keep_leaf_index(kind, monkeypatch):
    """Batches of scattered edits, each in one splice_elements call over
    up to ~50 locality clusters: the root equals a fresh build, the kept
    leaf counts and keys equal fresh ones, and the leaf index is rebuilt
    once, in the first call, whatever the number of clusters."""
    gen = _Items(kind, np.random.default_rng(15))
    items = gen.initial(1500)
    s = ChunkStore()
    tree = _build(s, kind, items)
    assert len(tree.levels[0]) > 200
    tree._leaf_keys()                   # kept current from here on too
    sorted_kind = kind != ck.LIST
    batches = [
        ("updates", lambda: [(i, i + 1, [gen.changed(items[i])])
                             for i in range(3, len(items), 29)]),
        ("inserts", lambda: [(i, i, [gen.before(items, i)])
                             for i in range(7, len(items), 31)]),
        ("deletes", lambda: [(i, i + (3 if k % 3 == 0 else 1), [])
                             for k, i in enumerate(range(11, len(items) - 3,
                                                         37))]),
        # the first and the last leaf, and past both ends
        ("ends", lambda: [
            (0, 1, [gen.before(items, 0) if sorted_kind else gen.value(),
                    gen.changed(items[0])]),
            (len(items) // 2, len(items) // 2 + 1,
             [gen.changed(items[len(items) // 2])]),
            (len(items) - 1, len(items),
             [gen.changed(items[-1]),
              gen.before(items, len(items)) if sorted_kind else gen.value()])]),
        ("grow", lambda: _grow_ops(tree, gen, items)),
    ]
    bitmaps = [0]
    bitmap = postree.boundary_bitmap

    def counted(*a, **kw):
        bitmaps[0] += 1
        return bitmap(*a, **kw)

    monkeypatch.setattr(postree, "boundary_bitmap", counted)
    was_on = obs.enabled()
    obs.enable()
    try:
        for n, (name, make_ops) in enumerate(batches):
            ops = make_ops()
            before = obs.counter(REBUILDS).value
            bitmaps[0] = 0
            _splice(tree, kind, items, ops)
            assert obs.counter(REBUILDS).value - before == (n == 0), name
            assert tree.root_cid == _build(s, kind, items).root_cid, name
            assert tree._cum is not None and tree._keycache is not None
            assert_leaf_index(tree)
            if name == "grow":
                assert len(ops) > 5 and bitmaps[0] > len(ops)  # it widened
    finally:
        if not was_on:
            obs.disable()


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
