"""ForkBase API semantics (Table 1, M1-M17) + fork/merge behaviour."""
import numpy as np
import pytest

from repro.core import (ChunkParams, Cluster, FBlob, FInt, FList, FMap,
                        FSet, FString, ForkBase, GuardFailed, MergeConflict,
                        aggregate_resolver, choose_one)

P8 = ChunkParams(q=8)


@pytest.fixture
def db():
    return ForkBase(params=P8)


def test_basic_kv_compliance(db):
    db.put("k", FString(b"v1"))
    assert db.get("k").string().value == b"v1"
    db.put("k", FString(b"v2"))
    assert db.get("k").string().value == b"v2"
    assert db.list_keys() == [b"k"]


def test_fig4_flow(db):
    db.put("my key", FBlob(b"my value" * 50))
    db.fork("my key", "master", "new branch")
    v = db.get("my key", "new branch")
    b = v.blob()
    b.remove(0, 10)
    b.append(b"some more")
    db.put("my key", b, "new branch")
    assert db.get("my key", "new branch").blob().read() == \
        (b"my value" * 50)[10:] + b"some more"
    assert db.get("my key", "master").blob().read() == b"my value" * 50


def test_track_and_lca(db):
    uids = [db.put("k", FInt(i)) for i in range(5)]
    hist = db.track("k", "master")
    assert [o.uid for o in hist] == uids[::-1]
    hist2 = db.track("k", "master", (1, 3))
    assert [o.uid for o in hist2] == uids[::-1][1:3]
    db.fork("k", uids[2], "side")
    u_side = db.put("k", FInt(99), "side")
    assert db.lca("k", uids[4], u_side) == uids[2]


def test_foc_untagged_branches(db):
    base = db.put("s", FMap({b"x": b"0"}))
    m1 = db.get("s", uid=base).map()
    m1.set(b"x", b"1")
    u1 = db.put("s", m1, base_uid=base)
    m2 = db.get("s", uid=base).map()
    m2.set(b"x", b"2")
    u2 = db.put("s", m2, base_uid=base)
    heads = db.list_untagged_branches("s")
    assert u1 in heads and u2 in heads and base not in heads
    with pytest.raises(MergeConflict):
        db.merge("s", u1, u2)
    merged = db.merge("s", u1, u2, resolver=choose_one(1))
    assert db.get("s", uid=merged).map().get(b"x") == b"2"
    assert set(db.list_untagged_branches("s")) >= {merged}


def test_merge_branches_m5(db):
    db.put("k", FMap({b"a": b"1", b"b": b"2"}))
    db.fork("k", "master", "dev")
    md = db.get("k", "dev").map()
    md.set(b"a", b"10")
    db.put("k", md, "dev")
    mm = db.get("k", "master").map()
    mm.set(b"b", b"20")
    db.put("k", mm, "master")
    db.merge("k", "master", "dev")
    final = db.get("k", "master").map()
    assert final.get(b"a") == b"10" and final.get(b"b") == b"20"


def test_guarded_put(db):
    db.put("g", FString(b"v1"))
    h = db.get("g").uid
    db.put("g", FString(b"v2"), guard_uid=h)
    with pytest.raises(GuardFailed):
        db.put("g", FString(b"v3"), guard_uid=h)


def test_branch_ops(db):
    db.put("k", FString(b"x"))
    db.fork("k", "master", "b1")
    db.rename("k", "b1", "b2")
    assert "b2" in db.list_tagged_branches("k")
    db.remove("k", "b2")
    assert "b2" not in db.list_tagged_branches("k")


def test_primitive_merges(db):
    base = db.put("n", FInt(10))
    c1 = db.get("n", uid=base).integer()
    c1.add(5)
    u1 = db.put("n", c1, base_uid=base)
    c2 = db.get("n", uid=base).integer()
    c2.add(7)
    u2 = db.put("n", c2, base_uid=base)
    m = db.merge("n", u1, u2, resolver=aggregate_resolver)
    assert db.get("n", uid=m).integer().value == 22


def test_list_and_set_types(db):
    l = FList([b"a", b"b", b"c"])
    db.put("l", l)
    ll = db.get("l").list()
    ll.insert(1, b"x")
    ll.delete(3)
    db.put("l", ll)
    assert list(db.get("l").list()) == [b"a", b"x", b"b"]
    s = FSet([b"p", b"q"])
    db.put("st", s)
    ss = db.get("st").set()
    ss.add(b"r")
    ss.remove(b"p")
    db.put("st", ss)
    assert set(db.get("st").set()) == {b"q", b"r"}


def test_verify_lineage(db):
    u1 = db.put("k", FString(b"a"))
    u2 = db.put("k", FString(b"b"))
    u3 = db.put("k", FString(b"c"))
    assert db.verify_lineage(u3, u1)
    assert not db.verify_lineage(u1, u3)


def test_cluster_balance(rng):
    counts = {}
    for mode in ("1LP", "2LP"):
        cl = Cluster(8, mode, P8)
        r = np.random.default_rng(1)
        for i in range(40):
            cl.put(f"hot{i % 2}", FBlob(r.bytes(16000)), branch=f"b{i}")
        dist = cl.storage_distribution()
        counts[mode] = (max(dist) + 1) / (min(dist) + 1)
    assert counts["2LP"] < counts["1LP"]


def test_cluster_api_roundtrip():
    cl = Cluster(4, "2LP", P8)
    cl.put("k", FBlob(b"hello world" * 100))
    assert cl.get("k").blob().read() == b"hello world" * 100
    cl.fork("k", "master", "dev")
    b = cl.get("k", "dev").blob()
    b.append(b"!")
    cl.put("k", b, "dev")
    assert cl.get("k", "dev").blob().read().endswith(b"!")
    assert len(cl.track("k", "dev")) == 2


# ------------------------------------------------------ batched value commit

def _blob_values(rng, n):
    """Mixed lengths: an empty value, one of many leaves, and short ones
    (P8: 256 B average leaf)."""
    return ([b"", rng.bytes(6000)]
            + [rng.bytes(int(m)) for m in rng.integers(1, 600, n - 2)])


def _stored(db):
    return {cid: db.store.get(cid) for cid in db.store.iter_cids()}


@pytest.mark.parametrize("edit", ["fresh", "replace"])
def test_put_batch_matches_sequential_puts(edit, rng):
    """New blobs and new versions of existing keys, a key put twice in
    one batch and a non-blob value: the same uids, heads and stored
    chunks as one ``put`` after another.  ``replace`` gives the second
    round's blobs as whole-value edits of the stored ones, which take
    ``put``'s own path inside the batch."""
    seq, bat = ForkBase(params=P8), ForkBase(params=P8)
    keys = [f"k{i}" for i in range(40)]
    first = _blob_values(rng, 40)
    assert [seq.put(k, FBlob(v, params=P8)) for k, v in zip(keys, first)] \
        == bat.put_batch([(k, FBlob(v, params=P8))
                          for k, v in zip(keys, first)])
    second = _blob_values(rng, 40)

    def requests(db):
        out = []
        for k, v in zip(keys, second):
            b = FBlob(v, params=P8)
            if edit == "replace" and db.get(k) is not None:
                b = db.get(k).blob()
                b.replace(0, len(b), v)
            out.append((k, b))
        return out + [(keys[3], FBlob(b"again", params=P8)),
                      (keys[3], b"a string"), (keys[1], FMap({b"a": b"1"}))]
    assert [seq.put(k, v) for k, v in requests(seq)] \
        == bat.put_batch(requests(bat))
    assert seq.branches.snapshot() == bat.branches.snapshot()
    assert _stored(seq) == _stored(bat)
    assert bat.get(keys[1]).map().get(b"a") == b"1"
    assert bat.get(keys[2]).blob().read() == second[2]


def test_put_batch_launches_do_not_grow_with_the_batch(monkeypatch, rng):
    """The chunker and ``content_hash_many`` are called as often for a
    batch of 10 new blobs as for one of 200.  Each batch holds the same
    empty value and the same value of many leaves; the others are
    shorter than the rolling window, one leaf each, so the index levels
    hashed (one batch a level) are the same in both."""
    from repro.core import chunker, hashing
    calls = {"chunker": 0, "hash": 0}
    bitmap, many = chunker._bitmap_impl, hashing.content_hash_many

    def counted_bitmap(*a, **k):
        calls["chunker"] += 1
        return bitmap(*a, **k)

    def counted_many(blobs):
        calls["hash"] += 1
        return many(blobs)
    monkeypatch.setattr(chunker, "_bitmap_impl", counted_bitmap)
    monkeypatch.setattr(hashing, "content_hash_many", counted_many)
    big = rng.bytes(6000)
    seen = []
    for n in (10, 200):
        db = ForkBase(params=P8)
        values = [b"", big] + [rng.bytes(int(m))
                               for m in rng.integers(1, 48, n - 2)]
        db.put_batch([(f"k{i}", FBlob(b"old", params=P8))
                      for i in range(n)])
        calls.update(chunker=0, hash=0)
        db.put_batch([(f"k{i}", FBlob(v, params=P8))
                      for i, v in enumerate(values)])
        seen.append(dict(calls))
    assert seen[0] == seen[1]
    assert seen[0]["chunker"] == 1
