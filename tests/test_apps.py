"""Application-level tests: blockchain, wiki, analytics vs baselines."""

from repro.apps import (ColumnTable, ForkBaseLedger, ForkBaseWiki,
                        KVLedger, OrpheusLite, RedisWiki, RowTable)
from repro.core import ChunkParams, ForkBase

P8 = ChunkParams(q=8)


def test_blockchain_equivalence(rng):
    fb, kv = ForkBaseLedger(ForkBase(params=P8)), KVLedger("bucket", 64)
    for blk in range(5):
        for i in range(8):
            k, v = f"k{(blk * 8 + i) % 12}", f"v{blk}.{i}".encode()
            fb.write("c", k, v)
            kv.write("c", k, v)
        fb.commit()
        kv.commit()
    idx = kv.build_scan_index()
    for key in ["k0", "k5", "k11"]:
        h_fb = [v for _, v in fb.state_scan("c", key)]
        h_kv = kv.state_scan("c", key, idx)
        assert h_fb == h_kv, key
    s_fb, s_kv = fb.block_scan(2), kv.block_scan(2)
    for (c, k), v in s_fb.items():
        assert s_kv[f"{c}/{k}".encode()] == v
    assert fb.verify_block(0) and fb.verify_block(4)


def test_blockchain_tamper_detection(rng):
    fb = ForkBaseLedger(ForkBase(params=P8))
    fb.write("c", "k", b"v1")
    u1 = fb.commit()
    fb.write("c", "k", b"v2")
    u2 = fb.commit()
    # a block not on the chain cannot be proven part of it
    other = ForkBaseLedger(ForkBase(params=P8))
    other.write("c", "k", b"evil")
    u_evil = other.commit()
    assert not fb.db.verify_lineage(u2, u_evil)


def test_wiki_vs_redis(rng):
    w, r = ForkBaseWiki(ForkBase(params=P8)), RedisWiki()
    text = rng.bytes(15000)
    w.create("p", text)
    r.create("p", text)
    cur = text
    for _ in range(10):
        pos = int(rng.integers(0, len(cur) - 100))
        ins = rng.bytes(64)
        cur = cur[:pos] + ins + cur[pos:]
        w.edit("p", lambda b, q=pos, s=ins: b.insert(q, s))
        r.edit("p", cur)
    assert w.load("p") == r.load("p") == cur
    for back in range(3):
        v, _, _ = w.read_version("p", back, None)
        assert v == r.read_version("p", back)
    assert w.storage_bytes() < 0.5 * sum(
        len(v) for vs in [[text] * 11] for v in vs), "dedup should win"


def test_wiki_chunk_cache(rng):
    w = ForkBaseWiki(ForkBase(params=P8))
    text = rng.bytes(20000)
    w.create("p", text)
    w.edit("p", lambda b: b.insert(100, b"xyz"))
    cache: set = set()
    _, f0, c0 = w.read_version("p", 0, cache)
    _, f1, c1 = w.read_version("p", 1, cache)
    # consecutive version mostly cache-hits (only the edited chunk differs)
    assert c1 >= 0.6 * (f1 + c1), (f1, c1)
    assert f1 <= 2


def test_analytics_row_col_orpheus(rng):
    db = ForkBase(params=P8)
    n = 1500
    recs = [[f"pk{i:06d}".encode(), str(i % 97).encode(),
             str(i % 13).encode(), rng.bytes(30)] for i in range(n)]
    rt = RowTable(db, "ds")
    u0 = rt.load({r[0]: r for r in recs})
    ol = OrpheusLite()
    v0 = ol.load(recs)
    ct = ColumnTable(db, "dsc", ["pk", "a", "b", "pay"])
    ct.load(recs)
    want = sum(i % 97 for i in range(n))
    assert rt.aggregate(1) == ol.aggregate(v0, 1) == ct.aggregate("a") \
        == want
    ups = {recs[i][0]: [recs[i][0], b"0", b"0", b"u"]
           for i in range(0, n, 50)}
    u1 = rt.update(ups)
    v1 = ol.commit(v0, {i: ups[recs[i][0]] for i in range(0, n, 50)})
    a, r, c = rt.diff(u1, u0)
    assert len(c) == len(ol.diff(v0, v1)) == len(ups)
    # fork isolation
    rt.fork("branchA")
    rtA = RowTable(db, "ds", "branchA")
    rtA.update({recs[0][0]: [recs[0][0], b"777", b"0", b"x"]})
    assert rt.get(recs[0][0])[1] == b"0"
    assert rtA.get(recs[0][0])[1] == b"777"


def test_archival_commit_matches_per_key_commit(rng):
    """The archival ledger's batched commit gives the block uids of a
    commit written key by key: a get, an in-place replace and a put per
    state key, then the level-2 maps, the level-1 map and the block,
    every blob and map chunked under the engine's parameters."""
    import json

    from repro.core import FBlob, FMap

    def per_key_commit(db, height, writes, txs):
        l2 = {}
        for c in dict.fromkeys(c for c, _ in writes):
            for (c2, k), v in writes.items():
                if c2 != c:
                    continue
                h = db.get(f"{c}/{k}")
                if h is None:
                    db.put(f"{c}/{k}", FBlob(v, params=P8))
                else:
                    b = h.blob()
                    b.replace(0, len(b), v)
                    db.put(f"{c}/{k}", b)
            h2 = db.get(f"__l2__/{c}")
            m2 = h2.map() if h2 is not None else FMap(params=P8)
            for c2, k in writes:
                if c2 == c:
                    m2.set(k.encode(), db.get(f"{c}/{k}").uid)
            l2[c] = db.put(f"__l2__/{c}", m2)
        h1 = db.get("__l1__")
        m1 = h1.map() if h1 is not None else FMap(params=P8)
        for c, uid in l2.items():
            m1.set(c.encode(), uid)
        root = db.put("__l1__", m1)
        blk = FMap({b"state": root, b"txs": json.dumps(txs).encode()})
        return db.put("chain", blk,
                      context=json.dumps({"height": height}).encode())

    fb = ForkBaseLedger(ForkBase(params=P8))
    plain = ForkBase(params=P8)
    for height in range(6):
        writes, txs = {}, []
        for _ in range(12):
            c = ["bank", "token"][int(rng.integers(0, 2))]
            k = f"acct{int(rng.integers(0, 30))}"
            v = rng.bytes(int(rng.integers(0, 700)))
            fb.write(c, k, v)
            writes[c, k] = v
            txs.append((c, "put", k))
        assert fb.commit() == per_key_commit(plain, height, writes, txs)
    assert fb.db.branches.snapshot() == plain.branches.snapshot()
