"""The SmallBank deployment's plain reference, with numpy and the standard
library only (and ``fbbench.reference`` for the sponge, the meta chunk
and a map's POS-Tree).

SmallBank (Alomari et al., ICDE 2008) runs on a plain dict of integer
balances (``Bank``).  ``transact`` is the contract: the driver runs it on
the ledger's reads and writes, the reference on the dict.  From the
replayed writes the reference rebuilds what ForkBase's Hyperledger
design (ForkBase §5.1, Fig. 7(b)) must have stored:

  * every state key's version chain: a blob of the balance's decimal
    bytes (leaves by the rolling-hash cut rule, unsorted index nodes
    above them) named by a meta chunk that holds the key, the depth and
    the previous version's uid (``Versions``);
  * each block's ``{state, txs}`` map and its meta chunk on ``chain``;
  * the last block's level-2 map (key -> newest version uid) and
    level-1 map (contract -> level-2 version uid).
"""
from __future__ import annotations

import bisect
import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from fbbench import reference as ref

CONTRACT = "smallbank"
L2_KEY = b"__l2__/" + CONTRACT.encode()
L1_KEY = b"__l1__"
CHAIN_KEY = b"chain"
WRITING = ("amalgamate", "deposit_checking", "send_payment",
           "transact_savings", "write_check")
ABORT = "abort"


def checking(account: int) -> str:
    return f"checking_{account}"


def savings(account: int) -> str:
    return f"savings_{account}"


# ------------------------------------------------------------ the contract

def transact(kind: str, a: int, b: int, read, write, amounts: dict):
    """Run one SmallBank transaction on account ``a`` (and ``b``) through
    ``read(key) -> int`` and ``write(key, int)``.  Returns Balance's
    answer, ``ABORT`` when the transaction aborts (it writes nothing),
    else None.  The amounts are OLTPBench's, in cents."""
    if kind == "balance":
        return read(checking(a)) + read(savings(a))
    if kind == "deposit_checking":
        write(checking(a), read(checking(a)) + amounts["deposit_checking"])
    elif kind == "transact_savings":
        left = read(savings(a)) - amounts["transact_savings"]
        if left < 0:
            return ABORT
        write(savings(a), left)
    elif kind == "write_check":
        amount = amounts["write_check"]
        c = read(checking(a))
        if c + read(savings(a)) < amount:
            amount += amounts["write_check_penalty"]
        write(checking(a), c - amount)
    elif kind == "send_payment":
        amount = amounts["send_payment"]
        c = read(checking(a))
        if c < amount:
            return ABORT
        write(checking(a), c - amount)
        write(checking(b), read(checking(b)) + amount)
    elif kind == "amalgamate":
        total = read(savings(a)) + read(checking(a))
        write(checking(a), 0)
        write(savings(a), 0)
        write(checking(b), read(checking(b)) + total)
    else:
        raise ValueError(f"SmallBank has no transaction {kind!r}")
    return None


class Bank:
    """SmallBank on a plain dict.  ``apply`` also gathers the open
    block's writes as the ledger buffers them: one entry per key in the
    order of its first write, holding the last value written, and one
    ``(contract, "put", key)`` record per write.  The genesis block, open
    from the start, writes every initial balance once."""

    def __init__(self, balances: dict[str, int], amounts: dict):
        self.bal: dict[str, int] = {}
        self.amounts = amounts
        self.writes: dict[str, bytes] = {}
        self.txs: list[tuple[str, str, str]] = []
        for k, v in balances.items():
            self._write(k, v)

    def _write(self, key: str, v: int) -> None:
        self.bal[key] = v
        self.writes[key] = b"%d" % v
        self.txs.append((CONTRACT, "put", key))

    def apply(self, kind: str, a: int, b: int):
        return transact(kind, a, b, self.bal.__getitem__, self._write,
                        self.amounts)

    def close_block(self) -> tuple[dict[str, bytes], bytes]:
        """The block's writes and its ``txs`` bytes; opens the next."""
        out = (self.writes, json.dumps(self.txs).encode())
        self.writes, self.txs = {}, []
        return out


# ------------------------------------------------------------ key versions

def hash_many(raws: list[bytes], step: int = 2048) -> list[bytes]:
    """``reference.fphash_many`` in slices over a few threads (numpy
    releases the interpreter lock inside its array operations)."""
    if len(raws) <= step:
        return ref.fphash_many(raws)
    parts = [raws[i:i + step] for i in range(0, len(raws), step)]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
        return [d for part in ex.map(ref.fphash_many, parts) for d in part]


def blob_leaves(value: bytes) -> list[tuple[bytes, int]]:
    """(raw chunk, byte count) of each leaf of a blob: a leaf ends after
    a byte that ends a pattern, and after MAX_CHUNK bytes at the latest;
    the empty blob is one empty leaf.  A value shorter than the window
    holds no pattern."""
    if len(value) < ref.WINDOW:
        return [(bytes([ref.BLOB]) + value, len(value))]
    hits = (np.flatnonzero(ref.pattern_bitmap(
        np.frombuffer(value, dtype=np.uint8))) + 1).tolist()
    out, start = [], 0
    while start < len(value):
        i = bisect.bisect_right(hits, start)
        end = min(hits[i] if i < len(hits) else len(value),
                  start + ref.MAX_CHUNK)
        out.append((bytes([ref.BLOB]) + value[start:end], end - start))
        start = end
    return out


class Versions:
    """Every key version the reference has committed: per key its newest
    ``(uid, depth)``, and per uid the version's chunks (meta chunk and
    blob tree, cid -> raw)."""

    def __init__(self):
        self.head: dict[bytes, tuple[bytes, int]] = {}
        self.chunks: dict[bytes, dict[bytes, bytes]] = {}

    def commit(self, writes: dict[bytes, bytes]) -> None:
        """One new version of each key, on its newest, hashed in batches:
        every leaf at once, then the index levels of values of several
        leaves, then every meta chunk."""
        keys = list(writes)
        leaves = [blob_leaves(writes[k]) for k in keys]
        flat = [raw for lv in leaves for raw, _ in lv]
        cids = iter(hash_many(flat))
        roots, trees = [], []
        for lv in leaves:
            chunks = {}
            entries = []
            for raw, count in lv:
                cid = next(cids)
                chunks[cid] = raw
                entries.append((cid, count, None))
            roots.append(ref._index_levels(ref.BLOB, entries, chunks))
            trees.append(chunks)
        metas = []
        for k, root in zip(keys, roots):
            base = self.head.get(k)
            metas.append(ref.encode_meta(
                ref.BLOB, k, root, base[1] + 1 if base else 0,
                (base[0],) if base else ()))
        for k, raw, uid, chunks in zip(keys, metas, hash_many(metas), trees):
            base = self.head.get(k)
            self.head[k] = (uid, base[1] + 1 if base else 0)
            chunks[uid] = raw
            self.chunks[uid] = chunks


# ------------------------------------------------------------ maps, blocks

def map_items(get, root: bytes) -> dict[bytes, bytes]:
    """The items of a stored map, walked from ``root`` through
    ``get(cids) -> raws``."""
    level, items = [root], {}
    while level:
        nxt = []
        for raw in get(level):
            if raw[0] == ref.SINDEX:
                nxt += [e[0] for e in ref.decode_index(raw)]
            elif raw[0] == ref.MAP:
                items.update(ref.unpack_kv(raw[1:]))
            else:
                raise ValueError(f"chunk kind {raw[0]} in a map")
        level = nxt
    return items


def block_map(state: bytes, txs: bytes) -> ref.Tree:
    return ref.map_tree([(b"state", state), (b"txs", txs)])


def checked_meta(raw: bytes, uid: bytes, key: bytes, depth: int,
                 base: bytes | None, context: bytes = b"") -> dict | None:
    """The decoded meta chunk of a map version; None when it does not
    hash to ``uid`` or names another kind, key, depth, parent or
    context."""
    meta = ref.decode_meta(raw)
    ok = (ref.fphash(raw) == uid and meta["kind"] == ref.MAP
          and meta["key"] == key and meta["depth"] == depth
          and meta["bases"] == ((base,) if base else ())
          and meta["context"] == context)
    return meta if ok else None


def block_context(height: int) -> bytes:
    return json.dumps({"height": height}).encode()
