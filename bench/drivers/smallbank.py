"""The SmallBank deployment: ForkBase's own Hyperledger design (ForkBase
§5.1, Fig. 7(b)) through ``ForkBaseLedger(live=False)`` on a durable
engine.  Every state key (``checking_<id>``, ``savings_<id>``) is a
versioned blob; a block puts the key versions it wrote in one
``put_batch``, then the level-2 and level-1 maps and the block on
``chain``, and is made durable by ``sync`` before its transactions count
as committed.

Operations: the six SmallBank transactions of ``smallbank_reference``'s
contract, run on the ledger's reads and writes.  ``balance`` reads both
balances of an account (``FBlob.read`` of each head) and completes at
once; a writing transaction completes when its block is committed and
synced; an abort (insufficient funds) is answered at once and writes
nothing.  Every ``block_txns`` writing transactions close a block.

The check replays the operation log on a plain dict
(``fbbench/smallbank_reference.py``): every Balance answer, every
block's meta chunk and ``{state, txs}`` map with its level-1 and
level-2 versions, the last level-1 root rebuilt from every key's
version chain, and a reopen of the durable root.
"""
from __future__ import annotations

import time
from pathlib import Path

import numpy as np

from fbbench import reference as ref
from fbbench import smallbank_reference as sb
from fbbench.driver import Base
from fbbench.loop import zipf_cdf


class Driver(Base):
    def __init__(self, cfg: dict, seed: int, workdir: Path, spans):
        self.cfg = cfg
        self.spans = spans
        self.root_dir = workdir / "smallbank"
        data_ss, op_ss, self.sample_ss = np.random.SeedSequence(
            seed).spawn(3)
        self.data_rng = np.random.default_rng(data_ss)
        self.op_rng = np.random.default_rng(op_ss)
        self.n_keys = cfg["accounts"]
        self.theta = cfg["zipf"]
        self.amounts = cfg["amount_cents"]

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.apps import ForkBaseLedger
        from repro.core import ForkBase
        from repro.core.postree import POSTree
        if not hasattr(POSTree, "build_bytes_many"):
            # genesis puts 2 x accounts key versions in one put_batch: an
            # engine that commits them value by value takes many minutes
            raise RuntimeError("this engine has no batched value commit "
                               "(POSTree.build_bytes_many)")
        cfg, n = self.cfg, self.n_keys
        self.cdf = zipf_cdf(n, self.theta)
        self.ids = self.data_rng.permutation(n).tolist()   # rank -> id
        lo, hi = cfg["initial_balance_cents"]
        bal = self.data_rng.integers(lo, hi, (n, 2)).tolist()
        self.genesis = {}
        for i, (c, s) in enumerate(bal):
            self.genesis[sb.checking(i)] = c
            self.genesis[sb.savings(i)] = s
        self.db = ForkBase(durable_root=str(self.root_dir),
                           hot_bytes=cfg["hot_bytes"],
                           segment_bytes=cfg["segment_bytes"])
        self._warm_kernels()
        self.ledger = ForkBaseLedger(self.db)
        for k, v in self.genesis.items():
            self.ledger.write(sb.CONTRACT, k, b"%d" % v)
        self.chain = [self.ledger.commit()]          # genesis, then blocks
        self.db.sync()
        self.log: list[tuple] = []
        self.pending: list[tuple[float, int]] = []   # (start, user bytes)
        self.aborts = 0
        self.reset_window()

    def _warm_kernels(self) -> None:
        """Compile every kernel shape a block can reach, before genesis:
        the chunker for streams of up to 64 rows (a block's values, the
        map splice's windows, the block map), and the hash for batches
        up to the level-2 map's bytes of chunks up to the largest index
        node (512 entries of cid, count and key).  Genesis's own larger
        streams compile as it meets them."""
        from fbbench.warm import warm_chunker, warm_fphash
        key = len(sb.savings(self.n_keys))
        warm_chunker(64)
        warm_fphash(2 * self.n_keys * (8 + key + 32), 1 + 512 * (44 + key))

    def warm(self) -> None:
        """Commit ``warm_blocks`` blocks of writing transactions through
        the window's own path, in turn of the writing kinds, on accounts
        drawn as the window draws them (the check replays them)."""
        blocks = len(self.chain) + self.cfg["warm_blocks"]
        i = 0
        while len(self.chain) < blocks:
            rank = int(np.searchsorted(self.cdf, self.op_rng.random(),
                                       side="right"))
            self.do(sb.WRITING[i % len(sb.WRITING)], rank)
            i += 1

    # ------------------------------------------------------------ window
    def begin_window(self) -> None:
        self.reset_window()

    def _other(self, a: int) -> int:
        """The second account: a Zipf draw of its own, other than ``a``."""
        while True:
            b = self.ids[int(np.searchsorted(self.cdf, self.op_rng.random(),
                                             side="right"))]
            if b != a:
                return b

    def _read(self, key: str) -> int:
        return int(self.ledger.read(sb.CONTRACT, key))

    def _commit_block(self) -> None:
        with self.spans("commit"):
            uid = self.ledger.commit()
        with self.spans("sync"):
            self.db.sync()
        done = time.perf_counter()
        self.commit_lat.extend(done - t0 for t0, _ in self.pending)
        self.completed += len(self.pending)
        self.user_bytes += sum(b for _, b in self.pending)
        self.pending.clear()
        self.chain.append(uid)
        self.stored1 = self.db.store.stats.physical_bytes
        self.log.append(("block",))

    def do(self, kind: str, rank: int) -> None:
        a = self.ids[rank]
        b = self._other(a) if kind in ("amalgamate", "send_payment") else a
        written = []

        def write(key: str, v: int) -> None:
            value = b"%d" % v
            self.ledger.write(sb.CONTRACT, key, value)
            written.append(len(sb.CONTRACT) + 1 + len(key) + len(value))
        t0 = time.perf_counter()
        with self.spans(kind):
            out = sb.transact(kind, a, b, self._read, write, self.amounts)
        self.log.append((kind, a, b, out))
        if kind == "balance":
            self.read_lat.append(time.perf_counter() - t0)
            self.completed += 1
        elif out == sb.ABORT:
            self.aborts += 1
            self.completed += 1
        else:
            self.pending.append((t0, sum(written)))
            if len(self.pending) >= self.cfg["block_txns"]:
                self._commit_block()

    def end_window(self) -> None:
        """Transactions of a block still open are not committed: they
        count neither as operations done nor as user bytes."""

    # ------------------------------------------------------------- check
    def check(self) -> list[tuple[str, int, int | None]]:
        from repro.core import ForkBase
        from repro.core.branch import DEFAULT_BRANCH
        prefix = sb.CONTRACT.encode() + b"/"
        bank = sb.Bank(self.genesis, self.amounts)
        versions = sb.Versions()
        blocks = []                       # per block: its txs bytes

        def close_block():
            writes, txs = bank.close_block()
            versions.commit({prefix + k.encode(): v
                             for k, v in writes.items()})
            blocks.append(txs)
        # 1. replay: the genesis block, then every transaction
        close_block()
        read_bad = reads = 0
        for entry in self.log:
            if entry[0] == "block":
                close_block()
                continue
            kind, a, b, out = entry
            want = bank.apply(kind, a, b)
            if kind == "balance":
                reads += 1
                read_bad += out != want
        # 2. every block's meta chunk, its {state, txs} map, and its
        #    level-1 and level-2 versions
        store = self.db.store
        chain_bad = abs(len(self.chain) - len(blocks))
        l1 = l2 = l2_prev = None
        for h, (uid, txs) in enumerate(zip(self.chain, blocks)):
            prev = self.chain[h - 1] if h else None
            meta = sb.checked_meta(store.get(uid), uid, sb.CHAIN_KEY, h,
                                   prev, sb.block_context(h))
            if meta is None:
                chain_bad += 1
                continue
            items = sb.map_items(store.get_many, meta["data"])
            state = items.get(b"state", b"")
            chain_bad += sb.block_map(state, txs).root != meta["data"]
            m1 = sb.checked_meta(store.get(state), state, sb.L1_KEY, h, l1)
            l1 = state
            if m1 is None:
                chain_bad += 1
                continue
            items = sb.map_items(store.get_many, m1["data"])
            new_l2 = items.get(sb.CONTRACT.encode(), b"")
            chain_bad += (list(items) != [sb.CONTRACT.encode()]
                          or sb.checked_meta(store.get(new_l2), new_l2,
                                             sb.L2_KEY, h, l2) is None)
            l2_prev, l2 = l2, new_l2
        # 3. the last level-1 root, rebuilt from every key's newest version
        l2_tree = ref.map_tree(sorted(
            (k[len(prefix):], uid) for k, (uid, _) in versions.head.items()))
        l2_meta = ref.encode_meta(ref.MAP, sb.L2_KEY, l2_tree.root,
                                  len(self.chain) - 1,
                                  (l2_prev,) if l2_prev else ())
        l1_tree = ref.map_tree([(sb.CONTRACT.encode(),
                                 ref.fphash(l2_meta))])
        last = ref.decode_meta(store.get(l1)) if l1 else {"data": b""}
        root_bad = int(l1_tree.root != last["data"])
        # 4. durability: reopen; the head is the last block, the last
        #    maps are whole, sampled key versions read back
        del store
        crashed, self.db, self.ledger = self.db, None, None
        re = ForkBase(durable_root=str(self.root_dir),
                      hot_bytes=self.cfg["hot_bytes"],
                      segment_bytes=self.cfg["segment_bytes"])
        lost = int(re.branches.head(sb.CHAIN_KEY, DEFAULT_BRANCH)
                   != self.chain[-1])
        tree = sb.block_map(l1, blocks[-1])
        wanted = {**l2_tree.chunks, **l1_tree.chunks, **tree.chunks}
        uids = sorted(versions.chunks)
        rng = np.random.default_rng(self.sample_ss)
        for i in rng.choice(len(uids), min(len(uids),
                                           self.cfg["check"]["versions"]),
                            replace=False).tolist():
            wanted.update(versions.chunks[uids[i]])
        cids = list(wanted)
        held = [c for c, h in zip(cids, re.store.has_many(cids)) if h]
        lost += len(cids) - len(held)
        for cid, raw in zip(held, re.store.get_many(held)):
            lost += raw != wanted[cid]
        re.store.close()
        del crashed
        return [("read_mismatch", read_bad, 0),
                ("chain_mismatch", chain_bad, 0),
                ("root_mismatch", root_bad, 0),
                ("durable_lost", lost, 0),
                ("checked_reads", reads, None),
                ("checked_blocks", len(self.chain) - 1, None),
                ("aborts", self.aborts, None)]
