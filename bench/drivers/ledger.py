"""The ledger deployment: account state as one ForkBase map, held through
the flat live table (``db.live``) and folded into the POS-Tree once per
block (``commit_epoch``), each block made durable by ``sync`` before its
transactions count as committed.

Operations: ``read`` (``live.get`` of an account), ``update``
(``live.put`` of a new account value; every ``block_updates`` updates
close a block), ``prove`` (a light client's state proof against the
newest block root: ``prove_member`` then ``verify_member``).

The check replays the operation log against a plain model: every read
and every proof (re-walked with the reference hash), the chain of block
versions, the final block's root rebuilt from the model by the reference,
and a reopen of the durable root holding every chunk of that root.
"""
from __future__ import annotations

import time
from pathlib import Path

import numpy as np

from fbbench import reference as ref
from fbbench.driver import Base

LEDGER = b"state"


class Driver(Base):
    def __init__(self, cfg: dict, seed: int, workdir: Path, spans):
        self.cfg = cfg
        self.spans = spans
        self.root_dir = workdir / "ledger"
        data_ss, op_ss, self.sample_ss = np.random.SeedSequence(
            seed).spawn(3)
        self.data_rng = np.random.default_rng(data_ss)
        self.op_rng = np.random.default_rng(op_ss)
        self.n_keys = cfg["accounts"]
        self.theta = cfg["zipf"]

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core import ForkBase
        from repro.live import EpochPolicy
        cfg = self.cfg
        n, kb, vb = self.n_keys, cfg["key_bytes"], cfg["value_bytes"]
        self.db = ForkBase(durable_root=str(self.root_dir),
                           hot_bytes=cfg["hot_bytes"],
                           segment_bytes=cfg["segment_bytes"])
        keys = self.data_rng.bytes(kb * n)
        vals = self.data_rng.bytes(vb * n)
        self.keys = [keys[i:i + kb] for i in range(0, kb * n, kb)]
        self.vals = [vals[i:i + vb] for i in range(0, vb * n, vb)]
        del keys, vals
        self._warm_kernels()
        manual = EpochPolicy(max_dirty_keys=None, max_dirty_bytes=None)
        self.table = self.db.live(LEDGER, policy=manual)
        for k, v in zip(self.keys, self.vals):
            self.table.put(k, v)
        self.db.commit_epoch(context=b"genesis")
        self.db.sync()
        self.chain = [self.db.get(LEDGER).uid]    # genesis, then blocks
        self.root = self.db.get(LEDGER).obj.data
        self.log: list[tuple] = []
        self.pending: list[float] = []
        self.reset_window()

    def _warm_kernels(self) -> None:
        """Compile every kernel shape genesis and the window can reach,
        before genesis, so that each run compiles or loads the same set
        whatever the seed: genesis hashes every leaf in one batch, whose
        buckets' row counts follow the seed's chunk sizes, and a block's
        fold re-chunks a few leaves around each updated key.  So: the
        chunker for streams of up to 64 rows (genesis chunks one stream
        whose length the sizes fix), and the hash for batches up to the
        genesis stream's bytes of chunks up to the largest index node
        (512 entries of cid, count and key)."""
        from fbbench.warm import warm_chunker, warm_fphash
        cfg = self.cfg
        warm_chunker(64)
        warm_fphash(self.n_keys * (8 + cfg["key_bytes"] + cfg["value_bytes"]),
                    1 + 512 * (44 + cfg["key_bytes"]))

    def warm(self) -> None:
        """Commit ``warm_blocks`` blocks of updates through the window's
        own path (their updates come from the seed like the window's, and
        the check replays them)."""
        self.counted = {"update"}
        for _ in range(self.cfg["warm_blocks"] * self.cfg["block_updates"]):
            self.do("update", int(self.op_rng.integers(0, self.n_keys)))

    # ------------------------------------------------------------ window
    def begin_window(self) -> None:
        self.reset_window()

    def _commit_block(self) -> None:
        n = len(self.chain)
        with self.spans("commit_epoch"):
            self.db.commit_epoch(context=b"block %d" % n)
        with self.spans("sync"):
            self.db.sync()
        done = time.perf_counter()
        self.commit_lat.extend(done - t0 for t0 in self.pending)
        self.completed += len(self.pending) * ("update" in self.counted)
        self.user_bytes += len(self.pending) * (self.cfg["key_bytes"]
                                                + self.cfg["value_bytes"])
        self.pending.clear()
        head = self.db.get(LEDGER)
        self.chain.append(head.uid)
        self.root = head.obj.data
        self.stored1 = self.db.store.stats.physical_bytes
        self.log.append(("block", n))

    def do(self, kind: str, rank: int) -> None:
        key = self.keys[rank]
        if kind == "read":
            with self.spans("live.get"):
                t0 = time.perf_counter()
                v = self.table.get(key)
                self.read_lat.append(time.perf_counter() - t0)
            self.log.append(("read", rank, v))
            self.completed += "read" in self.counted
        elif kind == "update":
            v = self.op_rng.bytes(self.cfg["value_bytes"])
            with self.spans("live.put"):
                t0 = time.perf_counter()
                self.table.put(key, v)
            self.pending.append(t0)
            self.log.append(("update", rank, v))
            if len(self.pending) >= self.cfg["block_updates"]:
                self._commit_block()
        elif kind == "prove":
            from repro.proof import verify_member
            t0 = time.perf_counter()
            with self.spans("prove_member"):
                proof = self.db.prove_member(LEDGER, item_key=key)
            with self.spans("verify_member"):
                claim = verify_member(self.root, proof)
            self.read_lat.append(time.perf_counter() - t0)
            self.log.append(("prove", rank, len(self.chain) - 1, proof,
                             claim.value))
            self.completed += "prove" in self.counted
        else:
            raise ValueError(f"ledger has no operation {kind!r}")

    def end_window(self) -> None:
        """Updates of a block still open are not committed: they count
        neither as operations done nor as user bytes."""

    # ------------------------------------------------------------- check
    def check(self) -> list[tuple[str, int, int | None]]:
        from repro.core import ForkBase
        from repro.core.branch import DEFAULT_BRANCH
        # 1. the chain of versions: genesis, then one per block
        chain_bad, roots = 0, []
        for depth, uid in enumerate(self.chain):
            raw = self.db.store.get(uid)
            meta = ref.decode_meta(raw)
            want_ctx = b"genesis" if depth == 0 else b"block %d" % depth
            want_bases = (self.chain[depth - 1],) if depth else ()
            chain_bad += (ref.fphash(raw) != uid or meta["kind"] != ref.MAP
                          or meta["key"] != LEDGER or meta["depth"] != depth
                          or meta["bases"] != want_bases
                          or meta["context"] != want_ctx)
            roots.append(meta["data"])
        # 2. replay: reads see every update so far, proofs the state of
        #    the block they were proven against
        live = list(self.vals)
        committed = list(self.vals)
        pending: list[tuple[int, bytes]] = []
        read_bad = proof_bad = proofs = reads = 0
        for entry in self.log:
            kind = entry[0]
            if kind == "update":
                _, rank, v = entry
                live[rank] = v
                pending.append((rank, v))
            elif kind == "block":
                for rank, v in pending:
                    committed[rank] = v
                pending.clear()
            elif kind == "read":
                _, rank, v = entry
                reads += 1
                read_bad += v != live[rank]
            else:
                _, rank, block, proof, value = entry
                proofs += 1
                got = ref.check_map_proof(roots[block], list(proof.nodes),
                                          proof.leaf, self.keys[rank])
                proof_bad += got != committed[rank] or value != got
        del live
        # 3. the last block's root, rebuilt from the model
        tree = ref.map_tree(sorted(zip(self.keys, committed)))
        root_bad = int(tree.root != roots[-1])
        del committed
        # 4. durability: reopen; the head is the last block, and the
        #    reopened store holds every chunk of its tree
        crashed, self.db, self.table = self.db, None, None
        re = ForkBase(durable_root=str(self.root_dir),
                      hot_bytes=self.cfg["hot_bytes"],
                      segment_bytes=self.cfg["segment_bytes"])
        lost = int(re.branches.head(LEDGER, DEFAULT_BRANCH) != self.chain[-1])
        cids = list(tree.chunks)
        held = {c for c, h in zip(cids, re.store.has_many(cids)) if h}
        lost += len(cids) - len(held)
        rng = np.random.default_rng(self.sample_ss)
        pick = rng.choice(len(tree.leaves),
                          min(len(tree.leaves),
                              self.cfg["check"]["leaves"]), replace=False)
        sample = [tree.leaves[i] for i in pick.tolist()
                  if tree.leaves[i] in held]
        for cid, raw in zip(sample, re.store.get_many(sample)):
            lost += raw != tree.chunks[cid]
        re.store.close()
        del crashed
        return [("read_mismatch", read_bad, 0),
                ("proof_mismatch", proof_bad, 0),
                ("chain_mismatch", chain_bad, 0),
                ("root_mismatch", root_bad, 0),
                ("durable_lost", lost, 0),
                ("checked_reads", reads, None),
                ("checked_proofs", proofs, None),
                ("checked_blocks", len(self.chain) - 1, None)]
