#!/usr/bin/env python3
"""Chip smoke run of ForkBase's device path on one TPU: the Pallas
content-defined chunker and the Pallas fphash cid kernel, driven through
the engine's normal entry points (``ForkBase`` on a durable root) at the
data sizes a deployment holds.  A smoke run, not a benchmark: it proves
the system starts and answers correctly on the chip; its timings are set-up
and wall-clock figures, not measurements.

Phases (each raises on the first wrong answer; nothing is caught):

  kernels  chunker on an 8 MiB random stream; fphash_many on 2,048 chunks
           of 1-32 KB (1-8 blocks); singular fphash from 0 B to 64 KB —
           each bit for bit against kernels/ref.py.
  wiki     >= 256 MiB of blob content as >= 32 values of 1-16 MiB, three
           rounds of small in-place edits committed as new versions; every
           head read back byte-exact, an old version read by uid with
           verification, then the durable root reopened with verify-on-read.
  ledger   1,000,000 accounts (20 B keys, 100 B values) through
           ``db.live`` + ``commit_epoch``; 10 blocks of 100 updates, one
           fold each; fork (100 updates a side), merge and diff;
           ``prove_member`` on 100 sampled keys, each checked with
           ``proof.verify_member`` against the folded root.

Two cuts keep the run inside its time limit, both forced by host code:
a fold on the splice path re-chunks a few leaves and launches both
kernels for each locality cluster (about one cluster per updated key),
and each ``prove_member`` decodes every index node of the tree
(``POSTree.from_root``).

The wiki and ledger operations run twice: on the device path, then with
the host chunker (core/rolling.py) and the vectorized numpy fphash sponge.
Every version uid and tree root must agree.

    python chip_smoke.py              # TPU only; last line {"ok": true, ...}
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                      # every phase at a tiny size; never ok

The persistent compile cache is JAX_COMPILATION_CACHE_DIR when that is
set, and <checkout>/.jax_cache otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Sizes:
    stream_bytes: int        # kernels: chunker input
    batch_chunks: int        # kernels: fphash_many batch
    wiki_values: int         # at least this many blob values ...
    wiki_total: int          # ... holding at least this many bytes
    wiki_min: int            # value size range
    wiki_max: int
    edit_rounds: int         # in-place edit versions per value
    accounts: int            # ledger state size
    blocks: int              # folds after genesis
    block_updates: int       # updates per block
    fork_updates: int        # updates on each side of the fork
    proofs: int              # prove_member / verify_member samples


FULL = Sizes(stream_bytes=8 << 20, batch_chunks=2048, wiki_values=32,
             wiki_total=256 << 20, wiki_min=1 << 20, wiki_max=16 << 20,
             edit_rounds=3, accounts=1_000_000, blocks=10,
             block_updates=100, fork_updates=100, proofs=100)
TINY = Sizes(stream_bytes=64 << 10, batch_chunks=64, wiki_values=3,
             wiki_total=96 << 10, wiki_min=16 << 10, wiki_max=48 << 10,
             edit_rounds=2, accounts=2_000, blocks=3, block_updates=50,
             fork_updates=20, proofs=20)

KEY_BYTES, VALUE_BYTES = 20, 100
LEDGER = b"state"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------ compiles

class CompileLog:
    """Counts XLA compiles, persistent-cache hits and compile seconds
    through jax.monitoring (every jit, the kernels' included) while the
    ``with`` block runs."""

    _STAGES = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self, monitoring):
        self._monitoring = monitoring
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def __enter__(self) -> "CompileLog":
        self._monitoring.register_event_duration_secs_listener(
            self._duration)
        self._monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        self._monitoring.unregister_event_duration_listener(self._duration)
        self._monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self._STAGES:
            self.seconds += secs
        if event == self._STAGES[-1]:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "compile_s": self.seconds}


def configure_compile_cache(jax) -> str:
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; without it the cache
    sits at a fixed path in the checkout (the path is part of the key, so
    a moving directory would never hit).  The kernels compile in about a
    second, under JAX's default threshold for caching, hence 0."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


# ------------------------------------------------------------- engines

def use_device_path() -> None:
    from repro.core import hashing
    from repro.kernels import ops
    ops.use_pallas_chunker(True)
    hashing.use_fphash()


def use_host_path() -> None:
    """The reference: host chunker and the numpy fphash sponge."""
    from repro.core import hashing
    from repro.kernels import ops
    from repro.kernels.fphash import fphash_many_host
    from repro.kernels.ref import fphash_ref
    ops.use_pallas_chunker(False)
    hashing.set_default_hash(fphash_ref, fphash_many_host)


def kernel_counts() -> dict:
    """Launches and input bytes per kernel, from the obs counters the
    kernels' dispatch code bumps."""
    from repro import obs
    return {f"{name}.{k}": obs.counter(name, {"kernel": k}).value
            for name in ("kernel_launches", "kernel_bytes")
            for k in ("chunker", "fphash")}


# ------------------------------------------------------------- phases

def phase_kernels(sz: Sizes, rng) -> dict:
    import numpy as np
    from repro.kernels.chunker import boundary_bitmap_pallas
    from repro.kernels.fphash import fphash, fphash_many
    from repro.kernels.ref import boundary_bitmap_ref, fphash_ref
    stream = rng.integers(0, 256, sz.stream_bytes, dtype=np.uint8)
    got = boundary_bitmap_pallas(stream, 48, 12)
    check(np.array_equal(got, boundary_bitmap_ref(stream, 48, 12)),
          "chunker bitmap differs from the reference")
    sizes = rng.integers(1, (32 << 10) + 1, sz.batch_chunks)
    pool = rng.bytes(int(sizes.sum()))
    ends = np.cumsum(sizes)
    blobs = [pool[e - n:e] for e, n in zip(ends.tolist(), sizes.tolist())]
    check(fphash_many(blobs) == [fphash_ref(b) for b in blobs],
          "fphash_many digests differ from the reference")
    singles = [0, 1, 4095, 4096, 4097, 9000, 32 << 10, 64 << 10]
    for n in singles:
        x = rng.bytes(n)
        check(fphash(x) == fphash_many([x])[0] == fphash_ref(x),
              f"fphash of {n} B differs from the reference")
    return {"stream_bytes": sz.stream_bytes,
            "boundaries": int(got.sum()),
            "batch_chunks": len(blobs), "batch_bytes": len(pool),
            "singular_sizes": singles}


def wiki_workload(sz: Sizes, rng):
    """Values and edits, made once from the seed and replayed on both
    paths.  Edits overwrite in place: (value, offset, new bytes)."""
    sizes: list[int] = []
    while len(sizes) < sz.wiki_values or sum(sizes) < sz.wiki_total:
        sizes.append(int(rng.integers(sz.wiki_min, sz.wiki_max + 1)))
    values = [rng.bytes(n) for n in sizes]
    edits = [[(i, int(rng.integers(0, n - 64)),
               rng.bytes(int(rng.integers(1, 65))))
              for i, n in enumerate(sizes)]
             for _ in range(sz.edit_rounds)]
    return values, edits


def run_wiki(db, values, edits) -> list[bytes]:
    """Commit every value, then each edit round; returns version uids."""
    from repro.core import FBlob
    uids = [db.put(b"page%04d" % i, FBlob(v)) for i, v in enumerate(values)]
    for rnd in edits:
        for i, off, new in rnd:
            blob = db.get(b"page%04d" % i).blob()
            blob.replace(off, len(new), new)
            uids.append(db.put(b"page%04d" % i, blob))
    return uids


def phase_wiki(sz: Sizes, rng, out: Path) -> dict:
    from repro.core import ForkBase
    from repro.storage import MemoryBackend
    values, edits = wiki_workload(sz, rng)
    use_device_path()
    t0 = time.perf_counter()
    root = out / "wiki"
    db = ForkBase(durable_root=str(root))
    uids = run_wiki(db, values, edits)
    model = [bytearray(v) for v in values]
    for rnd in edits:
        for i, off, new in rnd:
            model[i][off:off + len(new)] = new
    for i, want in enumerate(model):
        check(db.get(b"page%04d" % i).blob().read() == want,
              f"head of page {i} reads back wrong")
    old = db.get(b"page0000", uid=uids[0], verify=True).blob().read()
    check(old == values[0], "first version of page 0 reads back wrong")
    loaded = sum(map(len, values))
    versioned = loaded * (1 + len(edits))
    physical = db.store.stats.physical_bytes
    chunks = db.store.stats.puts
    db.sync()
    heads = db.branches.snapshot()
    db.store.close()
    # restart with verify-on-read: every chunk read is re-hashed on its
    # own through the singular fphash (chunks up to 32 KB)
    re = ForkBase(durable_root=str(root), verify_get=True)
    check(re.branches.snapshot() == heads, "heads differ after reopen")
    small = min(range(len(model)), key=lambda i: len(model[i]))
    check(re.get(b"page%04d" % small).blob().read() == model[small],
          f"page {small} reads back wrong after reopen")
    verifies = re.store.stats.verifies + re.store.cold.stats.verifies
    re.store.close()
    device_s = time.perf_counter() - t0
    use_host_path()
    t0 = time.perf_counter()
    ref_uids = run_wiki(ForkBase(MemoryBackend()), values, edits)
    check(ref_uids == uids, "wiki version uids differ from the host path")
    return {"values": len(values), "bytes_loaded": loaded,
            "versions": len(uids), "chunk_puts": chunks,
            "versioned_bytes": versioned, "physical_bytes": physical,
            "dedup_ratio": versioned / physical,
            "reopen_verified_chunks": verifies,
            "device_path_s": device_s,
            "host_reference_s": time.perf_counter() - t0,
            "roots_match_host": True}


def ledger_workload(sz: Sizes, rng):
    keys = rng.bytes(KEY_BYTES * sz.accounts)
    keys = [keys[i:i + KEY_BYTES]
            for i in range(0, len(keys), KEY_BYTES)]
    vals = rng.bytes(VALUE_BYTES * sz.accounts)
    vals = [vals[i:i + VALUE_BYTES]
            for i in range(0, len(vals), VALUE_BYTES)]

    def updates(n, pick):
        new = rng.bytes(VALUE_BYTES * n)
        return [(keys[int(j)], new[VALUE_BYTES * t:VALUE_BYTES * (t + 1)])
                for t, j in enumerate(pick)]
    blocks = [updates(sz.block_updates,
                      rng.integers(0, sz.accounts, sz.block_updates))
              for _ in range(sz.blocks)]
    sides = rng.permutation(sz.accounts)[:2 * sz.fork_updates]
    fork = (updates(sz.fork_updates, sides[:sz.fork_updates]),
            updates(sz.fork_updates, sides[sz.fork_updates:]))
    sample = [keys[int(j)] for j in
              rng.choice(sz.accounts, sz.proofs, replace=False)]
    return list(zip(keys, vals)), blocks, fork, sample


def run_ledger(db, genesis, blocks, fork):
    """Genesis fold, one fold per block, then fork / merge / diff.
    Returns (uids of every committed state, merge uid, diff)."""
    from repro.live import EpochPolicy
    manual = EpochPolicy(max_dirty_keys=None, max_dirty_bytes=None)
    master = db.live(LEDGER, policy=manual)
    for k, v in genesis:
        master.put(k, v)
    db.commit_epoch(context=b"genesis")
    uids = [db.get(LEDGER).uid]
    for n, blk in enumerate(blocks):
        for k, v in blk:
            master.put(k, v)
        db.commit_epoch(context=b"block %d" % n)
        uids.append(db.get(LEDGER).uid)
    db.fork(LEDGER, "master", "side")
    side = db.live(LEDGER, "side", policy=manual)
    for (k, v), (k2, v2) in zip(*fork):
        master.put(k, v)
        side.put(k2, v2)
    db.commit_epoch(context=b"fork")
    uids += [db.get(LEDGER).uid, db.get(LEDGER, "side").uid]
    merged = db.merge(LEDGER, "master", "side")
    uids.append(merged)
    return uids, merged, db.diff(merged, uids[len(blocks)])


def phase_ledger(sz: Sizes, rng, out: Path) -> dict:
    from repro.core import ForkBase
    from repro.proof import verify_member
    from repro.storage import MemoryBackend
    genesis, blocks, fork, sample = ledger_workload(sz, rng)
    use_device_path()
    t0 = time.perf_counter()
    db = ForkBase(durable_root=str(out / "ledger"))
    uids, merged, (added, removed, changed) = run_ledger(
        db, genesis, blocks, fork)
    model = dict(genesis)
    for blk in blocks:
        model.update(blk)
    for side in fork:
        model.update(side)
    touched = {k for side in fork for k, _ in side}
    check(not added and not removed and set(changed) == touched,
          "diff of the merge against the last block is wrong")
    head = db.get(LEDGER)
    check(head.uid == merged, "master head is not the merge")
    root = head.obj.data
    for k in sample:
        claim = verify_member(root, db.prove_member(LEDGER, item_key=k))
        check(claim.value == model[k], "proven value differs from the model")
    check(len(head.map()) == len(model), "ledger size changed")
    db.sync()
    chunks = db.store.stats.puts
    physical = db.store.stats.physical_bytes
    db.store.close()
    device_s = time.perf_counter() - t0
    use_host_path()
    t0 = time.perf_counter()
    ref_uids, _, _ = run_ledger(ForkBase(MemoryBackend()), genesis, blocks,
                                fork)
    check(ref_uids == uids, "ledger state uids differ from the host path")
    return {"accounts": len(genesis), "blocks": len(blocks),
            "block_updates": sz.block_updates,
            "states": len(uids), "diff_changed": len(changed),
            "proofs_verified": len(sample), "chunk_puts": chunks,
            "physical_bytes": physical, "device_path_s": device_s,
            "host_reference_s": time.perf_counter() - t0,
            "roots_match_host": True}


# --------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="every phase at a tiny size, on any platform; "
                         "never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path, default=ROOT / ".chip_smoke",
                    help="scratch directory for the durable roots "
                         "(emptied before and after)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: platform is {dev.platform!r}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 2
    cache = configure_compile_cache(jax) if on_tpu else None

    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np

    from repro import kernels, obs
    from repro.core import hashing
    from repro.kernels import ops
    check(kernels.interpret() is not on_tpu,
          "kernel platform decision disagrees with the device")
    obs.enable()                     # the kernel counters live in obs
    sz = TINY if args.rehearse else FULL
    print(f"chip smoke run (not a benchmark): device {dev.platform} "
          f"{dev.device_kind} x{len(jax.devices())}, "
          f"{'rehearsal sizes' if args.rehearse else 'full sizes'}, "
          f"seed {args.seed}, compile cache {cache}", flush=True)
    shutil.rmtree(args.out, ignore_errors=True)
    args.out.mkdir(parents=True)
    rng = np.random.default_rng(args.seed)
    total = time.perf_counter()
    with CompileLog(jax.monitoring) as compiles:
        try:
            run_phases(sz, rng, args.out, compiles, on_tpu)
        finally:
            ops.use_pallas_chunker(False)
            hashing.use_sha256()
            shutil.rmtree(args.out, ignore_errors=True)
    summary = {"wall_s": time.perf_counter() - total,
               **compiles.snapshot(),
               "jit_shapes": {
                   "chunker": kernels.chunker._run._cache_size(),
                   "fphash": kernels.fphash._run._cache_size()}}
    print("summary " + json.dumps(summary), flush=True)
    if not on_tpu or args.rehearse:
        print("rehearsal finished: not a chip run, no result",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


def run_phases(sz: Sizes, rng, out: Path, compiles: CompileLog,
               on_tpu: bool) -> None:
    """Run every phase in order and print one JSON line per phase."""
    for name, run in (("kernels", lambda: phase_kernels(sz, rng)),
                      ("wiki", lambda: phase_wiki(sz, rng, out)),
                      ("ledger", lambda: phase_ledger(sz, rng, out))):
        k0, c0 = kernel_counts(), compiles.snapshot()
        t0 = time.perf_counter()
        rec = {"phase": name, **run(), "wall_s": time.perf_counter() - t0}
        k1, c1 = kernel_counts(), compiles.snapshot()
        rec.update({k: k1[k] - k0[k] for k in k1})
        rec.update({k: c1[k] - c0[k] for k in c1})
        # on the chip both kernels run in every phase (off it,
        # fphash_many is the numpy sponge and never launches)
        check(not on_tpu or all(rec[f"kernel_launches.{k}"]
                                for k in ("chunker", "fphash")),
              f"{name}: a kernel never launched on the chip")
        print("phase " + json.dumps(rec), flush=True)


if __name__ == "__main__":
    sys.exit(main())
