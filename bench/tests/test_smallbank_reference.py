"""The SmallBank cell's plain reference against the system, at the
configuration's rehearsal size on the CPU: the key versions it rebuilds
are the engine's, a seeded run reads 0 on every check, and a corrupted
key version, a dropped map entry and a wrong balance are each caught."""
from contextlib import nullcontext

import numpy as np
import pytest

import run
from fbbench import smallbank_reference as sb
from fbbench.loop import Spans, Traffic

CELL = "ledger-smallbank"
SEED = 3_000_000_029


@pytest.fixture
def fphash_cids():
    from repro.core import hashing
    hashing.use_fphash()            # off the TPU: the numpy sponge
    yield
    hashing.use_sha256()


def test_key_versions_are_the_engines(fphash_cids):
    """Blobs across the cut rules: empty, shorter than the window, one
    leaf, many leaves with index levels, forced splits of a run of
    zeros; each a first version and a second on top of it."""
    from repro.core import FBlob, ForkBase
    rng = np.random.default_rng(7)
    values = [b"", b"7", rng.bytes(47), rng.bytes(48), rng.bytes(5000),
              rng.bytes(300_000), bytes(70_000)]
    keys = [b"smallbank/k%d" % i for i in range(len(values))]
    db = ForkBase()
    versions = sb.Versions()
    for round_ in range(2):
        vals = values if round_ == 0 else values[::-1]
        uids = db.put_batch([(k, FBlob(v)) for k, v in zip(keys, vals)])
        versions.commit(dict(zip(keys, vals)))
        assert [versions.head[k] for k in keys] == [(u, round_)
                                                   for u in uids]
    for uid, chunks in versions.chunks.items():
        assert dict(zip(chunks, db.store.get_many(list(chunks)))) == chunks


def run_driver(tmp_path, ops: int = 60):
    """Set up the rehearsal-size deployment and run ``ops`` operations
    of the cell's mix through its driver, as a window does."""
    spec = run.find_cell(CELL, rehearse=True)
    mod = run.load_module(spec["driver_file"], "bench_driver_smallbank")
    driver = mod.Driver(spec["config"], SEED, tmp_path, Spans(nullcontext))
    driver.setup()
    driver.warm()
    driver.begin_window()
    traffic = iter(Traffic(spec["mix"], driver.n_keys, driver.theta,
                           np.random.default_rng(SEED)))
    for _ in range(ops):
        driver.do(*next(traffic))
    return driver


def readings(driver) -> dict[str, int]:
    return {name: v for name, v, lim in driver.check() if lim is not None}


def test_a_seeded_run_reads_zero(tmp_path, fphash_cids):
    driver = run_driver(tmp_path)
    assert len(driver.chain) >= 5          # genesis, 2 warm blocks, more
    out = {name: v for name, v, _ in driver.check()}
    assert out["checked_reads"] > 0
    assert all(out[k] == 0 for k in ("read_mismatch", "chain_mismatch",
                                     "root_mismatch", "durable_lost"))


def test_a_corrupted_key_version_is_caught(tmp_path, fphash_cids):
    """A value of the open block altered before it commits: the block's
    transactions are as replayed, its key version is not."""
    driver = run_driver(tmp_path)
    (contract, key), v = next(iter(driver.ledger._writes.items()))
    driver.ledger._writes[contract, key] = v + b"1"
    driver._commit_block()
    got = readings(driver)
    assert got["root_mismatch"] == 1
    assert got["read_mismatch"] == got["chain_mismatch"] == 0


def test_a_dropped_map_entry_is_caught(tmp_path, fphash_cids):
    """An account's entry dropped from the level-2 map the next block
    commits."""
    driver = run_driver(tmp_path)
    kept = driver.ledger._map("__l2__/" + sb.CONTRACT)
    kept.delete(sb.savings(driver.ids[-1]).encode())
    driver._commit_block()
    got = readings(driver)
    assert got["root_mismatch"] == 1
    assert got["read_mismatch"] == got["chain_mismatch"] == 0


def test_a_wrong_balance_is_caught(tmp_path, fphash_cids):
    driver = run_driver(tmp_path)
    i = next(i for i, e in enumerate(driver.log) if e[0] == "balance")
    kind, a, b, answer = driver.log[i]
    driver.log[i] = (kind, a, b, answer + 1)
    got = readings(driver)
    assert got["read_mismatch"] == 1
    assert got["root_mismatch"] == got["chain_mismatch"] == 0
