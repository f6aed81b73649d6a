"""The program-span reduction (``fbbench/program.py``) and the per-layer
readers that take their numbers from it.

``data/v5e_ledger_block_spans.xplane.pb`` is a short ledger-block window
recorded on one TPU v5 lite chip with the program's spans mirrored into
the trace (``obs.annotate_with(jax.profiler.TraceAnnotation)`` set around
the traced window) under their bare names, before the mark."""
from pathlib import Path

import pytest

import run
from fbbench import program, trace

DATA = Path(__file__).resolve().parent / "data"
NAMES = trace.load_names()
SPANS = program.load_spans()
MARK = SPANS["mark"]
MS = 1_000_000


def _share(got: dict, name: str) -> float:
    """A span's self time over the window, in percent."""
    return 100.0 * got["spans"][name]["self_s"] / got["window_s"]


def _reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py", name)


def ev(name, s, e):
    return (name, int(s * MS), int(e * MS))


def _hand_made_program():
    """A fold and two proofs on one thread line, a span cut by the
    window's start, a benchmark annotation and runtime events (not
    program spans: no mark) and device operations of both kernels.
    Milliseconds."""
    def pev(name, s, e):
        return ev(MARK + name, s, e)
    host = {"python": [
        ev("bench.window", 0, 100),
        pev("engine.sync", -5, 5),
        pev("engine.commit_epoch", 10, 90), pev("live.fold", 11, 89),
        pev("engine.put", 12, 88),
        pev("postree.splice", 20, 50), pev("kernel.chunker", 25, 35),
        ev("PjitFunction(_run)", 26, 27),
        pev("postree.rebuild_index", 60, 70), pev("kernel.fphash", 62, 66),
        ev("live.get", 90.5, 91),
        pev("engine.prove_member", 92, 99), pev("postree.from_root", 93, 96),
        pev("engine.prove_member", 99.2, 99.8)],
        "other": [ev("python.idle", 0, 100)]}
    device = {"/device:TPU:0": [
        ev("%_run.1 = u8[8,4992]{1,0} custom-call(u8[8,5120] %rows.1)",
           28, 30),
        ev("%_run.1 = u32[1,8,128]{2,1,0} custom-call(s32[4] %lengths.1, "
           "u32[4,8,128] %words.1, u32[8,128] %init.1)", 63, 64),
        ev("late", 101, 102)]}
    return program.reduce_program(device, host, NAMES, SPANS)


def test_program_reduction_of_hand_made_events():
    got = _hand_made_program()
    assert got["window_s"] == pytest.approx(0.1)
    sp = got["spans"]
    assert set(sp) == {"engine.sync", "engine.commit_epoch", "live.fold",
                       "engine.put", "postree.splice", "kernel.chunker",
                       "postree.rebuild_index", "kernel.fphash",
                       "engine.prove_member", "postree.from_root"}
    ms = {n: (s["count"], s["total_s"] * 1e3, s["self_s"] * 1e3)
          for n, s in sp.items()}
    want = {"engine.sync": (1, 5, 5),               # cut at the window
            "engine.commit_epoch": (1, 80, 2), "live.fold": (1, 78, 2),
            "engine.put": (1, 76, 36), "postree.splice": (1, 30, 20),
            "kernel.chunker": (1, 10, 10),
            "postree.rebuild_index": (1, 10, 6), "kernel.fphash": (1, 4, 4),
            "engine.prove_member": (2, 7.6, 4.6),
            "postree.from_root": (1, 3, 3)}
    for n, (c, total, own) in want.items():
        assert ms[n][0] == c, n
        assert ms[n][1:] == pytest.approx((total, own)), n
    assert sp["postree.from_root"]["inside"] == {"engine.prove_member": 1}
    assert sp["kernel.chunker"]["inside"] == {
        "postree.splice": 1, "engine.put": 1, "live.fold": 1,
        "engine.commit_epoch": 1}
    assert sp["engine.commit_epoch"]["inside"] == {}
    # device idle, by the innermost program span open; the device ran
    # [28, 30] and [63, 64]
    idle = {n: v * 1e3 for n, v in got["idle"].items()}
    assert idle == pytest.approx({
        "engine.sync": 5, "none": 5 + 2 + 0.4, "engine.commit_epoch": 2,
        "live.fold": 2, "engine.put": 36, "postree.splice": 20,
        "kernel.chunker": 8, "postree.rebuild_index": 6,
        "kernel.fphash": 3, "engine.prove_member": 4.6,
        "postree.from_root": 3})
    assert list(got["idle"])[0] == "engine.put"          # largest first
    assert sum(idle.values()) == pytest.approx(100 - 3)
    assert got["kernel_host_s"] * 1e3 == pytest.approx(14 - 3)
    assert {k: v * 1e3 for k, v in got["kernel_device_s"].items()} == \
        pytest.approx({"chunker": 2, "fphash": 1})
    # the shares the per-layer metrics read
    assert _share(got, "postree.splice") == pytest.approx(20.0)
    assert _share(got, "postree.rebuild_index") == pytest.approx(6.0)
    assert _share(got, "postree.from_root") == pytest.approx(3.0)


def test_program_span_readers_on_hand_made_events():
    rec = {"program": _hand_made_program(),
           "counters": {"postree_leaf_index_rebuilds_total": 2}}
    assert _reader("splice_share_pct").read(rec) == pytest.approx(20.0)
    assert _reader("index_share_pct").read(rec) == pytest.approx(6.0)
    assert _reader("tree_load_share_pct").read(rec) == pytest.approx(3.0)
    assert _reader("kernel_host_share_pct").read(rec) == pytest.approx(11.0)
    # two proofs, one of which loaded its tree
    assert _reader("proof_cache_hit_pct").read(rec) == pytest.approx(50.0)
    assert _reader("leaf_index_rebuilds_per_block").read(rec) == 2.0


@pytest.mark.parametrize("metric", [
    "splice_share_pct", "index_share_pct", "tree_load_share_pct",
    "kernel_host_share_pct", "proof_cache_hit_pct",
    "leaf_index_rebuilds_per_block"])
def test_program_span_readers_find_nothing_to_read(metric):
    """An untraced run, a trace with no program span, or a program with no
    such counter: the reader returns nothing, never 0."""
    untraced = {"program": None, "counters": {}}
    bare = {"program": program.reduce_program(
                {"/device:TPU:0": [("late", 5 * MS, 6 * MS)]},
                {"python": [ev("bench.window", 0, 10)]}, NAMES, SPANS),
            "counters": {}}
    assert _reader(metric).read(untraced) is None
    assert _reader(metric).read(bare) is None


def test_program_without_spans_reduces_to_nothing():
    """A program that writes no spans into the trace (one older than its
    spans, or one traced with no factory set) gives empty results and no
    error: the benchmark's own annotation and an unmarked event named as a
    program span are no program spans."""
    got = program.reduce_program(
        {"/device:TPU:0": [("late", 5 * MS, 6 * MS)]},
        {"python": [("bench.window", 0, 10 * MS),
                    ("commit_epoch", 1 * MS, 9 * MS),
                    ("engine.commit_epoch", 2 * MS, 8 * MS)]}, NAMES, SPANS)
    assert got["spans"] == {} and got["kernel_device_s"] == {}
    assert got["kernel_host_s"] == 0
    assert got["idle"] == pytest.approx({"none": 0.009})


def test_program_reduction_needs_the_window_annotation():
    with pytest.raises(ValueError, match="bench.window"):
        program.reduce_program({}, {"python": [("live.fold", 0, MS)]},
                               NAMES, SPANS)


def test_program_span_names_stay_apart_from_the_benchmarks():
    """The mirrored factory writes each program span behind the mark, so
    a span of any name, one a driver's annotation shares among them, is
    told apart from the benchmark's annotations: ``trace.py``'s breakdown
    reads the same with the spans on, and the reduction keeps every
    marked span whatever its name."""
    opened = []
    factory = program.mirrored(lambda name: opened.append(name), SPANS)
    factory("engine.put")
    factory("ledger.transfer")
    assert opened == [MARK + "engine.put", MARK + "ledger.transfer"]
    assert not any(n.startswith(MARK) for n in NAMES["host_activities"])
    assert not NAMES["window"].startswith(MARK)
    got = program.reduce_program({}, {"python": [
        ev("bench.window", 0, 10), ev("ledger.transfer", 1, 9),
        ev(MARK + "ledger.transfer", 2, 8),
        ev(MARK + "engine.put", 3, 4)]}, NAMES, SPANS)
    assert {n: s["count"] for n, s in got["spans"].items()} == {
        "ledger.transfer": 1, "engine.put": 1}
    assert got["spans"]["engine.put"]["inside"] == {"ledger.transfer": 1}
    tr = trace.reduce_events({"/device:TPU:0": []}, [
        ("bench.window", 0, 10 * MS), ("ledger.transfer", 1 * MS, 9 * MS),
        (MARK + "ledger.transfer", 2 * MS, 8 * MS)], NAMES,
        {"ledger.transfer"})
    assert dict(tr["idle_gaps"]) == pytest.approx(
        {"ledger.transfer": 0.008, "client": 0.002})


def test_recorded_ledger_block_trace_with_program_spans():
    """``data/v5e_ledger_block_spans.xplane.pb``: a ledger-block run on one
    TPU v5 lite chip (seed 2147514001, ``--seconds 2.5``, traced with the
    program's spans mirrored into the trace: three blocks), cut to the
    lines the reductions read (the device's ``XLA Ops``, the host's
    Python thread) with every event's name, start and duration kept; both
    reductions read it as they read the whole recording.  Its program
    opened ``postree.splice`` inside ``_splice_span_elements``, so each
    cluster's frame teardown fell to ``engine.put``; the span now closes
    after the call, which moves time between those two, not counts.  Its
    spans carry no mark: the documented names of ``program_spans.json``
    are marked here as the mirrored factory marks them now."""
    path = DATA / "v5e_ledger_block_spans.xplane.pb"
    got = _recorded_program(path)
    ours = {"commit_epoch", "sync", "live.put", "live.get"}
    tr = trace.read_trace(path, NAMES, chips=1, activities=ours)
    assert got["window_s"] == pytest.approx(tr["window_s"])
    # the benchmark's own breakdown still sees only its annotations
    assert set(dict(tr["idle_gaps"])) == ours | {"client"}
    sp = got["spans"]
    blocks = sp["engine.commit_epoch"]["count"]
    assert blocks == 3
    for name in ("live.fold", "engine.put", "postree.rebuild_index",
                 "engine.sync"):
        assert sp[name]["count"] == blocks, name
    assert sum(s["count"] for s in sp.values()) <= 300 * blocks
    # one span per launch: the run's launch counters read 251 and 281
    assert sp["kernel.chunker"]["count"] == 251
    assert sp["kernel.fphash"]["count"] == 281
    assert sp["postree.splice"]["count"] == 251        # one launch each
    assert sp["kernel.chunker"]["inside"]["postree.splice"] == 251
    assert sp["postree.splice"]["inside"] == {
        "engine.put": 251, "live.fold": 251, "engine.commit_epoch": 251}
    assert sp["store.put"]["inside"]["engine.sync"] == blocks
    for s in sp.values():
        assert 0 <= s["self_s"] <= s["total_s"] * (1 + 1e-9)
    # every kernel's device time runs inside its own launch spans
    assert got["kernel_device_s"] == pytest.approx(tr["kernel_s"])
    assert sum(got["idle"].values()) == pytest.approx(
        tr["window_s"] - tr["busy_s"], rel=1e-9)
    assert list(got["idle"])[0] == "postree.splice"
    # what that run printed for the splice, index and launch-host shares
    assert _share(got, "postree.splice") == pytest.approx(
        69.61477423652191, rel=1e-9)
    assert _share(got, "postree.rebuild_index") == pytest.approx(
        2.1347831757746873, rel=1e-9)
    assert 100.0 * got["kernel_host_s"] / got["window_s"] == pytest.approx(
        20.4205512017943, rel=1e-9)
    # and what the per-layer readers make of it; the counter read 0
    rec = {"program": got,
           "counters": {"postree_leaf_index_rebuilds_total": 0}}
    assert _reader("splice_share_pct").read(rec) == pytest.approx(
        69.61477423652191, rel=1e-9)
    assert _reader("index_share_pct").read(rec) == pytest.approx(
        2.1347831757746873, rel=1e-9)
    assert _reader("kernel_host_share_pct").read(rec) == pytest.approx(
        20.4205512017943, rel=1e-9)
    assert _reader("leaf_index_rebuilds_per_block").read(rec) == 0.0
    # a ledger-block window has no proof
    assert _reader("tree_load_share_pct").read(rec) is None
    assert _reader("proof_cache_hit_pct").read(rec) is None


def _recorded_program(path: Path) -> dict:
    """The recorded trace reduced with its program spans marked."""
    bare = set(SPANS["spans"])
    device, host = program.load_events(path, NAMES, 1, lambda n: True)
    host = {line: [(MARK + n if n in bare else n, s, e)
                   for n, s, e in evs] for line, evs in host.items()}
    return program.reduce_program(device, host, NAMES, SPANS)
