"""The program-span reduction (``fbbench/program.py``).

``data/v5e_ledger_block_spans.xplane.pb`` is a short ledger-block window
recorded on one TPU v5 lite chip with the program's spans mirrored into
the trace (``obs.annotate_with(jax.profiler.TraceAnnotation)`` set around
the traced window)."""
from pathlib import Path

import pytest

from fbbench import program, trace

DATA = Path(__file__).resolve().parent / "data"
NAMES = trace.load_names()
SPANS = program.load_spans()
MS = 1_000_000


def _share(got: dict, name: str) -> float:
    """A span's self time over the window, in percent."""
    return 100.0 * got["spans"][name]["self_s"] / got["window_s"]


def _hand_made_program():
    """A fold and two proofs on one thread line, a span cut by the
    window's start, a benchmark annotation (not a program span) and
    device operations of both kernels.  Milliseconds."""
    def ev(name, s, e):
        return (name, int(s * MS), int(e * MS))
    host = {"python": [
        ev("bench.window", 0, 100),
        ev("engine.sync", -5, 5),
        ev("engine.commit_epoch", 10, 90), ev("live.fold", 11, 89),
        ev("engine.put", 12, 88),
        ev("postree.splice", 20, 50), ev("kernel.chunker", 25, 35),
        ev("postree.rebuild_index", 60, 70), ev("kernel.fphash", 62, 66),
        ev("live.get", 90.5, 91),
        ev("engine.prove_member", 92, 99), ev("postree.from_root", 93, 96),
        ev("engine.prove_member", 99.2, 99.8)],
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
    # the shares a later per-layer metric would read
    assert _share(got, "postree.splice") == pytest.approx(20.0)
    assert _share(got, "postree.rebuild_index") == pytest.approx(6.0)
    assert _share(got, "postree.from_root") == pytest.approx(3.0)


def test_program_without_spans_reduces_to_nothing():
    """A program that writes no spans into the trace (one older than its
    spans, or one traced with no factory set) gives empty results and no
    error: the benchmark's own annotation is no program span."""
    got = program.reduce_program(
        {"/device:TPU:0": [("late", 5 * MS, 6 * MS)]},
        {"python": [("bench.window", 0, 10 * MS),
                    ("commit_epoch", 1 * MS, 9 * MS)]}, NAMES, SPANS)
    assert got["spans"] == {} and got["kernel_device_s"] == {}
    assert got["kernel_host_s"] == 0
    assert got["idle"] == pytest.approx({"none": 0.009})


def test_program_reduction_needs_the_window_annotation():
    with pytest.raises(ValueError, match="bench.window"):
        program.reduce_program({}, {"python": [("live.fold", 0, MS)]},
                               NAMES, SPANS)


def test_program_span_names_stay_apart_from_the_benchmarks():
    """No program span shares a name with the benchmark's annotations, so
    ``trace.py``'s breakdown reads the same with the spans on."""
    ours = set(SPANS["spans"])
    assert len(ours) == len(SPANS["spans"]) == 13
    assert not ours & set(NAMES["host_activities"])
    assert NAMES["window"] not in ours


def test_recorded_ledger_block_trace_with_program_spans():
    """``data/v5e_ledger_block_spans.xplane.pb``: a ledger-block run on one
    TPU v5 lite chip (seed 2147514001, ``--seconds 2.5``, traced with the
    program's spans mirrored into the trace: three blocks), cut to the
    lines the reductions read (the device's ``XLA Ops``, the host's
    Python thread) with every event's name, start and duration kept; both
    reductions read it as they read the whole recording.  Its program
    opened ``postree.splice`` inside ``_splice_span_elements``, so each
    cluster's frame teardown fell to ``engine.put``; the span now closes
    after the call, which moves time between those two, not counts."""
    path = DATA / "v5e_ledger_block_spans.xplane.pb"
    got = program.read_program(path, NAMES, SPANS, chips=1)
    tr = trace.read_trace(path, NAMES, chips=1)
    assert got["window_s"] == pytest.approx(tr["window_s"])
    # the benchmark's own breakdown still sees only its annotations
    assert set(dict(tr["idle_gaps"])) == {"commit_epoch", "sync", "client",
                                          "live.put", "live.get"}
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
