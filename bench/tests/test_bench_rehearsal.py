"""Each cell end to end at the configuration's tiny rehearsal sizes on
the CPU.

In a child process, as a benchmark run starts: the harness finds the cell's
configuration, mix, driver and metric readers by name, the last line has
the contract's keys, and off a TPU no measurement is printed.

In this process, with the harness's look for a chip skipped: the check
passes a sound run and fails a run with each plant of fbbench/plants.py
(the control and the faults) armed under the timed path.

Both kinds build their state under bench/.work, so they live in one file
and never run at the same time."""
import argparse
import json
import os
import shutil
import subprocess
import sys
from contextlib import nullcontext
from pathlib import Path

import pytest

import run
from fbbench.plants import PLANTS, planted

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 3_000_000_019          # above 2**31, as benchmark seeds may be


def run_bench(*args, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(BENCH / "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_finds_the_cell_and_prints_no_measurement(cell):
    p = run_bench("--workload", cell, "--seed", str(SEED), "--seconds", "0.5",
                  "--trace", "1", "--rehearse")
    assert p.returncode == 3, p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL "), last
    line = json.loads(last[len("REHEARSAL "):])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-2:] == ["checks", "found"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] is None
    assert "breakdown" not in line
    assert all(m["value"] is None for m in line["metrics"].values())
    # every stdout line of a rehearsal: nothing measured is printed
    assert p.stdout.strip().splitlines() == [last]
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    found = line["found"]
    assert Path(found["mix"]) == BENCH / "mixes" / f"{w['traffic']}.json"
    assert Path(found["config"]) == BENCH / "configs" / f"{w['config']}.json"
    cfg = json.loads(Path(found["config"]).read_text())
    assert Path(found["driver"]) == BENCH / "drivers" / f"{cfg['driver']}.py"
    listed = {m["name"] for m in SPEC["per_layer"]
              if cell in m.get("workloads", [])}
    assert set(found["metrics"]) == listed
    for name, path in found["metrics"].items():
        assert Path(path) == BENCH / "metrics" / f"{name}.py"
    checks = line["checks"]
    assert all(set(c) == {"value", "limit"} for c in checks.values())
    assert checks["failed_ops"] == {"value": 0, "limit": 0}
    # the numbers compared are printed beside their limits, last on stderr
    tail = p.stderr.strip().splitlines()[-len(checks):]
    assert tail == [f"check {n} {c['value']} limit {c['limit']}"
                    for n, c in checks.items()]


def test_off_the_chip_a_run_prints_nothing():
    p = run_bench("--workload", CELLS[0], "--seed", str(SEED), "--seconds", "1",
                  "--trace", "0", timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "nothing was measured" in p.stderr


@pytest.fixture
def device_path_restored():
    yield
    from repro.core import hashing
    from repro.kernels import ops
    ops.use_pallas_chunker(False)
    hashing.use_sha256()


def run_planted(cell: str, plant: str | None, seed: int,
                trace: int = 0) -> dict:
    import jax
    spec = run.find_cell(cell, rehearse=True)
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5,
                              trace=trace)
    try:
        with planted(plant) if plant else nullcontext():
            return run.run_cell(spec, args, jax, None)
    finally:
        shutil.rmtree(run.WORK / cell, ignore_errors=True)


@pytest.mark.parametrize("cell,plant", [(c, p) for c in CELLS
                                        for p in [None, *PLANTS]])
def test_plant_makes_the_run_incorrect(cell, plant, device_path_restored):
    checks = run_planted(cell, plant, 2_900_000_007)["checks"]
    if plant is None:
        assert run.is_correct(checks), checks
    else:
        assert not run.is_correct(checks), checks


@pytest.fixture
def no_span_factory():
    from repro import obs
    obs.annotate_with(None)
    yield
    obs.annotate_with(None)


def test_untraced_run_counts_every_program_counter_and_sets_no_factory(
        monkeypatch, device_path_restored, no_span_factory):
    """``--trace 0``: the program runs with no span factory, and the
    readers get the window's delta of every counter of the program's
    registry, the kernels' among them."""
    from repro import obs
    calls = []
    monkeypatch.setattr(obs, "annotate_with", calls.append)
    out = run_planted(CELLS[0], None, 2_900_000_011)
    assert calls == []
    rec = out["rec"]
    assert rec["program"] is None and rec["trace"] is None
    counters = rec["counters"]
    for name in ("kernel_launches", "kernel_bytes"):
        assert counters[f'{name}{{kernel="chunker"}}'] == \
            rec["kernels"][f"{name}.chunker"] > 0
    assert counters['events_total{kind="live.fold"}'] > 0
    assert all(isinstance(v, int) and v >= 0 for v in counters.values())


@pytest.mark.parametrize("fails", [False, True], ids=["sound", "raises"])
def test_traced_run_restores_the_span_factory(fails, monkeypatch, tmp_path,
                                              device_path_restored,
                                              no_span_factory):
    """``--trace 1``: the mirrored factory is set for the traced window
    only, and the one set before is set again whether the window returns
    or raises; the trace is stopped either way."""
    import importlib

    import jax

    from fbbench import loop
    from repro import obs
    spans = importlib.import_module("repro.obs.trace")

    def before(name):
        return nullcontext()
    in_window = []
    window = loop.run_window

    def run_window(*a, **k):
        in_window.append(spans._annotation)
        if fails:
            raise RuntimeError("the window broke")
        return window(*a, **k)
    monkeypatch.setattr(loop, "run_window", run_window)
    obs.annotate_with(before)
    if fails:
        with pytest.raises(RuntimeError, match="the window broke"):
            run_planted(CELLS[0], None, 2_900_000_013, trace=1)
    else:
        rec = run_planted(CELLS[0], None, 2_900_000_013, trace=1)["rec"]
        # the program's spans, read back from the trace by their names
        assert rec["program"]["spans"]["engine.commit_epoch"]["count"] > 0
        assert "postree.splice" in rec["program"]["spans"]
    assert spans._annotation is before
    assert len(in_window) == 1 and in_window[0] not in (None, before)
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
