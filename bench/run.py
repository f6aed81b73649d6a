#!/usr/bin/env python3
"""ForkBase's benchmark: one cell of BENCHMARK.json per process, on a TPU.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration and a traffic mix.  The harness finds
everything by name: ``bench/configs/<config>.json`` (sizes, skew,
guarantees, flush policy, and the driver that runs it),
``bench/drivers/<driver>.py``, ``bench/mixes/<traffic>.json`` and one
reader ``bench/metrics/<metric>.py`` per per-layer metric.  A reader gets
the window's record: the benchmark's own timers, the delta of every
program counter, and with ``--trace 1`` the reduced device trace and the
program's spans read from it.

A run switches ForkBase to its device path (the Pallas chunker and the
Pallas fphash cid kernel), builds the cell's state from the seed, warms
every kernel shape the window can reach, then drives the cell with one
client in a closed loop for ``--seconds``.  With ``--trace 0`` the last
line of standard output holds the end-to-end metrics, with ``--trace 1``
the per-layer ones, read from a profiler trace of the window.  After the
window the plain reference (``fbbench/reference.py``) decides
``correct``; each number compared is printed beside its limit, last on
standard error and last in the result line.

Off a TPU the run prints nothing on standard output and exits 2.
``--rehearse`` runs the cell at the configuration's tiny rehearsal sizes
on any platform and never prints a measurement (its line starts with
``REHEARSAL`` and it exits 3).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WORK = BENCH / ".work"
CACHE = BENCH / ".jax_cache"
REHEARSAL_EXIT = 3


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deep_update(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (deep_update(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def find_cell(name: str, rehearse: bool) -> dict:
    """The cell's entry of BENCHMARK.json and every file it names."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_spec = next(c for c in spec["configs"] if c["name"] == w["config"])
    cfg_file = ROOT / cfg_spec["file"]
    cfg = json.loads(cfg_file.read_text())
    if rehearse:
        cfg = deep_update(cfg, cfg["rehearsal"])
    mix_file = BENCH / "mixes" / f"{w['traffic']}.json"
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layers = [m for m in spec["per_layer"]
              if (name in m["workloads"] if "workloads" in m
                  else m["moves"] in reported)]
    return {"workload": w, "config": cfg, "config_file": cfg_file,
            "mix": json.loads(mix_file.read_text()), "mix_file": mix_file,
            "driver_file": BENCH / "drivers" / f"{cfg['driver']}.py",
            "end_to_end": e2e, "per_layer": layers,
            "metric_files": {m["name"]: BENCH / "metrics" / f"{m['name']}.py"
                             for m in layers}}


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


def program_counters() -> dict:
    """Every counter in the program's registry, keyed by its name and
    labels as the registry renders them:
    ``kernel_launches{kernel="fphash"}``."""
    from repro import obs
    return {key: inst.value for key, inst in obs.REGISTRY.instruments()
            if isinstance(inst, obs.Counter)}


@contextlib.contextmanager
def traced(jax, trace_dir: Path):
    """Profile the block, the program's spans mirrored into the trace
    (``fbbench.program.mirrored``); the span factory that was set before
    is set again on every way out."""
    from fbbench.program import load_spans, mirrored
    from repro import obs
    before = importlib.import_module("repro.obs.trace")._annotation
    # host: annotations only, no Python tracer
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    obs.annotate_with(mirrored(jax.profiler.TraceAnnotation, load_spans()))
    try:
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.annotate_with(before)


def run_cell(cell: dict, args, jax, peaks: dict | None) -> dict:
    """Set up, warm, measure, reduce and check one cell; returns what the
    result line and the earlier lines hold."""
    import numpy as np

    from fbbench.compiles import CompileLog
    from fbbench.loop import Spans, Traffic, run_window
    from repro.core import hashing
    from repro.kernels import ops

    work = WORK / args.workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    ops.use_pallas_chunker(True)
    hashing.use_fphash()
    driver_mod = load_module(cell["driver_file"], "bench_driver")
    spans = Spans(jax.profiler.TraceAnnotation)
    with CompileLog(jax.monitoring) as log:
        driver = driver_mod.Driver(cell["config"], args.seed, work, spans)
        driver.setup()
        driver.warm()
        traffic = Traffic(cell["mix"], driver.n_keys, driver.theta,
                          np.random.default_rng(np.random.SeedSequence(
                              args.seed, spawn_key=(99,))),
                          start=cell["config"].get("cycle_start", 0))
        kinds = traffic.kinds
        driver.counted = set(cell["mix"].get("counted", kinds))
        setup_s = time.perf_counter() - T_START
        c0, p0 = log.snapshot(), program_counters()
        driver.begin_window()
        spans.on = True
        with (traced(jax, work / "trace") if args.trace
              else contextlib.nullcontext()):
            jax.config.update("jax_log_compiles", True)   # none expected
            with jax.profiler.TraceAnnotation("bench.window"):
                win = run_window(driver, traffic, args.seconds)
            jax.config.update("jax_log_compiles", False)
        spans.on = False
        c1, p1 = log.snapshot(), program_counters()
    stats = jax.devices()[0].memory_stats() or {}
    e2e = driver.metrics(win["window_s"])
    e2e["setup_s"] = setup_s
    counters = {k: v - p0.get(k, 0) for k, v in p1.items()}
    rec = {"window_s": win["window_s"], "ops": driver.completed,
           "attempted": win["attempted"],
           "spans": dict(spans.seconds),
           "counters": counters,
           "kernels": {f"{name}.{k}": counters.get(
               f'{name}{{kernel="{k}"}}', 0)
               for name in ("kernel_launches", "kernel_bytes")
               for k in ("chunker", "fphash")},
           "peaks": peaks, "trace": None, "program": None}
    if args.trace:
        from fbbench.program import load_spans, read_program
        from fbbench.trace import find_xplane, load_names, read_trace
        xplane = find_xplane(work / "trace")
        if xplane is not None:
            chips = cell["workload"]["chips"]
            rec["trace"] = read_trace(xplane, load_names(), chips,
                                      set(spans.seconds))
            rec["program"] = read_program(xplane, load_names(),
                                          load_spans(), chips)
    layer = {}
    for m in cell["per_layer"]:
        reader = load_module(cell["metric_files"][m["name"]],
                             f"bench_metric_{m['name']}")
        v = reader.read(rec)
        if v is not None:
            layer[m["name"]] = v
    try:
        checks = driver.check()
    except Exception:                  # a check that cannot finish fails
        traceback.print_exc()
        checks = [("check_raised", 1, 0)]
    checks.append(("failed_ops", win["failed"], 0))
    return {"e2e": e2e, "layer": layer, "rec": rec, "win": win,
            "checks": checks, "timings": driver.timings(),
            "compiles": {k: c1[k] - c0[k] for k in c1},
            "setup_compiles": c0, "memory_peak_bytes":
                int(stats.get("peak_bytes_in_use", 0))}


def is_correct(checks: list[tuple[str, int, int | None]]) -> bool:
    """Every number compared is within its limit (a number with no
    limit is reported, not compared)."""
    return all(lim is None or v <= lim for _, v, lim in checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform; prints no measurement")
    args = ap.parse_args(argv)

    cell = find_cell(args.workload, args.rehearse)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    devs = jax.devices()
    dev = devs[0]
    chips = cell["workload"]["chips"]
    on_tpu = dev.platform == "tpu"
    if not args.rehearse and (not on_tpu or len(devs) < chips):
        print(f"bench: {len(devs)} {dev.platform} device(s), the cell needs "
              f"{chips} TPU chip(s); nothing was measured", file=sys.stderr)
        return 2
    peaks = None
    if on_tpu:
        from fbbench.compiles import configure_compile_cache
        cache = configure_compile_cache(jax, CACHE)
        peaks = peaks_for(dev.device_kind)
        print(f"bench: {args.workload} on {dev.platform} {dev.device_kind} "
              f"x{len(devs)}, seed {args.seed}, compile cache {cache}",
              flush=True)
    try:
        out = run_cell(cell, args, jax, peaks)
    finally:
        shutil.rmtree(WORK / args.workload, ignore_errors=True)

    units = {m["name"]: m["unit"] for m in
             cell["end_to_end"] + cell["per_layer"]}
    chosen = out["layer"] if args.trace else {
        m["name"]: out["e2e"][m["name"]] for m in cell["end_to_end"]
        if m["name"] in out["e2e"]}
    checks = {name: {"value": v, "limit": lim}
              for name, v, lim in out["checks"]}
    correct = is_correct(out["checks"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes":
                  out["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": out["win"]["attempted"],
            "failed": out["win"]["failed"],
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in chosen.items()},
            "device": device}
    tr = out["rec"]["trace"]
    if args.trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        line["metrics"] = {k: {"value": None, "unit": units[k]}
                           for k in line["metrics"]}
        for k in ("memory_peak_bytes", "busy_s", "window_s"):
            if k in device:
                device[k] = None
        line.pop("breakdown", None)
        line["found"] = {"config": str(cell["config_file"]),
                         "mix": str(cell["mix_file"]),
                         "driver": str(cell["driver_file"]),
                         "metrics": {k: str(v) for k, v in
                                     cell["metric_files"].items()}}
        print("REHEARSAL " + json.dumps(line), flush=True)
        return REHEARSAL_EXIT
    print("timings " + json.dumps(out["timings"]))
    print("compiles_in_window " + json.dumps(out["compiles"]))
    print("setup_compiles " + json.dumps(out["setup_compiles"]))
    print("window " + json.dumps({
        "window_s": out["win"]["window_s"], "ops": out["rec"]["ops"],
        "errors": out["win"]["errors"], "spans": out["rec"]["spans"],
        "kernels": out["rec"]["kernels"], "e2e": out["e2e"],
        "layer": out["layer"]}))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
