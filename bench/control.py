#!/usr/bin/env python3
"""Run a cell with a plant from ``fbbench/plants.py`` (the control, or a
fault) on several seeds in one process, and print what the check read on
each: the readings the limits of ``correct`` are set from.

    python bench/control.py --workload <cell> --plant control \\
        --seeds 11,12,13 --seconds 30

``--plant none`` runs the program as it is (sound runs, for the lower
readings).  A TPU is required, as for ``run.py``; ``--rehearse`` runs at
the configuration's tiny rehearsal sizes on any platform.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import run
    cell = run.find_cell(args.workload, args.rehearse)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("control: not a TPU; nothing was run", file=sys.stderr)
        return 2
    if dev.platform == "tpu":
        from fbbench.compiles import configure_compile_cache
        configure_compile_cache(jax, run.CACHE)
    from contextlib import nullcontext

    from fbbench.plants import planted
    from repro.core import hashing
    from repro.kernels import ops
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        plant = (nullcontext() if args.plant == "none"
                 else planted(args.plant))
        try:
            with plant:
                out = run.run_cell(cell, ns, jax, None)
        finally:
            run.shutil.rmtree(run.WORK / args.workload, ignore_errors=True)
            ops.use_pallas_chunker(False)
            hashing.use_sha256()
        print("control " + json.dumps({
            "workload": args.workload, "plant": args.plant, "seed": seed,
            "correct": run.is_correct(out["checks"]),
            "failed": out["win"]["failed"],
            "errors": out["win"]["errors"],
            "checks": {n: v for n, v, _ in out["checks"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
