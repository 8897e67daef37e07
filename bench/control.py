#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip at the cell's
own size: for each seed, one run of the cell (a short window at the cell's
load) whose program readings give the lower end, and the plain reference
computed in bfloat16 in the program's place (the control) whose readings
give the upper end. One process, one JSON line per seed.

    python3 bench/control.py --workload riot21.steady --seeds 1,2,3 --seconds 10

``--draw-seed`` replaces a drawing traffic's ``draw_seed``, so the check can
be read on other draws of the swaps than the cell's fixed one.

The benchmark's own runs (``run.py``) never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="bfloat16")
    ap.add_argument("--draw-seed", type=int)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    from lib.cell import load_cell
    from lib.clock import CompileClock
    from lib.harness import execute

    cell = load_cell(args.workload, ROOT)
    if args.draw_seed is not None:
        if "draw_seed" not in cell.traffic:
            print(f"control: {args.workload}'s traffic draws nothing", file=sys.stderr)
            return 1
        cell.traffic = {**cell.traffic, "draw_seed": args.draw_seed}
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {args.workload} needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 1
    clock = CompileClock()
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        result = execute(cell, seed, args.seconds, False, t, devices[:cell.chips],
                         clock=clock, controls=(args.control,))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "draw_seed": cell.traffic.get("draw_seed"), "correct": result["correct"],
                          "program": {k: c["value"] for k, c in result["checks"].items()},
                          "control": result["controls"][args.control],
                          "run": result["run"], "metrics": result["metrics"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
