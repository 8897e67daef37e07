#!/usr/bin/env python3
"""Runs one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload riot21.steady --seed 7 --seconds 45 --trace 0

The cell (``--workload``) is an entry of ``BENCHMARK.json``: a configuration
(``bench/configs/<name>.json``) under a traffic mix
(``bench/traffic/<name>.json``). ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics with the device trace's busy and
window seconds and a breakdown. The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, ...);
the numbers compared to decide ``correct`` close standard error, each beside
its limit.

It needs a TPU with at least the cell's chips and never falls back to the
CPU: without them it exits 1 and prints no result. JAX's persistent
compilation cache is kept at ``.jax_cache`` in the checkout.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The cache sits in the checkout at a fixed path: the program takes JAX's
    # setting, so an inherited JAX_COMPILATION_CACHE_DIR must not win.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    from lib.cell import load_cell

    cell = load_cell(args.workload, ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 1
    import repro  # noqa: F401  (fails here, with no result, without the program)
    from lib.harness import execute

    result = execute(cell, args.seed, args.seconds, bool(args.trace), T_START,
                     devices[:cell.chips])
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
