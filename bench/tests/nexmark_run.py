"""One run of ``nexmark.steady`` on the host's CPU at a small size, with a
fault planted in the program when one is named; prints the result line.
Used by ``test_nexmark_cell.py`` in a process of its own.

    python bench/tests/nexmark_run.py <fault|none> <batch> <rate> <seconds> <seed>

``rate`` replaces the window tasks' events per second of event time, so a
short run at a small batch closes many windows (a Q5 pane is ``5 * rate``
events, a Q7 window ``10 * rate``; both must be at least the batch).

Faults:
- ``count_off``: Q5 emits the first count of every closing window one too
  high;
- ``pane_late``: every pane and window boundary falls one event late (the
  first event of each belongs to the one before);
- ``control``: no fault in the program; the plain reference in bfloat16 is
  judged in the program's place.
"""
import dataclasses
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from repro.ops import base, nexmark

    if fault == "count_off":
        orig = base.make_operator

        def make_operator(type_name, config):
            op = orig(type_name, config)
            if type_name != "nx_hot_items":
                return op

            def apply(s, x):
                s2, y = op.apply(s, x)
                return s2, y.at[0, 1].add(jnp.where(y[0, 6] > 0.5, 1.0, 0.0))
            return dataclasses.replace(op, apply=apply)
        base.make_operator = make_operator
    elif fault == "pane_late":
        orig_segments = nexmark._segments

        def segments(x, length):
            e = nexmark._join(x[:, 0], x[:, 1]) - 1
            late = x.at[:, 0].set((e // nexmark.SPLIT).astype(x.dtype))
            late = late.at[:, 1].set((e % nexmark.SPLIT).astype(x.dtype))
            valid, _, idx, seg, ends, present = orig_segments(late, length)
            return valid, e + 1, idx, seg, ends, present
        nexmark._segments = segments
    elif fault not in ("none", "control"):
        raise ValueError(fault)


def main() -> int:
    fault, batch, rate, seconds, seed = sys.argv[1:6]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import jax

    from lib import check
    from lib.cell import load_cell
    from lib.harness import execute

    plant(fault)
    cell = load_cell("nexmark.steady", ROOT)
    cell.collection = [dict(f, steps=[[t, dict(c, rate=int(rate)) if "rate" in c else c]
                                      for t, c in f["steps"]]) for f in cell.collection]
    controls = ("bfloat16",) if fault == "control" else ()
    result = execute(cell, int(seed), float(seconds), False, T_START, jax.devices()[:1],
                     batch=int(batch), controls=controls)
    if fault == "control":
        ok, checks = check.judge(result["controls"]["bfloat16"], cell.limits)
        result["correct"], result["checks"] = ok, checks
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
