"""Telemetry overhead — the obs plane must be invisible on the jit hot
path (PR 10 acceptance numbers, written to BENCH_pr10.json).

Three planes over the same submitted workload (OPMW pool on the
inprocess backend, ``execute=True`` so every step runs the jit-compiled
segment functions):

  * **off**     — ``configure_obs(metrics=False, trace=False)``: null
    registry, tracer disarmed. The honest baseline.
  * **default** — metrics registry live, tracing off. This is the
    out-of-the-box configuration; the acceptance bar applies here.
  * **traced**  — metrics + span recording at the default sample stride,
    the worst case anyone can switch on without touching knobs.

The bar: *default* overhead < 3% of *off* ms/step. Timing interleaves
the planes round-robin (one window each, repeated) so drift/thermal
noise hits all three equally, and takes the best window per plane.
*traced* overhead is recorded informationally (no bar — span recording
is opt-in).

Any missed bar exits 2 (the CI contract); ``--smoke`` shrinks the step
counts for the CI job while keeping the bar armed.

Usage:
    PYTHONPATH=src python benchmarks/obs_overhead_bench.py \
        [--steps 60] [--windows 7] [--smoke] \
        [--out results/benchmarks/BENCH_pr10.json]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, List

try:  # package (python -m benchmarks.run) vs script (python benchmarks/foo.py)
    from benchmarks._host import stamp
except ImportError:  # pragma: no cover - script execution path
    from _host import stamp

PLANES = ("off", "default", "traced")


def _make_session(plane: str):
    from repro.api import ReuseSession
    from repro.workloads import opmw_workload

    session = ReuseSession(strategy="signature", execute=True, backend="inprocess")
    for df in opmw_workload():
        session.submit(df.copy())
    if plane == "off":
        session.configure_obs(metrics=False, trace=False)
    elif plane == "default":
        session.configure_obs(metrics=True, trace=False)
    elif plane == "traced":
        session.configure_obs(metrics=True, trace=True)
    else:  # pragma: no cover - guarded by PLANES
        raise ValueError(plane)
    session.run(3)  # compile + warm every segment before any timed window
    return session


def bench_overhead(steps: int, windows: int) -> Dict[str, Any]:
    sessions = {plane: _make_session(plane) for plane in PLANES}
    best: Dict[str, float] = {plane: float("inf") for plane in PLANES}
    try:
        # round-robin windows: plane order rotates so no plane always runs
        # first (cold) or last (thermally throttled)
        for w in range(windows):
            order = PLANES[w % len(PLANES):] + PLANES[: w % len(PLANES)]
            for plane in order:
                session = sessions[plane]
                if plane == "traced":
                    session.drain_spans()  # empty ring: steady-state recording cost
                t0 = time.perf_counter()
                session.run(steps)
                best[plane] = min(best[plane], (time.perf_counter() - t0) / steps)
    finally:
        for session in sessions.values():
            session.close()
    ms = {plane: 1e3 * best[plane] for plane in PLANES}
    return {
        "steps": steps,
        "windows": windows,
        "ms_per_step": {k: round(v, 4) for k, v in ms.items()},
        "default_overhead_pct": round(100.0 * (ms["default"] / ms["off"] - 1.0), 2),
        "traced_overhead_pct": round(100.0 * (ms["traced"] / ms["off"] - 1.0), 2),
    }


def bench_instrument_cost(reps: int = 200_000) -> Dict[str, Any]:
    """Microcosts of one counter inc / histogram observe / sampled span,
    live vs null — context for the end-to-end number, no bar."""
    from repro.obs import MetricsRegistry, NULL_REGISTRY, Tracer

    rows: List[Dict[str, Any]] = []
    for name, reg in (("live", MetricsRegistry()), ("null", NULL_REGISTRY)):
        c = reg.counter("bench_counter", "bench")
        h = reg.histogram("bench_hist", "bench")
        t0 = time.perf_counter()
        for _ in range(reps):
            c.inc()
        inc_ns = 1e9 * (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            h.observe(1.5)
        obs_ns = 1e9 * (time.perf_counter() - t0) / reps
        rows.append(
            {"registry": name, "counter_inc_ns": round(inc_ns, 1),
             "histogram_observe_ns": round(obs_ns, 1)}
        )
    tracer = Tracer(enabled=True, capacity=4096)
    t0 = time.perf_counter()
    for _ in range(reps // 10):
        with tracer.span("bench", "step"):
            pass
    span_ns = 1e9 * (time.perf_counter() - t0) / (reps // 10)
    return {"reps": reps, "rows": rows, "span_ns": round(span_ns, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60, help="steps per timed window")
    ap.add_argument("--windows", type=int, default=7)
    ap.add_argument("--max-overhead-pct", type=float, default=3.0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: fewer/shorter windows, bar stays armed")
    ap.add_argument("--out", default=os.path.join("results", "benchmarks", "BENCH_pr10.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        args.steps, args.windows = min(args.steps, 25), min(args.windows, 4)

    print(f"obs overhead on the jit hot path ({args.windows} windows x {args.steps} steps):")
    overhead = bench_overhead(args.steps, args.windows)
    for plane in PLANES:
        print(f"  {plane:8s}: {overhead['ms_per_step'][plane]:8.3f} ms/step")
    print(f"  default overhead: {overhead['default_overhead_pct']:+.2f}%   "
          f"traced: {overhead['traced_overhead_pct']:+.2f}%")

    print("instrument microcosts (live vs null registry):")
    micro = bench_instrument_cost()
    for r in micro["rows"]:
        print(f"  {r['registry']:5s}: inc {r['counter_inc_ns']:7.1f} ns   "
              f"observe {r['histogram_observe_ns']:7.1f} ns")
    print(f"  span (enabled, stride 1): {micro['span_ns']:.1f} ns")

    bars = {
        "default_overhead_lt_3pct":
            overhead["default_overhead_pct"] < args.max_overhead_pct,
    }
    record = stamp(
        {
            "bench": "obs_overhead",
            "smoke": bool(args.smoke),
            "max_overhead_pct": args.max_overhead_pct,
            "overhead": overhead,
            "micro": micro,
            "bars": bars,
            "all_bars_met": all(bars.values()),
        }
    )
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")
    if not record["all_bars_met"]:
        print(f"ACCEPTANCE BARS MISSED: {[k for k, v in bars.items() if not v]}")
        return 2
    print("all acceptance bars met")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
