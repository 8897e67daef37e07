"""Hot-path roofline — zero-copy fetch, chain batching, segment fusion
(PR 8 acceptance numbers, written to BENCH_pr8.json).

Three sections, matching the three compounding hot-path changes:

  * **fetch**  — raw ``ShmTransport`` fetch cost across payload sizes,
    view (zero-copy, the new default) vs ``copy=True`` (the escape
    hatch). The bar: view-fetch cost is flat in payload size — it is a
    header decode + ``np.frombuffer`` over the mmap, no memcpy.
  * **chain** — a deep stack of same-worker segments (each submission
    extends the previous chain by one kalman stage, so the multiproc
    coordinator sees a 12-deep linear segment chain on one worker),
    stepped unbatched (one RPC per *wave*), chained (one ``step_chain``
    RPC per *step*), and chained+fused (the whole chain recompiled into
    one donated-buffer segment). The bar: chained ≥ ×1.5 step throughput
    over unbatched, fused at least as good as chained.
  * **trace** — the full OPMW rw1 churn trace replayed with and without
    periodic ``fuse()`` in both step modes; sink digests must be
    bit-identical (counts AND checksums).

Any missed bar exits 2 (the CI contract); ``--smoke`` shrinks the trace
section for the CI job while keeping every bar armed.

Usage:
    PYTHONPATH=src python benchmarks/hotpath_bench.py \
        [--depth 12] [--steps 30] [--smoke] \
        [--out results/benchmarks/BENCH_pr8.json]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, List

import numpy as np

try:  # package (python -m benchmarks.run) vs script (python benchmarks/foo.py)
    from benchmarks._host import stamp
except ImportError:  # pragma: no cover - script execution path
    from _host import stamp


# -- section 1: zero-copy fetch cost ------------------------------------------


def bench_fetch(sizes=(64, 1024, 16384, 131072), reps: int = 400) -> Dict[str, Any]:
    from repro.runtime.transport import ShmTransport

    t = ShmTransport()
    rows: List[Dict[str, Any]] = []
    try:
        for n in sizes:
            topic = f"stream/fetch{n}"
            batch = np.random.default_rng(7).random((n, 8)).astype(np.float32)
            t.publish(topic, batch)
            t.fetch(topic)  # attach + warm
            best = {"view": float("inf"), "copy": float("inf")}
            for mode, copy in (("view", False), ("copy", True)):
                for _ in range(5):
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        t.fetch(topic, copy=copy)
                    best[mode] = min(best[mode], (time.perf_counter() - t0) / reps)
            rows.append(
                {
                    "rows": n,
                    "nbytes": int(batch.nbytes),
                    "view_us": round(1e6 * best["view"], 3),
                    "copy_us": round(1e6 * best["copy"], 3),
                }
            )
    finally:
        t.close()
    # flatness: the largest payload's view fetch within 5x the smallest's
    # (both are O(1); the factor absorbs scheduler jitter on tiny times)
    vmin, vmax = rows[0]["view_us"], rows[-1]["view_us"]
    flat = vmax <= max(5.0 * vmin, vmin + 20.0)
    return {
        "rows": rows,
        "view_flat_in_size": bool(flat),
        "copy_over_view_at_largest": round(rows[-1]["copy_us"] / rows[-1]["view_us"], 2),
    }


# -- section 2: deep same-worker chain ----------------------------------------


def _stacked_chain_dags(depth: int):
    """dag k = source → kalman_1..k → sink_k; signature reuse makes each
    submission one new segment (kalman_k + sink_k) downstream of the
    previous — a depth-deep linear segment chain."""
    from repro.api import flow

    dags = []
    for k in range(1, depth + 1):
        b = flow(f"deep{k:02d}").source("sensor")
        for i in range(k):
            b.then("kalman", q=0.1, stage=i)
        dags.append(b.sink("store").build())
    return dags


def _bench_chain_plane(dags, steps: int, fuse: bool, chain_batching: bool,
                       base_batch: int, windows: int = 5):
    from repro.api import ReuseSession

    session = ReuseSession(
        strategy="signature",
        execute=True,
        base_batch=base_batch,
        backend="multiproc",
        workers=1,  # one worker = the whole chain is worker-local
        step_mode="concurrent",
        backend_options={"chain_batching": chain_batching},
    )
    for df in dags:
        session.submit(df.copy())
    if fuse:
        session.fuse()
    session.run(2)  # compile + warm
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        session.run(steps)
        best = min(best, (time.perf_counter() - t0) / steps)
    digests = {
        df.name: session.sink_digests(df.name) for df in dags
    }
    segments = len(session._system.backend.segments)
    session.close()
    return 1e3 * best, digests, segments


def bench_chain(depth: int, steps: int, base_batch: int = 64) -> Dict[str, Any]:
    dags = _stacked_chain_dags(depth)
    ms: Dict[str, float] = {}
    digests: Dict[str, Any] = {}
    segs: Dict[str, int] = {}
    for name, (fuse, chain) in {
        "unbatched": (False, False),
        "chained": (False, True),
        "chained_fused": (True, True),
    }.items():
        ms[name], digests[name], segs[name] = _bench_chain_plane(
            dags, steps, fuse, chain, base_batch
        )
        print(f"  {name:14s}: {ms[name]:8.2f} ms/step  ({segs[name]} segments)")
    identical = digests["chained"] == digests["unbatched"] == digests["chained_fused"]
    return {
        "depth": depth,
        "steps": steps,
        "base_batch": base_batch,
        "segments": segs,
        "ms_per_step": {k: round(v, 3) for k, v in ms.items()},
        "chained_speedup": round(ms["unbatched"] / ms["chained"], 2),
        "fused_speedup": round(ms["unbatched"] / ms["chained_fused"], 2),
        "digests_identical": bool(identical),
    }


# -- section 3: OPMW rw1 fused-vs-unfused identity ----------------------------


def bench_trace(step_modes=("sync", "concurrent"), max_events: int = 0) -> Dict[str, Any]:
    from repro.api import ReuseSession
    from repro.workloads import opmw_workload, replay, rw_trace

    dags = opmw_workload()
    events = rw_trace(dags, seed=11)  # the rw1 trace (seed convention)
    if max_events:
        events = events[:max_events]
    out: Dict[str, Any] = {"events": len(events), "modes": {}}
    for mode in step_modes:
        runs = {}
        for fuse in (False, True):
            session = ReuseSession(execute=True, backend="inprocess", step_mode=mode)
            fused_total = 0
            for i, _ in enumerate(replay(session, dags, events)):
                session.step()
                if fuse and i % 5 == 4:
                    fused_total += len(session.fuse())
            session.run(2)
            runs[fuse] = {
                n: session.sink_digests(n) for n in sorted(session.manager.submitted)
            }
            if fuse:
                out["modes"].setdefault(mode, {})["fuse_calls_nonempty"] = fused_total
            session.close()
        identical = runs[True] == runs[False]
        out["modes"].setdefault(mode, {})["digests_identical"] = bool(identical)
        print(f"  {mode:10s}: fused == unfused -> {identical}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=12)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--base-batch", type=int, default=64)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: truncate the OPMW trace section")
    ap.add_argument("--out", default=os.path.join("results", "benchmarks", "BENCH_pr8.json"))
    args = ap.parse_args(argv)

    print("zero-copy shm fetch (view vs copy):")
    fetch = bench_fetch()
    for r in fetch["rows"]:
        print(f"  {r['rows']:7d} rows ({r['nbytes']:>9d} B): "
              f"view {r['view_us']:8.2f} us   copy {r['copy_us']:8.2f} us")
    print(f"  view flat in size: {fetch['view_flat_in_size']}  "
          f"(copy/view at largest: x{fetch['copy_over_view_at_largest']})")

    print(f"deep same-worker chain (depth {args.depth}, batch {args.base_batch}):")
    chain = bench_chain(args.depth, args.steps, args.base_batch)
    print(f"  chained speedup x{chain['chained_speedup']}  "
          f"fused speedup x{chain['fused_speedup']}")

    print("OPMW rw1 trace, fused vs unfused:" + ("  [smoke]" if args.smoke else ""))
    trace = bench_trace(max_events=30 if args.smoke else 0)

    bars = {
        "fetch_view_flat": fetch["view_flat_in_size"],
        "chained_speedup_ge_1_5": chain["chained_speedup"] >= 1.5,
        "chain_digests_identical": chain["digests_identical"],
        "trace_digests_identical": all(
            m["digests_identical"] for m in trace["modes"].values()
        ),
    }
    record = stamp(
        {
            "bench": "hotpath",
            "smoke": bool(args.smoke),
            "fetch": fetch,
            "chain": chain,
            "trace": trace,
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
