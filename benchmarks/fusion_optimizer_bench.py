"""Locality-aware fusion planner + compiled-segment reuse cache
(PR 9 acceptance numbers, written to BENCH_pr9.json).

Four sections, matching the four compounding optimizer changes:

  * **cross_worker** — a 12-deep linear segment chain spread over 4
    workers (each submission extends the previous chain by one kalman
    stage; round-robin placement puts consecutive segments on different
    workers, so every hop crosses a process boundary). ``fuse()``
    migrates the whole chain onto one worker and recompiles it into one
    donated-buffer segment. The bar: fused ≥ ×2 step throughput over
    unfused, with bit-identical sink digests.
  * **cache** — the OPMW rw1 churn trace under the Default ("none")
    strategy, where every submission deploys its own segments: the
    compiled-segment reuse cache is what keeps resubmissions and
    structurally overlapping submissions from paying XLA again. The
    bars: end-of-trace hit rate ≥ 0.5, and cache-hit submissions land
    (submit + first step) faster than cold-compile submissions.
  * **wide_wave** — 8 parallel two-segment chains balanced over 4
    workers. Consolidating them all onto the cheapest worker would
    serialize a wide wave; the wave-aware planner must keep step time
    from regressing (≤ ×1.25 of unfused) while still taking whatever
    fusions are free.
  * **trace** — the full OPMW rw1 trace replayed with and without
    periodic ``fuse()`` (now wave-scored, with the peephole pallas
    kernels active on fused segments) in both step modes; sink digests
    must be bit-identical.

Any missed bar exits 2 (the CI contract); ``--smoke`` shrinks the trace
sections for the CI job while keeping every bar armed.

Usage:
    PYTHONPATH=src python benchmarks/fusion_optimizer_bench.py \
        [--depth 12] [--steps 30] [--smoke] \
        [--out results/benchmarks/BENCH_pr9.json]
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


# -- section 1: cross-worker chain fusion --------------------------------------


def _stacked_chain_dags(depth: int):
    """dag k = source → kalman_1..k → sink_k; signature reuse makes each
    submission one new segment downstream of the previous — a depth-deep
    linear segment chain, placed round-robin across the workers."""
    from repro.api import flow

    dags = []
    for k in range(1, depth + 1):
        b = flow(f"deep{k:02d}").source("sensor")
        for i in range(k):
            b.then("kalman", q=0.1, stage=i)
        dags.append(b.sink("store").build())
    return dags


def _bench_cross_worker_plane(dags, steps: int, fuse: bool, workers: int,
                              base_batch: int, windows: int = 5):
    from repro.api import ReuseSession

    session = ReuseSession(
        strategy="signature",
        execute=True,
        base_batch=base_batch,
        backend="multiproc",
        workers=workers,
        transport="shm",
        step_mode="concurrent",
        backend_options={"chain_batching": True},
    )
    for df in dags:
        session.submit(df.copy())
    backend = session._system.backend
    spread_before = len(set(backend.device_of.values()))
    session.run(2)  # compile + warm (also feeds the latency model)
    report = None
    if fuse:
        session.fuse()
        report = session.fusion_report.to_dict() if session.fusion_report else None
    session.run(2)  # warm the (possibly recompiled) plane — equal step counts
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        session.run(steps)
        best = min(best, (time.perf_counter() - t0) / steps)
    digests = {df.name: session.sink_digests(df.name) for df in dags}
    spread_after = len(set(backend.device_of.values()))
    segments = len(backend.segments)
    session.close()
    return 1e3 * best, digests, {
        "segments": segments,
        "workers_occupied_before": spread_before,
        "workers_occupied_after": spread_after,
        "fusion_report": report,
    }


def bench_cross_worker(depth: int, steps: int, workers: int = 4,
                       base_batch: int = 64) -> Dict[str, Any]:
    dags = _stacked_chain_dags(depth)
    unfused_ms, unfused_digests, unfused_info = _bench_cross_worker_plane(
        dags, steps, False, workers, base_batch
    )
    print(f"  unfused: {unfused_ms:8.2f} ms/step  "
          f"({unfused_info['segments']} segments on "
          f"{unfused_info['workers_occupied_after']} workers)")
    fused_ms, fused_digests, fused_info = _bench_cross_worker_plane(
        dags, steps, True, workers, base_batch
    )
    print(f"  fused  : {fused_ms:8.2f} ms/step  "
          f"({fused_info['segments']} segments on "
          f"{fused_info['workers_occupied_after']} workers)")
    return {
        "depth": depth,
        "steps": steps,
        "workers": workers,
        "base_batch": base_batch,
        "ms_per_step": {"unfused": round(unfused_ms, 3), "fused": round(fused_ms, 3)},
        "unfused": unfused_info,
        "fused": fused_info,
        "fused_speedup": round(unfused_ms / fused_ms, 2),
        "digests_identical": bool(fused_digests == unfused_digests),
    }


# -- section 2: compiled-segment reuse cache under churn -----------------------


def bench_cache(max_events: int = 0) -> Dict[str, Any]:
    from repro.api import ReuseSession
    from repro.workloads import opmw_workload, rw_trace

    dags = opmw_workload()
    by_name = {d.name: d for d in dags}
    events = rw_trace(dags, seed=11)  # the rw1 trace (seed convention)
    if max_events:
        events = events[:max_events]
    session = ReuseSession(strategy="none", execute=True, backend="inprocess")
    miss_lat: List[float] = []
    hit_lat: List[float] = []
    prev_misses = 0
    for ev in events:
        if ev.op == "remove":
            session.remove(ev.name)
            continue
        t0 = time.perf_counter()
        session.submit(by_name[ev.name].copy())
        session.step()  # first step = trace/compile (or cache hit) + run
        dt = 1e3 * (time.perf_counter() - t0)
        misses = session.stats().compile_cache_misses
        (miss_lat if misses > prev_misses else hit_lat).append(dt)
        prev_misses = misses
    st = session.stats()
    session.close()
    total = st.compile_cache_hits + st.compile_cache_misses
    hit_rate = st.compile_cache_hits / total if total else 0.0
    cold_ms = sum(miss_lat) / len(miss_lat) if miss_lat else 0.0
    warm_ms = sum(hit_lat) / len(hit_lat) if hit_lat else float("inf")
    print(f"  {len(events)} events: {st.compile_cache_hits} hits / "
          f"{st.compile_cache_misses} misses (rate {hit_rate:.2f})")
    print(f"  submit+step: cold {cold_ms:8.2f} ms   warm {warm_ms:8.2f} ms")
    return {
        "events": len(events),
        "hits": st.compile_cache_hits,
        "misses": st.compile_cache_misses,
        "evictions": st.compile_cache_evictions,
        "entries": st.compile_cache_entries,
        "hit_rate": round(hit_rate, 3),
        "cold_submit_step_ms": round(cold_ms, 3),
        "warm_submit_step_ms": round(warm_ms, 3),
        "warm_below_cold": bool(warm_ms < cold_ms),
    }


# -- section 3: wide wave — planner must not serialize parallel chains ---------


def _wide_wave_dags(chains: int):
    """chain c = two stacked submissions (base, extension): each pair
    becomes a two-segment private chain, independent of the others."""
    from repro.api import flow

    dags = []
    for c in range(chains):
        base = flow(f"wave{c:02d}a").source("sensor")
        base.then("kalman", q=0.1, lane=c)
        dags.append(base.sink("store").build())
        ext = flow(f"wave{c:02d}b").source("sensor")
        ext.then("kalman", q=0.1, lane=c)
        ext.then("kalman", q=0.2, lane=c)
        dags.append(ext.sink("store").build())
    return dags


def _bench_wave_plane(dags, steps: int, fuse: bool, workers: int,
                      base_batch: int, windows: int = 5):
    from repro.api import ReuseSession

    session = ReuseSession(
        strategy="signature",
        execute=True,
        base_batch=base_batch,
        backend="multiproc",
        workers=workers,
        transport="shm",
        step_mode="concurrent",
        backend_options={"chain_batching": True},
    )
    for df in dags:
        session.submit(df.copy())
    session.run(3)  # warm + latency samples for the planner's cost model
    report = None
    if fuse:
        session.fuse()
        report = session.fusion_report.to_dict() if session.fusion_report else None
    session.run(2)  # equal step counts on both planes (digest comparison)
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        session.run(steps)
        best = min(best, (time.perf_counter() - t0) / steps)
    digests = {df.name: session.sink_digests(df.name) for df in dags}
    session.close()
    return 1e3 * best, digests, report


def bench_wide_wave(chains: int, steps: int, workers: int = 4,
                    base_batch: int = 64) -> Dict[str, Any]:
    dags = _wide_wave_dags(chains)
    unfused_ms, unfused_digests, _ = _bench_wave_plane(
        dags, steps, False, workers, base_batch
    )
    fused_ms, fused_digests, report = _bench_wave_plane(
        dags, steps, True, workers, base_batch
    )
    accepted = len(report["accepted"]) if report else 0
    rejected = len(report["rejected"]) if report else 0
    ratio = fused_ms / unfused_ms
    print(f"  unfused: {unfused_ms:8.2f} ms/step   planner-on: {fused_ms:8.2f} "
          f"ms/step  (x{ratio:.2f}; {accepted} fused, {rejected} kept wide)")
    return {
        "chains": chains,
        "steps": steps,
        "workers": workers,
        "ms_per_step": {"unfused": round(unfused_ms, 3), "planner": round(fused_ms, 3)},
        "planner_over_unfused": round(ratio, 3),
        "chains_fused": accepted,
        "chains_kept_wide": rejected,
        "fusion_report": report,
        "digests_identical": bool(fused_digests == unfused_digests),
    }


# -- section 4: OPMW rw1 fused-vs-unfused identity -----------------------------


def bench_trace(step_modes=("sync", "concurrent"), max_events: int = 0) -> Dict[str, Any]:
    from repro.api import ReuseSession
    from repro.workloads import opmw_workload, replay, rw_trace

    dags = opmw_workload()
    events = rw_trace(dags, seed=11)
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
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--chains", type=int, default=8)
    ap.add_argument("--base-batch", type=int, default=64)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: truncate the trace-driven sections")
    ap.add_argument("--out", default=os.path.join("results", "benchmarks", "BENCH_pr9.json"))
    args = ap.parse_args(argv)
    steps = 10 if args.smoke else args.steps

    print(f"cross-worker chain fusion (depth {args.depth}, {args.workers} workers):")
    cross = bench_cross_worker(args.depth, steps, args.workers, args.base_batch)
    print(f"  fused speedup x{cross['fused_speedup']}")

    print("compiled-segment reuse cache (OPMW rw1, Default strategy):"
          + ("  [smoke]" if args.smoke else ""))
    cache = bench_cache(max_events=40 if args.smoke else 0)

    print(f"wide wave ({args.chains} chains over {args.workers} workers):")
    wave = bench_wide_wave(args.chains, steps, args.workers, args.base_batch)

    print("OPMW rw1 trace, fused vs unfused:" + ("  [smoke]" if args.smoke else ""))
    trace = bench_trace(max_events=30 if args.smoke else 0)

    bars = {
        "cross_worker_speedup_ge_2": cross["fused_speedup"] >= 2.0,
        "cross_worker_digests_identical": cross["digests_identical"],
        "cache_hit_rate_ge_0_5": cache["hit_rate"] >= 0.5,
        "cache_warm_below_cold": cache["warm_below_cold"],
        "wide_wave_no_regression": wave["planner_over_unfused"] <= 1.25,
        "wide_wave_digests_identical": wave["digests_identical"],
        "trace_digests_identical": all(
            m["digests_identical"] for m in trace["modes"].values()
        ),
    }
    record = stamp(
        {
            "bench": "fusion_optimizer",
            "smoke": bool(args.smoke),
            "cross_worker": cross,
            "cache": cache,
            "wide_wave": wave,
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
