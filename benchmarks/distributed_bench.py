"""Distributed data plane benchmark — lifting the GIL cap on concurrent
stepping (PR 5 acceptance numbers, written to BENCH_pr5.json).

PR 4's wave/ready-queue scheduler overlaps independent segments on a
thread pool, but per-segment Python dispatch holds the GIL, capping the
measured sync→concurrent speedup on the 8-kalman-chain deployment. This
benchmark steps the *same* deployment through three data planes:

  * ``sync``      — in-process jit, one-thread launch-order sweep;
  * ``threads``   — sharded backend, ``step_mode="concurrent"`` — PR 4's
                    thread-pool dispatch (the GIL-capped plane);
  * ``multiproc`` — worker *processes* over the shm transport
                    (``backend="multiproc"``): segments compile and step
                    in separate interpreters, boundary streams ride
                    shared-memory ring buffers, and each dependency wave
                    is one batched pipe RPC per worker.

Two regimes are measured, because they bound different things:

  * **dispatch-bound** (small batch): per-segment Python dispatch is the
    step cost. Threads gain ~nothing over sync here — the GIL serializes
    exactly the part that dominates — while worker processes run their
    dispatch in parallel interpreters. This is the regime the acceptance
    bar targets: multiproc must beat the threaded plane's ms/step.
  * **compute-bound** (large batch): XLA kernels dominate. Every plane is
    then limited by the host's *effective* parallel capacity, which the
    benchmark calibrates directly (two pure-CPU burner processes vs one);
    on a 2-core CI container that ceiling is ~×1.3, so threads and
    processes land within noise of each other — reported for context,
    with the calibrated ceiling alongside.

Sink digests are asserted identical across all three planes in both
regimes (the determinism contract), and the calibrated dry-run makespan
model is reported as the unlimited-hardware roofline.

Usage:
    PYTHONPATH=src python benchmarks/distributed_bench.py \
        [--chains 8] [--steps 20] [--workers N] [--out results/benchmarks/BENCH_pr5.json]
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import time
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, List

from repro.api import ReuseSession, flow

try:  # package (python -m benchmarks.run) vs script (python benchmarks/foo.py)
    from benchmarks._host import stamp
except ImportError:  # pragma: no cover - script execution path
    from _host import stamp


def _chains(n_chains: int, depth: int = 4) -> List:
    """Independent compute-heavy kalman chains — one segment each, one
    dependency wave: the best case for overlap (kalman is a lax.scan over
    the batch, so each segment is real single-stream work)."""
    dags = []
    for i in range(n_chains):
        b = flow(f"cc{i}").source(f"sensor{i}")
        for k in range(depth):
            b.then("kalman", q=0.1 + i, stage=k)
        dags.append(b.sink("store").build())
    return dags


def _burn(q):
    t0 = time.perf_counter()
    x = 0
    for i in range(30_000_000):
        x += i
    q.put(time.perf_counter() - t0)


def host_parallel_ceiling(n: int = 2) -> float:
    """Effective speedup this host gives n CPU-bound *processes* vs one —
    the hard upper bound on any concurrency mechanism's compute-bound
    gain (cloud CI containers often deliver well under their nominal
    core count)."""
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    _burn(q)
    serial = q.get()
    procs = [ctx.Process(target=_burn, args=(q,)) for _ in range(n)]
    t0 = time.perf_counter()
    for p in procs:
        p.start()
    for p in procs:
        p.join()
    wall = time.perf_counter() - t0
    for _ in procs:
        q.get()
    return n * serial / wall


def _bench_session(session: ReuseSession, dags, steps: int, windows: int = 5):
    """Best-of-N windows ms/step (the min is the honest floor under the
    container's CPU scheduling jitter); compiles warm outside the clock."""
    for df in dags:
        session.submit(df.copy())
    session.run(2)  # compile + warm
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        session.run(steps)
        best = min(best, (time.perf_counter() - t0) / steps)
    return 1e3 * best


def _measure_plane(kw: Dict, chains: int, depth: int, base_batch: int,
                   steps: int):
    dags = _chains(chains, depth)
    session = ReuseSession(
        strategy="signature", execute=True, base_batch=base_batch, **kw
    )
    ms = _bench_session(session, dags, steps)
    counts = {
        df.name: {s: v["count"] for s, v in session.sink_digests(df.name).items()}
        for df in dags
    }
    session.close()
    return ms, counts


def _in_child(fn, *args):
    """``fn(*args)`` in a fresh spawned interpreter. The parent never
    touches JAX, so each plane's process (and the multiproc plane's
    workers, one per chip) can take an accelerator of its own."""
    with ProcessPoolExecutor(1, mp_context=mp.get_context("spawn")) as pool:
        return pool.submit(fn, *args).result()


def _measure_regime(chains: int, depth: int, base_batch: int, steps: int,
                    workers: int) -> Dict[str, float]:
    planes = {
        "sync": dict(backend="inprocess", step_mode="sync"),
        "threads": dict(backend="sharded", step_mode="concurrent",
                        max_workers=workers),
        "multiproc": dict(backend="multiproc", step_mode="concurrent",
                          workers=workers, max_workers=max(workers, 2)),
    }
    ms: Dict[str, float] = {}
    counts: Dict[str, Dict] = {}
    for name, kw in planes.items():
        ms[name], counts[name] = _in_child(
            _measure_plane, kw, chains, depth, base_batch, steps
        )
        print(f"  {name:10s}: {ms[name]:8.2f} ms/step")
    for name in ("threads", "multiproc"):
        assert counts[name] == counts["sync"], f"{name} diverged from sync sink counts"
    return ms


def _dryrun_roofline(chains: int, depth: int, base_batch: int) -> Dict[str, float]:
    """Makespan model of the deployment, calibrated from a short jit run."""
    from repro.ops.costs import fit_latency_model

    dags = _chains(chains, depth)
    cal = ReuseSession(strategy="signature", execute=True, backend="inprocess",
                       base_batch=base_batch, step_mode="sync")
    for df in dags:
        cal.submit(df.copy())
    cal.run(2)
    cal._system.backend.reports.clear()
    cal.run(5)
    model = fit_latency_model(cal._system.backend.latency_samples())
    cal.close()
    dry = {}
    for mode in ("sync", "concurrent"):
        s = ReuseSession(strategy="signature", execute=True, backend="dryrun",
                         base_batch=base_batch, step_mode=mode)
        s._system.backend.calibrate(model)
        for df in dags:
            s.submit(df.copy())
        dry[mode] = s.run(1)[0].makespan_ms
        s.close()
    return dry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chains", type=int, default=8)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dispatch-batch", type=int, default=64,
                    help="base_batch for the dispatch-bound (GIL-cap) regime")
    ap.add_argument("--compute-batch", type=int, default=8192,
                    help="base_batch for the compute-bound regime")
    ap.add_argument("--workers", type=int, default=0, help="multiproc pool (0 = cpu count)")
    ap.add_argument("--out", default=os.path.join("results", "benchmarks", "BENCH_pr5.json"))
    args = ap.parse_args(argv)

    workers = args.workers or (os.cpu_count() or 2)

    ceiling = host_parallel_ceiling(workers)
    print(f"host: {os.cpu_count()} cpus, effective parallel ceiling ×{ceiling:.2f} "
          f"for {workers} processes")

    print(f"dispatch-bound regime (batch {args.dispatch_batch}):")
    disp = _measure_regime(args.chains, args.depth, args.dispatch_batch,
                           args.steps, workers)
    print(f"compute-bound regime (batch {args.compute_batch}):")
    comp = _measure_regime(args.chains, args.depth, args.compute_batch,
                           args.steps, workers)
    dry = _in_child(_dryrun_roofline, args.chains, args.depth, args.compute_batch)

    record = {
        "bench": "distributed_data_plane",
        "deployment": {
            "chains": args.chains, "depth": args.depth, "steps": args.steps,
        },
        "host_cpus": os.cpu_count(),
        "host_parallel_ceiling": round(ceiling, 2),
        "workers": workers,
        "transport": "shm",
        "dispatch_bound": {
            "base_batch": args.dispatch_batch,
            "sync_ms_per_step": round(disp["sync"], 2),
            "threads_ms_per_step": round(disp["threads"], 2),
            "multiproc_ms_per_step": round(disp["multiproc"], 2),
            "threads_speedup_vs_sync": round(disp["sync"] / disp["threads"], 2),
            "multiproc_speedup_vs_sync": round(disp["sync"] / disp["multiproc"], 2),
            "multiproc_speedup_vs_threads": round(disp["threads"] / disp["multiproc"], 2),
        },
        "compute_bound": {
            "base_batch": args.compute_batch,
            "sync_ms_per_step": round(comp["sync"], 2),
            "threads_ms_per_step": round(comp["threads"], 2),
            "multiproc_ms_per_step": round(comp["multiproc"], 2),
            "threads_speedup_vs_sync": round(comp["sync"] / comp["threads"], 2),
            "multiproc_speedup_vs_sync": round(comp["sync"] / comp["multiproc"], 2),
            "multiproc_speedup_vs_threads": round(comp["threads"] / comp["multiproc"], 2),
        },
        "dryrun_makespan_sync_ms": round(dry["sync"], 2),
        "dryrun_makespan_concurrent_ms": round(dry["concurrent"], 2),
        "dryrun_makespan_ratio": round(dry["sync"] / max(dry["concurrent"], 1e-12), 2),
        "sink_counts_identical": True,
    }
    print(
        f"\ndispatch-bound: threads ×{record['dispatch_bound']['threads_speedup_vs_sync']} vs sync "
        f"(GIL-capped), multiproc ×{record['dispatch_bound']['multiproc_speedup_vs_sync']} "
        f"(×{record['dispatch_bound']['multiproc_speedup_vs_threads']} over threads)\n"
        f"compute-bound: threads ×{record['compute_bound']['threads_speedup_vs_sync']}, "
        f"multiproc ×{record['compute_bound']['multiproc_speedup_vs_sync']} "
        f"(host ceiling ×{record['host_parallel_ceiling']}); "
        f"dryrun roofline ×{record['dryrun_makespan_ratio']} on unlimited hardware"
    )
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(stamp(record), f, indent=1)
    print(f"wrote {args.out}")
    # The PR acceptance bar: where the GIL is the binding constraint,
    # worker processes must beat the threaded plane's ms/step. Exit code 2
    # is reserved for missing the bar (so CI smokes on noisy shared
    # runners can tolerate it while still failing hard on crashes).
    if record["dispatch_bound"]["multiproc_ms_per_step"] >= record["dispatch_bound"]["threads_ms_per_step"]:
        print("WARNING: multiproc did not beat threaded concurrent stepping "
              "in the dispatch-bound regime")
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
