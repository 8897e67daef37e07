#!/usr/bin/env python3
"""Chip smoke: the reuse data plane end to end on a TPU, through the
entry points a user calls, at the stream size users run.

    python chip_smoke.py            # one chip: the phases below
    python chip_smoke.py --chips 4  # riot/rw1 on backend="sharded" over
                                    # four chips vs the one-chip run

One-chip phases (``BATCH`` = 16384 events per source per step, the
largest documented stepping batch):

1. churn: the RIoTBench-21 collection under the ``riot/rw1``
   arrival/departure trace on ``ReuseSession(strategy="signature",
   backend="inprocess")``, one step per trace event;
2. reference: the same events under the Default (``strategy="none"``,
   ``step_mode="sync"``). After every event, every running dataflow's
   sink counts must equal the Default's. Wherever the two runs feed a
   dataflow the same stream, its sink checksum and the sum of the value
   channels of its sink's last batch must agree within ``RTOL``: for the
   dataflows whose submission reused nothing (a later joiner of a running
   source sees the live stream from the join onward, the Default a fresh
   source from step 0), and for all 21 dataflows submitted together. The
   checksum alone cannot show wrong values: it folds in the event-id
   column, which outweighs the value channels;
3. kernel: a fused ``senml_parse → senml_parse → rmsnorm`` segment must
   run the Pallas kernel (``tpu_custom_call`` in its compiled program),
   and in the fused (``affine_rmsnorm`` kernel) and the unfused
   (``rmsnorm`` kernel) run the output must match float64 numpy of the
   same batch within ``RTOL``;
4. serving: two tenants submit the collection to a ``ServeFrontend``
   through ``ServeClient``, step it, and the second tenant's overlapping
   submissions must save slots.

Compile time (JAX's backend-compile events, which include persistent-
cache reads) is printed apart from step time, with the persistent cache's
state at start, the device kind and the peak device memory. The last line
is one JSON object naming the device. Without a TPU the script exits 1
and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

BATCH = 16384
RTOL = 1e-4  # relative tolerance: float sums under different fusions
VAL = slice(1, 6)  # an event's value channels (repro.ops.riot.VAL)
KERNEL_STAGES = ((2.0, 0.5), (0.7, -0.1))  # the two senml_parse (scale, offset)
KERNEL_GAIN = 1.5
KERNEL_EPS = 1e-6  # the rmsnorm op's default
TRACE_SEED = 11  # riot/rw1, as repro.launch.dryrun replays it
STATIC_STEPS = 3
SERVE_STEPS = 3


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums JAX's compile events: backend compiles (persistent-cache reads
    included) and jaxpr tracing + lowering, with persistent-cache hits."""

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.trace_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def rw1_events():
    from repro.workloads import riot_workload, rw_trace

    dags = riot_workload()
    return dags, rw_trace(dags, seed=TRACE_SEED)


@functools.cache
def _value_sums():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda last: jnp.stack(
        [jnp.sum(last[:, VAL]), jnp.sum(jnp.abs(last[:, VAL]))]))


def sink_last(session, name):
    """Each sink of a running dataflow: the last batch it consumed."""
    system = session._system
    task_map = system.manager.task_maps[name]
    return {sink_id: system.backend.sink_state(task_map[sink_id])["last"]
            for sink_id in system.manager.submitted[name].sink_ids}


def sink_digests(session, name):
    """The session's per-sink count and checksum, plus the sum and the
    absolute sum of the value channels of the sink's last batch."""
    digests = session.sink_digests(name)
    for sink_id, last in sink_last(session, name).items():
        digests[sink_id]["values"] = tuple(float(v) for v in _value_sums()(last))
    return digests


def replay_rw1(clock, dags, events, **session_kw):
    """One step per trace event; after each, the sink digests of every
    running dataflow and the set whose submission reused nothing, and
    the device each segment was placed on (sharded backend only)."""
    from repro.api import ReuseSession
    from repro.workloads import replay

    session = ReuseSession(execute=True, base_batch=BATCH, **session_kw)
    record, fresh, step_s, placed = [], set(), [], {}
    t_start = time.perf_counter()
    c0 = clock.compile_s
    try:
        for ev, receipt in replay(session, dags, events):
            if ev.op == "add" and receipt.num_reused == 0:
                fresh.add(ev.name)
            elif ev.op == "remove":
                fresh.discard(ev.name)
            n0 = clock.compiles
            t0 = time.perf_counter()
            session.step()
            dt = time.perf_counter() - t0
            if clock.compiles == n0:
                step_s.append(dt)
            record.append((
                {name: sink_digests(session, name) for name in session.names},
                frozenset(fresh),
            ))
            if len(record) % 25 == 0:
                log(f"    event {len(record)}/{len(events)} at "
                    f"{time.perf_counter() - t_start:.1f}s")
            backend = session._system.backend
            for seg, idx in getattr(backend, "device_of", {}).items():
                placed[seg] = str(backend.devices[idx])
        stats = session.stats()
    finally:
        session.close()
    wall = time.perf_counter() - t_start
    med = statistics.median(step_s) if step_s else float("nan")
    log(f"  {session_kw}: {len(events)} events in {wall:.2f}s, "
        f"compile {clock.compile_s - c0:.2f}s, median step without a "
        f"compile {med * 1e3:.3f} ms over {len(step_s)} steps, "
        f"peak running dataflows {max(len(r[0]) for r in record)}, "
        f"compile-cache hits {stats.compile_cache_hits}")
    return record, placed


def compare(got_record, want_record, all_checksums: bool):
    """Counts must be equal everywhere. Where the streams are the same,
    returns the largest relative checksum difference and the largest
    difference of the value-channel sums relative to their absolute sum.
    Returns (count checks, checksum checks, max checksum rel, max value rel)."""
    if len(got_record) != len(want_record):
        raise AssertionError("the runs recorded different numbers of events")
    n_counts = n_sums = 0
    max_rel = max_vrel = 0.0
    for i, ((got, fresh), (want, _)) in enumerate(zip(got_record, want_record)):
        if got.keys() != want.keys():
            raise AssertionError(f"event {i}: running dataflows differ: "
                                 f"{sorted(got)} vs {sorted(want)}")
        for name, sinks in got.items():
            for sink, g in sinks.items():
                w = want[name][sink]
                if g["count"] != w["count"]:
                    raise AssertionError(
                        f"event {i}: {name}/{sink} count {g['count']} != "
                        f"reference {w['count']}")
                n_counts += 1
                if all_checksums or name in fresh:
                    rel = abs(g["checksum"] - w["checksum"]) / max(abs(w["checksum"]), 1e-30)
                    (g_sum, _), (w_sum, w_abs) = g["values"], w["values"]
                    vrel = abs(g_sum - w_sum) / max(w_abs, 1e-30)
                    max_rel = max(max_rel, rel)
                    max_vrel = max(max_vrel, vrel)
                    n_sums += 1
    if n_sums == 0:
        raise AssertionError("no checksum was compared")
    return n_counts, n_sums, max_rel, max_vrel


def check_checksums(label: str, n_counts: int, n_sums: int, max_rel: float,
                    max_vrel: float) -> None:
    log(f"{label}: {n_counts} sink counts equal; largest relative checksum "
        f"difference {max_rel:.3e}, largest value-channel sum difference "
        f"{max_vrel:.3e} of the absolute sum, over {n_sums} sinks "
        f"(tolerance {RTOL:g})")
    if not (max_rel <= RTOL and max_vrel <= RTOL):
        raise AssertionError(f"{label}: checksum difference {max_rel:.3e} or "
                             f"value difference {max_vrel:.3e} > {RTOL:g}")


def phase_static(clock) -> None:
    """All 21 dataflows submitted before the first step: every source
    starts together, so every checksum is comparable."""
    from repro.api import ReuseSession
    from repro.workloads import riot_workload

    dags = riot_workload()
    records = {}
    for strategy in ("signature", "none"):
        session = ReuseSession(strategy=strategy, execute=True, base_batch=BATCH,
                               step_mode="sync")
        try:
            for df in dags:
                session.submit(df.copy())
            session.run(STATIC_STEPS)
            records[strategy] = [(
                {df.name: sink_digests(session, df.name) for df in dags}, frozenset()
            )]
        finally:
            session.close()
    check_checksums("reference (21 dataflows together)",
                    *compare(records["signature"], records["none"], all_checksums=True))


def kernel_flows():
    """``kernel_prefix`` (the first ``senml_parse``) and ``kernel_flow``
    (both, then ``rmsnorm``). The prefix flow runs first, so the full flow
    adds a downstream ``senml_parse → rmsnorm`` segment: fuse() has a
    segment chain to collapse, and that private run becomes one
    ``affine_rmsnorm`` kernel; unfused it runs the ``rmsnorm`` kernel. The
    prefix's sink takes the batch the second ``senml_parse`` reads."""
    from repro.api import flow

    (s1, o1), (s2, o2) = KERNEL_STAGES
    prefix = flow("kernel_prefix").source("urban").then("senml_parse", scale=s1, offset=o1)
    full = (flow("kernel_flow").source("urban")
            .then("senml_parse", scale=s1, offset=o1)
            .then("senml_parse", scale=s2, offset=o2)
            .then("rmsnorm", gain=KERNEL_GAIN))
    return prefix.sink("store").build(), full.sink("store").build()


def rmsnorm_error(session) -> float:
    """Largest difference between the output in ``kernel_flow``'s sink and
    float64 numpy of the second ``senml_parse`` and the RMS norm applied to
    its input, ``kernel_prefix``'s sink batch, relative to the largest
    reference value."""
    (x,) = sink_last(session, "kernel_prefix").values()
    (got,) = sink_last(session, "kernel_flow").values()
    scale, offset = KERNEL_STAGES[1]
    x = np.asarray(x, np.float64)[:, VAL] * scale + offset
    got = np.asarray(got, np.float64)[:, VAL]
    want = KERNEL_GAIN * x / np.sqrt(np.mean(x * x, axis=1, keepdims=True) + KERNEL_EPS)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def kernel_run(fused: bool):
    """Steps the kernel flows, fused or not. Returns the digest record of
    ``kernel_flow``, the fused segment's compiled text (``None`` unfused)
    and :func:`rmsnorm_error`."""
    from repro.api import ReuseSession

    prefix, full = kernel_flows()
    session = ReuseSession(strategy="signature", execute=True, base_batch=BATCH)
    text = None
    try:
        session.submit(prefix.copy())
        session.submit(full.copy())
        session.run(1)
        if fused:
            if not session.fuse():
                raise AssertionError("fuse() collapsed no segment chain")
            backend = session._system.backend
            (seg,) = [s for s in backend.segments.values() if s.spec.fused]
            inputs = {t: backend.transport.fetch(t) for t in seg.boundary_topics}
            text = seg.step_fn.lower(seg.states, seg.active, inputs).compile().as_text()
        session.run(2)
        record = [({"kernel_flow": sink_digests(session, "kernel_flow")},
                   frozenset({"kernel_flow"}))]
        return record, text, rmsnorm_error(session)
    finally:
        session.close()


def phase_kernel() -> None:
    from repro.kernels import ops as kernel_ops

    if kernel_ops.backend() != "pallas":
        raise AssertionError(f"kernel backend is {kernel_ops.backend()!r}, not 'pallas'")
    fused, text, fused_err = kernel_run(True)
    if "tpu_custom_call" not in text:
        raise AssertionError("fused segment compiled without a Pallas kernel")
    unfused, _, unfused_err = kernel_run(False)
    log(f"kernel: backend pallas, tpu_custom_call in the fused segment; output "
        f"vs float64 numpy: largest difference {fused_err:.3e} "
        f"fused, {unfused_err:.3e} unfused, of the largest value "
        f"(tolerance {RTOL:g})")
    if not (fused_err <= RTOL and unfused_err <= RTOL):
        raise AssertionError("the rmsnorm kernels disagree with the numpy reference")
    check_checksums("kernel fused vs unfused",
                    *compare(fused, unfused, all_checksums=True))


def phase_serving() -> None:
    from repro.serve import ServeClient, ServeFrontend, TenantQuota
    from repro.workloads import riot_workload, tenant_copy

    dags = riot_workload()
    frontend = ServeFrontend(slots=512, strategy="signature", backend="inprocess",
                             default_quota=TenantQuota(max_slots=256),
                             base_batch=BATCH)
    host, port = frontend.start()
    t0 = time.perf_counter()
    with frontend, ServeClient((host, port), timeout=900.0) as alice, \
            ServeClient((host, port), timeout=900.0) as bob:
        for df in dags:
            for client, tenant in ((alice, "alice"), (bob, "bob")):
                out = client.submit(tenant, tenant_copy(df, tenant))
                if out["status"] != "ADMITTED":
                    raise AssertionError(f"{tenant}/{df.name}: {out}")
        for _ in range(SERVE_STEPS):
            alice.step(1)  # a server-side error raises here
        stats = bob.stats()
    saved = {t: l["slots_saved"] for t, l in stats["ledgers"].items()}
    log(f"serving: {2 * len(dags)} submissions, {SERVE_STEPS} steps in "
        f"{time.perf_counter() - t0:.2f}s, slots used {stats['slots_used']}, "
        f"naive {stats['naive_slots']}, effective capacity "
        f"{stats['effective_capacity']:.3f}, slots saved {saved}")
    if not sum(saved.values()) > 0:
        raise AssertionError("serving saved no slots")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import persistent_cache_dir

    cache_dir = persistent_cache_dir()
    warm = os.path.isdir(cache_dir) and any(os.scandir(cache_dir))
    log(f"device: {devices[0].device_kind} x{len(devices)}; persistent compile "
        f"cache {'warm' if warm else 'cold'} at {cache_dir}")
    clock = CompileClock()
    t_start = time.perf_counter()
    dags, events = rw1_events()

    if args.chips == 4:
        log(f"riot/rw1 sharded over {args.chips} chips vs inprocess on one:")
        sharded, placed = replay_rw1(clock, dags, events, strategy="signature",
                                     backend="sharded")
        single, _ = replay_rw1(clock, dags, events, strategy="signature",
                               backend="inprocess")
        by_device = {}
        for seg, dev in sorted(placed.items(), key=lambda kv: (len(kv[0]), kv[0])):
            by_device.setdefault(dev, []).append(seg)
        for dev, segs in sorted(by_device.items()):
            log(f"  {dev}: {len(segs)} segments: {', '.join(segs)}")
        log(f"segments landed on {len(by_device)} devices")
        if len(by_device) < 2:
            raise AssertionError("the sharded run used a single device")
        check_checksums("sharded vs inprocess",
                        *compare(sharded, single, all_checksums=True))
    else:
        log("churn and reference (riot/rw1, one step per event):")
        reuse, _ = replay_rw1(clock, dags, events, strategy="signature",
                              backend="inprocess")
        default, _ = replay_rw1(clock, dags, events, strategy="none",
                                backend="inprocess", step_mode="sync")
        check_checksums("reference (churn)",
                        *compare(reuse, default, all_checksums=False))
        phase_static(clock)
        phase_kernel()
        phase_serving()

    peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"compile {clock.compile_s:.2f}s over {clock.compiles} backend compiles "
        f"({clock.cache_hits} persistent-cache hits), tracing+lowering "
        f"{clock.trace_s:.2f}s, total {time.perf_counter() - t_start:.2f}s; "
        f"peak device memory {peak} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
