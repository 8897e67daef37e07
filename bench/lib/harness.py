"""One run of one cell through the program's public API.

Set-up (``setup_s``, from process start): the session is built, the plan's
dataflows are submitted, each source started in set-up gets its step counter
from the seed, and every segment structure the window will use is made:

- steady traffic steps ``warmup_steps`` times;
- churn traffic applies the window's own swaps once, one step each, then
  removes every dataflow and submits the preloaded set again, so the window
  replays swaps whose segments the program has already built (its in-memory
  compile cache holds their canonical programs). Reuse never resumes a
  paused task, so this leaves the paused residue a deployment would have
  after as many swaps.

Window: steps back to back for ``seconds``; swaps, when the traffic has
them, are due open loop at ``k / swaps_per_s`` and applied at the first step
boundary after they fall due. Each step blocks until every sink holds its
batch (the program blocks on each segment). With ``trace`` the program's
spans are on and a ``jax.profiler`` trace covers a few seconds of it.

After the window: per-layer readers run on what was recorded, peak device
memory is read, the sinks are copied to the host, the program is freed, and
the plain reference runs over the run's log (``check.py`` compares them).
"""
from __future__ import annotations

import gc
import hashlib
import os
import shutil
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Tuple

from . import check, devtrace, program, reference
from .cell import ROOT, Cell, reader, reference_module
from .clock import CompileClock
from .traffic import make_plan

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_AFTER = 0.3  # share of the window before the profiler starts
TRACE_STEPS = 1  # whole steps traced: a riot21 step records ~1.2M device ops
COUNTER_SPAN = 1 << 16  # seeded source counters start in [0, 2**16)


def counter_start(seed: int, source_type: str) -> int:
    digest = hashlib.sha256(f"{seed}:{source_type}".encode()).digest()
    return int.from_bytes(digest[:4], "little") % COUNTER_SPAN


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Run:
    """The session plus the log the reference replays."""

    def __init__(self, cell: Cell, seed: int, batch: int, devices):
        from repro.api import ReuseSession

        cfg = cell.config
        options = dict(cfg.get("backend_options", {}))
        if cfg["backend"] == "sharded":
            options["devices"] = list(devices)
        self.session = ReuseSession(
            strategy=cfg["strategy"], execute=True, backend=cfg["backend"],
            base_batch=batch, step_mode=cfg["step_mode"], backend_options=options or None)
        self.devices = list(devices)
        self.flows = {f["name"]: f for f in cell.collection}
        self.seed = seed
        self.steps = 0
        self.log: List[Tuple[int, str, str]] = []
        self.counter_start: Dict[Tuple[str, int], int] = {}
        self._seen_sources: set = set()

    def _build(self, name: str):
        from repro.api import flow

        f = self.flows[name]
        b = flow(name).source(f["source"])
        for typ, cfg in f["steps"]:
            b.then(typ, **cfg)
        return b.sink(f["sink"]).build()

    def submit(self, name: str) -> None:
        self.session.submit(self._build(name))
        self.log.append((self.steps, "submit", name))

    def remove(self, name: str) -> None:
        self.session.remove(name)
        self.log.append((self.steps, "remove", name))

    def step(self):
        report = self.session.step()
        self.steps += 1
        return report

    def seed_sources(self) -> None:
        """Start every source deployed since the last call at its counter
        from the seed (runtime state: no program changes with the seed)."""
        started = program.start_sources(self.session, self._seen_sources,
                                        lambda source_type: counter_start(self.seed, source_type))
        for source_type, start in started.items():
            self.counter_start[(source_type, self.steps)] = start


def _setup(run: Run, plan) -> None:
    for name in plan.preload:
        run.submit(name)
    run.seed_sources()
    if plan.swaps:
        for out, inn in plan.swaps:
            run.remove(out)
            run.submit(inn)
            run.step()
        for name in list(run.session.names):
            run.remove(name)
        for name in plan.preload:
            run.submit(name)
        run.seed_sources()
    for _ in range(plan.warmup_steps):
        run.step()


def _window(run: Run, plan, seconds: float, batch: int, clock, trace: bool):
    import jax

    rec = SimpleNamespace(step_s=[], admit_s=[], reports=[], events=0, swaps=0,
                          late_s=0.0, devtrace=None, traced=None)
    n_swaps, rate = len(plan.swaps), plan.swaps_per_s
    compiles0 = clock.compiles
    rec.start_us = time.monotonic_ns() // 1000  # the program's span clock
    t0 = prev = time.perf_counter()
    due = [t0 + k / rate for k in range(n_swaps)] if n_swaps else []
    k = 0
    trace_state = "off"
    annotation = None
    while prev - t0 < seconds:
        if trace and trace_state == "off" and prev - t0 >= TRACE_AFTER * seconds:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the benchmark's own spans suffice
            options.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            annotation = _annotate(devtrace.WINDOW)
            annotation.__enter__()
            trace_state, traced_steps = "on", 0
        pending = []
        now = time.perf_counter()
        while k < n_swaps and due[k] <= now:
            out, inn = plan.swaps[k]
            with _annotate("bench.swap"):
                run.remove(out)
                run.submit(inn)
            rec.late_s = max(rec.late_s, now - due[k])
            pending.append(due[k])
            k += 1
        with _annotate(devtrace.STEP):
            report = run.step()
        end = time.perf_counter()
        rec.step_s.append(end - prev)
        rec.admit_s.extend(end - d for d in pending)
        rec.swaps += len(pending)
        rec.reports.append(report)
        rec.events += len(run.session.names) * batch
        prev = end
        if trace_state == "on":
            traced_steps += 1
            if traced_steps >= TRACE_STEPS:
                annotation.__exit__(None, None, None)
                jax.profiler.stop_trace()
                trace_state = "done"
    if trace_state == "on":
        annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        trace_state = "done"
    rec.window_s = prev - t0
    rec.compiles_in_window = clock.compiles - compiles0
    if trace_state == "done":
        rec.traced = devtrace.find_xplane(TRACE_DIR)
    return rec


def execute(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
            devices, batch: int = 0, keep_trace: str = "",
            clock: CompileClock = None, controls: Tuple[str, ...] = ()) -> Dict[str, Any]:
    """The run's result line (a dict), without printing it. ``batch``
    overrides the configuration's (tests). ``controls`` names lower
    precisions in which the reference is also run and compared with the
    full-precision one, as the program is (``result["controls"]``); the
    benchmark's own runs run none."""
    import jax

    batch = batch or int(cell.config["batch"])
    extra = reference_module(cell.config)  # a bad file fails here, before set-up
    clock = clock or CompileClock()
    plan = make_plan(cell.traffic, [f["name"] for f in cell.collection], seconds)
    run = Run(cell, seed, batch, devices)
    if trace:
        run.session.configure_obs(trace=True, trace_capacity=1 << 21)
    _setup(run, plan)
    setup_s = time.perf_counter() - t_start
    setup_clock = clock.snapshot()
    rec = _window(run, plan, seconds, batch, clock, trace)
    if rec.traced:
        if keep_trace:
            shutil.copy(rec.traced, keep_trace)
        rec.devtrace = devtrace.reduce_trace(devtrace.load_planes(rec.traced),
                                             [d.id for d in devices])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)

    ctx = SimpleNamespace(
        cell=cell, seconds=seconds, batch=batch, setup_s=setup_s,
        run=run, session=run.session,
        spans=run.session.drain_spans() if trace else [], **vars(rec))
    metrics_spec = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metrics_spec:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    got = program.sinks(run.session, run.session.names)
    steps = run.steps
    log, starts = list(run.log), dict(run.counter_start)
    run.session.close()
    del run, ctx
    gc.collect()

    flows = {f["name"]: reference.Flow(f["name"], f["source"], [tuple(s) for s in f["steps"]], f["sink"])
             for f in cell.collection}
    semantics = dict(fallback=cell.config.get("task_fallback"), device=devices[0], extra=extra)
    t_ref = time.perf_counter()
    want = reference.run_reference(flows, log, steps, batch, starts, dtype=cell.config["dtype"],
                                   **semantics)
    ref_s = time.perf_counter() - t_ref
    ok, checks = check.judge(check.readings(got, want), cell.limits)
    control_readings = {}
    for dtype in controls:
        low = reference.run_reference(flows, log, steps, batch, starts, dtype=dtype, **semantics)
        control_readings[dtype] = check.readings(low, want)
    dev = devices[0]
    result: Dict[str, Any] = {
        "correct": ok,
        "attempted": len(rec.step_s) + rec.swaps,
        "failed": 0,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": int(peak)},
        "run": {"seed": seed, "steps_in_window": len(rec.step_s), "window_s": rec.window_s,
                "swaps_in_window": rec.swaps, "generator_late_s": rec.late_s,
                "steps_total": steps, "reference_s": ref_s,
                "setup_compile_s": setup_clock["compile_s"],
                "setup_trace_s": setup_clock["trace_s"],
                "setup_compiles": setup_clock["compiles"],
                "setup_cache_hits": setup_clock["cache_hits"]},
    }
    if rec.devtrace is not None:
        result["device"]["busy_s"] = rec.devtrace["busy_s"]
        result["device"]["window_s"] = rec.devtrace["window_s"]
        result["breakdown"] = {"device_ops": [list(x) for x in rec.devtrace["device_ops"]],
                               "idle_gaps": [list(x) for x in rec.devtrace["idle_gaps"]]}
    if controls:
        result["controls"] = control_readings
    result["checks"] = checks
    return result
