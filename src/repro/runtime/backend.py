"""ExecutionBackend — the pluggable data-plane contract behind StreamSystem.

The paper's Manager (§4.3) binds the merge/unmerge control plane to one
concrete runtime (Storm). This module makes that binding an API instead:
:class:`StreamSystem` is a thin policy layer that drives any
:class:`ExecutionBackend` through a fixed verb set —

  ``deploy / kill / forward / pause / resume / step / snapshot /
  sink_state / account / dump_state / restore_state``

— and backends plug in by name through a registry that mirrors the
``MergeStrategy`` registry in :mod:`repro.core.strategies`. Three ship
built-in:

  * ``"inprocess"`` — :class:`repro.runtime.executor.InProcessJitBackend`,
    today's jit data plane (segments compiled to one XLA step each, broker
    topics between them);
  * ``"sharded"`` — :class:`repro.runtime.sharded.ShardedBackend`, the same
    jit plane with segments placed across ``jax.devices()`` via a pluggable
    :class:`~repro.runtime.scheduler.PlacementPolicy`;
  * ``"dryrun"`` — :class:`repro.runtime.dryrun.DryRunBackend`, no JAX at
    all: pure cost-model stepping over ``cost_weight × batch`` accounting,
    fast enough to sweep full OPMW/RIoT arrival-departure traces in
    milliseconds. Its ``live_tasks``/``paused_tasks``/``cost`` trajectories
    are contract-identical to the jit backends (checksums are jit-only).

This module is deliberately **JAX-free**: it holds the shared contract
(:class:`SegmentSpec`, :class:`StepReport`, the accounting constants, the
O(1) task→segment reverse index, straggler bookkeeping) so that a
``backend="dryrun"`` session never imports JAX.
"""
from __future__ import annotations

import importlib
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Set, Tuple, Type, Union

from repro.core.graph import Dataflow, Task
from repro.obs import NULL_REGISTRY, MetricsRegistry, Tracer

from .checkpoint import decode_pytree, encode_pytree
from .scheduler import WaveEvent, compute_waves, run_ready_queue

STEP_MODES = ("sync", "concurrent")

# Fraction of a task's cost still consumed while paused (deployed-but-idle
# Storm bolt). Calibrated so the paper's drain-phase crossover reproduces.
PAUSE_EPSILON = 0.03
# events·cost_weight per core: 1 core ≡ one weight-1.0 task at 10 ev/s ×
# 32-event batches — matches the paper's constant 10 ev/s input rate setup.
CORE_CALIBRATION = 320.0
# Straggler detection floor: below this median step-time the k·median test
# would flag pure perf_counter jitter (the dry-run backend steps in
# microseconds), so segments are only judged once steps cost real time.
STRAGGLER_MIN_MEDIAN_MS = 0.05

PyTree = Any


@dataclass
class SegmentSpec:
    """Static description of a segment before compilation/instantiation."""

    name: str
    dag_name: str  # running DAG this segment belongs to
    task_ids: List[str]  # topological order within the segment
    # task id -> parent ids in canonical (signature-sorted) order; parents may
    # live outside the segment (boundary inputs fetched from the broker).
    parents: Dict[str, List[str]]
    # tasks initially forwarding their output to the broker (boundary streams
    # known at deploy time). The backend can extend this set at runtime —
    # the paper's control-topic "forward" signal — without recompiling,
    # because the compiled step returns every task's output.
    publish: Set[str]
    batch_of: Dict[str, int]  # per-task output batch size
    created_at: int = 0  # launch sequence number (segments step in this order)
    # Fusion-compiled hot path: the jit planes compile this segment's step
    # with XLA buffer donation (pre-step states donated to post-step
    # states), so intermediate buffers never materialize. Donation
    # invalidates the donated arrays after each step — callers must not
    # retain references to a fused segment's states across steps (the
    # system layer therefore skips fusion under background checkpointing).
    fused: bool = False


@dataclass
class StepReport:
    step: int
    live_tasks: int
    paused_tasks: int
    cost: float  # core-equivalents this step
    wall_ms: float
    segment_ms: Dict[str, float] = field(default_factory=dict)
    stragglers: List[str] = field(default_factory=list)
    # Modelled step latency from the segment dependency DAG: Σ over waves of
    # the wave max in concurrent mode (independent segments overlap), Σ of
    # all segment_ms in sync mode (one serial sweep). For the dry-run
    # backend this *is* the predicted wall-clock of a concurrent deployment.
    makespan_ms: float = 0.0


def _encode_report(r: StepReport) -> Dict[str, Any]:
    """JSON-safe StepReport for the opt-in checkpoint ring buffer."""
    return {
        "step": int(r.step),
        "live_tasks": int(r.live_tasks),
        "paused_tasks": int(r.paused_tasks),
        "cost": float(r.cost),
        "wall_ms": float(r.wall_ms),
        "segment_ms": {k: float(v) for k, v in r.segment_ms.items()},
        "stragglers": list(r.stragglers),
        "makespan_ms": float(r.makespan_ms),
    }


def _decode_report(rec: Dict[str, Any]) -> StepReport:
    return StepReport(
        step=int(rec["step"]),
        live_tasks=int(rec["live_tasks"]),
        paused_tasks=int(rec["paused_tasks"]),
        cost=float(rec["cost"]),
        wall_ms=float(rec["wall_ms"]),
        segment_ms={k: float(v) for k, v in rec.get("segment_ms", {}).items()},
        stragglers=list(rec.get("stragglers", ())),
        makespan_ms=float(rec.get("makespan_ms", 0.0)),
    )


@dataclass
class BackendSnapshot:
    """Point-in-time backend state — the ``snapshot`` verb of the protocol."""

    backend: str
    step_count: int
    segments: Dict[str, List[str]]  # segment name -> deployed task ids
    paused: Set[str]
    live_tasks: int
    paused_tasks: int
    cost: float
    device_of: Dict[str, Any] = field(default_factory=dict)  # sharded only


def _nbytes(tree: Any) -> int:
    """Bytes of a state pytree's array leaves (dicts, lists and tuples)."""
    if isinstance(tree, dict):
        return sum(_nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(_nbytes(v) for v in tree)
    return int(getattr(tree, "nbytes", 0))


def compute_batches(
    order: List[str],
    parents: Dict[str, List[str]],
    known: Dict[str, int],
    base_batch: int,
) -> Dict[str, int]:
    """Static per-task batch sizes: sources B₀, else Σ parent batches."""
    out = dict(known)
    for tid in order:
        if tid in out:
            continue
        ps = parents[tid]
        out[tid] = base_batch if not ps else sum(out[p] for p in ps)
    return out


class ExecutionBackend:
    """Data-plane protocol + the runtime-agnostic bookkeeping.

    Concrete backends implement two hooks:

      * :meth:`_build` — turn a :class:`SegmentSpec` into a segment object
        exposing ``spec``, ``states``, ``active``, ``cost_of``,
        ``pause``/``resume`` and ``live_task_ids``;
      * :meth:`_step_one` — advance one segment one step (returning a
        simulated duration in ms, or ``None`` to use the measured one).

    Everything else — the O(1) task→segment reverse index (replacing the
    old linear scans in ``forward``/``_owner``), the segment dependency
    DAG driving the sync/concurrent stepping pipeline, pause/resume
    flags, the cost accounting that reproduces the paper's Fig. 2/3
    counters, straggler EWMAs and state-preserving defragmentation — is
    shared here, so every backend reports identical control-plane
    trajectories by construction.

    Stepping runs in one of two modes (:meth:`configure_stepping`):
    ``"sync"`` — the original single-thread sweep in launch order — or
    ``"concurrent"`` — a dependency-aware ready-queue dispatch where every
    segment whose boundary producers have finished steps immediately on a
    thread pool (simulated clock on the dry-run backend). Both modes
    produce identical sink counts: concurrent dispatch respects the same
    producer-before-consumer order the launch-order sweep implies, and the
    broker's per-topic sequencing enforces it on the data path.
    """

    name: str = ""
    # Whether concurrent mode actually uses threads. The dry-run backend
    # flips this off: it keeps the dependency-DAG *makespan model* (wave
    # max, not wave sum) but steps on the caller's thread.
    concurrent_dispatch: bool = True

    def __init__(
        self,
        straggler_factor: float = 3.0,
        ewma_alpha: float = 0.3,
        step_mode: str = "sync",
        max_workers: Optional[int] = None,
    ):
        self.segments: Dict[str, Any] = {}
        self.forwarding: Dict[str, Set[str]] = {}  # segment -> task ids forwarded
        self.paused: Set[str] = set()  # running task ids paused (global view)
        self.step_count = 0
        self._launch_seq = 0
        # O(1) reverse index: task id -> owning segment name, maintained
        # across deploy/kill/defragment (was an O(segments·tasks) scan).
        self._owner_of: Dict[str, str] = {}
        # task id -> ⟨type, config⟩ definition, kept so checkpoints can
        # redeploy paused tasks whose running DAGs are long gone.
        self.task_defs: Dict[str, Task] = {}
        # Segment dependency DAG: segment -> upstream segments producing its
        # boundary inputs. Maintained incrementally across deploy/kill (and
        # therefore merge/unmerge/defragment/restore, which compose them);
        # derived state — never checkpointed, always rebuilt by redeploy.
        self.seg_deps: Dict[str, Set[str]] = {}
        self._waves_cache: Optional[List[List[str]]] = None
        # stepping pipeline knobs (see configure_stepping)
        if step_mode not in STEP_MODES:
            raise ValueError(f"step_mode must be one of {STEP_MODES}, got {step_mode!r}")
        self.step_mode = step_mode
        self.max_workers = max_workers
        # Persistent dispatch pool for concurrent stepping, created lazily
        # on the first concurrent step and reused across steps (pool
        # spin-up costs more than a small step); dropped when max_workers
        # changes and on close().
        self._pool: Optional[ThreadPoolExecutor] = None
        self.on_wave: Optional[Callable[[WaveEvent], None]] = None
        # cluster-plane health surface: every backend accepts the hook, the
        # single-process backends just never emit (worker_health() -> None)
        self.worker_events: List[Any] = []
        self.on_worker_event: Optional[Callable[[Any], None]] = None
        # opt-in StepReport ring buffer: bounds self.reports in memory AND
        # persists the tail in checkpoints (None = unbounded, not persisted)
        self.history_limit: Optional[int] = None
        # straggler tracking
        self.straggler_factor = straggler_factor
        self.ewma_alpha = ewma_alpha
        self.ewma_ms: Dict[str, float] = {}
        self.redispatches: List[Tuple[int, str]] = []
        self.reports: List[StepReport] = []
        # state-leaf encoder used by dump_state/_dump_extra — swapped for a
        # deferring marker during background-checkpoint snapshots
        self._state_encoder: Callable[[Any], Any] = encode_pytree
        # telemetry plane (repro.obs): a per-backend metrics registry (so
        # tests running many systems in one process don't cross-pollute)
        # and a span tracer, disabled until configure_obs(trace=True)
        self.metrics: MetricsRegistry = MetricsRegistry()
        self.tracer = Tracer(enabled=False)
        # keyed window tasks (Operator.window): task id -> (type, state
        # bytes, the state's ``rows`` at the last harvest); a step adds
        # nothing here
        self._window_tasks: Dict[str, Tuple[str, int, int]] = {}
        self._window_types: Set[str] = set()
        self._mint_instruments()

    def _mint_instruments(self) -> None:
        """Pre-mint the hot-path instruments so step() does no name lookups."""
        m = self.metrics
        self._m_steps = m.counter("repro_steps_total", "data-plane steps completed")
        self._m_step_wall = m.histogram(
            "repro_step_wall_ms", "whole-step wall time (ms)"
        )
        self._m_seg_ms = m.histogram(
            "repro_segment_step_ms", "per-segment step time (ms)"
        )
        self._m_live = m.gauge("repro_tasks_live", "live (active) deployed tasks")
        self._m_paused = m.gauge("repro_tasks_paused", "paused deployed tasks")
        self._m_cost = m.gauge(
            "repro_cost_cores", "core-equivalents consumed by the last step"
        )
        self._m_window_rows = m.counter(
            "repro_ops_window_rows_total", "valid rows fed to keyed window tasks, by type"
        )
        self._m_window_bytes = m.gauge(
            "repro_ops_window_state_bytes", "device bytes of deployed window task state, by type"
        )
        m.add_collector(self._harvest_windows)
        self._sum_windows()

    def configure_obs(
        self,
        metrics: Optional[bool] = None,
        trace: Optional[bool] = None,
        sample_stride: Optional[int] = None,
        trace_capacity: Optional[int] = None,
    ) -> "ExecutionBackend":
        """Telemetry knobs (None leaves a knob unchanged).

        ``metrics=False`` swaps the registry for a no-op twin (the honest
        baseline of the overhead benchmark); ``trace=True`` arms span
        recording at ``sample_stride`` (record every Nth span per name).
        The multiproc backend additionally forwards trace configuration to
        its worker processes.
        """
        if metrics is not None:
            self.metrics = MetricsRegistry() if metrics else NULL_REGISTRY
            self._mint_instruments()
        if trace is not None or sample_stride is not None or trace_capacity is not None:
            self.tracer.configure(
                enabled=trace, sample_stride=sample_stride, capacity=trace_capacity
            )
        return self

    def metrics_snapshot(self) -> Dict[str, Any]:
        """Aggregated metrics snapshot (overridden by worker-pool backends
        to merge worker registries shipped over the ``metrics`` RPC)."""
        return self.metrics.snapshot()

    def drain_spans(self) -> List[Dict[str, Any]]:
        """Pop all buffered trace spans (coordinator + any worker pools)."""
        return self.tracer.drain()

    def configure_stepping(
        self,
        step_mode: Optional[str] = None,
        max_workers: Optional[int] = None,
        on_wave: Optional[Callable[[WaveEvent], None]] = None,
        report_history: Optional[int] = None,
    ) -> "ExecutionBackend":
        """Set the stepping-pipeline knobs (None leaves a knob unchanged).

        Safe between steps at any point in the lifecycle — switching
        ``step_mode`` mid-run changes only the dispatch schedule, never
        the results.
        """
        if step_mode is not None:
            if step_mode not in STEP_MODES:
                raise ValueError(
                    f"step_mode must be one of {STEP_MODES}, got {step_mode!r}"
                )
            self.step_mode = step_mode
        if max_workers is not None and max_workers != self.max_workers:
            self.max_workers = max_workers
            self._reset_pool()  # resize on next concurrent step
        if on_wave is not None:
            self.on_wave = on_wave
        if report_history is not None:
            if report_history < 1:
                raise ValueError("report_history must be >= 1")
            self.history_limit = report_history
        return self

    # -- hooks for concrete backends ------------------------------------------
    def _build(
        self,
        spec: SegmentSpec,
        dataflow: Dataflow,
        init_states: Optional[Dict[str, PyTree]],
    ) -> Any:
        raise NotImplementedError

    def _step_one(self, seg: Any) -> Optional[float]:
        """Advance one segment one step.

        Returns a simulated duration in ms (dry-run latency model) or
        ``None`` to report the measured wall-time. In concurrent mode this
        runs on a worker thread; it may touch only its own segment plus
        thread-safe transports (the broker).
        """
        raise NotImplementedError

    def _drop_streams(self, seg: Any) -> None:
        """Release any transport resources of a killed segment (broker topics)."""

    def _begin_concurrent_step(self) -> None:
        """Hook before a concurrent dispatch (jit backends snapshot per-topic
        sequence targets here so boundary reads sync on their producers)."""

    def _end_concurrent_step(self) -> None:
        """Hook after a concurrent dispatch completes or fails."""

    # -- deployment -----------------------------------------------------------
    def deploy(
        self,
        spec: SegmentSpec,
        dataflow: Dataflow,
        init_states: Optional[Dict[str, PyTree]] = None,
    ) -> Any:
        spec.created_at = self._launch_seq
        self._launch_seq += 1
        seg = self._build(spec, dataflow, init_states)
        self.segments[spec.name] = seg
        self.forwarding[spec.name] = set(spec.publish)
        # Dependency DAG: boundary parents resolve to their owning segments.
        # Merges only add segments *downstream* of existing ones (launch
        # order is topological), so deploying never changes the deps of
        # already-deployed segments — the edge set grows incrementally.
        in_segment = set(spec.task_ids)
        deps = {
            self._owner_of[p]
            for tid in spec.task_ids
            for p in spec.parents.get(tid, ())
            if p not in in_segment and p in self._owner_of
        }
        for tid in spec.task_ids:
            self._owner_of[tid] = spec.name
            self.task_defs[tid] = dataflow.tasks[tid]
        deps.discard(spec.name)
        self.seg_deps[spec.name] = deps
        self._waves_cache = None
        operators = getattr(seg, "operators", None) or {}
        windows = [t for t in spec.task_ids if getattr(operators.get(t), "window", False)]
        for tid in windows:
            state = seg.states[tid]  # carried over, its ``rows`` already counted
            self._window_tasks[tid] = (
                operators[tid].type, _nbytes(state), int(state["rows"])
            )
        if windows:
            self._sum_windows()
        return seg

    def _sum_windows(self) -> None:
        """Set the state gauge: bytes of every deployed window task (paused
        included) by type, 0 for a type with none left."""
        total = dict.fromkeys(self._window_types, 0)
        for typ, nbytes, _ in self._window_tasks.values():
            total[typ] = total.get(typ, 0) + nbytes
        self._window_types |= set(total)
        for typ, nbytes in total.items():
            self._m_window_bytes.set(nbytes, type=typ)

    def _harvest_windows(
        self, task_ids: Optional[Iterable[str]] = None, segment: Any = None
    ) -> None:
        """Add to ``repro_ops_window_rows_total`` the valid rows each window
        task (all by default) counted on the device since the last harvest:
        at every metrics snapshot (a collector) and at kill. The state's
        int32 ``rows`` wraps, so the difference is taken mod 2**32: exact
        while a task sees fewer than 2**32 rows between two harvests."""
        for tid in list(self._window_tasks if task_ids is None else task_ids):
            typ, nbytes, seen = self._window_tasks[tid]
            seg = segment if segment is not None else self.segments[self._owner_of[tid]]
            now = int(seg.states[tid]["rows"])
            self._m_window_rows.inc((now - seen) % (1 << 32), type=typ)
            self._window_tasks[tid] = (typ, nbytes, now)

    def kill(self, segment_name: str) -> None:
        seg = self.segments.pop(segment_name)
        self.forwarding.pop(segment_name, None)
        self.ewma_ms.pop(segment_name, None)
        self.seg_deps.pop(segment_name, None)
        for deps in self.seg_deps.values():
            deps.discard(segment_name)
        self._waves_cache = None
        self._drop_streams(seg)
        windows = [t for t in seg.spec.task_ids
                   if t in self._window_tasks and self._owner_of.get(t) == segment_name]
        self._harvest_windows(windows, seg)
        for tid in seg.spec.task_ids:
            self.paused.discard(tid)
            if self._owner_of.get(tid) == segment_name:
                del self._owner_of[tid]
                self.task_defs.pop(tid, None)
                self._window_tasks.pop(tid, None)
        if windows:
            self._sum_windows()

    # -- control signals (paper §4.3 control topic) -----------------------------
    def forward(self, task_id: str) -> None:
        """Ask the segment owning ``task_id`` to forward its output stream."""
        owner = self._owner_of.get(task_id)
        if owner is None:
            raise KeyError(f"task {task_id!r} not deployed")
        self.forwarding[owner].add(task_id)

    def pause(self, task_ids: Set[str]) -> None:
        for seg in self.segments.values():
            seg.pause(task_ids)
        self.paused |= {t for t in task_ids if t in self._owner_of}

    def resume(self, task_ids: Set[str]) -> None:
        for seg in self.segments.values():
            seg.resume(task_ids)
        self.paused -= set(task_ids)

    def _owner(self, task_id: str) -> Optional[str]:
        return self._owner_of.get(task_id)

    # -- stepping pipeline --------------------------------------------------------
    def segment_waves(self) -> List[List[str]]:
        """Topological levels of the segment dependency DAG (cached; segments
        in one wave are independent and step concurrently)."""
        if self._waves_cache is None:
            order = {n: s.spec.created_at for n, s in self.segments.items()}
            self._waves_cache = compute_waves(self.seg_deps, order)
        return self._waves_cache

    def _step_named(self, name: str) -> float:
        if self.tracer.enabled:
            with self.tracer.span(name, "segment", step=self.step_count):
                ms = self._step_timed(name)
        else:
            ms = self._step_timed(name)
        self._m_seg_ms.observe(ms)
        return ms

    def _step_timed(self, name: str) -> float:
        seg = self.segments[name]
        s0 = time.perf_counter()
        simulated = self._step_one(seg)
        return simulated if simulated is not None else (time.perf_counter() - s0) * 1e3

    def _step_segments(self) -> Dict[str, float]:
        """The sync sweep: every segment once, in launch order (topological)."""
        ordered = sorted(self.segments, key=lambda n: self.segments[n].spec.created_at)
        return {name: self._step_named(name) for name in ordered}

    def _step_segments_concurrent(self) -> Dict[str, float]:
        """Dependency-aware concurrent dispatch (ready-queue over a thread
        pool); falls back to the caller's thread when the backend models
        time instead of spending it (``concurrent_dispatch = False``)."""
        if not self.concurrent_dispatch:
            return self._step_segments()
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.max_workers, thread_name_prefix="repro-step"
            )
        self._begin_concurrent_step()
        try:
            order = {n: s.spec.created_at for n, s in self.segments.items()}
            if self.tracer.enabled:
                with self.tracer.span(
                    "wave_dispatch", "step", step=self.step_count,
                    segments=len(self.segments),
                ):
                    return run_ready_queue(
                        self.seg_deps, self._step_named, self.max_workers, order,
                        pool=self._pool, recover=self._step_recover,
                    )
            return run_ready_queue(
                self.seg_deps, self._step_named, self.max_workers, order,
                pool=self._pool, recover=self._step_recover,
            )
        finally:
            self._end_concurrent_step()

    def _reset_pool(self) -> None:
        """Drop the dispatch pool only (recreated lazily at the next
        concurrent step) — the pool-resize half of :meth:`close`, safe to
        call on a live backend."""
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    # -- cluster-plane hooks (overridden by the multiproc backend) --------------
    def _step_recover(self, name: str, exc: BaseException) -> bool:
        """Attempt to recover from a failed segment step so the dispatch
        loop can re-queue the item instead of erroring the step. Backends
        without a self-healing worker pool decline."""
        return False

    def worker_health(self) -> Optional[Dict[str, Any]]:
        """Worker-pool health snapshot; ``None`` for in-process backends."""
        return None

    def _emit_worker_event(self, kind: str, worker: Optional[int] = None,
                           detail: str = "", ms: float = 0.0) -> None:
        """Record a cluster-plane event and forward it to the user hook.

        A failing user hook must never break recovery, so hook exceptions
        are swallowed after the event is recorded."""
        from repro.cluster.events import WorkerEvent

        event = WorkerEvent(kind=kind, worker=worker, step=self.step_count,
                            detail=detail, ms=ms)
        self.worker_events.append(event)
        if len(self.worker_events) > 256:
            del self.worker_events[:-256]
        if self.on_worker_event is not None:
            try:
                self.on_worker_event(event)
            except Exception:  # pragma: no cover - user-hook safety
                pass

    def close(self) -> None:
        """Release stepping resources (the persistent dispatch pool).

        Idempotent; stepping after close() lazily recreates the pool."""
        self._reset_pool()

    def step(self) -> StepReport:
        if self.tracer.enabled:
            with self.tracer.span("step", "step", step=self.step_count + 1):
                return self._step_impl()
        return self._step_impl()

    def _step_impl(self) -> StepReport:
        t0 = time.perf_counter()
        if self.step_mode == "concurrent":
            seg_ms = self._step_segments_concurrent()
        else:
            seg_ms = self._step_segments()
        waves = self.segment_waves()
        concurrent = self.step_mode == "concurrent"
        wave_ms = [
            (max if concurrent else sum)([seg_ms[n] for n in wave if n in seg_ms] or [0.0])
            for wave in waves
        ]
        if self.tracer.enabled:
            with self.tracer.span("account", "step"):
                live, paused_n, cost = self.account()
        else:
            live, paused_n, cost = self.account()
        stragglers = self._update_stragglers(seg_ms)
        self.step_count += 1
        if self.on_wave is not None:
            for i, wave in enumerate(waves):
                self.on_wave(
                    WaveEvent(
                        step=self.step_count,
                        index=i,
                        segments=tuple(wave),
                        wave_ms=wave_ms[i],
                    )
                )
        report = StepReport(
            step=self.step_count,
            live_tasks=live,
            paused_tasks=paused_n,
            cost=cost,
            wall_ms=(time.perf_counter() - t0) * 1e3,
            segment_ms=seg_ms,
            stragglers=stragglers,
            makespan_ms=sum(wave_ms),
        )
        self._m_steps.inc()
        self._m_step_wall.observe(report.wall_ms)
        self._m_live.set(live)
        self._m_paused.set(paused_n)
        self._m_cost.set(cost)
        self.reports.append(report)
        if self.history_limit is not None and len(self.reports) > self.history_limit:
            del self.reports[: len(self.reports) - self.history_limit]
        return report

    def run(self, steps: int) -> List[StepReport]:
        return [self.step() for _ in range(steps)]

    # -- accounting ----------------------------------------------------------------
    def account(self) -> Tuple[int, int, float]:
        """(live tasks, paused tasks, core-equivalents) — the Fig. 2/3 counters."""
        live = 0
        paused_n = 0
        cost = 0.0
        for seg in self.segments.values():
            for tid in seg.spec.task_ids:
                w = seg.cost_of[tid] * seg.spec.batch_of[tid]
                if bool(seg.active[tid]):
                    live += 1
                    cost += w
                else:
                    paused_n += 1
                    cost += PAUSE_EPSILON * w
        return live, paused_n, cost / CORE_CALIBRATION

    @property
    def live_task_count(self) -> int:
        return sum(len(s.live_task_ids()) for s in self.segments.values())

    @property
    def deployed_task_count(self) -> int:
        return sum(len(s.spec.task_ids) for s in self.segments.values())

    def sink_state(self, task_id: str) -> Any:
        owner = self._owner_of.get(task_id)
        if owner is None:
            raise KeyError(f"sink task {task_id!r} not deployed")
        return self.segments[owner].states[task_id]

    def snapshot(self) -> BackendSnapshot:
        live, paused_n, cost = self.account()
        return BackendSnapshot(
            backend=self.name or type(self).__name__,
            step_count=self.step_count,
            segments={n: list(s.spec.task_ids) for n, s in self.segments.items()},
            paused=set(self.paused),
            live_tasks=live,
            paused_tasks=paused_n,
            cost=cost,
            device_of=dict(getattr(self, "device_of", {})),
        )

    def spawn_config(self) -> Dict[str, Any]:
        """Constructor kwargs that reproduce this backend's topology.

        Checkpoints persist this next to the backend name so a restore can
        re-create the same data plane — transport kind, worker count,
        placement policy — without the caller re-specifying it. Keys must
        be JSON-safe and accepted by the backend's constructor."""
        return {}

    # -- durability (checkpoint/restore verbs) ------------------------------------
    def dump_state(self, state_encoder: Optional[Callable[[Any], Any]] = None) -> Dict[str, Any]:
        """Serialize everything a restore needs to resume stepping exactly.

        The payload is backend-portable: segment specs carry each task's
        ⟨type, config⟩ so a restoring backend can rebuild operators (or cost
        entries) without the original running DAGs — deployed-but-paused
        tasks may no longer exist in any running DAG. Backend-specific
        extras (broker buffers, device maps) ride in ``extra`` via
        :meth:`_dump_extra` and are ignored by backends that don't know
        them, which is what makes inprocess ↔ dryrun cross-restores work.

        ``state_encoder`` overrides how state leaves are serialized — the
        background checkpointer passes a deferring marker so the cheap
        snapshot happens on the stepping thread and the base64 encoding on
        the writer thread (states are replaced wholesale each step, never
        mutated in place, so captured references stay consistent).
        """
        self._state_encoder = (
            encode_pytree if state_encoder is None else state_encoder
        )
        try:
            return self._dump_state_inner()
        finally:
            self._state_encoder = encode_pytree

    def _dump_state_inner(self) -> Dict[str, Any]:
        enc = self._state_encoder
        segments: List[Dict[str, Any]] = []
        for name, seg in sorted(
            self.segments.items(), key=lambda kv: kv[1].spec.created_at
        ):
            spec = seg.spec
            segments.append(
                {
                    "name": name,
                    "dag_name": spec.dag_name,
                    "task_ids": list(spec.task_ids),
                    "parents": {t: list(ps) for t, ps in spec.parents.items()},
                    # the *current* forwarding set, so runtime forward()
                    # signals survive the restore as the new publish set
                    "publish": sorted(self.forwarding.get(name, set())),
                    "batch_of": {t: int(b) for t, b in spec.batch_of.items()},
                    "created_at": int(spec.created_at),
                    "fused": bool(spec.fused),
                    "tasks": {
                        t: {"type": self.task_defs[t].type, "config": self.task_defs[t].config}
                        for t in spec.task_ids
                    },
                    "states": {
                        t: enc(seg.states[t]) for t in spec.task_ids
                    },
                    "steps_run": int(getattr(seg, "steps_run", 0)),
                }
            )
        state = {
            "step_count": int(self.step_count),
            "launch_seq": int(self._launch_seq),
            "paused": sorted(self.paused),
            "ewma_ms": {k: float(v) for k, v in self.ewma_ms.items()},
            "redispatches": [[int(s), n] for s, n in self.redispatches],
            "segments": segments,
            "extra": self._dump_extra(),
        }
        if self.history_limit is not None:
            # opt-in monitoring history: the StepReport ring buffer survives
            # restarts (dashboards resume with the pre-crash trajectory)
            state["history_limit"] = int(self.history_limit)
            state["reports"] = [_encode_report(r) for r in self.reports]
        return state

    def restore_state(self, state: Dict[str, Any]) -> None:
        """Redeploy every checkpointed segment and resume the counters.

        Must be called on a *fresh* backend. Segments re-deploy in their
        original launch order (so the launch-order-is-topological invariant
        survives), with task states decoded through the backend-specific
        :meth:`_decode_init_states` hook — that hook is where cross-backend
        restores coerce states (jit ⇄ dry-run). Sharded backends re-place
        segments through their PlacementPolicy as a side effect of
        ``deploy``; device pinning is *not* restored verbatim.
        """
        if self.segments:
            raise ValueError("restore_state() needs a fresh backend (segments deployed)")
        # Extras first: they carry transport buffers/counters and the
        # checkpoint-time placement map — restore-time placement policies
        # (sticky) consult the latter while the segments redeploy below.
        self._restore_extra(state.get("extra", {}))
        for rec in sorted(state["segments"], key=lambda r: r["created_at"]):
            spec = SegmentSpec(
                name=rec["name"],
                dag_name=rec["dag_name"],
                task_ids=list(rec["task_ids"]),
                parents={t: list(ps) for t, ps in rec["parents"].items()},
                publish=set(rec["publish"]),
                batch_of={t: int(b) for t, b in rec["batch_of"].items()},
                fused=bool(rec.get("fused", False)),
            )
            # Synthetic task-definition container: deploy only reads
            # dataflow.tasks[tid] (operator/cost construction), so the
            # checkpointed ⟨type, config⟩ records are sufficient.
            df = Dataflow(rec["dag_name"])
            for tid in spec.task_ids:
                t = rec["tasks"][tid]
                df.add_task(Task.make(tid, t["type"], t["config"]))
            init_states = self._decode_init_states(spec, df, rec["states"])
            self._launch_seq = int(rec["created_at"])
            seg = self.deploy(spec, df, init_states=init_states)
            seg.steps_run = int(rec.get("steps_run", 0))
        self._launch_seq = int(state["launch_seq"])
        paused = set(state.get("paused", ()))
        if paused:
            self.pause(paused)
        self.step_count = int(state["step_count"])
        self.ewma_ms = {k: float(v) for k, v in state.get("ewma_ms", {}).items()}
        self.redispatches = [(int(s), n) for s, n in state.get("redispatches", ())]
        if state.get("history_limit") is not None:
            self.history_limit = int(state["history_limit"])
            self.reports = [_decode_report(r) for r in state.get("reports", ())]

    def _decode_init_states(
        self, spec: SegmentSpec, dataflow: Dataflow, states_enc: Dict[str, Any]
    ) -> Dict[str, PyTree]:
        """Decode checkpointed states into this backend's native form."""
        return {tid: decode_pytree(enc) for tid, enc in states_enc.items()}

    def _dump_extra(self) -> Dict[str, Any]:
        """Backend-specific durable extras (broker buffers, device maps)."""
        return {}

    def _restore_extra(self, extra: Dict[str, Any]) -> None:
        """Consume :meth:`_dump_extra` output; unknown keys must be ignored."""

    # -- compiled-segment reuse cache ---------------------------------------------
    def compile_cache_stats(self) -> Dict[str, int]:
        """Hit/miss/evict counters of the compiled-segment reuse cache.

        Backends that compile in-process expose their coordinator cache
        (``self.compile_cache``); the multiproc backend overrides this to
        aggregate its workers' process-local caches. Backends that never
        compile (dryrun) report zeros.
        """
        cache = getattr(self, "compile_cache", None)
        if cache is None:
            return {"hits": 0, "misses": 0, "evictions": 0, "entries": 0}
        return cache.stats()

    # -- dry-run latency calibration feed ----------------------------------------
    def latency_samples(self) -> List[Tuple[Dict[str, float], float]]:
        """⟨per-task-type work units, measured segment ms⟩ calibration pairs.

        Joins every recorded ``StepReport.segment_ms`` entry with the
        deployed segment's per-task ``cost_weight × batch`` work units,
        grouped by task type — the observations
        :func:`repro.ops.costs.fit_latency_model` fits so the dry-run
        backend can report realistic ``segment_ms`` instead of ~0.
        """
        samples: List[Tuple[Dict[str, float], float]] = []
        for report in self.reports:
            for name, ms in report.segment_ms.items():
                seg = self.segments.get(name)
                if seg is None:  # segment killed since — spec no longer known
                    continue
                units: Dict[str, float] = {}
                for tid in seg.spec.task_ids:
                    ttype = self.task_defs[tid].type
                    work = seg.cost_of[tid] * seg.spec.batch_of[tid]
                    units[ttype] = units.get(ttype, 0.0) + work
                samples.append((units, float(ms)))
        return samples

    def segment_latency_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-segment latency digest from the SAME ``StepReport.segment_ms``
        history that feeds :meth:`latency_samples` (killed segments skipped
        identically), so the dry-run calibrator and any monitoring reader
        agree by construction. This — not ``ewma_ms``, which is a smoothed
        straggler-detection signal that resets on redispatch — is the
        canonical per-segment latency surface; use
        ``StreamSystem.segment_latency_ms()`` from the API layer.

        Returns ``{segment: {"mean_ms", "last_ms", "max_ms", "samples"}}``.
        """
        agg: Dict[str, Dict[str, float]] = {}
        for report in self.reports:
            for name, ms in report.segment_ms.items():
                if name not in self.segments:  # killed since — same skip as above
                    continue
                cell = agg.get(name)
                if cell is None:
                    cell = agg[name] = {
                        "mean_ms": 0.0, "last_ms": 0.0, "max_ms": 0.0,
                        "samples": 0, "_sum": 0.0,
                    }
                ms = float(ms)
                cell["_sum"] += ms
                cell["samples"] += 1
                cell["last_ms"] = ms
                cell["max_ms"] = max(cell["max_ms"], ms)
        for cell in agg.values():
            cell["mean_ms"] = cell.pop("_sum") / cell["samples"]
        return agg

    # -- straggler mitigation -----------------------------------------------------
    def _update_stragglers(self, seg_ms: Dict[str, float]) -> List[str]:
        flagged: List[str] = []
        for name, ms in seg_ms.items():
            prev = self.ewma_ms.get(name)
            self.ewma_ms[name] = ms if prev is None else (
                self.ewma_alpha * ms + (1 - self.ewma_alpha) * prev
            )
        # prune EWMAs of killed segments
        for name in list(self.ewma_ms):
            if name not in self.segments:
                del self.ewma_ms[name]
        if len(self.ewma_ms) >= 2:
            vals = sorted(self.ewma_ms.values())
            median = vals[len(vals) // 2]
            for name, ew in list(self.ewma_ms.items()):
                if median > STRAGGLER_MIN_MEDIAN_MS and ew > self.straggler_factor * median:
                    flagged.append(name)
                    self.redispatch(name)
        return flagged

    def redispatch(self, segment_name: str) -> None:
        """Re-dispatch a straggling segment (hardware: move to spare host).

        The compiled executable and task states are retained; the EWMA is
        reset so the relocated segment is judged afresh.
        """
        self.redispatches.append((self.step_count, segment_name))
        self.ewma_ms.pop(segment_name, None)

    # -- defragmentation (enactment; planning in repro.core.defrag) -----------------
    def defragment(
        self,
        dag_name: str,
        fused_spec: SegmentSpec,
        dataflow: Dataflow,
    ) -> Any:
        """Replace all segments of ``dag_name`` by one fused segment.

        Task states carry over (state-preserving defrag — beyond the paper,
        which would relaunch cold). Paused tasks are dropped entirely,
        reclaiming their ε overhead.
        """
        carried: Dict[str, PyTree] = {}
        for name, seg in list(self.segments.items()):
            if seg.spec.dag_name != dag_name:
                continue
            for tid in fused_spec.task_ids:
                if tid in seg.spec.task_ids:
                    carried[tid] = seg.states[tid]
            self.kill(name)
        return self.deploy(fused_spec, dataflow, init_states=carried)

    def fuse_segments(
        self,
        fused_spec: SegmentSpec,
        dataflow: Dataflow,
        members: List[str],
    ) -> Any:
        """Replace ``members`` (a linear same-DAG segment chain) by ONE
        fusion-compiled segment, carrying task states over.

        The enactment twin of :func:`repro.core.defrag.plan_fusion` — like
        :meth:`defragment` but member-scoped (other segments of the DAG
        stay deployed untouched), and the replacement deploys with
        ``fused_spec.fused`` set so the jit planes compile its whole task
        chain into a single donated-buffer step: the chain's intermediate
        streams become XLA temporaries that never materialize on a topic.
        """
        carried: Dict[str, PyTree] = {}
        # kill() forgets member pause flags and deploy() starts all-active,
        # so paused tasks inside the chain must be re-paused afterwards.
        repause = {t for t in fused_spec.task_ids if t in self.paused}
        for name in members:
            seg = self.segments[name]
            for tid in fused_spec.task_ids:
                if tid in seg.spec.task_ids:
                    carried[tid] = seg.states[tid]
            self.kill(name)
        seg = self.deploy(fused_spec, dataflow, init_states=carried)
        if repause:
            self.pause(repause)
        return seg


# -- backend registry ----------------------------------------------------------

_BACKENDS: Dict[str, Type[ExecutionBackend]] = {}
# Built-ins resolve lazily so that naming "dryrun" never imports JAX and
# naming "inprocess" only pays the JAX import when actually used.
_LAZY_BUILTINS: Dict[str, Tuple[str, str]] = {
    "inprocess": ("repro.runtime.executor", "InProcessJitBackend"),
    "sharded": ("repro.runtime.sharded", "ShardedBackend"),
    "dryrun": ("repro.runtime.dryrun", "DryRunBackend"),
    "multiproc": ("repro.runtime.worker", "MultiprocBackend"),
}


def register_backend(cls: Type[ExecutionBackend]) -> Type[ExecutionBackend]:
    """Class decorator: register ``cls`` under ``cls.name``."""
    if not cls.name:
        raise ValueError(f"backend class {cls.__name__} has no name")
    if cls.name in _BACKENDS or cls.name in _LAZY_BUILTINS:
        raise ValueError(f"execution backend {cls.name!r} already registered")
    _BACKENDS[cls.name] = cls
    return cls


def available_backends() -> List[str]:
    return sorted(set(_BACKENDS) | set(_LAZY_BUILTINS))


def resolve_backend(
    backend: Union[str, ExecutionBackend, Type[ExecutionBackend]],
    **kwargs: Any,
) -> ExecutionBackend:
    """Name / instance / class → backend instance (names hit the registry)."""
    if isinstance(backend, ExecutionBackend):
        return backend
    if isinstance(backend, type) and issubclass(backend, ExecutionBackend):
        return backend(**kwargs)
    if isinstance(backend, str):
        cls = _BACKENDS.get(backend)
        if cls is None and backend in _LAZY_BUILTINS:
            module, attr = _LAZY_BUILTINS[backend]
            cls = getattr(importlib.import_module(module), attr)
        if cls is None:
            raise ValueError(
                f"unknown backend {backend!r} (registered: {', '.join(available_backends())})"
            )
        return cls(**kwargs)
    raise TypeError(
        f"backend must be a name or ExecutionBackend, got {type(backend).__name__}"
    )
