"""Compiled-segment reuse cache — collaborative reuse extended from streams
and tasks down to XLA executables.

The paper shares *streams* between overlapping dataflows; PR 2's backends
share *tasks* within one running DAG. This module closes the last gap:
two segments that are **structurally identical** — same task types, same
canonical configs, same batch sizes, same internal wiring, same fused
flag — lower to byte-identical XLA programs, so compiling both is pure
waste. That situation is the common case under churn: a removed dataflow
resubmitted later, dozens of users submitting the same template, or a
Default-strategy run where every submission deploys its own copy.

Mechanism:

  * :func:`structural_signature` — canonicalize a :class:`SegmentSpec`
    (task ids → ``t0, t1, …`` in spec order, external boundary parents →
    ``x0, x1, …`` in first-appearance order) and hash types/configs/
    batches/wiring with the same length-prefixed SHA-256 the merge
    algorithm uses (:mod:`repro.core.signatures`). Task *names* and topic
    *strings* are erased; everything the compiled program depends on is
    kept. Boundary array shapes are **not** part of the key — JAX keys
    its own trace cache by argument shapes under one callable, so a
    shared callable handles differing boundary shapes correctly (each
    new shape pays its own trace, subsequent segments with that shape hit).
  * :class:`CompileCache` — an LRU of **canonical** jitted step functions.
    On miss, the segment builder compiles a canonicalized twin of the
    spec and caches *that*; hit or miss, the real segment steps through a
    :class:`_RenamedStepFn` adapter that maps its task ids / topics onto
    the canonical names per call. The first trace therefore always lands
    on the shared canonical callable — a later structurally identical
    segment reuses the traced executable and skips XLA entirely.

Placement of the cache mirrors where compilation happens: the in-process
jit and sharded backends hold one cache in the coordinator
(``backend.compile_cache``); the multiproc backend's workers each hold a
process-local cache (:func:`process_compile_cache`) surfaced through the
``cache_stats`` worker RPC. Hit/miss/evict counters flow up to
``session.stats()``.

The in-memory cache lives as long as its process. Across processes and
runs, :func:`enable_persistent_cache` turns on JAX's persistent
compilation cache for the jit planes (the in-process backends' constructor
and each jit worker's startup), so a fresh process loads the segment
programs an earlier run compiled instead of compiling them again.

This module is import-safe without JAX (the coordinator of the multiproc
backend is JAX-free); :func:`~repro.runtime.segment.build_segment` is
imported lazily at first miss.
"""
from __future__ import annotations

from collections import OrderedDict
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from repro.core.graph import Dataflow, Task
from repro.core.signatures import _digest

from .backend import SegmentSpec
from .broker import topic_for

__all__ = [
    "CompileCache",
    "enable_persistent_cache",
    "persistent_cache_dir",
    "process_compile_cache",
    "program_name",
    "structural_signature",
]

# src/repro/runtime/compile_cache.py -> the checkout's root
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def _canonical_maps(spec: SegmentSpec) -> Tuple[Dict[str, str], Dict[str, str]]:
    """Task-id and external-parent renamings erasing all naming history.

    Task ids map in ``spec.task_ids`` order; external (boundary) parents
    map in first-appearance order over the per-task parent lists — the
    same order :func:`build_segment` derives its boundary topics in, so
    the canonical segment's boundary wiring is isomorphic to the real one.
    """
    tid_map = {t: f"t{i}" for i, t in enumerate(spec.task_ids)}
    ext: List[str] = []
    for t in spec.task_ids:
        for p in spec.parents[t]:
            if p not in tid_map and p not in ext:
                ext.append(p)
    ext_map = {p: f"x{i}" for i, p in enumerate(ext)}
    return tid_map, ext_map


def structural_signature(spec: SegmentSpec, dataflow: Dataflow) -> str:
    """Structural identity of a segment's compiled program.

    Two specs with equal signatures compile to the same XLA program:
    the key covers the fused flag and, per task in order, ⟨type,
    canonical config, batch, canonically renamed parent refs⟩. Parent
    refs keep their per-task *list order* (concatenation order is
    semantics); ``publish`` is excluded (the step returns every task's
    output regardless — forwarding is a runtime choice).
    """
    tid_map, ext_map = _canonical_maps(spec)
    parts: List[bytes] = [b"fused" if spec.fused else b"unfused"]
    for t in spec.task_ids:
        task = dataflow.tasks[t]
        refs = ",".join(
            tid_map[p] if p in tid_map else ext_map[p] for p in spec.parents[t]
        )
        parts.extend(
            (
                task.type.encode(),
                task.config.encode(),
                str(int(spec.batch_of[t])).encode(),
                refs.encode(),
            )
        )
    return _digest(parts)


def program_name(signature: str) -> str:
    """The jit name of a segment program: stable across runs and
    processes for one structure (``jit_segment_<12 hex>`` in HLO and the
    profiler's trace)."""
    return f"segment_{signature[:12]}"


def _canonicalize(
    spec: SegmentSpec, dataflow: Dataflow
) -> Tuple[SegmentSpec, Dataflow, Dict[str, str], Dict[str, str]]:
    """The canonical twin of ⟨spec, dataflow⟩ plus the renaming maps."""
    tid_map, ext_map = _canonical_maps(spec)
    ref = {**tid_map, **ext_map}
    canon_spec = SegmentSpec(
        name="canonical",
        dag_name="canonical",
        task_ids=[tid_map[t] for t in spec.task_ids],
        parents={
            tid_map[t]: [ref[p] for p in spec.parents[t]] for t in spec.task_ids
        },
        publish={tid_map[t] for t in spec.publish if t in tid_map},
        batch_of={tid_map[t]: int(spec.batch_of[t]) for t in spec.task_ids},
        created_at=0,
        fused=spec.fused,
    )
    canon_df = Dataflow("canonical")
    for t in spec.task_ids:
        task = dataflow.tasks[t]
        # direct construction: config is already a canonical string and must
        # round-trip byte-exactly into the canonical task definition
        canon_df.add_task(Task(id=tid_map[t], type=task.type, config=task.config))
    return canon_spec, canon_df, tid_map, ext_map


class _RenamedStepFn:
    """Per-segment adapter over a shared canonical jitted step function.

    Renames the segment's dict keys (task ids, boundary topic strings)
    onto the canonical names on the way in and back on the way out. Key
    order is irrelevant — JAX flattens dict pytrees in sorted-key order —
    so renaming preserves the traced argument structure exactly, and a
    donated canonical call (fused specs) donates the caller's own arrays.
    Exposes ``lower`` so :func:`~repro.runtime.segment.donation_report`
    keeps working on cached segments.
    """

    def __init__(self, fn: Any, tid_map: Dict[str, str], topic_map: Dict[str, str]):
        self._fn = fn
        self._tid = dict(tid_map)
        self._topic = dict(topic_map)  # real boundary topic -> canonical topic
        self._tid_rev = {v: k for k, v in tid_map.items()}

    def _rename_in(self, states, active, inputs):
        return (
            {self._tid[k]: v for k, v in states.items()},
            {self._tid[k]: v for k, v in active.items()},
            {self._topic[k]: v for k, v in inputs.items()},
        )

    def __call__(self, states, active, inputs):
        new_states, outputs = self._fn(*self._rename_in(states, active, inputs))
        return (
            {self._tid_rev[k]: v for k, v in new_states.items()},
            {self._tid_rev[k]: v for k, v in outputs.items()},
        )

    def lower(self, states, active, inputs):
        return self._fn.lower(*self._rename_in(states, active, inputs))


class CompileCache:
    """LRU cache of canonical jitted segment step functions.

    ``capacity`` bounds the number of distinct structures held; eviction
    is least-recently-used (the evicted executable stays alive only while
    segments still reference it). Counters are cumulative for the cache's
    lifetime — ``stats()`` is the surface ``session.stats()`` aggregates.
    """

    def __init__(self, capacity: int = 128):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._entries: "OrderedDict[str, Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # optional repro.obs.Tracer set by the owning backend/worker; a miss
        # (canonical build → trace + jit) is the expensive event worth a span
        self.tracer: Optional[Any] = None

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> Dict[str, int]:
        return {
            "hits": int(self.hits),
            "misses": int(self.misses),
            "evictions": int(self.evictions),
            "entries": len(self._entries),
        }

    def step_fn_for(self, spec: SegmentSpec, dataflow: Dataflow) -> _RenamedStepFn:
        """The (shared, canonical) step function for a spec, adapter-wrapped.

        On miss the canonical twin is built uncached — its jitted callable
        is the cached artifact. Even the missing segment steps through the
        adapter, so the first trace happens on the shared callable and
        every later structurally identical segment reuses it.
        """
        key = structural_signature(spec, dataflow)
        fn = self._entries.get(key)
        if fn is not None:
            self.hits += 1
            self._entries.move_to_end(key)
            canon_spec, _, tid_map, ext_map = _canonicalize(spec, dataflow)
        else:
            self.misses += 1
            from .segment import build_segment  # lazy: imports JAX

            canon_spec, canon_df, tid_map, ext_map = _canonicalize(spec, dataflow)
            tracer = self.tracer
            if tracer is not None and tracer.enabled:
                with tracer.span("compile_miss", "compile",
                                 signature=key[:12], tasks=len(spec.task_ids),
                                 fused=bool(spec.fused)):
                    fn = build_segment(canon_spec, canon_df).step_fn
            else:
                fn = build_segment(canon_spec, canon_df).step_fn
            self._entries[key] = fn
            if len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
        topic_map = {topic_for(p): topic_for(c) for p, c in ext_map.items()}
        return _RenamedStepFn(fn, tid_map, topic_map)


# One cache per worker process (the multiproc data plane compiles inside
# its workers; the coordinator stays JAX-free and aggregates over RPC).
_PROCESS_CACHE: Optional[CompileCache] = None


def process_compile_cache() -> CompileCache:
    global _PROCESS_CACHE
    if _PROCESS_CACHE is None:
        _PROCESS_CACHE = CompileCache()
    return _PROCESS_CACHE


def persistent_cache_dir() -> str:
    """Where compiled programs persist: JAX's own
    ``jax_compilation_cache_dir`` (set from ``$JAX_COMPILATION_CACHE_DIR``
    or by the embedding application) when it is set, else ``.jax_cache``
    at the root of the checkout. The default is a fixed path, never a
    temporary, per-pid or timed one: a cache directory that moves between
    runs is never hit."""
    import jax

    return jax.config.jax_compilation_cache_dir or str(_CHECKOUT_CACHE_DIR)


def enable_persistent_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at
    :func:`persistent_cache_dir` and write every program to it; returns
    the directory, or ``None`` when the cache is switched off
    (``JAX_ENABLE_COMPILATION_CACHE=false``, as the test suite runs).

    Segment programs compile in well under JAX's default one-second
    write threshold, so the threshold drops to zero: otherwise most of
    them would never be written and every new process would compile
    them again."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    path = persistent_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
