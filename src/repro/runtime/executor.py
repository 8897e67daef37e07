"""InProcessJitBackend — the jit data plane behind the ExecutionBackend API.

Steps deployed segments in launch order: merges only ever add segments
*downstream* of existing ones (boundary streams flow old → new; see
DESIGN.md invariant), so launch order is a valid topological order of the
segment graph.

Resource accounting, straggler EWMAs, pause flags and the task→segment
reverse index (O(1) ``forward``/``_owner`` instead of the old linear scan
over segments) live in the shared :class:`repro.runtime.backend.ExecutionBackend`
base — this module adds only what is jit-specific: segment compilation,
broker transport, and real device buffers for task states.

``Executor`` remains as a backwards-compatible alias.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.core.graph import Dataflow

from .backend import (
    CORE_CALIBRATION,
    PAUSE_EPSILON,
    ExecutionBackend,
    PyTree,
    SegmentSpec,
    StepReport,
)
from .broker import topic_for
from .checkpoint import decode_pytree
from .segment import Segment, build_segment
from .transport import Transport, resolve_transport

__all__ = [
    "CORE_CALIBRATION",
    "Executor",
    "InProcessJitBackend",
    "PAUSE_EPSILON",
    "StepReport",
]


class InProcessJitBackend(ExecutionBackend):
    """Today's Executor: one jit-compiled step function per segment, broker
    topics between segments, device-resident task states.

    Boundary streams ride a pluggable :class:`~repro.runtime.transport.Transport`
    (``transport=``): the default ``"inproc"`` is the zero-copy in-process
    broker; ``"shm"`` / ``"tcp"`` move the same topics through shared
    memory or sockets — the data plane's publish/fetch path is
    transport-agnostic. ``self.broker`` stays as an alias for the
    transport (pre-transport-API name)."""

    name = "inprocess"

    def __init__(
        self,
        straggler_factor: float = 3.0,
        ewma_alpha: float = 0.3,
        step_mode: str = "sync",
        max_workers: Optional[int] = None,
        transport: Any = "inproc",
        transport_options: Optional[Dict[str, Any]] = None,
    ):
        super().__init__(
            straggler_factor=straggler_factor,
            ewma_alpha=ewma_alpha,
            step_mode=step_mode,
            max_workers=max_workers,
        )
        self.transport: Transport = resolve_transport(
            transport, **(transport_options or {})
        )
        self.broker = self.transport  # backwards-compatible alias
        # Compiled-segment reuse: structurally identical segments share one
        # canonical jitted executable instead of recompiling (coordinator-
        # side — this backend compiles in-process); across processes the
        # persistent cache keeps what earlier runs compiled.
        from .compile_cache import CompileCache, enable_persistent_cache

        enable_persistent_cache()
        self.compile_cache = CompileCache()
        self.compile_cache.tracer = self.tracer
        # recorded spans also open host annotations on the profiler's clock
        self.tracer.annotate = jax.profiler.TraceAnnotation
        # Per-topic sequence targets for the concurrent step in flight
        # (None outside one): each forwarding task publishes exactly once
        # per step, so a boundary read of this step must observe sequence
        # start+1 on its producer's topic — and only on that topic.
        self._topic_target: Optional[Dict[str, int]] = None

    # -- ExecutionBackend hooks -------------------------------------------------
    def _build(
        self,
        spec: SegmentSpec,
        dataflow: Dataflow,
        init_states: Optional[Dict[str, Any]],
    ) -> Segment:
        return build_segment(
            spec, dataflow, init_states=init_states, cache=self.compile_cache
        )

    def _drop_streams(self, seg: Segment) -> None:
        for tid in seg.spec.task_ids:
            self.broker.drop(topic_for(tid))

    def _fetch_inputs(self, seg: Segment, copy: bool = False) -> Dict[str, Any]:
        """Boundary inputs for one segment (hook — sharded moves them on-device).

        During a concurrent step each topic read synchronizes on *its*
        producer's publish of this step (per-topic sequencing) — the
        ready-queue already dispatched producers first, so the wait is a
        cheap verification, but it hard-guarantees deterministic inputs
        even for custom backends with looser dispatch.
        """
        targets = self._topic_target
        if targets is None:
            return {t: self.broker.fetch(t, copy=copy) for t in seg.boundary_topics}
        return {
            t: self.broker.fetch_synced(t, targets[t], copy=copy) if t in targets
            else self.broker.fetch(t, copy=copy)
            for t in seg.boundary_topics
        }

    def _gather_inputs(self, seg: Segment) -> Tuple[Dict[str, Any], Dict[str, int]]:
        """Boundary inputs plus revalidation tokens for the zero-copy path.

        On transports exposing :meth:`fetch_view` (shm), inputs are
        read-only views into the ring and ``tokens`` maps each topic to the
        sequence observed — ``_step_one`` revalidates them after computing
        and recomputes from private copies if the ring lapped mid-step.
        Fused segments skip the view path entirely: donation invalidates
        the pre-step states, so a recompute is impossible — they pay one
        private copy per boundary topic instead.
        """
        fused = bool(getattr(seg.spec, "fused", False))
        views = None if fused else getattr(self.transport, "fetch_view", None)
        if views is None:
            return self._fetch_inputs(seg, copy=fused), {}
        targets = self._topic_target or {}
        inputs: Dict[str, Any] = {}
        tokens: Dict[str, int] = {}
        for t in seg.boundary_topics:
            inputs[t], tokens[t] = views(t, min_seq=targets.get(t))
        return inputs, tokens

    def _begin_concurrent_step(self) -> None:
        # one sequences() snapshot instead of a seq() per topic — on the
        # tcp transport each seq() would be its own socket round-trip
        seqs = self.transport.sequences()
        self._topic_target = {
            topic_for(tid): seqs.get(topic_for(tid), 0) + 1
            for name, tids in self.forwarding.items()
            if name in self.segments
            for tid in tids
        }

    def _end_concurrent_step(self) -> None:
        self._topic_target = None

    def _step_one(self, seg: Segment) -> Optional[float]:
        if self.tracer.enabled:
            with self.tracer.span("fetch", "transport", segment=seg.name,
                                  topics=len(seg.boundary_topics)):
                inputs, tokens = self._gather_inputs(seg)
        else:
            inputs, tokens = self._gather_inputs(seg)
        if self.tracer.enabled:
            with self.tracer.span("dispatch", "device", segment=seg.name):
                new_states, outputs = seg.step_fn(seg.states, seg.active, inputs)
        else:
            new_states, outputs = seg.step_fn(seg.states, seg.active, inputs)
        if tokens:
            # Zero-copy stale-view check: the CPU jit may alias the host
            # views, so the computation must finish before we can trust it;
            # if any source slot lapped mid-step, recompute from private
            # copies and the untouched pre-step states. Publishes and the
            # state commit happen only after validation (exactly-once).
            if self.tracer.enabled:
                with self.tracer.span("wait", "device", segment=seg.name):
                    jax.block_until_ready((new_states, outputs))
            else:
                jax.block_until_ready((new_states, outputs))
            if not all(self.transport.view_valid(t, s) for t, s in tokens.items()):
                for t in tokens:
                    inputs[t] = self.transport.fetch(t, copy=True)
                new_states, outputs = seg.step_fn(seg.states, seg.active, inputs)
        seg.states = new_states
        if self.tracer.enabled:
            with self.tracer.span("publish", "transport", segment=seg.name):
                for tid in self.forwarding[seg.name]:
                    if tid in outputs:
                        self.broker.publish(topic_for(tid), outputs[tid])
        else:
            for tid in self.forwarding[seg.name]:
                if tid in outputs:
                    self.broker.publish(topic_for(tid), outputs[tid])
        # Block on the segment's computation (the Storm worker finishes its
        # batch before acking). JAX dispatch is async — without this,
        # segment_ms measures dispatch (~µs), the straggler EWMAs are
        # noise, and the sync/concurrent distinction evaporates. Blocking
        # here is what lets concurrent dispatch genuinely overlap devices:
        # each worker thread waits on *its* device while the others run.
        if self.tracer.enabled:
            with self.tracer.span("wait", "device", segment=seg.name):
                jax.block_until_ready(new_states)
        else:
            jax.block_until_ready(new_states)
        seg.steps_run += 1
        return None  # report measured wall-time

    # -- durability hooks ---------------------------------------------------------
    def _decode_init_states(
        self, spec: SegmentSpec, dataflow: Dataflow, states_enc: Dict[str, Any]
    ) -> Dict[str, PyTree]:
        """Conform checkpointed states to this backend's operator templates.

        Same-backend restores round-trip bit-exactly (arrays decode to the
        original bytes). Cross-backend restores from the dry-run backend
        carry only sink counters and ``()`` placeholders; leaves that don't
        structurally match the operator's ``init_state`` template fall back
        to the template — so e.g. a dry-run sink state ``{count, checksum}``
        seeds the jit sink's ``count`` while ``last`` re-initializes to
        zeros, keeping sink *counts* exactly continuous (checksums are
        jit-only state and restart from the template in that direction).
        """
        from repro.ops import operator_for_task

        out: Dict[str, PyTree] = {}
        for tid, enc in states_enc.items():
            value = decode_pytree(enc)
            op = operator_for_task(dataflow.tasks[tid], batch=spec.batch_of[tid])
            out[tid] = _conform_state(value, op.init_state(spec.batch_of[tid]))
        return out

    def _dump_extra(self) -> Dict[str, Any]:
        """Transport topic buffers + publish counters.

        Strictly, buffers are reconstructible (launch order is topological,
        so every boundary topic is re-published upstream within the first
        post-restore step before its consumer fetches it) — but persisting
        them keeps a restored transport observable-identical, including for
        tooling that reads topics between steps.
        """
        counters = self.transport.counters()
        return {
            "broker": {
                topic: self._state_encoder(batch)
                for topic, batch in sorted(self.transport.topics().items())
            },
            "broker_bytes_published": int(counters["bytes_published"]),
            "broker_publishes": int(counters["publishes"]),
        }

    def _restore_extra(self, extra: Dict[str, Any]) -> None:
        for topic, enc in extra.get("broker", {}).items():
            self.transport.publish(topic, decode_pytree(enc))
        # publish() above bumped the counters; restore the checkpointed view
        self.transport.restore_counters(
            int(extra.get("broker_bytes_published", 0)),
            int(extra.get("broker_publishes", 0)),
        )

    def spawn_config(self) -> Dict[str, Any]:
        return {"transport": self.transport.name}


def _conform_state(value: Any, template: Any) -> Any:
    """Merge a decoded state pytree onto an operator's init-state template.

    Matching leaves adopt the checkpointed value (cast to the template's
    dtype); structural mismatches — missing dict keys, wrong tuple arity,
    wrong array shape, ``()`` placeholders from a dry-run checkpoint —
    resolve to the template, leaf by leaf."""
    if isinstance(template, dict):
        if not isinstance(value, dict):
            return template
        return {k: _conform_state(value.get(k, _MISSING), t) for k, t in template.items()}
    if isinstance(template, (tuple, list)):
        if not isinstance(value, (tuple, list)) or len(value) != len(template):
            return template
        return type(template)(_conform_state(v, t) for v, t in zip(value, template))
    if value is _MISSING or value is None:
        return template
    tmpl = np.asarray(template)
    try:
        arr = np.asarray(value)
    except Exception:
        return template
    if arr.shape != tmpl.shape:
        return template
    return arr.astype(tmpl.dtype)


_MISSING = object()


# Backwards-compatible name: the pre-API-redesign data plane class.
Executor = InProcessJitBackend
