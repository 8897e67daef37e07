"""Segments — jit-compiled partial DAGs (the Storm-topology analogue).

A segment owns a subset of a running DAG's tasks and compiles their
composition into **one** jitted step function. Immutability of the compiled
XLA executable mirrors Storm topology immutability; structural changes are
made by launching new segments wired through the broker (incremental merge)
or by defragmentation (relaunch as one fused segment).

Batched event semantics:
  * every stream carries one ``(B_t, EVENT_WIDTH)`` batch per step;
  * a task's input batch is the concatenation of its parents' outputs in
    **canonical order** (sorted by Merkle ancestor signature — equivalent
    tasks sort identically, so Default and Reuse runs process events in the
    same order and sink outputs are bit-identical);
  * interleave semantics ⇒ B_task = Σ B_parent; sources emit B₀.

Pause (paper §4.3): each task has an ``active`` flag in the carried state.
A paused task's body is skipped via ``lax.cond`` and it emits zeros; this is
the control-topic pause signal — no recompilation, no disruption to the
segment. Termination closure (terminated sets are descendant-closed — see
DESIGN.md) guarantees no live task ever consumes a paused task's output.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp

from repro.core.graph import Dataflow
from repro.ops import EVENT_WIDTH, Operator, operator_for_task

from .backend import SegmentSpec, compute_batches  # noqa: F401 — canonical home
from .broker import topic_for
from .compile_cache import program_name, structural_signature

PyTree = Any


@dataclass
class Segment:
    spec: SegmentSpec
    operators: Dict[str, Operator]
    step_fn: Callable  # jitted: (states, active, inputs) -> (states, outputs, taps)
    states: Dict[str, PyTree]
    active: Dict[str, jnp.ndarray]
    boundary_topics: List[str]  # topics fetched from the broker each step
    cost_of: Dict[str, float] = field(default_factory=dict)  # per-task cost_weight
    steps_run: int = 0

    @property
    def name(self) -> str:
        return self.spec.name

    def live_task_ids(self) -> List[str]:
        return [t for t in self.spec.task_ids if bool(self.active[t])]

    def pause(self, task_ids: Set[str]) -> None:
        for tid in task_ids:
            if tid in self.active:
                self.active[tid] = jnp.zeros((), jnp.bool_)

    def resume(self, task_ids: Set[str]) -> None:
        for tid in task_ids:
            if tid in self.active:
                self.active[tid] = jnp.ones((), jnp.bool_)


def _peephole_fused_kernels(
    spec: SegmentSpec,
    dataflow: Dataflow,
    operators: Dict[str, Operator],
    parents: Dict[str, List[str]],
) -> None:
    """Collapse straight-line elementwise runs onto multi-op pallas kernels.

    Within a *fused* segment, a run ``elementwise → … → (rmsnorm|elementwise)``
    where every link is a private single-parent/single-consumer edge computes
    a pure composition — the tail's operator is swapped for one fused kernel
    applied to the run head's input (``repro.ops.riot.make_fused_operator``),
    so the whole run is one pallas launch on accelerator backends. Interior
    operators keep computing: every task's output stays published-switchable
    (a later merge may subscribe to any topic), and on the ref/CPU path XLA
    CSEs the duplicated affine work away inside the single jitted step.

    Mutates ``operators`` and ``parents`` (the step closure's locals) only —
    ``spec`` is untouched, so boundary wiring, checkpoint state structure,
    and per-task cost accounting are unchanged. Deterministic in spec order,
    and driven purely by ⟨type, config, batch, wiring, fused⟩ — exactly the
    compile-cache key — so cached canonical twins fuse identically.
    """
    if not spec.fused:
        return
    from repro.ops.riot import (  # deferred: keep op registry init lazy
        FUSABLE_ELEMENTWISE,
        FUSED_TAILS,
        make_fused_operator,
    )

    in_segment = set(spec.task_ids)
    children: Dict[str, List[str]] = {}
    for t in spec.task_ids:
        for p in parents[t]:
            if p in in_segment:
                children.setdefault(p, []).append(t)
    used: Set[str] = set()
    for tid in reversed(spec.task_ids):  # tails first (task_ids is topo-sorted)
        if tid in used or dataflow.tasks[tid].type not in FUSED_TAILS:
            continue
        run = [tid]
        cur = tid
        while True:
            ps = parents[cur]
            if len(ps) != 1:
                break
            p = ps[0]
            if (
                p not in in_segment
                or children.get(p) != [cur]
                or dataflow.tasks[p].type not in FUSABLE_ELEMENTWISE
            ):
                break
            run.append(p)
            cur = p
        if len(run) < 2:
            continue
        run.reverse()  # head .. tail
        fused_op = make_fused_operator(
            [dataflow.tasks[t] for t in run], batch=spec.batch_of[tid]
        )
        if fused_op is None:
            continue
        operators[tid] = fused_op
        parents[tid] = list(parents[run[0]])
        used.update(run[:-1])


def build_segment(
    spec: SegmentSpec,
    dataflow: Dataflow,
    init_states: Optional[Dict[str, PyTree]] = None,
    cache: Any = None,
) -> Segment:
    """Compile a segment: one jitted step over all its tasks.

    With a ``cache`` (a :class:`repro.runtime.compile_cache.CompileCache`),
    the jitted step function is looked up by the spec's structural
    signature — a structurally identical segment built earlier shares its
    traced executable and this call skips XLA compilation entirely.

    The program is named for its structure (``jit_segment_<signature>`` in
    HLO and the profiler's trace) and each task's ops sit in a
    ``jax.named_scope`` of its task type, so a device trace can be reduced
    by segment structure and by task type.
    """
    operators: Dict[str, Operator] = {}
    for tid in spec.task_ids:
        operators[tid] = operator_for_task(dataflow.tasks[tid], batch=spec.batch_of[tid])

    in_segment = set(spec.task_ids)
    boundary_parents: List[str] = []
    for tid in spec.task_ids:
        for p in spec.parents[tid]:
            if p not in in_segment and p not in boundary_parents:
                boundary_parents.append(p)
    boundary_topics = [topic_for(p) for p in boundary_parents]

    states: Dict[str, PyTree] = {}
    for tid in spec.task_ids:
        if init_states and tid in init_states:
            states[tid] = init_states[tid]
        else:
            states[tid] = operators[tid].init_state(spec.batch_of[tid])
    if spec.fused:
        # committed device arrays from step 0: donation only holds for
        # device-resident inputs (restored checkpoint states arrive as
        # host numpy, which XLA cannot alias)
        states = jax.device_put(states)
    active = {tid: jnp.ones((), jnp.bool_) for tid in spec.task_ids}

    task_ids = list(spec.task_ids)
    task_type = {t: dataflow.tasks[t].type for t in task_ids}
    parents = {t: list(spec.parents[t]) for t in task_ids}
    batch_of = dict(spec.batch_of)
    _peephole_fused_kernels(spec, dataflow, operators, parents)

    def step_fn(
        states: Dict[str, PyTree],
        active: Dict[str, jnp.ndarray],
        inputs: Dict[str, jnp.ndarray],
    ):
        outputs: Dict[str, jnp.ndarray] = {}  # task id -> output batch
        new_states: Dict[str, PyTree] = {}
        for tid in task_ids:
            with jax.named_scope(task_type[tid]):
                op, st, flag = operators[tid], states[tid], active[tid]
                if op.is_source:
                    st2, y = jax.lax.cond(
                        flag,
                        lambda op=op, st=st: op.apply(st),
                        lambda st=st, b=batch_of[tid]: (
                            st,
                            jnp.zeros((b, EVENT_WIDTH), jnp.float32),
                        ),
                    )
                else:
                    xs = [
                        outputs[p] if p in outputs else inputs[topic_for(p)]
                        for p in parents[tid]
                    ]
                    x = xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)
                    if op.is_sink:
                        st2 = jax.lax.cond(
                            flag,
                            lambda op=op, st=st, x=x: op.apply(st, x)[0],
                            lambda st=st: st,
                        )
                        y = None
                    else:
                        # ops may change the event width (e.g. lm_embed lifts
                        # (B, 8) → (B, d)); the paused branch must emit zeros of
                        # the op's *output* shape, not the input's.
                        _, y_abs = jax.eval_shape(op.apply, st, x)
                        st2, y = jax.lax.cond(
                            flag,
                            lambda op=op, st=st, x=x: op.apply(st, x),
                            lambda st=st, y_abs=y_abs: (
                                st,
                                jnp.zeros(y_abs.shape, y_abs.dtype),
                            ),
                        )
            new_states[tid] = st2
            if y is not None:
                outputs[tid] = y
        # Return *all* task outputs; the executor publishes the forwarding
        # subset to the broker (runtime-switchable, no recompilation).
        return new_states, outputs

    step_fn.__name__ = step_fn.__qualname__ = program_name(
        structural_signature(spec, dataflow)
    )
    if cache is not None:
        # Compiled-segment reuse: step through the cache's canonical jitted
        # callable (adapter-renamed per call). Structurally identical
        # segments — resubmitted dataflows, template copies — share one
        # traced executable instead of recompiling. The canonical twin is
        # built with the same fused flag, so donation semantics carry over.
        jitted = cache.step_fn_for(spec, dataflow)
    elif spec.fused:
        # Fusion-compiled hot path: donate the pre-step states to XLA so
        # the post-step states reuse their buffers in place and the fused
        # chain's intermediate streams live only as executable temporaries.
        # Donation invalidates the donated arrays — safe here because the
        # executors replace ``seg.states`` wholesale right after each call
        # and never step the same states twice (checkpoint/defrag reads
        # happen between steps, on the *new* states).
        jitted = jax.jit(step_fn, donate_argnums=(0,))
    else:
        jitted = jax.jit(step_fn)
    return Segment(
        spec=spec,
        operators=operators,
        step_fn=jitted,
        states=states,
        active=active,
        boundary_topics=boundary_topics,
        cost_of={tid: operators[tid].cost_weight for tid in spec.task_ids},
    )


def donation_report(seg: Segment, inputs: Dict[str, Any]) -> Dict[str, Any]:
    """Verify that buffer donation actually holds for a segment's step.

    Lowers and compiles the segment's step for the given boundary
    ``inputs`` and reads the executable's memory analysis — the modern
    JAX surface of the classic ``setup_alias`` / ``total_allocation_size``
    check: ``alias_size_in_bytes`` counts the input bytes XLA aliased to
    outputs (> 0 iff donation held), and the argument/output/temp sizes
    give the roofline of what the step materializes.
    """
    lowered = seg.step_fn.lower(seg.states, seg.active, inputs)
    compiled = lowered.compile()
    try:
        mem = compiled.memory_analysis()
    except Exception:  # pragma: no cover - backend without memory stats
        mem = None
    report: Dict[str, Any] = {
        "fused": bool(seg.spec.fused),
        "donation_holds": False,
        "alias_size_in_bytes": 0,
    }
    if mem is not None:
        report.update(
            alias_size_in_bytes=int(getattr(mem, "alias_size_in_bytes", 0)),
            argument_size_in_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
            output_size_in_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
            temp_size_in_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
        )
        # total live bytes a step allocates beyond its aliased inputs —
        # the number the fused-vs-unfused roofline compares
        report["total_allocation_size"] = (
            report["argument_size_in_bytes"]
            + report["output_size_in_bytes"]
            + report["temp_size_in_bytes"]
            - report["alias_size_in_bytes"]
        )
        report["donation_holds"] = report["alias_size_in_bytes"] > 0
    return report
