"""Operator protocol + task-type registry.

The paper's tasks are user logic ``⟨type, config⟩`` executed once per input
event. On a TPU data plane events are *batched*: every stream carries an
event-batch tensor of shape ``(B, EVENT_WIDTH)`` per step, and a task is a
pure JAX function over one batch, with explicit state (a pytree) — the
analogue of a Storm Bolt's instance fields. Tasks therefore compose into a
single jit-compiled program per segment (see :mod:`repro.runtime.segment`).

Semantics (paper §3.1):
  * *interleave* — a task with multiple input streams is applied once per
    incoming batch, in deterministic (sorted-parent) order;
  * *duplicate* — each consumer of a task's output receives the same batch
    (zero-copy fan-out of one device buffer).

``cost_weight`` is the relative per-event CPU cost used by the resource
accounting that reproduces the paper's Fig. 3 (cumulative cores); it is
calibrated per task family and also cross-checked against measured FLOPs.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from .costs import parse_config  # noqa: F401 — canonical home is the jax-free cost model

# Payload width of an event batch: every event is a fixed-width float vector
# (sensor observations: timestamp, value channels, quality flags ...).
EVENT_WIDTH = 8

PyTree = Any
ApplyFn = Callable[[PyTree, jnp.ndarray], Tuple[PyTree, Optional[jnp.ndarray]]]


@dataclass
class Operator:
    """A compiled-composable task implementation.

    ``init_state(batch)`` returns the task's state pytree (fixed shapes);
    ``apply(state, x)`` consumes one event batch and returns
    ``(new_state, output batch | None)``. Sources take ``x=None``; sinks
    return ``None`` output.
    """

    type: str
    init_state: Callable[[int], PyTree]
    apply: ApplyFn
    cost_weight: float = 1.0
    is_source: bool = False
    is_sink: bool = False
    # a keyed event-time window task (repro.ops.nexmark): the backend counts
    # the rows fed to it and the device bytes of its state
    window: bool = False


OperatorFactory = Callable[[Dict[str, Any]], Operator]

_REGISTRY: Dict[str, OperatorFactory] = {}
_FALLBACK: Optional[OperatorFactory] = None


def register(type_name: str) -> Callable[[OperatorFactory], OperatorFactory]:
    def deco(factory: OperatorFactory) -> OperatorFactory:
        if type_name in _REGISTRY:
            raise ValueError(f"operator type {type_name!r} already registered")
        _REGISTRY[type_name] = factory
        return factory

    return deco


def register_fallback(factory: OperatorFactory) -> OperatorFactory:
    """Factory used for unknown task types (the OPMW workload replaces all
    task logic with an iterative π computation — paper §5.1)."""
    global _FALLBACK
    _FALLBACK = factory
    return factory


def make_operator(type_name: str, config: Any) -> Operator:
    """Instantiate the operator for a concrete task ⟨type, config⟩."""
    cfg = parse_config(config)
    factory = _REGISTRY.get(type_name)
    if factory is None:
        if _FALLBACK is None:
            raise KeyError(f"no operator registered for task type {type_name!r}")
        cfg = dict(cfg, _type=type_name)
        return _FALLBACK(cfg)
    return factory(cfg)


def registered_types() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# -- conveniences for defining ops ------------------------------------------

def stateless(type_name: str, fn: Callable[[jnp.ndarray], jnp.ndarray], cost: float) -> Operator:
    """Operator with no state: y = fn(x)."""

    def init_state(batch: int) -> PyTree:
        return ()

    def apply(state: PyTree, x: jnp.ndarray):
        return state, fn(x)

    return Operator(type=type_name, init_state=init_state, apply=apply, cost_weight=cost)
