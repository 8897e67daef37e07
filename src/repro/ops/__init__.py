"""Task-type registry: ⟨type, config⟩ → executable JAX operator.

Real RIoT-style IoT task logic (:mod:`repro.ops.riot`), deterministic
synthetic sources (:mod:`repro.ops.sources`), the NEXmark generator and
queries (:mod:`repro.ops.nexmark`), digest sinks (:mod:`repro.ops.sinks`),
and the OPMW π fallback. Model-block operators
(embed / layer-group / head for multi-tenant LM serving) are registered by
:mod:`repro.serve.model_ops` when imported.

The package init is lazy (PEP 562): the JAX operator modules only load on
first attribute access, so the jax-free cost model (:mod:`repro.ops.costs`,
used by the dry-run backend) can be imported without pulling in JAX.
"""
from __future__ import annotations

import importlib
from typing import TYPE_CHECKING

from .costs import cost_weight_for, cost_weight_for_task, parse_config

_BASE_NAMES = {
    "EVENT_WIDTH",
    "Operator",
    "make_operator",
    "register",
    "register_fallback",
    "registered_types",
    "stateless",
}

if TYPE_CHECKING:  # pragma: no cover - static imports for type checkers
    from .base import (
        EVENT_WIDTH,
        Operator,
        make_operator,
        register,
        register_fallback,
        registered_types,
        stateless,
    )
    from .sinks import make_sink
    from .sources import make_source

__all__ = [
    "EVENT_WIDTH",
    "Operator",
    "cost_weight_for",
    "cost_weight_for_task",
    "make_operator",
    "make_sink",
    "make_source",
    "operator_for_task",
    "parse_config",
    "register",
    "register_fallback",
    "registered_types",
    "stateless",
]


def operator_for_task(task, batch: int = 32):
    """Instantiate the JAX operator for a concrete task (source/sink aware)."""
    from . import nexmark, riot  # noqa: F401 — populate the registry (import JAX)
    from .base import make_operator
    from .sinks import make_sink
    from .sources import make_source

    if task.is_source:
        if task.type.split(":")[0] == "nexmark":
            return nexmark.make_source(task.type, batch=batch)
        return make_source(task.type, batch=batch)
    if task.is_sink:
        return make_sink(task.type)
    return make_operator(task.type, task.config)


def __getattr__(name: str):
    if name in _BASE_NAMES:
        from . import nexmark, riot  # noqa: F401 — registry side effects before use
        module = importlib.import_module(f"{__name__}.base")
    elif name == "make_sink":
        module = importlib.import_module(f"{__name__}.sinks")
    elif name == "make_source":
        module = importlib.import_module(f"{__name__}.sources")
    else:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(module, name)
    globals()[name] = value  # cache for subsequent lookups
    return value
