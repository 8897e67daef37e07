"""Every reach of the benchmark past the program's public API.

The window drives ``repro.api.ReuseSession`` alone, and sinks' counts and
checksums come from ``session.sink_digests``. Three things the session does
not offer yet, so they are read or written here, and only here:

- a source's starting step counter (runtime state, set from the run's seed);
- a sink's last batch (the check compares it with the reference);
- where each segment sits and what it fetches (cross-chip hops).

Each private name is looked up through ``_need``, which raises ``SeamError``
naming what is missing, so a change to the program's internals stops a run
with that message instead of a wrong number.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

import numpy as np


class SeamError(RuntimeError):
    """The program no longer has a private name this module relies on."""


def _need(obj: Any, *path: str) -> Any:
    for i, name in enumerate(path):
        if not hasattr(obj, name):
            raise SeamError(f"the program has no {'.'.join(path[:i + 1])}; "
                            f"bench/lib/program.py needs a new way to reach it")
        obj = getattr(obj, name)
    return obj


def _backend(session) -> Any:
    return _need(session, "_system", "backend")


def start_sources(session, seen: set, start_of) -> Dict[str, int]:
    """Set the step counter of every running source deployed since the last
    call (task ids in ``seen`` are skipped and added to) to
    ``start_of(source type)``; returns the counters set, by source type."""
    import jax

    backend = _backend(session)
    task_defs, paused = _need(backend, "task_defs"), _need(backend, "paused")
    started = {}
    for seg in _need(backend, "segments").values():
        states = _need(seg, "states")
        for tid in _need(seg, "spec", "task_ids"):
            task = task_defs[tid]
            if not task.is_source or tid in seen:
                continue
            seen.add(tid)
            if tid in paused:
                continue
            old = states[tid]
            if getattr(old, "shape", None) != () or np.dtype(old.dtype) != np.int32:
                raise SeamError(f"source {tid}'s state is not an int32 step counter: {old!r}")
            start = int(start_of(task.type))
            states[tid] = jax.device_put(np.int32(start), old.sharding)
            started[task.type] = start
    return started


def sinks(session, names: Iterable[str]) -> Dict[str, Dict[str, Any]]:
    """Each named dataflow's one sink on the host: ``count`` and ``checksum``
    from ``session.sink_digests``, ``last`` from the backend's sink state."""
    backend = _backend(session)
    task_maps = _need(session, "manager", "task_maps")
    out = {}
    for name in names:
        ((sink_id, digest),) = session.sink_digests(name).items()
        state = _need(backend, "sink_state")(task_maps[name][sink_id])
        if "last" not in state:
            raise SeamError(f"sink {sink_id} of {name} keeps no last batch")
        out[name] = {"count": digest["count"], "checksum": digest["checksum"],
                     "last": np.asarray(state["last"])}
    return out


def cross_chip_hops(session) -> Optional[int]:
    """Boundary fetches per step whose producer segment sits on another chip
    than its consumer; ``None`` where the backend places nothing."""
    backend = _backend(session)
    placed = getattr(backend, "device_of", None)
    if not placed:
        return None
    owner = _need(backend, "_owner")
    hops = 0
    for name, seg in _need(backend, "segments").items():
        inside = set(seg.spec.task_ids)
        producers = {owner(p) for t in seg.spec.task_ids
                     for p in seg.spec.parents[t] if p not in inside}
        hops += sum(1 for p in producers if p is not None and placed[p] != placed[name])
    return hops
