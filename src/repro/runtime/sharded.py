"""ShardedBackend — the jit data plane spread across ``jax.devices()``.

Each segment is pinned to one device by a pluggable
:class:`~repro.runtime.scheduler.PlacementPolicy` (round-robin by default —
the Storm scheme generalized from worker slots to devices). A segment's
task states live on its device; boundary batches fetched from the transport
are moved to the consuming segment's device before the jitted step, so
cross-device streams pay exactly one transfer per hop — the device-mesh
analogue of the paper's broker indirection.

Placement bookkeeping (slot map, EWMA device aggregates with idle decay,
policy-driven straggler migration, restore-time sticky hints) is shared
with the multiproc backend via
:class:`~repro.runtime.scheduler.PlacedBackendMixin`.

On a single-device host this degenerates to :class:`InProcessJitBackend`
with placement bookkeeping (useful in CI); with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` or real accelerator
meshes the same code shards the segment set N ways.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import jax

from repro.core.graph import Dataflow

from .backend import SegmentSpec
from .executor import InProcessJitBackend
from .scheduler import PlacedBackendMixin, PlacementPolicy
from .segment import Segment


class ShardedBackend(PlacedBackendMixin, InProcessJitBackend):
    name = "sharded"

    def __init__(
        self,
        placement: Union[str, PlacementPolicy] = "round_robin",
        devices: Optional[Sequence[Any]] = None,
        straggler_factor: float = 3.0,
        ewma_alpha: float = 0.3,
        ewma_decay: float = 0.6,
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
            transport=transport,
            transport_options=transport_options,
        )
        self.devices: List[Any] = list(devices) if devices is not None else list(jax.devices())
        if not self.devices:
            raise ValueError("ShardedBackend needs at least one device")
        self._init_placement(placement, ewma_decay=ewma_decay)

    def _mint_instruments(self) -> None:
        super()._mint_instruments()
        m = self.metrics
        self._m_xchip_fetches = m.counter(
            "repro_transport_cross_chip_fetches_total",
            "boundary batches fetched from another chip than the consumer's",
        )
        self._m_xchip_bytes = m.counter(
            "repro_transport_cross_chip_bytes_total",
            "bytes of the boundary batches fetched from another chip",
        )

    # -- placement hooks (PlacedBackendMixin) -----------------------------------
    def _n_slots(self) -> int:
        return len(self.devices)

    def _move_segment(self, seg: Segment, old: int, new: int) -> None:
        """Migrate a segment's buffers: the compiled executable is
        device-agnostic; only task states move."""
        dev = self.devices[new]
        seg.states = jax.device_put(seg.states, dev)
        seg.active = jax.device_put(seg.active, dev)

    def _build(
        self,
        spec: SegmentSpec,
        dataflow: Dataflow,
        init_states: Optional[Dict[str, Any]],
    ) -> Segment:
        seg = super()._build(spec, dataflow, init_states)
        idx = self._assign_slot(spec)
        dev = self.devices[idx]
        seg.states = jax.device_put(seg.states, dev)
        seg.active = jax.device_put(seg.active, dev)
        return seg

    def _fetch_inputs(self, seg: Segment, copy: bool = False) -> Dict[str, Any]:
        """Move boundary batches onto the consuming segment's device (one
        transfer per cross-segment hop); per-topic synchronization comes
        from the base fetch (concurrent steps sync on producers only)."""
        dev = self.devices[self.device_of[seg.spec.name]]
        out = {}
        for t, batch in super()._fetch_inputs(seg, copy=copy).items():
            leaves = jax.tree_util.tree_leaves(batch)
            if any(_elsewhere(x, dev) for x in leaves):
                self._m_xchip_fetches.inc()
                self._m_xchip_bytes.inc(sum(x.nbytes for x in leaves))
            out[t] = jax.device_put(batch, dev)
        return out

    def _gather_inputs(self, seg: Segment):
        # No view path here: device_put on the host platform may alias
        # numpy memory, so shm ring views must be privatized *before* the
        # transfer — fetch with copy=True on lappable transports instead
        # of revalidating after the fact.
        copy = getattr(self.transport, "fetch_view", None) is not None
        return self._fetch_inputs(seg, copy=copy), {}

    # -- durability hooks ---------------------------------------------------------
    def _dump_extra(self) -> Dict[str, Any]:
        extra = super()._dump_extra()
        extra["device_of"] = {name: int(i) for name, i in self.device_of.items()}
        extra["n_devices"] = len(self.devices)
        return extra

    def _restore_extra(self, extra: Dict[str, Any]) -> None:
        super()._restore_extra(extra)
        self.device_of_at_checkpoint = {
            name: int(i) for name, i in extra.get("device_of", {}).items()
        }
        if extra.get("n_devices") is not None:
            self._n_slots_at_checkpoint = int(extra["n_devices"])

    def spawn_config(self) -> Dict[str, Any]:
        cfg = super().spawn_config()
        if getattr(self.policy, "name", ""):
            cfg["placement"] = self.policy.name
        return cfg


def _elsewhere(leaf: Any, dev: Any) -> bool:
    """Whether a fetched leaf sits on another device than ``dev``; host
    arrays (the shm and tcp transports deliver numpy) sit on none."""
    devices = getattr(leaf, "devices", None)
    return devices is not None and devices() != {dev}
