"""The one traffic generator: a traffic file's parameters in, a plan of
submissions and removals out.

Parameters (``bench/traffic/<name>.json``):

- ``live_fraction``: the share of the collection running during the window
  (1.0: all of it). Which dataflows, when fewer than all, is drawn; they are
  submitted in the collection's order.
- ``swaps_per_s``: open-loop churn. Swap k is due ``k / swaps_per_s``
  seconds into the window. It removes one running dataflow and submits one
  that is not running, both drawn. 0 means no churn.
- ``warmup_steps``: steps stepped in set-up before the window (after any
  warm-up swaps).
- ``draw_seed``: the seed of every draw; required where there is one (fewer
  than all live, or churn). The run's seed changes only the events (each
  source's starting counter), so every seed does the same work and builds
  the same segment programs, which the persistent compile cache holds after
  a checkout's first run.

A churn plan fixes the window's swaps (``swaps_per_s`` times the window's
seconds of them) before the run, so set-up can make every segment structure
the window will build: the harness applies the same swaps in set-up, then
returns to the preloaded set before the window (see ``harness.py``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np


@dataclass
class Plan:
    preload: List[str]
    swaps: List[Tuple[str, str]] = field(default_factory=list)  # (remove, submit)
    swaps_per_s: float = 0.0
    warmup_steps: int = 1


def make_plan(params: Dict[str, Any], names: Sequence[str], seconds: float) -> Plan:
    unknown = set(params) - {"live_fraction", "swaps_per_s", "warmup_steps", "draw_seed", "why"}
    if unknown:
        raise ValueError(f"unknown traffic parameters {sorted(unknown)}")
    names = list(names)
    live_n = int(np.floor(len(names) * float(params.get("live_fraction", 1.0)) + 1e-9))
    if not 1 <= live_n <= len(names):
        raise ValueError(f"live_fraction gives {live_n} of {len(names)} dataflows")
    rate = float(params.get("swaps_per_s", 0.0))
    if rate > 0 and live_n == len(names):
        raise ValueError("churn needs dataflows that are not running")
    if live_n == len(names):
        return Plan(preload=names, warmup_steps=int(params.get("warmup_steps", 1)))
    if "draw_seed" not in params:
        raise ValueError("a traffic that draws (live_fraction below 1) needs draw_seed")
    rng = np.random.default_rng(int(params["draw_seed"]))
    chosen = set(rng.choice(len(names), size=live_n, replace=False).tolist())
    preload = [n for i, n in enumerate(names) if i in chosen]
    live, absent = list(preload), [n for n in names if n not in preload]
    swaps: List[Tuple[str, str]] = []
    for _ in range(int(rate * seconds)):
        out = live.pop(int(rng.integers(len(live))))
        inn = absent.pop(int(rng.integers(len(absent))))
        live.append(inn)
        absent.append(out)
        swaps.append((out, inn))
    return Plan(preload=preload, swaps=swaps, swaps_per_s=rate,
                warmup_steps=int(params.get("warmup_steps", 1)))
