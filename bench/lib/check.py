"""The comparison that decides ``correct``: every sink of every dataflow
running at the window's end, as the program left it, against the plain
reference (``reference.py``) over the same log.

Numbers compared, each against its cell's limit (``bench/limits/<cell>.json``):

- ``running``: dataflows whose running state differs (running in one and not
  the other, or a sink missing). Exact: limit 0.
- ``count``: sinks whose count of consumed batches differs. Exact: limit 0.
- ``checksum``: largest relative difference of a sink's folded checksum.
- ``last``: largest difference in a sink's last batch, per channel relative
  to the reference's largest magnitude in that channel, so a wrong value
  channel shows even where the id channel dominates the checksum.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

NAMES = ("running", "count", "checksum", "last")


def readings(got: Dict[str, Dict[str, Any]], want: Dict[str, Dict[str, Any]]) -> Dict[str, float]:
    running = len(set(got) ^ set(want))
    count = 0
    checksum = last = 0.0
    for name in set(got) & set(want):
        g, w = got[name], want[name]
        if g["count"] != w["count"]:
            count += 1
        checksum = max(checksum, abs(g["checksum"] - w["checksum"])
                       / max(abs(w["checksum"]), 1e-30))
        gl = np.asarray(g["last"], np.float64)
        wl = np.asarray(w["last"], np.float64)
        if gl.shape != wl.shape:
            last = float("inf")
            continue
        scale = np.maximum(np.abs(wl).max(axis=0), 1e-6)
        last = max(last, float((np.abs(gl - wl).max(axis=0) / scale).max()))
    return {"running": float(running), "count": float(count),
            "checksum": float(checksum), "last": float(last)}


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    checks = {k: {"value": values[k], "limit": float(limits[k])} for k in NAMES}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
