"""Q5's keyed count kernel's share of its roofline: the least time any
implementation needs per step over the kernel's device time per step
(``ops.window_ms_per_step``, the ``window_count`` calls).

The least time is the bytes every implementation of the Q5 tasks must move,
over the HBM bandwidth of the chips used (``lib/peaks.json``): each valid
bid fed to a Q5 task has its auction key read once, 4 bytes, and each Q5
task's state (its open pane tables) is written once. The bids come from the
program counter ``repro_ops_window_rows_total{type="nx_hot_items"}`` over the
run's steps, the state from the gauge
``repro_ops_window_state_bytes{type="nx_hot_items"}``: Q7's rows and state
are left out, as its work is not in the kernel's time. Nothing where the
program has no such counter or the kernel's time is not in the trace."""
import json
import os

from lib.cell import reader

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "lib", "peaks.json")
TYPE = "nx_hot_items"
BYTES_PER_ROW = 4


def least_bytes_per_step(rows_per_step: float, state_bytes: float) -> float:
    return rows_per_step * BYTES_PER_ROW + state_bytes


def _of_type(entry):
    return sum(v for labels, v in entry["values"] if labels.get("type") == TYPE)


def read(ctx):
    snap = ctx.session.metrics_snapshot()
    rows, state = snap.get("repro_ops_window_rows_total"), snap.get("repro_ops_window_state_bytes")
    ms = reader("ops.window_ms_per_step")(ctx)
    if rows is None or state is None or ms is None or not ctx.run.steps:
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)["devices"]
    kind = ctx.run.devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f"no peak for device kind {kind!r} in {PEAKS}")
    bandwidth = peaks[kind]["hbm_bytes_per_s"] * len(ctx.run.devices)
    least_s = least_bytes_per_step(_of_type(rows) / ctx.run.steps, _of_type(state)) / bandwidth
    return 100.0 * least_s / (ms * 1e-3)
