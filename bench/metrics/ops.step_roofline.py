"""The step's share of its roofline: the least time any implementation needs
per step over the device-busy time per step (profiler trace).

The least time is the bytes every implementation must write, over the HBM
bandwidth of all the chips used (the busy time is the mean over them): each
running sink keeps the whole batch it consumed (``count, checksum, last``),
so per step every sink writes its batch of
``batch x 8`` float32 values. The count follows from the running dataflows
and the batch, so it reads the same work whatever implements the ops."""
import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "lib", "peaks.json")
EVENT_BYTES = 8 * 4


def least_bytes_per_step(sinks: int, batch: int) -> int:
    return sinks * batch * EVENT_BYTES


def read(ctx):
    t = ctx.devtrace
    if t is None or not t["steps"] or t["busy_s"] <= 0:
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)["devices"]
    kind = ctx.run.devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f"no peak for device kind {kind!r} in {PEAKS}")
    bandwidth = peaks[kind]["hbm_bytes_per_s"] * len(ctx.run.devices)
    least_s = least_bytes_per_step(len(ctx.session.names), ctx.batch) / bandwidth
    return 100.0 * least_s / (t["busy_s"] / t["steps"])
