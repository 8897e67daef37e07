"""Host time per window step that the device cannot overlap in ``sync``
stepping: the program's ``step`` span less the ``wait`` spans inside it
(repro.obs, stream system and backend). Nothing where the program records no
``wait`` span."""
import bisect


def read(ctx):
    steps = [s for s in ctx.spans
             if s["name"] == "step" and s["cat"] == "step" and s["ts"] >= ctx.start_us]
    waits = sorted((s["ts"], s["dur"]) for s in ctx.spans
                   if s["name"] == "wait" and s["cat"] == "device")
    if not steps or not waits:
        return None
    starts = [ts for ts, _ in waits]
    host_us = 0
    for s in steps:
        lo = bisect.bisect_left(starts, s["ts"])
        hi = bisect.bisect_right(starts, s["ts"] + s["dur"])
        host_us += s["dur"] - sum(dur for _, dur in waits[lo:hi])
    return host_us / len(steps) / 1e3
