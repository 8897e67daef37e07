"""Device time per step of Q5's keyed count kernel (``kernels/window.py``),
from the profiler trace: the self time of the ops named ``window_count``
(the per-pane count of bids by auction, one call per running Q5 task) among
the traced step's top device ops, mean over the chips used. It is a Pallas
call, whose HLO op takes the call's name; XLA names its own ops by their
kind (``fusion``, ``copy``, ``while``, ...), so no other op of the cell has
this name. The Q5 tasks' closing work (the table's maximum, the ties, the
packed rows) and Q7 lower to ordinary fusions and are not counted. Nothing
where the name is not among the top ops."""

NAME = "window_count"


def read(ctx):
    t = ctx.devtrace
    if t is None or not t["steps"]:
        return None
    seconds = sum(s for name, s in t["device_ops"] if name == NAME)
    if seconds <= 0:
        return None
    return seconds / len(t["per_chip"]) / t["steps"] * 1e3
