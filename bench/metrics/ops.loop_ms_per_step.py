"""Device time per step in XLA ``while`` loops (the row-by-row scans and the
pi loops), from the profiler trace (mean over the chips used)."""


def read(ctx):
    t = ctx.devtrace
    if t is None or not t["steps"] or t["loop_s"] <= 0:
        return None
    return t["loop_s"] / t["steps"] * 1e3
