"""Device time per step in XLA ``while`` loops, from the profiler trace (mean
over the chips used): the ``pi`` tasks' loops (``opmw35``), the only loops
on the benchmarked path (the ``riot21`` ops run none). Nothing where no loop
runs."""


def read(ctx):
    t = ctx.devtrace
    if t is None or not t["steps"] or t["loop_s"] <= 0:
        return None
    return t["loop_s"] / t["steps"] * 1e3
