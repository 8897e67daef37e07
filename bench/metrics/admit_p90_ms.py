"""90th percentile over the window's submissions of the time from when the
submission was due (open loop) to the end of the first step that delivered
events to its sink."""
import statistics


def read(ctx):
    if len(ctx.admit_s) < 10:
        return None
    return statistics.quantiles(ctx.admit_s, n=10)[-1] * 1e3
