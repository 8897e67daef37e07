"""95th percentile over the window's steps of the time from the end of the
previous step (when the step's events began to accumulate) to the end of the
step (when every sink holds them)."""
import statistics


def read(ctx):
    if len(ctx.step_s) < 20:
        return None
    return statistics.quantiles(ctx.step_s, n=20)[-1] * 1e3
