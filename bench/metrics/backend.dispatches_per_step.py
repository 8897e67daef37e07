"""Mean number of segments stepped per window step (``StepReport.segment_ms``),
paused residue included."""


def read(ctx):
    if not ctx.reports:
        return None
    return sum(len(r.segment_ms) for r in ctx.reports) / len(ctx.reports)
