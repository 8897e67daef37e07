"""Mean time per window swap in the program's ``merge`` and ``unmerge`` spans
(repro.obs, control plane)."""


def read(ctx):
    if not ctx.swaps:
        return None
    spans = [s for s in ctx.spans if s["name"] in ("merge", "unmerge") and s["ts"] >= ctx.start_us]
    if not spans:
        return None
    return sum(s["dur"] for s in spans) / 1e3 / ctx.swaps
