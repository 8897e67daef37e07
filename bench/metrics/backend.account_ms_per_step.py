"""Mean time per window step in the program's ``account`` span, the live,
paused and cost count after the segments have stepped (repro.obs, stream
system and backend). Nothing where the program records no ``account`` span."""


def read(ctx):
    window = [s for s in ctx.spans if s["ts"] >= ctx.start_us]
    steps = sum(1 for s in window if s["name"] == "step" and s["cat"] == "step")
    account_us = [s["dur"] for s in window if s["name"] == "account" and s["cat"] == "step"]
    if not steps or not account_us:
        return None
    return sum(account_us) / steps / 1e3
