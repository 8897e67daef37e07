"""Events delivered to all running sinks in the window, over its seconds."""


def read(ctx):
    return ctx.events / ctx.window_s
