"""Backend compiles in the window, persistent-cache reads included (JAX's
monitoring events)."""


def read(ctx):
    return ctx.compiles_in_window
