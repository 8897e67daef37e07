"""Seconds from process start to the window: JAX start-up, submission,
compiles (persistent-cache reads included), seeding and warm-up."""


def read(ctx):
    return ctx.setup_s
