"""Boundary fetches per step whose producer segment sits on another chip
than the consumer, at the window's end (placement ``device_of``)."""
from lib import program


def read(ctx):
    return program.cross_chip_hops(ctx.session)
