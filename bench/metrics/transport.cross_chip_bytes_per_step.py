"""Bytes fetched per step from another chip than the consuming segment's
(``repro_transport_cross_chip_bytes_total``, a program counter of the sharded
backend), over every step of the run: set-up steps move the same batches,
since placement is fixed in a steady cell. Nothing where the program has no
such counter."""


def read(ctx):
    entry = ctx.session.metrics_snapshot().get("repro_transport_cross_chip_bytes_total")
    if entry is None or not ctx.run.steps:
        return None
    return sum(value for _, value in entry["values"]) / ctx.run.steps
