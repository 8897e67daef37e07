"""One run of a cell on the host's CPU at a small size, with the timed path
broken underneath when a fault is named; prints the result line. Used by
``test_correct.py`` in a process of its own (the sharded cell needs four
host devices, which JAX must be given before it starts).

    python bench/tests/fault_run.py <cell> <fault|none> <batch> <seconds> <seed> [<draw seed>]

A draw seed replaces the traffic's ``draw_seed`` (other draws of the swaps).

Faults:
- ``state_unchanged``: sources return their state unchanged (the counter
  never advances);
- ``half_batch``: sinks consume the first half of each batch twice, the
  second half left out;
- ``no_exchange``: the sharded backend hands a consumer zeros in place of a
  batch produced on another chip;
- ``altered``: every task's output has one value altered by 1% where it
  is produced;
- ``control``: no fault in the program; the plain reference in bfloat16 is
  judged in the program's place.
"""
import dataclasses
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from repro.ops import base, sinks, sources

    if fault == "state_unchanged":
        orig = sources.make_source

        def make_source(type_name, batch=32):
            op = orig(type_name, batch=batch)
            return dataclasses.replace(op, apply=lambda s, x=None: (s, op.apply(s, x)[1]))
        sources.make_source = make_source
    elif fault == "half_batch":
        orig = sinks.make_sink

        def make_sink(type_name):
            op = orig(type_name)

            def apply(s, x):
                half = x[: x.shape[0] // 2]
                return op.apply(s, jnp.concatenate([half, half], axis=0))
            return dataclasses.replace(op, apply=apply)
        sinks.make_sink = make_sink
    elif fault == "no_exchange":
        from repro.runtime.sharded import ShardedBackend

        def fetch(self, seg, copy=False):
            dev = self.devices[self.device_of[seg.spec.name]]
            out = {}
            for t, batch in super(ShardedBackend, self)._fetch_inputs(seg, copy=copy).items():
                same = next(iter(batch.devices())) == dev
                out[t] = batch if same else jnp.zeros_like(batch, device=dev)
            return out
        ShardedBackend._fetch_inputs = fetch
    elif fault == "altered":
        orig = base.make_operator

        def make_operator(type_name, config):
            op = orig(type_name, config)

            def apply(s, x):
                s2, y = op.apply(s, x)
                return s2, y.at[0, 1].multiply(1.01)
            return dataclasses.replace(op, apply=apply)
        base.make_operator = make_operator
    elif fault not in ("none", "control"):
        raise ValueError(fault)


def main() -> int:
    cell_name, fault, batch, seconds, seed = sys.argv[1:6]
    draw = sys.argv[6:7]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import jax

    from lib import check
    from lib.cell import load_cell, make_cell
    from lib.harness import execute

    plant(fault)
    try:
        cell = load_cell(cell_name, ROOT)
    except KeyError:  # a cell kept as files only: <config>.<traffic>
        config, traffic = cell_name.split(".")
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            spec = json.load(f)
        with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
            chips = json.load(f)["chips"]
        cell = make_cell(cell_name, config, traffic, chips, spec)
    if draw:
        cell.traffic = {**cell.traffic, "draw_seed": int(draw[0])}
    controls = ("bfloat16",) if fault == "control" else ()
    result = execute(cell, int(seed), float(seconds), False, T_START,
                     jax.devices()[:cell.chips], batch=int(batch), controls=controls)
    if fault == "control":
        ok, checks = check.judge(result["controls"]["bfloat16"], cell.limits)
        result["correct"], result["checks"] = ok, checks
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
