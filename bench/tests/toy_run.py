"""One run, on the host's CPU at a small size, of a configuration that brings
its own reference semantics (``toy_reference.py``): the program's side of
its task and source types is registered here, the harness judges the run by
the toy module's semantics, and prints the result line. Used by
``test_reference_ext.py`` in a process of its own.

    python bench/tests/toy_run.py <none|off> <seconds> <seed>

``off`` puts the program's ``toy_scale`` 1% off its stated factor.
"""
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

BATCH = 128
CONFIG = {"name": "toy", "batch": BATCH, "dtype": "float32", "strategy": "signature",
          "backend": "inprocess", "step_mode": "sync", "chips": 1,
          "reference": "bench/tests/toy_reference.py"}
COLLECTION = [
    {"name": "a", "source": "ramp", "steps": [["toy_drift", {}], ["toy_scale", {"f": 2}]]},
    {"name": "b", "source": "ramp", "steps": [["toy_drift", {}], ["toy_scale", {"f": 3}]]},
    {"name": "c", "source": "ramp", "steps": [["toy_scale", {"f": 0.5}], ["toy_drift", {}]]},
    {"name": "d", "source": "ramp:2", "steps": [["toy_drift", {}]]},
    {"name": "e", "source": "ramp",
     "steps": [["toy_drift", {}], ["toy_scale", {"f": 2}], ["toy_drift", {}]]},
    {"name": "f", "source": "ramp:2", "steps": [["toy_scale", {"f": 4}]]},
]
TRAFFIC = {"live_fraction": 0.6667, "swaps_per_s": 4, "warmup_steps": 2, "draw_seed": 5}
LIMITS = {"running": 0, "count": 0, "checksum": 1e-4, "last": 1e-3}


def register(off: bool) -> None:
    """The program's operators for the toy types, in its (B, 8) layout."""
    import jax.numpy as jnp

    from repro.ops import base, sources

    def ramp(type_name, batch):
        def apply(counter, x=None):
            t = counter.astype(jnp.float32) + jnp.arange(batch, dtype=jnp.float32) / batch
            c = jnp.arange(1, 6, dtype=jnp.float32)[None, :]
            out = jnp.zeros((batch, base.EVENT_WIDTH), jnp.float32)
            out = out.at[:, 0].set(t).at[:, 1:6].set(c * jnp.sin(0.01 * c * t[:, None]))
            out = out.at[:, 6].set(1.0).at[:, 7].set((counter * batch + jnp.arange(batch)).astype(jnp.float32))
            return counter + 1, out
        return base.Operator(type=type_name, init_state=lambda b: jnp.zeros((), jnp.int32),
                             apply=apply, is_source=True)

    orig = sources.make_source

    def make_source(type_name, batch=32):
        return ramp(type_name, batch) if type_name.split(":")[0] == "ramp" else orig(type_name, batch)
    sources.make_source = make_source

    @base.register("toy_scale")
    def toy_scale(cfg):
        f = float(cfg.get("f", 1.0)) * (1.01 if off else 1.0)
        return base.stateless("toy_scale", lambda x: x.at[:, 1:6].multiply(f), cost=1.0)

    @base.register("toy_drift")
    def toy_drift(cfg):
        def apply(total, x):
            total = total + x[:, 1:6].mean(axis=0)
            return total, x.at[:, 1:6].add(total[None, :])
        return base.Operator(type="toy_drift", init_state=lambda b: jnp.zeros((5,), jnp.float32),
                             apply=apply)


def main() -> int:
    fault, seconds, seed = sys.argv[1:4]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import jax

    from lib.cell import Cell
    from lib.harness import execute

    if fault not in ("none", "off"):
        raise ValueError(fault)
    register(fault == "off")
    collection = [dict(f, sink="store") for f in COLLECTION]
    cell = Cell(name="toy.churn", chips=1, config=CONFIG, collection=collection,
                traffic=TRAFFIC, limits=LIMITS,
                end_to_end=[{"name": "sink_events_per_s", "unit": "events/s"}], per_layer=[])
    result = execute(cell, int(seed), float(seconds), False, T_START, jax.devices()[:1])
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
