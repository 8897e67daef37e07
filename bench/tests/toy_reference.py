"""A configuration's own reference semantics, as a deployment with task and
source types that ``bench/lib/reference.py`` does not know would bring them
(its ``"reference"`` file). Used by ``test_reference_ext.py``.

- ``ramp`` source: channel 0 the time ``counter + i / B``, value channel c
  ``(c + 1) * sin(0.01 * (c + 1) * t)``, flag 1, id ``counter * B + i``;
- ``toy_scale`` task (stateless): every value times ``f``;
- ``toy_drift`` task (stateful): adds the running sum, over the batches seen
  so far this one included, of each value channel's batch mean.

Batches are channel-major ``(8, B)``, as the plain reference holds them.
"""
import jax.numpy as jnp

VAL = slice(1, 6)


def ramp(batch):
    def emit(counter, dtype):
        t = counter.astype(jnp.float32) + jnp.arange(batch, dtype=jnp.float32) / batch
        c = jnp.arange(1, 6, dtype=jnp.float32)[:, None]
        vals = c * jnp.sin(0.01 * c * t[None, :])
        ids = (counter * batch + jnp.arange(batch)).astype(jnp.float32)
        out = jnp.concatenate([t[None], vals, jnp.ones((1, batch), jnp.float32), ids[None]])
        return out.astype(dtype)
    return emit


def toy_scale(cfg):
    f = float(cfg.get("f", 1.0))
    return None, lambda s, x: (s, x.at[VAL].set(x[VAL] * f))


def toy_drift(cfg):
    def apply(total, x):
        total = total + x[VAL].mean(axis=1)
        return total, x.at[VAL].set(x[VAL] + total[:, None])
    return (lambda dt: jnp.zeros((5,), dt)), apply


TASKS = {"toy_scale": toy_scale, "toy_drift": toy_drift}
SOURCES = {"ramp": ramp}
