"""`interpolate` and `kalman` against row-by-row NumPy oracles.

Both ops run their within-batch recurrence as a parallel prefix over the
batch. The oracles below walk the rows one at a time, as the recurrences
are written, and import nothing of the program's op code. Each case runs
three consecutive batches with the state carried between them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ops import make_operator

BATCHES = (1, 7, 128, 4096)
FLAGS = ("all_valid", "none_valid", "leading_invalid", "random_30pct_invalid")
NOISES = ((0.1, 1.0), (0.5, 1.0), (0.0, 1.0), (10.0, 0.01))
N_BATCHES = 3


def _events(rng, batch: int, flags: str) -> np.ndarray:
    """Sensor-like batches (8 channels): values scattered around per-channel
    biases, one of them negative, far enough from zero for a relative
    tolerance to mean something."""
    x = np.zeros((batch, 8), np.float32)
    x[:, 0] = np.arange(batch)
    x[:, 1:6] = np.array([20.0, 5.0, 50.0, 8.0, -30.0]) + rng.normal(0, 1.0, (batch, 5))
    if flags == "all_valid":
        x[:, 6] = 1.0
    elif flags == "none_valid":
        x[:, 6] = 0.0
    elif flags == "leading_invalid":
        x[:, 6] = (np.arange(batch) >= batch // 3 + 1).astype(np.float32)
    else:
        x[:, 6] = (rng.random(batch) >= 0.3).astype(np.float32)
    x[:, 7] = rng.integers(0, 1 << 20, batch)
    return x


def _interpolate_oracle(carry, x):
    y = x.copy()
    for i in range(x.shape[0]):
        if x[i, 6] > 0.5:
            carry = x[i, 1:6].copy()
        y[i, 1:6] = carry
        y[i, 6] = 1.0
    return carry, y


def _kalman_oracle(xe, p, x, q, r):
    y = x.astype(np.float64)
    for i in range(x.shape[0]):
        p_pred = p + q
        k = p_pred / (p_pred + r)
        xe = xe + k * (y[i, 1:6] - xe)
        p = (1.0 - k) * p_pred
        y[i, 1:6] = xe
    return xe, p, y


def _run(op, batch, xs):
    state = op.init_state(batch)
    apply = jax.jit(op.apply)
    outs = []
    for x in xs:
        state, y = apply(state, jnp.asarray(x))
        outs.append(np.asarray(y))
    return state, outs


@pytest.mark.parametrize("flags", FLAGS)
@pytest.mark.parametrize("batch", BATCHES)
def test_interpolate_matches_the_row_loop_exactly(batch, flags):
    rng = np.random.default_rng(batch * 31 + FLAGS.index(flags))
    xs = [_events(rng, batch, flags) for _ in range(N_BATCHES)]
    state, outs = _run(make_operator("interpolate", {"k": 2}), batch, xs)
    carry = np.zeros(5, np.float32)
    for x, y in zip(xs, outs):
        carry, want = _interpolate_oracle(carry, x)
        np.testing.assert_array_equal(y, want)
    np.testing.assert_array_equal(np.asarray(state), carry)


@pytest.mark.parametrize("q,r", NOISES)
@pytest.mark.parametrize("batch", BATCHES)
def test_kalman_matches_the_row_loop(batch, q, r):
    rng = np.random.default_rng(batch * 17 + NOISES.index((q, r)))
    xs = [_events(rng, batch, "random_30pct_invalid") for _ in range(N_BATCHES)]
    state, outs = _run(make_operator("kalman", {"q": q, "r": r}), batch, xs)
    xe, p = np.zeros(5), np.ones(5)
    for x, y in zip(xs, outs):
        xe, p, want = _kalman_oracle(xe, p, x, q, r)
        np.testing.assert_allclose(y[:, 1:6], want[:, 1:6], rtol=1e-5)
        np.testing.assert_array_equal(y[:, [0, 6, 7]], x[:, [0, 6, 7]])
    np.testing.assert_allclose(np.asarray(state["x"]), xe, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state["p"]), p, rtol=1e-5)


@pytest.mark.parametrize("typ,cfg", [("interpolate", {"k": 2}), ("kalman", {"q": 0.5})])
@pytest.mark.parametrize("batch", (1, 4096))
def test_state_keeps_its_structure_and_dtypes(typ, cfg, batch):
    op = make_operator(typ, cfg)
    init = op.init_state(batch)
    x = jnp.asarray(_events(np.random.default_rng(0), batch, "leading_invalid"))
    state, y = jax.jit(op.apply)(init, x)
    assert jax.tree.structure(state) == jax.tree.structure(init)
    for got, want in zip(jax.tree.leaves(state), jax.tree.leaves(init)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
    assert (y.shape, y.dtype) == (x.shape, x.dtype)
