"""Real IoT task logic in JAX — the RIoTBench task families (paper §5.1).

The RIoT workload composes ~19 distinct task types (parse/filter/quality,
windowed statistics, predictive analytics) into 21 IoT dataflows. Each task
here is real numerics over event batches of shape ``(B, EVENT_WIDTH)``:

  channel 0    timestamp
  channels 1-5 observation values (5 sensor channels)
  channel 6    validity flag (1.0 = valid)
  channel 7    event id / hash key

Cost weights are relative per-event CPU costs used by the Fig. 3 resource
accounting; they were chosen to mirror the relative costs reported for
RIoTBench task categories (parse < filter < window stats < predict).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .base import EVENT_WIDTH, Operator, register, register_fallback, stateless
from .costs import RIOT_COSTS, parse_config, pi_cost

VAL = slice(1, 6)  # observation channels
FLAG = 6
KEY = 7

# Straight-line runs of these types can be collapsed onto one multi-op
# pallas kernel when a fused segment is compiled (see
# runtime/segment.py:_peephole_fused_kernels): FUSABLE_ELEMENTWISE types
# may appear anywhere in the run, FUSED_TAILS terminate it.
FUSABLE_ELEMENTWISE = ("senml_parse",)
FUSED_TAILS = ("rmsnorm", "senml_parse")


def make_fused_operator(tasks, batch: int) -> Any:
    """One operator computing a ``senml_parse* → (rmsnorm|senml_parse)`` run.

    ``tasks`` is the run in head→tail dataflow order. The returned
    operator replaces the *tail* task inside a fused segment and consumes
    the head's input; it dispatches through the multi-op pallas kernels
    (:func:`repro.kernels.ops.affine_rmsnorm` / ``map_chain``) with the
    stages replayed sequentially, so outputs are bit-identical to the
    unfused op-by-op execution on every backend. State structure and cost
    weight are the tail's (both tails are stateless), keeping checkpoint
    layout and Fig. 3 cost accounting unchanged. Returns ``None`` for
    runs this factory does not understand.
    """
    if len(tasks) < 2:
        return None
    *heads, tail = tasks
    if any(t.type not in FUSABLE_ELEMENTWISE for t in heads):
        return None
    if tail.type not in FUSED_TAILS:
        return None

    def _stage(cfg: Dict[str, Any]):
        return (float(cfg.get("scale", 1.0)), float(cfg.get("offset", 0.0)))

    stages = tuple(_stage(parse_config(t.config)) for t in heads)
    tail_cfg = parse_config(tail.config)

    if tail.type == "rmsnorm":
        eps = float(tail_cfg.get("eps", 1e-6))
        gain = float(tail_cfg.get("gain", 1.0))

        def fn(x: jnp.ndarray) -> jnp.ndarray:
            from repro.kernels import ops as kernel_ops

            scale = jnp.full((5,), gain, dtype=x.dtype)
            vals = kernel_ops.affine_rmsnorm(x[:, VAL], scale, stages=stages, eps=eps)
            return x.at[:, VAL].set(vals)

    else:  # senml_parse tail — its own affine is just the last stage
        all_stages = stages + (_stage(tail_cfg),)

        def fn(x: jnp.ndarray) -> jnp.ndarray:
            from repro.kernels import ops as kernel_ops

            vals = kernel_ops.map_chain(x[:, VAL], stages=all_stages)
            return x.at[:, VAL].set(vals)

    return stateless(tail.type, fn, cost=RIOT_COSTS[tail.type])


def _hash_channel(x: jnp.ndarray, salt: int) -> jnp.ndarray:
    """Cheap integer hash of the id channel (splitmix-style)."""
    z = (x[:, KEY] * 2654435761.0 + float(salt)).astype(jnp.int32)
    z = jnp.bitwise_xor(z, z >> 16) * jnp.int32(0x45D9F3B)
    z = jnp.bitwise_xor(z, z >> 16)
    return z


# -- ETL family ---------------------------------------------------------------

@register("senml_parse")
def senml_parse(cfg: Dict[str, Any]) -> Operator:
    """Decode: per-channel affine normalization (scale/offset from config)."""
    scale = float(cfg.get("scale", 1.0))
    offset = float(cfg.get("offset", 0.0))

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        vals = x[:, VAL] * scale + offset
        return x.at[:, VAL].set(vals)

    return stateless("senml_parse", fn, cost=RIOT_COSTS["senml_parse"])


@register("csv_parse")
def csv_parse(cfg: Dict[str, Any]) -> Operator:
    """Field re-ordering + cast — a fixed channel permutation."""
    shift = int(cfg.get("shift", 1)) % 5

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        vals = jnp.roll(x[:, VAL], shift=shift, axis=1)
        return x.at[:, VAL].set(vals)

    return stateless("csv_parse", fn, cost=RIOT_COSTS["csv_parse"])


@register("range_filter")
def range_filter(cfg: Dict[str, Any]) -> Operator:
    """Quality check: flag events whose channel-1 value is out of [lo, hi]."""
    lo = float(cfg.get("lo", -1e3))
    hi = float(cfg.get("hi", 1e3))

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        ok = (x[:, 1] >= lo) & (x[:, 1] <= hi)
        return x.at[:, FLAG].set(x[:, FLAG] * ok.astype(x.dtype))

    return stateless("range_filter", fn, cost=RIOT_COSTS["range_filter"])


@register("bloom_filter")
def bloom_filter(cfg: Dict[str, Any]) -> Operator:
    """Membership filter with a real bitset state (m buckets, k salts)."""
    m = int(cfg.get("m", 1024))
    salts = tuple(range(int(cfg.get("k", 3))))

    def init_state(batch: int):
        return jnp.zeros((m,), dtype=jnp.int32)

    def apply(state, x):
        seen = jnp.ones((x.shape[0],), dtype=jnp.bool_)
        new = state
        for s in salts:
            idx = jnp.abs(_hash_channel(x, s)) % m
            seen = seen & (state[idx] > 0)
            new = new.at[idx].set(1)
        # mark duplicate events invalid (flag *= not-seen)
        y = x.at[:, FLAG].set(x[:, FLAG] * (~seen).astype(x.dtype))
        return new, y

    return Operator("bloom_filter", init_state, apply, cost_weight=RIOT_COSTS["bloom_filter"])


@register("interpolate")
def interpolate(cfg: Dict[str, Any]) -> Operator:
    """Replace invalid observations with the last valid value (per channel).

    Parallel over the batch: a running max of the valid rows' indices names
    each row's last valid row, whose values are gathered (channel-major, so
    the gather runs along the batch); rows before the batch's first valid
    row take the carried values. Bit-identical to the row-by-row recurrence.
    """

    def init_state(batch: int):
        return jnp.zeros((5,), dtype=jnp.float32)

    def apply(state, x):
        rows = jnp.arange(x.shape[0])
        last = jax.lax.cummax(jnp.where(x[:, FLAG] > 0.5, rows, -1), axis=0)
        vals = jnp.take(x[:, VAL].T, jnp.maximum(last, 0), axis=1, mode="clip")
        vals = jnp.where(last >= 0, vals, state[:, None])  # (5, B)
        return vals[:, -1], x.at[:, VAL].set(vals.T).at[:, FLAG].set(1.0)

    return Operator("interpolate", init_state, apply, cost_weight=RIOT_COSTS["interpolate"])


@register("join")
def join(cfg: Dict[str, Any]) -> Operator:
    """Interleave-join: pass events through, stamping a join counter."""

    def init_state(batch: int):
        return jnp.zeros((), dtype=jnp.int32)

    def apply(state, x):
        return state + 1, x.at[:, 0].add(0.0)  # timestamp untouched; count advances

    return Operator("join", init_state, apply, cost_weight=RIOT_COSTS["join"])


@register("annotate")
def annotate(cfg: Dict[str, Any]) -> Operator:
    """Metadata annotation: add a constant tag into channel 5."""
    tag = float(cfg.get("tag", 1.0))

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        return x.at[:, 5].set(tag)

    return stateless("annotate", fn, cost=RIOT_COSTS["annotate"])


# -- STATS family --------------------------------------------------------------

def _delay(a, shift: int):
    """``a`` moved ``shift`` places later along its last axis, zeros first."""
    pad = [(0, 0)] * (a.ndim - 1) + [(shift, 0)]
    return jnp.pad(a[..., :-shift], pad)


def _moebius_prefix(q: float, r: float, n: int) -> np.ndarray:
    """Normalised powers M^1..M^n of the Kalman variance map's matrix.

    One row maps the variance p to r(p+q)/(p+q+r), the Moebius map of
    M = [[r, qr], [1, q+r]], so p after t rows is M^t applied to p. The
    powers depend only on (q, r, n): a prefix product by doubling, in
    float64 at trace time, each normalised by its largest entry (raw powers
    overflow float32 within a few hundred rows). Returns (4, n): the
    entries 00, 01, 10, 11 of each power.
    """
    pw = np.repeat(np.array([[r], [q * r], [1.0], [q + r]]), n, axis=1)
    shift = 1
    while shift < n:  # pw[t] <- pw[t] @ pw[t - shift]
        u, v = pw[:, :-shift], pw[:, shift:]
        c = np.stack([v[0] * u[0] + v[1] * u[2], v[0] * u[1] + v[1] * u[3],
                      v[2] * u[0] + v[3] * u[2], v[2] * u[1] + v[3] * u[3]])
        pw[:, shift:] = c / np.abs(c).max(axis=0)
        shift *= 2
    return pw


@register("kalman")
def kalman(cfg: Dict[str, Any]) -> Operator:
    """Scalar Kalman filter per observation channel (real recurrence).

    Parallel over the batch, channel-major: the variance before each row is
    a Moebius map of the carried variance by a power of a fixed 2x2 matrix
    (:func:`_moebius_prefix`); given the gains k, the estimate follows the
    affine maps x -> x - k x + k z, composed by a doubling prefix scan. A
    composed map is kept as (c, b) for x -> x - c x + b: c = 1 - prod(1-k)
    loses no precision where the product is near 1 (small gains).
    """
    q = float(cfg.get("q", 0.1))  # process noise
    r = float(cfg.get("r", 1.0))  # measurement noise

    def init_state(batch: int):
        return {"x": jnp.zeros((5,)), "p": jnp.ones((5,))}

    def apply(state, x):
        n = x.shape[0]
        pw = jnp.asarray(_moebius_prefix(q, r, n), x.dtype)
        p0 = state["p"][:, None]
        after = (pw[0] * p0 + pw[1]) / (pw[2] * p0 + pw[3])  # (5, B)
        p_pred = jnp.concatenate([p0, after[:, :-1]], axis=1) + q
        c = p_pred / (p_pred + r)  # the gains: one row's map
        b = c * x[:, VAL].T
        shift = 1
        while shift < n:  # compose each map after the one ending `shift` rows before
            uc, ub = _delay(c, shift), _delay(b, shift)  # zeros: the identity map
            c, b = uc + c * (1.0 - uc), ub - c * ub + b
            shift *= 2
        x0 = state["x"][:, None]
        xe = x0 - c * x0 + b
        return {"x": xe[:, -1], "p": after[:, -1]}, x.at[:, VAL].set(xe.T)

    return Operator("kalman", init_state, apply, cost_weight=RIOT_COSTS["kalman"])


@register("win")
def sliding_window(cfg: Dict[str, Any]) -> Operator:
    """Sliding window: ring buffer of the last w batch-means, emits window mean."""
    w = int(cfg.get("w", 10))

    def init_state(batch: int):
        return {"buf": jnp.zeros((w, 5)), "n": jnp.zeros((), jnp.int32)}

    def apply(state, x):
        mean = x[:, VAL].mean(axis=0)
        idx = state["n"] % w
        buf = state["buf"].at[idx].set(mean)
        n = state["n"] + 1
        denom = jnp.minimum(n, w).astype(jnp.float32)
        agg = buf.sum(axis=0) / denom
        # values re-centered around the window aggregate
        return {"buf": buf, "n": n}, x.at[:, VAL].set(x[:, VAL] - agg)

    return Operator("win", init_state, apply, cost_weight=RIOT_COSTS["win"])


@register("avg")
def block_average(cfg: Dict[str, Any]) -> Operator:
    """Running (cumulative) average — Welford mean per channel."""

    def init_state(batch: int):
        return {"mean": jnp.zeros((5,)), "n": jnp.zeros((), jnp.float32)}

    def apply(state, x):
        bmean = x[:, VAL].mean(axis=0)
        n = state["n"] + 1.0
        mean = state["mean"] + (bmean - state["mean"]) / n
        return {"mean": mean, "n": n}, x.at[:, VAL].set(x[:, VAL] - mean)

    return Operator("avg", init_state, apply, cost_weight=RIOT_COSTS["avg"])


@register("moment2")
def second_order_moment(cfg: Dict[str, Any]) -> Operator:
    """Running variance (Welford) — stamps normalized values."""

    def init_state(batch: int):
        return {"mean": jnp.zeros((5,)), "m2": jnp.zeros((5,)), "n": jnp.zeros(())}

    def apply(state, x):
        bmean = x[:, VAL].mean(axis=0)
        n = state["n"] + 1.0
        delta = bmean - state["mean"]
        mean = state["mean"] + delta / n
        m2 = state["m2"] + delta * (bmean - mean)
        var = m2 / jnp.maximum(n - 1.0, 1.0)
        y = x.at[:, VAL].set((x[:, VAL] - mean) * jax.lax.rsqrt(var + 1e-6))
        return {"mean": mean, "m2": m2, "n": n}, y

    return Operator("moment2", init_state, apply, cost_weight=RIOT_COSTS["moment2"])


@register("rmsnorm")
def rmsnorm_op(cfg: Dict[str, Any]) -> Operator:
    """RMS-normalize the observation channels via the kernel library.

    Dispatches through :func:`repro.kernels.ops.rmsnorm` — the Pallas
    kernel on TPU, the reference einsum elsewhere — so fusion-compiled
    segment chains exercise real accelerator kernels where they exist.
    """
    eps = float(cfg.get("eps", 1e-6))
    gain = float(cfg.get("gain", 1.0))

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        from repro.kernels import ops as kernel_ops

        scale = jnp.full((5,), gain, dtype=x.dtype)
        vals = kernel_ops.rmsnorm(x[:, VAL], scale, eps=eps)
        return x.at[:, VAL].set(vals)

    return stateless("rmsnorm", fn, cost=RIOT_COSTS["rmsnorm"])


@register("distinct_count")
def distinct_count(cfg: Dict[str, Any]) -> Operator:
    """Approximate distinct count (linear-counting bitset)."""
    m = int(cfg.get("m", 512))

    def init_state(batch: int):
        return jnp.zeros((m,), dtype=jnp.int32)

    def apply(state, x):
        idx = jnp.abs(_hash_channel(x, 7)) % m
        bits = state.at[idx].set(1)
        zeros = (m - bits.sum()).astype(jnp.float32)
        est = -float(m) * jnp.log(jnp.maximum(zeros, 1.0) / float(m))
        return bits, x.at[:, 5].set(est)

    return Operator("distinct_count", init_state, apply, cost_weight=RIOT_COSTS["distinct_count"])


# -- PREDICT family --------------------------------------------------------------

@register("linreg")
def multivar_linreg(cfg: Dict[str, Any]) -> Operator:
    """Multi-variate linear regression predict: ŷ = w·x + b (fixed weights)."""
    seed = int(cfg.get("seed", 0))
    w = jax.random.normal(jax.random.PRNGKey(seed), (5,)) * 0.3

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        pred = x[:, VAL] @ w
        return x.at[:, 5].set(pred)

    return stateless("linreg", fn, cost=RIOT_COSTS["linreg"])


@register("dtree")
def decision_tree(cfg: Dict[str, Any]) -> Operator:
    """Fixed-depth decision-tree classifier over the observation channels."""
    t1 = float(cfg.get("t1", 0.0))
    t2 = float(cfg.get("t2", 0.5))
    t3 = float(cfg.get("t3", -0.5))

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        c = jnp.where(
            x[:, 1] > t1,
            jnp.where(x[:, 2] > t2, 2.0, 1.0),
            jnp.where(x[:, 3] > t3, 0.0, -1.0),
        )
        return x.at[:, 5].set(c)

    return stateless("dtree", fn, cost=RIOT_COSTS["dtree"])


@register("sliding_linreg")
def sliding_linreg(cfg: Dict[str, Any]) -> Operator:
    """OLS trend over a ring buffer of batch means (2x2 normal equations)."""
    w = int(cfg.get("w", 16))

    def init_state(batch: int):
        return {"buf": jnp.zeros((w,)), "n": jnp.zeros((), jnp.int32)}

    def apply(state, x):
        mean = x[:, 1].mean()
        idx = state["n"] % w
        buf = state["buf"].at[idx].set(mean)
        n = state["n"] + 1
        t = jnp.arange(w, dtype=jnp.float32)
        mask = (t < jnp.minimum(n, w)).astype(jnp.float32)
        cnt = mask.sum()
        tm = (t * mask).sum() / cnt
        ym = (buf * mask).sum() / cnt
        cov = ((t - tm) * (buf - ym) * mask).sum()
        var = ((t - tm) ** 2 * mask).sum()
        slope = cov / jnp.maximum(var, 1e-6)
        return {"buf": buf, "n": n}, x.at[:, 5].set(slope)

    return Operator("sliding_linreg", init_state, apply, cost_weight=RIOT_COSTS["sliding_linreg"])


@register("error_estimate")
def error_estimate(cfg: Dict[str, Any]) -> Operator:
    """|prediction − observation| into channel 4."""

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        return x.at[:, 4].set(jnp.abs(x[:, 5] - x[:, 1]))

    return stateless("error_estimate", fn, cost=RIOT_COSTS["error_estimate"])


# -- OPMW synthetic π task (paper §5.1) -----------------------------------------

@register("pi")
def pi_task(cfg: Dict[str, Any]) -> Operator:
    return _pi_operator(cfg, "pi")


@register_fallback
def _fallback(cfg: Dict[str, Any]) -> Operator:
    """Unknown task types (the OPMW workload) run the iterative π logic —
    exactly the paper's substitution of OPMW task internals."""
    return _pi_operator(cfg, cfg.get("_type", "pi"))


def _pi_operator(cfg: Dict[str, Any], type_name: str) -> Operator:
    iters = int(cfg.get("iters", 100))

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        def body(i, acc):
            k = i.astype(jnp.float32)
            return acc + jnp.where(i % 2 == 0, 1.0, -1.0) * 4.0 / (2.0 * k + 1.0)

        pi_est = jax.lax.fori_loop(0, iters, body, jnp.zeros(()))
        return x.at[:, 5].set(pi_est)

    # π cost scales with the iteration count (CPU-intensive per event).
    return stateless(type_name, fn, cost=pi_cost(cfg))
