"""Plain reference of the stream semantics the benchmark checks.

Written from the semantics alone and independent of the program: it imports
nothing of ``repro`` and takes nothing the program made. Given the log of a
run (which dataflow was submitted or removed before which step) it computes
what every sink of every dataflow running at the end must hold: the number
of batches it consumed, its folded checksum and the last batch.

Semantics (the configuration's guarantees):

- A dataflow is a chain ``source -> task* -> sink``. Two running tasks are
  the same task when their whole prefix (types and configs from the source
  down) is equal; a submission reuses every prefix that is running and starts
  the rest at the step it was admitted. A task whose prefix no running
  dataflow uses any more stops and is never resumed; a later submission of
  that prefix starts a new one.
- Every running task consumes one batch per step, exactly once and in order:
  its parent's output of the same step.
- A source's output is a pure function of its type and its step counter,
  which starts at the value recorded in the log (0 unless set) and advances
  by one per step.

The stream is processed in blocks of ``block`` steps: for each running source
the chains below it are walked depth first, one jitted call per task and
block, so the reference never holds more than one block per chain level.
Within a batch the recurrences (interpolate, kalman) run as parallel prefix
scans, written here from their row-by-row definitions.

A configuration whose task or source types this module does not know brings
their semantics in a module of its own (``extra``: its ``"reference"`` file,
which imports nothing of ``repro`` either). It maps each new task type to
``factory(cfg)`` with ``make_task``'s contract (``TASKS``) and, optionally,
each new source type (the part before any ':') to ``factory(batch)`` with
``make_source``'s (``SOURCES``). It may add types, never redefine one here:
the chain walk, reuse by prefix, the sink and the event layout stay this
module's.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

EVENT_WIDTH = 8
VAL = slice(1, 6)
FLAG = 6
KEY = 7

# Source signal profiles: (bias, amplitude, period, noise), by the part of the
# source type before any ':'; other types take the default profile.
PROFILES = {
    "urban": (20.0, 5.0, 60.0, 0.8),
    "meter": (1.2, 0.6, 1440.0, 0.1),
    "grid": (50.0, 0.05, 3600.0, 0.02),
    "taxi": (8.0, 6.0, 720.0, 2.0),
}
DEFAULT_PROFILE = (0.0, 1.0, 100.0, 0.5)


KNOWN_TASKS = ("senml_parse", "csv_parse", "range_filter", "bloom_filter", "interpolate",
               "annotate", "kalman", "win", "avg", "moment2", "sliding_linreg",
               "distinct_count", "linreg", "dtree", "error_estimate", "pi")


def canonical(cfg: Any) -> str:
    return json.dumps(cfg, sort_keys=True, separators=(",", ":"))


@dataclass
class Flow:
    name: str
    source: str
    steps: List[Tuple[str, Dict[str, Any]]]
    sink: str

    def prefixes(self) -> List[Tuple]:
        """The key of each task from the source down to the sink."""
        keys, cur = [], (("source", self.source),)
        keys.append(cur)
        for typ, cfg in self.steps:
            cur = cur + ((typ, canonical(cfg)),)
            keys.append(cur)
        keys.append(cur + (("sink", self.sink),))
        return keys


@dataclass
class Node:
    key: Tuple
    created: int
    children: List["Node"] = field(default_factory=list)


def live_nodes(flows: Dict[str, Flow], log: Sequence[Tuple[int, str, str]]):
    """Replays ``log`` ((step, "submit"|"remove", name), in order). Returns the
    dataflows running at the end and, for each task key they use, the step at
    which that running task started."""
    created: Dict[Tuple, int] = {}
    users: Dict[Tuple, int] = {}
    running: Dict[str, Flow] = {}
    for step, op, name in log:
        flow = flows[name]
        if op == "submit":
            if name in running:
                raise ValueError(f"{name} submitted twice")
            running[name] = flow
            for key in flow.prefixes():
                if users.get(key, 0) == 0:
                    created[key] = step
                users[key] = users.get(key, 0) + 1
        elif op == "remove":
            del running[name]
            for key in flow.prefixes():
                users[key] -= 1
                if users[key] == 0:
                    del created[key]
        else:
            raise ValueError(op)
    return running, created


# -- task semantics, one batch at a time -----------------------------------------
#
# A batch is held channel-major, x[c, i] for channel c of event i (an (8, B)
# array): on a TPU the long event axis then fills the vector lanes. Tables
# are read and set through one-hot comparisons rather than gathers and
# scatters, and the row-by-row recurrences run as parallel prefix scans.

def _hash(jnp, x, salt: int):
    z = (x[KEY].astype(np.float32) * 2654435761.0 + float(salt)).astype(jnp.int32)
    z = jnp.bitwise_xor(z, z >> 16) * jnp.int32(0x45D9F3B)
    return jnp.bitwise_xor(z, z >> 16)


def _onehot(jnp, idx, m: int):
    return idx[:, None] == jnp.arange(m, dtype=idx.dtype)[None, :]  # (B, m)


def _set(x, rows, v):
    return x.at[rows].set(v.astype(x.dtype) if hasattr(v, "astype") else v)


def make_task(typ: str, cfg: Dict[str, Any]):
    """(init_state(dtype) or None, apply(state, x) -> (state, y)) for one task."""
    import jax
    import jax.numpy as jnp

    if typ == "senml_parse":
        scale, offset = float(cfg.get("scale", 1.0)), float(cfg.get("offset", 0.0))
        return None, lambda s, x: (s, _set(x, VAL, x[VAL] * scale + offset))
    if typ == "csv_parse":
        shift = int(cfg.get("shift", 1)) % 5
        return None, lambda s, x: (s, _set(x, VAL, jnp.roll(x[VAL], shift, axis=0)))
    if typ == "range_filter":
        lo, hi = float(cfg.get("lo", -1e3)), float(cfg.get("hi", 1e3))

        def apply(s, x):
            ok = ((x[1] >= lo) & (x[1] <= hi)).astype(x.dtype)
            return s, _set(x, FLAG, x[FLAG] * ok)
        return None, apply
    if typ == "bloom_filter":
        m, k = int(cfg.get("m", 1024)), int(cfg.get("k", 3))

        def apply(bits, x):
            seen = jnp.ones((x.shape[1],), bool)
            hit = jnp.zeros((m,), bool)
            for salt in range(k):
                onehot = _onehot(jnp, jnp.abs(_hash(jnp, x, salt)) % m, m)
                seen = seen & jnp.any(onehot & (bits > 0)[None, :], axis=1)
                hit = hit | jnp.any(onehot, axis=0)
            new = jnp.where(hit, 1, bits).astype(bits.dtype)
            return new, _set(x, FLAG, x[FLAG] * (~seen).astype(x.dtype))
        return (lambda dt: jnp.zeros((m,), jnp.int32)), apply
    if typ == "interpolate":
        def apply(carry, x):
            valid = jnp.broadcast_to(x[FLAG] > 0.5, x[VAL].shape)

            def fill(u, v):  # u earlier: whether a valid event came, and its values
                return u[0] | v[0], jnp.where(v[0], v[1], u[1])
            any_valid, vals = jax.lax.associative_scan(fill, (valid, x[VAL]), axis=1)
            vals = jnp.where(any_valid, vals, carry[:, None])
            y = _set(_set(x, VAL, vals), FLAG, jnp.ones((), x.dtype))
            return vals[:, -1], y
        return (lambda dt: jnp.zeros((5,), dt)), apply
    if typ == "annotate":
        tag = float(cfg.get("tag", 1.0))
        return None, lambda s, x: (s, x.at[5].set(tag))
    if typ == "kalman":
        q, r = float(cfg.get("q", 0.1)), float(cfg.get("r", 1.0))

        def apply(st, x):
            xe0, p0 = st
            p_before, p_end = _kalman_variance(jax, jnp, p0, q, r, x.shape[1])
            gain = (p_before + q) / (p_before + q + r)  # (5, B)
            a, b = 1.0 - gain, gain * x[VAL]

            def combine(u, v):  # u earlier: x -> v_a * (u_a * x + u_b) + v_b
                return u[0] * v[0], v[0] * u[1] + v[1]
            acc_a, acc_b = jax.lax.associative_scan(combine, (a, b), axis=1)
            xe = acc_a * xe0[:, None] + acc_b
            return (xe[:, -1], p_end), _set(x, VAL, xe)
        return (lambda dt: (jnp.zeros((5,), dt), jnp.ones((5,), dt))), apply
    if typ == "win":
        w = int(cfg.get("w", 10))

        def apply(st, x):
            buf, n = st
            buf = buf.at[n % w].set(x[VAL].mean(axis=1))
            n = n + 1
            agg = buf.sum(axis=0) / jnp.minimum(n, w).astype(x.dtype)
            return (buf, n), _set(x, VAL, x[VAL] - agg[:, None])
        return (lambda dt: (jnp.zeros((w, 5), dt), jnp.zeros((), jnp.int32))), apply
    if typ == "avg":
        def apply(st, x):
            mean, n = st
            n = n + 1.0
            mean = mean + (x[VAL].mean(axis=1) - mean) / n
            return (mean, n), _set(x, VAL, x[VAL] - mean[:, None])
        return (lambda dt: (jnp.zeros((5,), dt), jnp.zeros((), dt))), apply
    if typ == "moment2":
        def apply(st, x):
            mean, m2, n = st
            bmean = x[VAL].mean(axis=1)
            n = n + 1.0
            delta = bmean - mean
            mean = mean + delta / n
            m2 = m2 + delta * (bmean - mean)
            var = m2 / jnp.maximum(n - 1.0, 1.0)
            y = (x[VAL] - mean[:, None]) * jax.lax.rsqrt(var + 1e-6)[:, None]
            return (mean, m2, n), _set(x, VAL, y)
        return (lambda dt: (jnp.zeros((5,), dt), jnp.zeros((5,), dt), jnp.zeros((), dt))), apply
    if typ == "sliding_linreg":
        w = int(cfg.get("w", 16))

        def apply(st, x):
            buf, n = st
            buf = buf.at[n % w].set(x[1].mean())
            n = n + 1
            t = jnp.arange(w, dtype=x.dtype)
            mask = (t < jnp.minimum(n, w)).astype(x.dtype)
            cnt = mask.sum()
            tm = (t * mask).sum() / cnt
            ym = (buf * mask).sum() / cnt
            cov = ((t - tm) * (buf - ym) * mask).sum()
            var = ((t - tm) ** 2 * mask).sum()
            return (buf, n), x.at[5].set(cov / jnp.maximum(var, 1e-6))
        return (lambda dt: (jnp.zeros((w,), dt), jnp.zeros((), jnp.int32))), apply
    if typ == "distinct_count":
        m = int(cfg.get("m", 512))

        def apply(bits, x):
            hit = jnp.any(_onehot(jnp, jnp.abs(_hash(jnp, x, 7)) % m, m), axis=0)
            bits = jnp.where(hit, 1, bits).astype(bits.dtype)
            zeros = (m - bits.sum()).astype(x.dtype)
            est = -float(m) * jnp.log(jnp.maximum(zeros, 1.0) / float(m))
            return bits, x.at[5].set(est)
        return (lambda dt: jnp.zeros((m,), jnp.int32)), apply
    if typ == "linreg":
        w = np.asarray(jax.random.normal(jax.random.PRNGKey(int(cfg.get("seed", 0))), (5,)) * 0.3)

        def apply(s, x):
            return s, x.at[5].set((x[VAL] * w[:, None].astype(x.dtype)).sum(axis=0))
        return None, apply
    if typ == "dtree":
        t1, t2, t3 = (float(cfg.get(k, d)) for k, d in (("t1", 0.0), ("t2", 0.5), ("t3", -0.5)))

        def apply(s, x):
            c = jnp.where(x[1] > t1, jnp.where(x[2] > t2, 2.0, 1.0),
                          jnp.where(x[3] > t3, 0.0, -1.0))
            return s, x.at[5].set(c.astype(x.dtype))
        return None, apply
    if typ == "error_estimate":
        return None, lambda s, x: (s, x.at[4].set(jnp.abs(x[5] - x[1])))
    if typ == "pi":
        iters = int(cfg.get("iters", 100))

        def apply(s, x):
            acc = jnp.zeros((), x.dtype)
            for i in range(iters):  # unrolled: the series, term by term in order
                acc = acc + (1.0 if i % 2 == 0 else -1.0) * 4.0 / (2.0 * float(i) + 1.0)
            return s, x.at[5].set(acc)
        return None, apply
    raise KeyError(f"the reference has no semantics for task type {typ!r}")


def _kalman_variance(jax, jnp, p0, q: float, r: float, n: int):
    """The variance before each of ``n`` rows, (5, n), and after the last.
    Each row maps p to r(p+q)/(p+q+r), a Moebius map with matrix
    [[r, qr], [1, q+r]], so the variance after k rows comes from the k-th
    matrix power, taken by a parallel prefix product (normalised, since
    only ratios matter)."""
    mats = jnp.broadcast_to(jnp.asarray([r, q * r, 1.0, q + r], p0.dtype)[:, None], (4, n))

    def combine(u, v):  # v after u: v @ u, entries (00, 01, 10, 11)
        c = jnp.stack([v[0] * u[0] + v[1] * u[2], v[0] * u[1] + v[1] * u[3],
                       v[2] * u[0] + v[3] * u[2], v[2] * u[1] + v[3] * u[3]])
        return c / jnp.max(jnp.abs(c), axis=0, keepdims=True)
    pw = jax.lax.associative_scan(combine, mats, axis=1)  # M^1 .. M^n, (4, n)
    pk = p0[:, None]
    after = (pw[0][None, :] * pk + pw[1][None, :]) / (pw[2][None, :] * pk + pw[3][None, :])
    before = jnp.concatenate([pk, after[:, :-1]], axis=1)
    return before, after[:, -1]


def make_source(typ: str, batch: int):
    """``emit(counter, dtype)``: the source's (8, B) batch at that counter.
    Its profile stays a constant of the program, so the sinusoid's argument
    is formed and rounded as any program with the same constants forms it."""
    import jax
    import jax.numpy as jnp

    bias, amp, period, noise = PROFILES.get(typ.split(":")[0], DEFAULT_PROFILE)
    seed = int.from_bytes(hashlib.sha256(typ.encode()).digest()[:4], "little")

    def emit(counter, dtype):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), counter)
        t = counter.astype(jnp.float32) + jnp.arange(batch, dtype=jnp.float32) / batch
        base = bias + amp * jnp.sin(2.0 * jnp.pi * t / period)
        vals = base[None, :] + noise * jax.random.normal(key, (batch, 5)).T
        ids = (counter * batch + jnp.arange(batch)).astype(jnp.float32)
        out = jnp.concatenate([t[None], vals, jnp.ones((1, batch), jnp.float32), ids[None]], axis=0)
        return out.astype(dtype)
    return emit


def _sink_apply(st, x):
    import jax.numpy as jnp

    count, checksum, last = st
    return (count + 1, checksum * 0.5 + jnp.sum(x, dtype=x.dtype), x)


# -- block programs: one task over K steps, masked by the steps it runs -------------

def _extension(extra) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``extra``'s (TASKS, SOURCES); raises where it redefines a type this
    module knows."""
    if extra is None:
        return {}, {}
    tasks, sources = dict(extra.TASKS), dict(getattr(extra, "SOURCES", {}))
    clash = sorted(set(tasks) & set(KNOWN_TASKS)) + sorted(
        t for t in sources if t.split(":")[0] in PROFILES)
    if clash:
        raise ValueError(f"the configuration's reference redefines {clash}")
    return tasks, sources


class Programs:
    """Jitted block programs, cached by task definition and dtype."""

    def __init__(self, batch: int, dtype, fallback: Optional[str], extra=None):
        self.batch, self.dtype, self.fallback = batch, dtype, fallback
        self.extra_tasks, self.extra_sources = _extension(extra)
        self._cache: Dict[Any, Any] = {}

    def source(self, typ: str):
        """``run(counters)`` -> (K, 8, B) for one source type."""
        key = ("source", typ)
        if key not in self._cache:
            import jax

            factory = self.extra_sources.get(typ.split(":")[0])
            emit = factory(self.batch) if factory else make_source(typ, self.batch)
            dtype = self.dtype
            self._cache[key] = jax.jit(lambda counters: jax.vmap(lambda c: emit(c, dtype))(counters))
        return self._cache[key]

    def task(self, typ: str, cfg_key: str):
        if typ not in KNOWN_TASKS and typ not in self.extra_tasks and self.fallback is not None:
            typ = self.fallback  # one program for every task the fallback covers
        key = ("task", typ, cfg_key)
        if key not in self._cache:
            import jax
            import jax.numpy as jnp

            factory = self.extra_tasks.get(typ)
            cfg = json.loads(cfg_key)
            init, apply = factory(cfg) if factory else make_task(typ, cfg)
            if init is None:
                run = jax.jit(lambda xs: jax.vmap(lambda x: apply((), x)[1])(xs))
                self._cache[key] = (None, run)
            else:
                def body(st, inp):
                    x, on = inp
                    new, y = apply(st, x)
                    new = jax.tree_util.tree_map(lambda a, b: jnp.where(on, a, b), new, st)
                    return new, y
                run = jax.jit(lambda st, xs, mask: jax.lax.scan(body, st, (xs, mask)))
                self._cache[key] = (init, run)
        return self._cache[key]

    def sink(self):
        key = ("sink",)
        if key not in self._cache:
            import jax
            import jax.numpy as jnp

            def body(st, inp):
                x, on = inp
                new = _sink_apply(st, x)
                return jax.tree_util.tree_map(lambda a, b: jnp.where(on, a, b), new, st), None
            self._cache[key] = jax.jit(lambda st, xs, mask: jax.lax.scan(body, st, (xs, mask))[0])
        return self._cache[key]


def run_reference(flows: Dict[str, Flow], log: Sequence[Tuple[int, str, str]],
                  steps: int, batch: int, counter_start: Dict[Tuple[str, int], int],
                  dtype="float32", block: int = 16, fallback: Optional[str] = None,
                  device=None, extra=None) -> Dict[str, Dict[str, Any]]:
    """Sink contents of every dataflow running after ``steps`` steps.

    ``counter_start`` maps (source type, step the source started) to its
    first counter where it was set; ``extra`` is the configuration's own
    reference module, if it has one. Returns ``{dataflow: {"count",
    "checksum", "last"}}`` with numpy values.
    """
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    running, created = live_nodes(flows, log)
    # the tree of running tasks that some running sink depends on
    nodes: Dict[Tuple, Node] = {}
    sink_of: Dict[Tuple, str] = {}
    for name, flow in running.items():
        keys = flow.prefixes()
        sink_of[keys[-1]] = name
        parent = None
        for key in keys:
            if key not in nodes:
                nodes[key] = Node(key, created[key])
                if parent is not None:
                    parent.children.append(nodes[key])
            parent = nodes[key]
    roots = [n for k, n in nodes.items() if len(k) == 1]
    progs = Programs(batch, dtype, fallback, extra)
    out: Dict[str, Dict[str, Any]] = {}
    with jax.default_device(device) if device is not None else contextlib.nullcontext():
        states: Dict[Tuple, Any] = {}
        for root in roots:
            start = counter_start.get((root.key[0][1], root.created), 0)
            for b0 in range(root.created, steps, block):
                idx = np.arange(b0, b0 + block)
                counters = jnp.asarray(start + (idx - root.created), jnp.int32)
                xs = progs.source(root.key[0][1])(counters)
                _walk(root, xs, idx, steps, states, progs, dtype)
        for key, name in sink_of.items():
            count, checksum, last = states[key]
            out[name] = {"count": int(count), "checksum": float(checksum),
                         "last": np.asarray(last, np.float32).T}
    return out


def _walk(node: Node, xs, idx, steps, states, progs: Programs, dtype) -> None:
    import jax.numpy as jnp

    for child in node.children:
        mask = (idx >= child.created) & (idx < steps)  # host-side: no device sync
        if not mask.any():
            continue
        typ, cfg = child.key[-1]
        if typ == "sink":
            st = states.get(child.key)
            if st is None:
                st = (jnp.zeros((), jnp.int32), jnp.zeros((), dtype),
                      jnp.zeros((EVENT_WIDTH, progs.batch), dtype))
            states[child.key] = progs.sink()(st, xs, mask)
            continue
        init, run = progs.task(typ, cfg)
        if init is None:
            ys = run(xs)
        else:
            st = states.get(child.key)
            if st is None:
                st = init(dtype)
            st, ys = run(st, xs, mask)
            states[child.key] = st
        _walk(child, ys, idx, steps, states, progs, dtype)
