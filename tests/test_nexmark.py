"""NEXmark Q1, Q2, Q5 and Q7 on the stream path, on the CPU at 128 events
per step and a small event-time rate (30 events/s: a Q5 pane of 150 events,
a Q7 window of 300), so a short run crosses many panes and windows.

The program is held to ``nexmark_reference.py``, a row-by-row event loop
that imports nothing of it. Counts and integer outputs must be equal; the
sinks' checksums agree to a relative 1e-6 only: the program folds its
checksum in float32 (``checksum * 0.5 + sum(batch)``), the reference in
float64, so the float32 rounding of the fold differs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nexmark_reference as ref
from repro.api import ReuseSession, flow
from repro.kernels import ops as kernel_ops
from repro.kernels import window as kw
from repro.ops import make_operator, nexmark, operator_for_task

BATCH = 128
RATE = 30
LATE = (1 << 16) - 1 + 8192  # the highest seeded counter plus a long run
FLOWS = {
    "q1": ("nexmark", [("nx_bids", {}), ("nx_convert", {})], "store"),
    "q2": ("nexmark", [("nx_bids", {}), ("nx_select", {"modulus": 123})], "store"),
    "q5": ("nexmark", [("nx_bids", {}), ("nx_hot_items", {"rate": RATE})], "store"),
    "q7": ("nexmark", [("nx_bids", {}), ("nx_highest_bid", {"rate": RATE})], "store"),
    "q7_euros": ("nexmark", [("nx_bids", {}), ("nx_convert", {}),
                             ("nx_highest_bid", {"rate": RATE})], "store"),
    # over a thinned stream, so windows are skipped between bids
    "q5_thin": ("nexmark", [("nx_bids", {}), ("nx_select", {"modulus": 3}),
                            ("nx_hot_items", {"rate": RATE})], "store"),
    "q7_thin": ("nexmark", [("nx_bids", {}), ("nx_select", {"modulus": 3}),
                            ("nx_highest_bid", {"rate": RATE})], "store"),
    "q5_late": ("nexmark", [("nx_bids", {}), ("nx_convert", {}),
                            ("nx_hot_items", {"rate": RATE})], "store"),
}


def _build(name):
    source, steps, sink = FLOWS[name]
    b = flow(name).source(source)
    for typ, cfg in steps:
        b.then(typ, **cfg)
    return b.sink(sink).build()


def _start_sources(session, seen, counter):
    """Set the step counter of every source deployed since the last call
    (ids in ``seen`` are skipped, then added) to ``counter``."""
    backend = session._system.backend
    for seg in backend.segments.values():
        for tid in seg.spec.task_ids:
            if backend.task_defs[tid].is_source and tid not in seen:
                seen.add(tid)
                seg.states[tid] = jnp.int32(counter)


def _run(strategy, log, steps, start=0):
    """The program over ``log``: each running dataflow's (count, checksum,
    last) and the session's metrics snapshot. A source deployed at step
    ``s`` starts at counter ``start + s``: the live stream's position, so
    a Default (no reuse) joiner reads what a Reuse joiner shares."""
    session = ReuseSession(strategy=strategy, execute=True, base_batch=BATCH)
    seen = set()
    try:
        for s in range(steps):
            for step, op, name in log:
                if step == s:
                    session.submit(_build(name)) if op == "submit" else session.remove(name)
            _start_sources(session, seen, start + s)
            session.step()
        backend = session._system.backend
        out = {}
        for name in session.names:
            ((sink_id, digest),) = session.sink_digests(name).items()
            state = backend.sink_state(session.manager.task_maps[name][sink_id])
            out[name] = (digest["count"], digest["checksum"], np.asarray(state["last"]))
        return out, session.metrics_snapshot()
    finally:
        session.close()


def _same(got, want):
    assert sorted(got) == sorted(want)
    for name, (count, checksum, last) in want.items():
        g_count, g_checksum, g_last = got[name]
        assert g_count == count, name
        np.testing.assert_array_equal(g_last, last, err_msg=name)
        assert g_checksum == pytest.approx(checksum, rel=1e-6, abs=1e-6), name


# -- the generator -------------------------------------------------------------------

@pytest.mark.parametrize("batch,counter", [(128, 0), (128, 977), (128, LATE), (16384, LATE)])
def test_generator_equals_the_reference(batch, counter):
    source = nexmark.make_source("nexmark", batch)
    _, got = jax.jit(source.apply)(jnp.int32(counter), None)
    want = ref.generate("nexmark", counter, batch)
    np.testing.assert_array_equal(np.asarray(got, np.float64), want)


def test_generator_mix_is_1_3_46_in_every_50_ids():
    source = nexmark.make_source("nexmark", 16384)
    _, x = jax.jit(source.apply)(jnp.int32(LATE), None)
    x = np.asarray(x)
    ids = x[:, 0].astype(np.int64) * 10_000 + x[:, 1].astype(np.int64)
    np.testing.assert_array_equal(ids, LATE * 16384 + np.arange(16384))
    first = (-ids[0]) % 50  # the first id of a whole block of 50
    kinds = x[first:first + 50 * ((16384 - first) // 50), 7].reshape(-1, 50)
    np.testing.assert_array_equal(kinds, np.tile([0, 1, 1, 1] + [2] * 46, (kinds.shape[0], 1)))


def test_hot_auction_share_is_one_half_within_five_sigma():
    """A bid names the hot auction with probability 1/2, and a cold draw
    names it with 1/111 (it lies among the 111 candidates): p = 0.5 + 0.5 /
    111. Over n bids the share lies within 5 sigma of p."""
    source = jax.jit(nexmark.make_source("nexmark", 16384).apply)
    hits = n = 0
    for counter in range(LATE, LATE + 8):
        x = np.asarray(source(jnp.int32(counter), None)[1]).astype(np.int64)
        bid = x[:, 7] == 2
        e = x[bid, 0] * 10_000 + x[bid, 1]
        hot = 1000 + ((3 * (e // 50) + 2) // 100) * 100
        hits += int(np.sum(x[bid, 2] * 10_000 + x[bid, 3] == hot))
        n += int(bid.sum())
    p = 0.5 + 0.5 / 111
    assert abs(hits / n - p) <= 5 * np.sqrt(p * (1 - p) / n), (hits, n)


def test_prices_are_whole_cents_in_the_published_range():
    x = np.asarray(jax.jit(nexmark.make_source("nexmark", 16384).apply)(jnp.int32(LATE), None)[1])
    price = (x[:, 4].astype(np.int64) * 10_000 + x[:, 5].astype(np.int64))[x[:, 7] == 2]
    assert price.min() >= 100 and price.max() <= 10 ** 8
    assert (x[:, 5] < 10_000).all() and (x[:, 5] == np.round(x[:, 5])).all()


# -- queries over consecutive batches ----------------------------------------------------

@pytest.mark.parametrize("start", [0, 3, LATE])
@pytest.mark.parametrize("chain", [["nx_convert"], ["nx_select"], ["nx_hot_items"],
                                   ["nx_highest_bid"], ["nx_select", "nx_hot_items"],
                                   ["nx_convert", "nx_highest_bid"]],
                         ids=lambda c: "+".join(c))
def test_query_chain_equals_the_reference(chain, start):
    cfgs = {"nx_convert": {}, "nx_select": {"modulus": 3}, "nx_hot_items": {"rate": RATE},
            "nx_highest_bid": {"rate": RATE}}
    ops = [make_operator(t, cfgs.get(t, {})) for t in ["nx_bids", *chain]]
    states = [op.init_state(BATCH) for op in ops]
    steps = [jax.jit(op.apply) for op in ops]
    oracles = [ref.make_task(t, cfgs.get(t, {})) for t in ["nx_bids", *chain]]
    source = jax.jit(nexmark.make_source("nexmark", BATCH).apply)
    fired = 0
    for counter in range(start, start + 40):
        x = source(jnp.int32(counter), None)[1]
        want = ref.generate("nexmark", counter, BATCH)
        for i, (step, oracle) in enumerate(zip(steps, oracles)):
            states[i], x = step(states[i], x)
            want = oracle(want)
        np.testing.assert_array_equal(np.asarray(x, np.float64), want, err_msg=str(counter))
        fired += int(want[:, 6].sum() > 0)
    if chain[-1] in ("nx_hot_items", "nx_highest_bid"):
        assert fired >= 5  # windows closed along the way


def test_deployment_sizes_equal_the_reference_within_the_tie_capacity():
    """At the cell's sizes (16384 events per step, 10,000 events/s: a Q5
    pane every 3.05 steps, a Q7 window every 6.1) Q5 and Q7 equal the
    reference, and no window comes near 64 tied rows under Beam's skew."""
    batch = 16384
    source = jax.jit(nexmark.make_source("nexmark", batch).apply)
    ops = {t: make_operator(t, {}) for t in ("nx_bids", "nx_hot_items", "nx_highest_bid")}
    states = {t: op.init_state(batch) for t, op in ops.items()}
    steps = {t: jax.jit(op.apply) for t, op in ops.items()}
    oracles = {t: ref.make_task(t, {}) for t in ops}
    closed = {"nx_hot_items": [], "nx_highest_bid": []}
    for counter in range(LATE, LATE + 14):
        states["nx_bids"], bid = steps["nx_bids"](states["nx_bids"], source(jnp.int32(counter), None)[1])
        want_bid = oracles["nx_bids"](ref.generate("nexmark", counter, batch))
        for t in closed:
            states[t], y = steps[t](states[t], bid)
            want = oracles[t](want_bid)
            np.testing.assert_array_equal(np.asarray(y, np.float64), want, err_msg=f"{t} {counter}")
            closed[t] += list(want[want[:, 6] > 0.5, 7])
    assert len(closed["nx_hot_items"]) >= 3 and len(closed["nx_highest_bid"]) >= 1
    assert max(closed["nx_hot_items"] + closed["nx_highest_bid"]) < nexmark.CAPACITY // 4


def test_run_equals_the_reference_for_every_sink():
    log = [(0, "submit", n) for n in FLOWS if n != "q5_late"]
    got, _ = _run("signature", log, 30, start=LATE)
    want = ref.run(FLOWS, log, 30, BATCH, {("nexmark", 0): LATE})
    _same(got, want)
    q5 = want["q5"][2]
    assert q5[:, 6].sum() >= 1 or got["q5"][0] == 30


def test_default_and_reuse_agree_when_tenants_leave_and_join_mid_window():
    """q7 leaves and q5_late joins in the middle of windows (a Q7 window is
    2.3 steps, a Q5 pane 1.2); q5_late's first window is its partial one."""
    log = [(0, "submit", n) for n in ("q1", "q5", "q7", "q7_euros", "q5_thin")]
    log += [(11, "remove", "q7"), (13, "submit", "q5_late"), (17, "submit", "q7")]
    reuse, _ = _run("signature", log, 32, start=500)
    default, _ = _run("none", log, 32, start=500)
    assert sorted(reuse) == sorted(default)
    for name in reuse:
        assert reuse[name][:2] == default[name][:2], name
        np.testing.assert_array_equal(reuse[name][2], default[name][2])
    _same(reuse, ref.run(FLOWS, log, 32, BATCH, {("nexmark", 0): 500}))


# -- the kernels ------------------------------------------------------------------------

@pytest.fixture
def interpret():
    kernel_ops.set_backend("interpret")
    yield
    kernel_ops.set_backend(None)


@pytest.mark.parametrize("n", [128, 300, 2048])
def test_window_kernels_in_interpret_mode_equal_the_jnp_path(n, interpret):
    rng = np.random.default_rng(n)
    keys = rng.integers(-3, 260, n).astype(np.int32)
    keys[: n // 2] = 17  # half the rows on one key, as NEXmark's hot auction
    np.testing.assert_array_equal(kw.pane_counts(jnp.asarray(keys), 256),
                                  kw.pane_counts_ref(jnp.asarray(keys), 256))


def test_ties_ascend_and_stop_at_the_capacity():
    mask = np.zeros(500, bool)
    mask[[3, 9, 40, 41, 499]] = True
    pos, n = kw.first_positions(jnp.asarray(mask), 4)
    assert list(np.asarray(pos)) == [3, 9, 40, 41] and int(n) == 5
    pos, n = kw.first_positions(jnp.asarray(mask), 8)
    assert list(np.asarray(pos))[:5] == [3, 9, 40, 41, 499] and int(n) == 5


def test_highest_bid_emits_every_tied_bid_in_bid_order():
    """Three bids share the top price across two batches of one window."""
    op = make_operator("nx_highest_bid", {"rate": 10, "size": 40})  # 400 events
    x = np.zeros((2, BATCH, 8), np.float32)
    for b in range(2):
        e = b * BATCH + np.arange(BATCH)
        x[b, :, 0], x[b, :, 1], x[b, :, 6], x[b, :, 7] = e // 10_000, e % 10_000, 1, 2
        x[b, :, 3] = 1000 + e % 7
        x[b, :, 5] = e % 50
    x[0, [20, 90], 4] = 3  # price 30000 + (e % 50)
    x[0, 20, 5] = x[0, 90, 5] = 5
    x[1, 7, 4], x[1, 7, 5] = 3, 5
    state = op.init_state(BATCH)
    for b in range(2):
        state, _ = op.apply(state, jnp.asarray(x[b]))
    closing = np.zeros((BATCH, 8), np.float32)
    closing[0] = [0, 400, 0, 1000, 0, 0, 1, 2]
    _, y = op.apply(state, jnp.asarray(closing))
    y = np.asarray(y)
    np.testing.assert_array_equal(y[:3, 1], [20, 90, BATCH + 7])
    np.testing.assert_array_equal(y[:3, 4:8], [[3, 5, 1, 3]] * 3)
    assert not y[3:].any()


def test_nexmark_source_is_routed_to_the_generator():
    task = _build("q1").tasks
    (src,) = [t for t in task.values() if t.is_source]
    op = operator_for_task(src, batch=BATCH)
    assert op.is_source and not op.window
    assert make_operator("nx_hot_items", {"rate": RATE}).window


def test_a_pane_shorter_than_the_batch_is_refused():
    op = make_operator("nx_hot_items", {"rate": 10})  # 50-event panes
    with pytest.raises(ValueError, match="shorter than the batch"):
        op.apply(op.init_state(BATCH), jnp.zeros((BATCH, 8)))


# -- the counters --------------------------------------------------------------------------

def _metric(snapshot, name, **labels):
    entry = snapshot.get(name)
    if entry is None:
        return None
    return sum(v for lab, v in entry["values"] if all(lab.get(k) == w for k, w in labels.items()))


def _bids(steps):
    """Bids among the first ``steps`` batches of a source started at 0."""
    return sum(e % nexmark.EPOCH >= nexmark.PERSONS + nexmark.AUCTIONS
               for e in range(steps * BATCH))


def test_window_counters_count_rows_and_free_state_at_kill():
    session = ReuseSession(strategy="signature", execute=True, base_batch=BATCH)
    try:
        session.submit(_build("q1"))
        session.submit(_build("q5"))
        session.submit(_build("q7"))
        slots = nexmark.table_size(RATE * 5)
        q5_bytes = 4 * (2 * slots + 2)
        q7_bytes = 4 * (4 + 2 * nexmark.CAPACITY)
        snap = session.metrics_snapshot()
        assert _metric(snap, "repro_ops_window_state_bytes", type="nx_hot_items") == q5_bytes
        assert _metric(snap, "repro_ops_window_state_bytes", type="nx_highest_bid") == q7_bytes
        for _ in range(3):
            session.step()
        snap = session.metrics_snapshot()
        # the valid rows, the bids: nx_bids zeroes the persons and auctions
        assert _metric(snap, "repro_ops_window_rows_total", type="nx_hot_items") == _bids(3)
        assert _metric(snap, "repro_ops_window_rows_total", type="nx_highest_bid") == _bids(3)
        session.remove("q5")  # the Q5 task is paused: its state stays deployed
        session.step()
        snap = session.metrics_snapshot()
        assert _metric(snap, "repro_ops_window_rows_total", type="nx_hot_items") == _bids(3)
        assert _metric(snap, "repro_ops_window_state_bytes") == q5_bytes + q7_bytes
        session.defragment()  # kills the paused residue; Q7 carries its state over
        session.step()
        snap = session.metrics_snapshot()
        assert _metric(snap, "repro_ops_window_state_bytes", type="nx_hot_items") == 0
        assert _metric(snap, "repro_ops_window_state_bytes") == q7_bytes
        assert _metric(snap, "repro_ops_window_rows_total", type="nx_hot_items") == _bids(3)
        assert _metric(snap, "repro_ops_window_rows_total", type="nx_highest_bid") == _bids(5)
    finally:
        session.close()
