"""Compile the stream path's kernels and segment programs for a described
TPU v5e chip, at the stream size the chip smoke runs (16384 events per
source per step), without an attached chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and test
workers import every test file. Compiles run with JAX's persistent cache
off, since a program compiled for a described chip cannot be read back
without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import ReuseSession, flow
from repro.kernels import ops as kernel_ops
from repro.kernels.fused import affine_rmsnorm, map_chain
from repro.kernels.rmsnorm import rmsnorm
from repro.workloads import riot_workload

BATCH = 16384
STAGES = ((2.0, 0.5), (0.7, -0.1))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def pallas_backend():
    """Segment ops pick their kernel path at trace time from the default
    backend, which is the CPU here: steer them to the Pallas kernels."""
    kernel_ops.set_backend("pallas")
    yield
    kernel_ops.set_backend(None)


def _on_chip(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a), sharding=sharding),
        tree,
    )


def _segment_text(seg, sharding) -> str:
    args = _on_chip((seg.states, seg.active, {}), sharding)
    assert not seg.boundary_topics  # the segments below hold their source
    return seg.step_fn.lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "kernel",
    [
        lambda x, s: rmsnorm(x, s),
        lambda x, s: map_chain(x, stages=STAGES),
        lambda x, s: affine_rmsnorm(x, s, stages=STAGES),
    ],
    ids=["rmsnorm", "map_chain", "affine_rmsnorm"],
)
def test_stream_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    x = jax.ShapeDtypeStruct((BATCH, 5), jnp.float32, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((5,), jnp.float32, sharding=one_chip)
    text = jax.jit(kernel).lower(x, scale).compile().as_text()
    assert "tpu_custom_call" in text


def test_merged_riot_etl_segment_compiles_for_v5e(one_chip, no_persistent_cache):
    etl, stats_avg = riot_workload()[:2]  # urban ETL + a flow sharing its prefix
    session = ReuseSession(strategy="signature", execute=True, base_batch=BATCH)
    try:
        session.submit(etl.copy())
        session.submit(stats_avg.copy())
        backend = session._system.backend
        assert len(backend.segments) == 2  # the second flow merged onto the first
        first = min(backend.segments.values(), key=lambda s: s.spec.created_at)
        types = {etl.tasks[t].type for t in etl.tasks}
        assert {"interpolate", "kalman"} <= types
        text = _segment_text(first, one_chip)
    finally:
        session.close()
    assert "while" not in text  # interpolate and kalman are parallel prefixes, no row loop


def test_fused_rmsnorm_segment_compiles_for_v5e(one_chip, no_persistent_cache,
                                               pallas_backend):
    prefix = flow("kp").source("urban")
    full = flow("kf").source("urban")
    for scale, offset in STAGES:
        prefix.then("senml_parse", scale=scale, offset=offset)
        full.then("senml_parse", scale=scale, offset=offset)
    session = ReuseSession(strategy="signature", execute=True, base_batch=BATCH)
    try:
        session.submit(prefix.sink("store").build())
        session.submit(full.then("rmsnorm", gain=1.5).sink("store").build())
        assert session.fuse()
        (seg,) = [s for s in session._system.backend.segments.values() if s.spec.fused]
        text = _segment_text(seg, one_chip)
    finally:
        session.close()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "steps,kernel",
    [
        ([("nx_bids", {}), ("nx_select", {"modulus": 123}), ("nx_hot_items", {})], "window_count"),
        ([("nx_bids", {}), ("nx_convert", {}), ("nx_highest_bid", {})], None),
    ],
    ids=["q5_over_q2", "q7_over_q1"],
)
def test_nexmark_segment_compiles_for_v5e(steps, kernel, one_chip, no_persistent_cache,
                                          pallas_backend):
    """A NEXmark dataflow's segment (generator, queries, keyed window, sink)
    at the deployment's rate: Q5's count kernel as a Pallas call, Q7 with
    no Pallas call (its maximum is XLA's reduce)."""
    df = flow("nx").source("nexmark")
    for typ, cfg in steps:
        df.then(typ, **cfg)
    session = ReuseSession(strategy="signature", execute=True, base_batch=BATCH)
    try:
        session.submit(df.sink("store").build())
        (seg,) = session._system.backend.segments.values()
        text = _segment_text(seg, one_chip)
    finally:
        session.close()
    if kernel is None:
        assert "tpu_custom_call" not in text
    else:
        assert "tpu_custom_call" in text and f"%{kernel}" in text
