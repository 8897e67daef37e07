"""Distribution features that need >1 device: run in fresh subprocesses
with XLA_FLAGS device-count overrides (the pytest process keeps 1 device)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every test here spawns a fresh interpreter with a multi-device XLA config —
# seconds each; excluded from the fast sweep (-m "not slow").
pytestmark = pytest.mark.slow


def _run(code: str, devices: int = 4, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=ROOT,
    )


def test_sharded_cross_chip_counters_match_placement():
    """On four devices the sharded backend counts one cross-chip fetch per
    boundary topic whose producer sits on another device than its
    consumer, and the bytes of those batches, once per step."""
    code = """
import sys
sys.path.insert(0, "tests")
from helpers import chain_df, fig1
from repro.runtime.broker import topic_for
from repro.runtime.system import StreamSystem

s = StreamSystem(strategy="signature", backend="sharded", base_batch=16, step_mode="sync")
# a fifth segment, round-robin back on the first device, reads A's kalman
# there: one boundary fetch that crosses no chip
E = chain_df("E", "urban", [("parse", {}), ("kalman", {"q": 0.1}), ("avg", {})], "store_e")
for df in (*fig1(), E):
    s.submit(df.copy())
be, steps = s.backend, 3
s.run(steps)
hops = local = nbytes = 0
batches = be.transport.topics()
for name, seg in be.segments.items():
    inside = set(seg.spec.task_ids)
    for p in {p for t in seg.spec.task_ids for p in seg.spec.parents[t] if p not in inside}:
        if be.device_of[be._owner(p)] != be.device_of[name]:
            hops += 1
            nbytes += batches[topic_for(p)].nbytes
        else:
            local += 1
assert hops > 0 and local > 0, (be.device_of, hops, local)
snap = s.metrics_snapshot()
fetches = sum(v for _, v in snap["repro_transport_cross_chip_fetches_total"]["values"])
moved = sum(v for _, v in snap["repro_transport_cross_chip_bytes_total"]["values"])
assert fetches == steps * hops, (fetches, steps, hops)
assert moved == steps * nbytes, (moved, steps, nbytes)
print("OK", hops, nbytes)
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_moe_ep_matches_dense_dispatch():
    """Expert-parallel shard_map MoE ≡ GSPMD scatter dispatch (no drops)."""
    code = """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.models import mlp as M
from repro.models import sharding as shd
from repro.models.common import KeyGen

cfg = configs.get_smoke_config("deepseek-v2-236b")
cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
mesh = jax.make_mesh((2, 2), ("data", "model"))
rules = shd.AxisRules({"data": 2, "model": 2}); rules.mesh = mesh
p = M.moe_params(KeyGen(jax.random.PRNGKey(0)), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
y_dense = M.moe_layer(p, x, cfg)
with mesh:
    M.MOE_IMPL = "ep"
    with shd.use_rules(rules):
        y_ep = jax.jit(lambda p, x: M.moe_layer(p, x, cfg))(p, x)
np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_ep), rtol=2e-4, atol=2e-4)
print("OK")
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dryrun_cell_compiles_multipod():
    """One real dry-run cell on the 512-device multi-pod mesh."""
    r = _run(
        "import repro.launch.dryrun as d; import sys; "
        "sys.exit(d.main(['--arch','seamless-m4t-medium','--shape','train_4k','--multi-pod']))",
        devices=1,  # dryrun sets its own XLA_FLAGS before jax import
        timeout=1800,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert '"status": "ok"' in r.stdout


def test_sharded_train_step_on_mesh():
    """A reduced train step jits with real in_shardings on a 2×2 mesh and
    the loss matches the unsharded step."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import sharding as shd
from repro.train import AdamWConfig, make_train_step, train_state_init

cfg = configs.get_smoke_config("qwen3-4b")
opt = AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=4)
step = make_train_step(cfg, opt, accum=2)
state = train_state_init(cfg, opt, jax.random.PRNGKey(0))
batch = {
  "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size),
  "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab_size),
}
_, m_ref = jax.jit(step)(jax.tree.map(lambda x: x, state), batch)

from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2, 2), ("data", "model"))
rules = shd.AxisRules({"data": 2, "model": 2}); rules.mesh = mesh
pspecs = shd.infer_param_specs(state["params"], rules)
sspecs = {"step": P(), "params": pspecs, "mu": pspecs, "nu": pspecs}
bspecs = {"tokens": P("data", None), "labels": P("data", None)}
ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
with mesh:
    with shd.use_rules(rules):
        sharded = jax.jit(step, in_shardings=(ns(sspecs), ns(bspecs)))
        state2, m = sharded(state, batch)
np.testing.assert_allclose(float(m["loss"]), float(m_ref["loss"]), rtol=1e-4)
print("OK", float(m["loss"]))
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_pipeline_parallel_decode_runs():
    """PP decode (shard_map manual-data/auto-model) compiles and runs a
    steady-state round on a 2×2 mesh; logits finite, cache len advances."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch import specs as S
from repro.models import decode as dec
from repro.models import init_params, init_cache

cfg = configs.get_smoke_config("granite-20b")  # 2 layers % 2 stages == 0
mesh = jax.make_mesh((2, 2), ("data", "model"))
rules = S.make_rules(mesh); rules.mesh = mesh
params = init_params(cfg, jax.random.PRNGKey(0))
B = 4
cache = dict(init_cache(cfg, B, 32))
cache["len"] = jnp.asarray(8, jnp.int32)
cache["pp_h"] = jnp.zeros((B, 1, cfg.d_model), jnp.dtype(cfg.dtype))
tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab_size)
with mesh:
    logits, new_cache = jax.jit(
        lambda p, t, c: dec.decode_step_pp(p, cfg, t, c, rules)
    )(params, tokens, cache)
assert logits.shape == (B, cfg.padded_vocab), logits.shape
assert bool(jnp.isfinite(logits).all())
assert int(new_cache["len"]) == 9
assert new_cache["pp_h"].shape == (B, 1, cfg.d_model)
print("OK")
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
