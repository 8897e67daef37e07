"""Fusion/locality optimizer (PR 9): wave-aware planner scoring,
cross-worker fusion via member migration, the compiled-segment reuse
cache, and the multi-op fused pallas kernels.

  * planner — score_fusion_plan accept/reject model (critical path vs
    slot-load consolidation), fusion_report surfacing, plan_fusion
    hardening against killed segments (merge→fuse→unmerge→fuse cycles);
  * cache — structural signatures, hit/miss/evict counters through
    session.stats(), invalidation on config change, per-backend caches
    (transport change, restore on a fresh backend), digest identity of
    cache-hit segments;
  * cross-worker fusion — members spread over 4 workers are migrated to
    one slot, fused, and sink digests stay bit-identical to unfused in
    both step modes; sync-mode chain batching digest identity;
  * kernels — fused affine→rmsnorm / map-chain ops are bit-identical to
    the op-by-op ref path and allclose in pallas interpret mode.
"""
from __future__ import annotations

import numpy as np
import pytest

from helpers import chain_df, fig1


# -- structural signatures ------------------------------------------------------


def _spec(name, tids, parents, batch=8, fused=False, publish=()):
    from repro.runtime.backend import SegmentSpec

    return SegmentSpec(
        name=name,
        dag_name="d",
        task_ids=list(tids),
        parents={t: list(parents.get(t, [])) for t in tids},
        publish=set(publish),
        batch_of={t: batch for t in tids},
        fused=fused,
    )


def _df(tasks):
    from repro.core.graph import Dataflow, Task

    df = Dataflow("d")
    for tid, typ, cfg in tasks:
        df.add_task(Task.make(tid, typ, cfg))
    return df


class TestStructuralSignature:
    def sig(self, tids, parents, cfgs, **kw):
        from repro.runtime.compile_cache import structural_signature

        df = _df([(t, typ, cfg) for t, (typ, cfg) in zip(tids, cfgs.values())])
        return structural_signature(_spec("s", tids, parents, **kw), df)

    def test_names_and_topics_are_erased(self):
        cfgs_a = {"a.k": ("kalman", {"q": 0.1}), "a.s": ("store", "SINK")}
        cfgs_b = {"b.k2": ("kalman", {"q": 0.1}), "b.s9": ("store", "SINK")}
        sa = self.sig(["a.k", "a.s"], {"a.k": ["up.x"], "a.s": ["a.k"]}, cfgs_a)
        sb = self.sig(["b.k2", "b.s9"], {"b.k2": ["up.y"], "b.s9": ["b.k2"]}, cfgs_b)
        assert sa == sb  # different task ids AND different boundary parent

    def test_config_change_invalidates(self):
        base = {"t": ("kalman", {"q": 0.1})}
        changed = {"t": ("kalman", {"q": 0.2})}
        assert self.sig(["t"], {"t": ["x"]}, base) != self.sig(
            ["t"], {"t": ["x"]}, changed
        )

    def test_batch_fused_and_wiring_matter(self):
        cfgs = {"t": ("kalman", {"q": 0.1}), "u": ("win", {"w": 4})}
        p_chain = {"t": ["x"], "u": ["t"]}
        p_split = {"t": ["x"], "u": ["x"]}
        s = self.sig(["t", "u"], p_chain, cfgs)
        assert s != self.sig(["t", "u"], p_split, cfgs)
        assert s != self.sig(["t", "u"], p_chain, cfgs, batch=16)
        assert s != self.sig(["t", "u"], p_chain, cfgs, fused=True)

    def test_publish_is_not_part_of_the_key(self):
        cfgs = {"t": ("kalman", {"q": 0.1})}
        assert self.sig(["t"], {"t": ["x"]}, cfgs) == self.sig(
            ["t"], {"t": ["x"]}, cfgs, publish=("t",)
        )


# -- compile cache --------------------------------------------------------------


def _linear(name, stages):
    return chain_df(name, "urban", stages)


STAGES = [("senml_parse", {"scale": 2.0, "offset": 0.5}), ("kalman", {"q": 0.1})]


class TestCompileCache:
    def test_identical_resubmissions_hit(self):
        from repro.runtime.system import StreamSystem

        system = StreamSystem(strategy="none", backend="inprocess")
        for i in range(3):  # Default strategy: each copy deploys its own segment
            system.submit(_linear(f"c{i}", STAGES))
        system.run(2)
        stats = system.backend.compile_cache_stats()
        assert stats["misses"] == 1 and stats["hits"] == 2
        assert stats["entries"] == 1
        # cache-hit segments step through the shared executable with
        # renamed keys — outputs must be identical across the copies
        d = [system.sink_digests(f"c{i}") for i in range(3)]
        assert list(d[0].values()) == list(d[1].values()) == list(d[2].values())
        system.close()

    def test_segment_program_names_its_structure_and_task_types(self):
        """A riot segment's program is ``jit_segment_<signature>`` and each
        task's ops sit under a named scope of its task type."""
        import re
        from types import SimpleNamespace

        from repro.runtime.compile_cache import program_name, structural_signature
        from repro.runtime.system import StreamSystem
        from repro.workloads.riot import riot_workload

        df = riot_workload()[0]
        system = StreamSystem(strategy="signature", backend="inprocess", base_batch=16)
        system.submit(df)
        be = system.backend
        (seg,) = be.segments.values()
        name = program_name(structural_signature(seg.spec, SimpleNamespace(tasks=be.task_defs)))
        lowered = seg.step_fn.lower(seg.states, seg.active, be._fetch_inputs(seg))
        text = lowered.as_text(debug_info=True)
        scopes = set(re.findall(rf"jit\({name}\)/(\w+)/", text))
        assert scopes == {t.type for t in df.tasks.values()}
        assert f"jit_{name}" in lowered.as_text()
        system.close()

    def test_config_change_misses(self):
        from repro.runtime.system import StreamSystem

        system = StreamSystem(strategy="none", backend="inprocess")
        system.submit(_linear("a", STAGES))
        system.submit(_linear("b", [("senml_parse", {"scale": 3.0}), ("kalman", {"q": 0.1})]))
        system.step()
        stats = system.backend.compile_cache_stats()
        assert stats["misses"] == 2 and stats["hits"] == 0
        system.close()

    def test_caches_are_per_backend(self):
        # the key is structural, but executables never leak across
        # backends/transports — a fresh backend starts cold
        from repro.runtime.system import StreamSystem

        for transport in ("inproc", "shm"):
            system = StreamSystem(
                strategy="none", backend="inprocess", transport=transport
            )
            system.submit(_linear("a", STAGES))
            system.step()
            stats = system.backend.compile_cache_stats()
            assert stats["hits"] == 0 and stats["misses"] == 1
            system.close()

    def test_restore_compiles_on_the_fresh_backend_then_hits(self, tmp_path):
        from repro.runtime.system import StreamSystem

        system = StreamSystem(
            strategy="none", backend="inprocess", checkpoint_dir=str(tmp_path)
        )
        system.submit(_linear("a", STAGES))
        system.run(3)
        ref = system.sink_digests("a")
        system.checkpoint()
        system.close()

        restored = StreamSystem.restore(str(tmp_path))
        stats = restored.backend.compile_cache_stats()
        assert stats["hits"] == 0 and stats["misses"] >= 1  # cold cache
        assert restored.sink_digests("a") == ref
        restored.submit(_linear("b", STAGES))  # same structure — warm now
        restored.step()
        assert restored.backend.compile_cache_stats()["hits"] >= 1
        restored.close()

    def test_lru_eviction_counter(self):
        from repro.runtime.compile_cache import CompileCache
        from repro.runtime.segment import build_segment

        cache = CompileCache(capacity=1)
        for q in (0.1, 0.2, 0.3):
            df = _df([("t", "kalman", {"q": q})])
            spec = _spec("s", ["t"], {"t": ["x"]})
            build_segment(spec, df, cache=cache)
        stats = cache.stats()
        assert stats == {"hits": 0, "misses": 3, "evictions": 2, "entries": 1}

    def test_session_stats_surface(self):
        from repro.api import ReuseSession

        session = ReuseSession(strategy="none", execute=True, backend="inprocess")
        session.submit(_linear("a", STAGES))
        session.submit(_linear("b", STAGES))
        session.step()
        st = session.stats()
        assert st.compile_cache_misses == 1
        assert st.compile_cache_hits == 1
        assert st.compile_cache_entries == 1
        assert st.compile_cache_evictions == 0
        session.close()

    def test_control_plane_session_reports_zeros(self):
        from repro.api import ReuseSession

        st = ReuseSession(strategy="signature").stats()
        assert st.compile_cache_hits == st.compile_cache_misses == 0


# -- wave-aware planner scoring -------------------------------------------------


def _chain_plan(*chains):
    from repro.core.defrag import FusionChain, FusionPlan

    return FusionPlan(chains=[FusionChain(dag_name="d", members=list(c)) for c in chains])


class TestFusionPlannerScoring:
    def test_single_slot_always_accepts(self):
        from repro.core.defrag import score_fusion_plan

        deps = {"a": set(), "b": {"a"}, "c": {"b"}}
        report = score_fusion_plan(
            _chain_plan(["a", "b", "c"]), deps, {"a": 5.0, "b": 5.0, "c": 5.0},
            slot_of=None, n_slots=1,
        )
        (d,) = report.decisions
        assert d.accepted and d.est_penalty_ms == pytest.approx(0.0)
        assert report.accepted and not report.rejected

    def test_deep_chain_across_workers_accepted(self):
        # a 12-deep serial chain spread over 4 slots: the critical path IS
        # the whole chain, so consolidating onto one slot can't stretch
        # the makespan — fuse it
        from repro.core.defrag import score_fusion_plan

        members = [f"s{i}" for i in range(12)]
        deps = {m: ({members[i - 1]} if i else set()) for i, m in enumerate(members)}
        report = score_fusion_plan(
            _chain_plan(members), deps, {m: 1.0 for m in members},
            slot_of={m: i % 4 for i, m in enumerate(members)}, n_slots=4,
        )
        (d,) = report.decisions
        assert d.accepted
        assert d.est_penalty_ms == pytest.approx(0.0)

    def test_wide_wave_consolidation_rejected(self):
        # 4 independent 2-deep chains, one per slot-pair, on a balanced
        # 4-slot pool: every fusion targets the same cheapest slot and
        # would pile work there — makespan stretch >> dispatch saving
        from repro.core.defrag import score_fusion_plan

        deps, slot_of, chains = {}, {}, []
        for c in range(4):
            a, b = f"a{c}", f"b{c}"
            deps[a], deps[b] = set(), {a}
            slot_of[a], slot_of[b] = c, (c + 1) % 4
            chains.append([a, b])
        report = score_fusion_plan(
            _chain_plan(*chains), deps, {n: 10.0 for n in deps},
            slot_of=slot_of, n_slots=4, overhead_ms=0.25,
        )
        rejected = report.rejected
        assert rejected  # at least the later chains must be refused
        assert all("wide" in d.reason for d in rejected)
        assert all(d.est_penalty_ms > d.est_benefit_ms for d in rejected)

    def test_accepted_chains_update_the_load_picture(self):
        # two chains on an empty 2-slot pool: both would pick slot 0 in
        # isolation; greedy accounting must spread them
        from repro.core.defrag import score_fusion_plan

        deps = {"a": set(), "b": {"a"}, "c": set(), "d": {"c"}}
        report = score_fusion_plan(
            _chain_plan(["a", "b"], ["c", "d"]), deps,
            {n: 1.0 for n in deps},
            slot_of={"a": 0, "b": 1, "c": 0, "d": 1}, n_slots=2,
            overhead_ms=10.0,  # make both worth fusing
        )
        assert [d.accepted for d in report.decisions] == [True, True]
        assert report.decisions[0].target_slot != report.decisions[1].target_slot

    def test_report_to_dict_explains_every_verdict(self):
        from repro.core.defrag import score_fusion_plan

        deps = {"a": set(), "b": {"a"}}
        report = score_fusion_plan(_chain_plan(["a", "b"]), deps, {"a": 1.0, "b": 1.0})
        out = report.to_dict()
        assert set(out) == {"accepted", "rejected"}
        assert out["accepted"][0]["members"] == ["a", "b"]
        assert out["accepted"][0]["reason"]


# -- plan_fusion hardening (satellite: killed segments / idempotency) ----------


class TestPlanFusionHardening:
    def test_killed_segments_never_proposed(self):
        from repro.core.defrag import plan_fusion

        # seg_deps still holds a stale edge onto killed segment "dead",
        # and "ghost" appears in deps but was killed from dag_of
        seg_deps = {"a": set(), "b": {"a"}, "c": {"b", "dead"}, "ghost": {"c"}}
        dag_of = {"a": "d", "b": "d", "c": "d"}
        plan = plan_fusion(seg_deps, dag_of)
        for chain in plan.chains:
            assert "dead" not in chain.members
            assert "ghost" not in chain.members

    def test_merge_fuse_unmerge_fuse_cycle(self):
        from repro.runtime.system import StreamSystem

        dags = {d.name: d for d in fig1()}
        system = StreamSystem(strategy="signature", backend="inprocess")
        system.submit(dags["A"].copy())
        system.submit(dags["B"].copy())  # merges onto A's chain
        system.run(2)
        first = system.fuse()
        assert first  # B's suffix fused
        system.run(1)
        system.remove("B")  # unmerge — pauses B-only tasks
        system.step()
        # the re-run must be safe and never reference killed members
        second = system.fuse()
        alive = set(system.backend.segments)
        for members in second.values():
            assert set(members) <= alive | set(second)
        assert system.fuse() == {}  # idempotent once nothing linear remains
        system.close()

    def test_fuse_after_defragment(self):
        from repro.runtime.system import StreamSystem

        dags = {d.name: d for d in fig1()}
        system = StreamSystem(strategy="signature", backend="inprocess")
        system.submit(dags["A"].copy())
        system.submit(dags["C"].copy())
        system.run(2)
        system.fuse()
        system.remove("A")
        system.defragment()  # kills everything, relaunches fused-per-DAG
        system.step()
        system.fuse()  # must not touch killed segment names
        ref = system.sink_digests("C")
        system.run(2)
        sink = "C.sink.store_c"
        assert system.sink_digests("C")[sink]["count"] > ref[sink]["count"]
        system.close()


# -- cross-worker fusion + sync chains (multiproc) ------------------------------


def _stacked(depth):
    dags = []
    for k in range(1, depth + 1):
        stages = [("kalman", {"q": 0.1, "stage": i}) for i in range(k)]
        dags.append(chain_df(f"deep{k:02d}", "urban", stages))
    return dags


def _run_stacked(step_mode, fuse, chain_batching=True, workers=4, depth=4):
    from repro.runtime.system import StreamSystem

    system = StreamSystem(
        strategy="signature", backend="multiproc", workers=workers,
        transport="shm", step_mode=step_mode,
        backend_options={"chain_batching": chain_batching},
    )
    for df in _stacked(depth):
        system.submit(df.copy())
    system.run(2)
    spread = set(system.backend.device_of.values())
    if fuse:
        fused = system.fuse()
        assert fused, "the stacked chain must fuse"
        assert len(spread) > 1, "members should start spread across workers"
        # all members were consolidated: the fused segment occupies ONE slot
        assert len(set(system.backend.device_of.values())) == 1
        assert system.fusion_report is not None and system.fusion_report.accepted
    system.run(3)
    digests = {n: system.sink_digests(n) for n in sorted(system.manager.submitted)}
    system.close()
    return digests


@pytest.mark.slow
class TestCrossWorkerFusion:
    @pytest.mark.parametrize("step_mode", ["sync", "concurrent"])
    def test_fused_identical_to_unfused_across_workers(self, step_mode):
        ref = _run_stacked(step_mode, fuse=False)
        got = _run_stacked(step_mode, fuse=True)
        assert got == ref  # migration + recompile is bit-exact

    def test_worker_cache_counters_aggregate(self):
        from repro.runtime.system import StreamSystem

        system = StreamSystem(
            strategy="none", backend="multiproc", workers=2, transport="shm",
        )
        system.submit(_linear("a", STAGES))
        system.submit(_linear("b", STAGES))  # may land on either worker
        system.step()
        stats = system.backend.compile_cache_stats()
        assert stats["misses"] + stats["hits"] == 2
        assert stats["misses"] >= 1
        system.close()


@pytest.mark.slow
class TestSyncChainBatching:
    def test_sync_chains_on_off_digests_identical(self):
        ref = _run_stacked("sync", fuse=False, chain_batching=False)
        got = _run_stacked("sync", fuse=False, chain_batching=True)
        assert got == ref

    def test_sync_uses_chains_when_enabled(self):
        from repro.runtime.system import StreamSystem

        system = StreamSystem(
            strategy="signature", backend="multiproc", workers=1,
            step_mode="sync",
        )
        assert system.backend._use_chains()
        for df in _stacked(3):
            system.submit(df.copy())
        system.run(2)  # exercises the chain-batched sync sweep
        assert system.backend.step_count == 2
        # worker-measured chain timings must keep feeding the placement
        # EWMAs (straggler detection relies on them in the batched path)
        assert any(v > 0 for v in system.backend.device_ewma().values())
        system.close()


# -- fused multi-op kernels -----------------------------------------------------


class TestFusedKernels:
    def test_ref_composition_is_bit_identical(self):
        import jax.numpy as jnp

        from repro.kernels import ops as kernel_ops

        x = jnp.asarray(
            np.random.default_rng(3).standard_normal((17, 5)), dtype=jnp.float32
        )
        stages = ((2.0, 0.5), (0.7, -0.1))
        scale = jnp.full((5,), 1.5, dtype=jnp.float32)
        # op-by-op, exactly as the unfused operators compute
        y = x
        for s, o in stages:
            y = y * s + o
        want_map = y
        want_norm = kernel_ops.rmsnorm(y, scale, eps=1e-6)
        got_map = kernel_ops.map_chain(x, stages=stages)
        got_norm = kernel_ops.affine_rmsnorm(x, scale, stages=stages, eps=1e-6)
        assert np.array_equal(np.asarray(got_map), np.asarray(want_map))
        assert np.array_equal(np.asarray(got_norm), np.asarray(want_norm))

    def test_interpret_mode_matches_ref(self):
        import jax.numpy as jnp

        from repro.kernels import ops as kernel_ops

        x = jnp.asarray(
            np.random.default_rng(5).standard_normal((33, 8)), dtype=jnp.float32
        )
        stages = ((1.3, 0.2),)
        scale = jnp.ones((8,), dtype=jnp.float32)
        kernel_ops.set_backend("interpret")
        try:
            got_map = kernel_ops.map_chain(x, stages=stages)
            got_norm = kernel_ops.affine_rmsnorm(x, scale, stages=stages)
        finally:
            kernel_ops.set_backend(None)
        np.testing.assert_allclose(
            np.asarray(got_map), np.asarray(x * 1.3 + 0.2), rtol=1e-5, atol=1e-6
        )
        from repro.kernels.ref import affine_rmsnorm_ref

        np.testing.assert_allclose(
            np.asarray(got_norm),
            np.asarray(affine_rmsnorm_ref(x, scale, stages)),
            rtol=1e-5,
            atol=1e-6,
        )

    def test_make_fused_operator_matches_op_sequence(self):
        import jax.numpy as jnp

        from repro.core.graph import Task
        from repro.ops import operator_for_task
        from repro.ops.riot import make_fused_operator

        chain = [
            Task.make("p1", "senml_parse", {"scale": 2.0, "offset": 0.5}),
            Task.make("p2", "senml_parse", {"scale": 0.7, "offset": -0.1}),
            Task.make("n", "rmsnorm", {"gain": 1.5}),
        ]
        fused = make_fused_operator(chain, batch=9)
        assert fused is not None
        assert fused.cost_weight == operator_for_task(chain[-1], batch=9).cost_weight
        x = jnp.asarray(
            np.random.default_rng(7).standard_normal((9, 8)), dtype=jnp.float32
        )
        y = x
        st_unused = fused.init_state(9)
        for t in chain:
            op = operator_for_task(t, batch=9)
            _, y = op.apply(op.init_state(9), y)
        _, got = fused.apply(st_unused, x)
        assert np.array_equal(np.asarray(got), np.asarray(y))

    def test_make_fused_operator_declines_unknown_runs(self):
        from repro.core.graph import Task
        from repro.ops.riot import make_fused_operator

        k = Task.make("k", "kalman", {"q": 0.1})
        n = Task.make("n", "rmsnorm", {})
        assert make_fused_operator([k, n], batch=4) is None
        assert make_fused_operator([n], batch=4) is None

    def test_peephole_rewires_the_tail(self):
        from repro.ops import operator_for_task
        from repro.runtime.segment import _peephole_fused_kernels

        tasks = [
            ("s", "urban", "SOURCE"),
            ("p1", "senml_parse", {"scale": 2.0}),
            ("p2", "senml_parse", {"scale": 0.5}),
            ("n", "rmsnorm", {}),
            ("k", "store", "SINK"),
        ]
        df = _df(tasks)
        spec = _spec(
            "s0", [t for t, _, _ in tasks],
            {"p1": ["s"], "p2": ["p1"], "n": ["p2"], "k": ["n"]},
            fused=True,
        )
        operators = {
            t: operator_for_task(df.tasks[t], batch=spec.batch_of[t])
            for t in spec.task_ids
        }
        parents = {t: list(spec.parents[t]) for t in spec.task_ids}
        _peephole_fused_kernels(spec, df, operators, parents)
        assert parents["n"] == ["s"]  # tail consumes the run head's input
        assert parents["p1"] == ["s"] and parents["p2"] == ["p1"]  # interiors keep
        assert spec.parents["n"] == ["p2"]  # spec untouched

    def test_peephole_skipped_for_unfused_specs(self):
        from repro.ops import operator_for_task
        from repro.runtime.segment import _peephole_fused_kernels

        tasks = [("p1", "senml_parse", {"scale": 2.0}), ("n", "rmsnorm", {})]
        df = _df(tasks)
        spec = _spec("s0", ["p1", "n"], {"p1": ["x"], "n": ["p1"]}, fused=False)
        operators = {
            t: operator_for_task(df.tasks[t], batch=8) for t in spec.task_ids
        }
        parents = {t: list(spec.parents[t]) for t in spec.task_ids}
        _peephole_fused_kernels(spec, df, operators, parents)
        assert parents["n"] == ["p1"]


class TestFusedKernelDigestIdentity:
    """Session-level: a fused chain whose tail dispatches the multi-op
    pallas path must keep sink digests bit-identical to unfused."""

    def _run(self, fuse):
        from repro.runtime.system import StreamSystem

        stages = [
            ("senml_parse", {"scale": 2.0, "offset": 0.5}),
            ("senml_parse", {"scale": 0.7, "offset": -0.1}),
            ("rmsnorm", {"gain": 1.5}),
            ("kalman", {"q": 0.1}),
        ]
        A = chain_df("FA", "urban", stages[:2])
        B = chain_df("FB", "urban", stages)
        system = StreamSystem(strategy="signature", backend="inprocess")
        system.submit(A.copy())
        system.submit(B.copy())
        system.run(2)
        if fuse:
            assert system.fuse()
        system.run(4)
        out = {n: system.sink_digests(n) for n in ("FA", "FB")}
        system.close()
        return out

    def test_fused_equals_unfused(self):
        assert self._run(True) == self._run(False)


# -- persistent compilation cache ------------------------------------------------


@pytest.fixture
def jax_cache_dir_config():
    """Restores JAX's compilation-cache directory setting after the test."""
    import jax

    saved = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", saved)


class TestPersistentCachePath:
    def test_env_directory_wins(self, tmp_path):
        import os
        import subprocess
        import sys

        src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path), PYTHONPATH=src)
        out = subprocess.run(
            [sys.executable, "-c", "from repro.runtime.compile_cache import "
             "persistent_cache_dir; print(persistent_cache_dir())"],
            env=env, capture_output=True, text=True, check=True,
        )
        assert out.stdout.strip() == str(tmp_path)

    def test_config_directory_wins(self, jax_cache_dir_config, tmp_path):
        from repro.runtime.compile_cache import persistent_cache_dir

        jax_cache_dir_config.update("jax_compilation_cache_dir", str(tmp_path))
        assert persistent_cache_dir() == str(tmp_path)

    def test_default_is_fixed_inside_the_checkout(self, jax_cache_dir_config):
        import os

        from repro.runtime.compile_cache import persistent_cache_dir

        jax_cache_dir_config.update("jax_compilation_cache_dir", None)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert persistent_cache_dir() == os.path.join(root, ".jax_cache")

    def test_enable_points_jax_at_the_directory(self, jax_cache_dir_config, tmp_path):
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        from repro.runtime.compile_cache import enable_persistent_cache

        assert enable_persistent_cache() is None  # the suite runs with it off
        keys = ("jax_enable_compilation_cache",
                "jax_persistent_cache_min_compile_time_secs")
        saved = {k: getattr(jax.config, k) for k in keys}
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        try:
            jax.config.update("jax_enable_compilation_cache", True)
            assert enable_persistent_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()
