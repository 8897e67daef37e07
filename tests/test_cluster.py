"""Elastic cluster plane tests: supervision, crash recovery, autoscaling.

Five layers:
  * pure policy units: :class:`AutoscalePolicy` hysteresis/patience/
    cooldown/bounds and its constructor validation;
  * the scheduler's self-healing seam: ``run_ready_queue(recover=...)``
    re-queues recovered items with bounded retries;
  * supervisor/autoscaler attach validation and the ``snapshot_mode``
    auto-resolution (spill for same-host launchers, wire otherwise);
  * crash recovery conformance: SIGKILL a worker mid-trace (fig-1 churn
    and an OPMW rw1 slice at a seeded-random step) under supervision —
    sink counts must be identical to an uninterrupted run, in both
    snapshot modes, on the dry and (slow tier) jit worker planes;
  * elasticity: ``resize_pool`` grow/shrink conformance, the autoscaler
    end to end, the subprocess launcher end to end, heartbeat detection
    of idle crashes, and the worker-health/event surfaces.

The CI cluster-resilience job re-runs this module with
``REPRO_TEST_STEP_MODE`` sync and concurrent; results must be
mode-invariant, and worker logs are uploaded as artifacts on failure.
"""
from __future__ import annotations

import os
import random
import signal
import time

import pytest

from repro.cluster import Autoscaler, AutoscalePolicy, WorkerSupervisor
from repro.cluster.events import (
    HEARTBEAT_MISSED,
    POOL_GROWN,
    POOL_SHRUNK,
    SEGMENT_REDEPLOYED,
    WORKER_RESPAWNED,
)
from repro.runtime.backend import resolve_backend
from repro.runtime.scheduler import run_ready_queue
from repro.runtime.system import StreamSystem
from repro.runtime.worker import MultiprocBackend

from helpers import chain_df, fig1

STEP_MODE = os.environ.get("REPRO_TEST_STEP_MODE") or "sync"
MAX_WORKERS = int(os.environ.get("REPRO_TEST_MAX_WORKERS", "4"))

FIG1_OPS = [
    ("add", "A"),
    ("add", "B"),
    ("add", "C"),
    ("add", "D"),
    ("remove", "B"),
    ("defrag", ""),
    ("remove", "A"),
    ("add", "B"),
]


def _apply(system, dags, op, name):
    if op == "add":
        system.submit(dags[name].copy())
    elif op == "remove":
        system.remove(name)
    else:
        system.defragment()


def _counts(system):
    return {
        name: {s: d["count"] for s, d in system.sink_digests(name).items()}
        for name in sorted(system.manager.submitted)
    }


def _digests(system):
    return {
        name: system.sink_digests(name) for name in sorted(system.manager.submitted)
    }


def _run_fig1(backend, ops=FIG1_OPS, step_mode=STEP_MODE, tail_steps=3,
              kill_at=None, victim=1, supervise=None):
    """Replay fig-1 churn; optionally SIGKILL worker ``victim`` just
    before stepping event ``kill_at``. Returns (digests, event kinds,
    respawn count)."""
    dags = {d.name: d for d in fig1()}
    system = StreamSystem(
        strategy="signature", backend=backend, step_mode=step_mode,
        max_workers=MAX_WORKERS,
    )
    sup = None
    if supervise is not None:
        sup = WorkerSupervisor(system.backend, **supervise).start()
    for i, (op, name) in enumerate(ops):
        _apply(system, dags, op, name)
        if kill_at is not None and i == kill_at:
            be = system.backend
            os.kill(be._procs[victim % be.n_workers].pid, signal.SIGKILL)
        system.step()
    for _ in range(tail_steps):
        system.step()
    digests = _digests(system)
    kinds = [e.kind for e in system.backend.worker_events]
    respawns = len(system.backend.respawns)
    if sup is not None:
        sup.stop()
    system.close()
    return digests, kinds, respawns


# -- policy units ----------------------------------------------------------------


class TestAutoscalePolicy:
    def _policy(self, **kw):
        kw.setdefault("min_workers", 1)
        kw.setdefault("max_workers", 4)
        kw.setdefault("high_ms", 10.0)
        kw.setdefault("low_ms", 1.0)
        kw.setdefault("patience", 3)
        kw.setdefault("cooldown", 0)
        return AutoscalePolicy(**kw)

    def test_grow_needs_patience_consecutive_highs(self):
        p = self._policy()
        assert p.decide(50.0, 1) == 1
        assert p.decide(50.0, 1) == 1
        assert p.decide(50.0, 1) == 2  # third consecutive high

    def test_shrink_needs_patience_consecutive_lows(self):
        p = self._policy()
        assert p.decide(0.1, 3) == 3
        assert p.decide(0.1, 3) == 3
        assert p.decide(0.1, 3) == 2

    def test_in_band_observation_resets_streaks(self):
        p = self._policy()
        p.decide(50.0, 1)
        p.decide(50.0, 1)
        assert p.decide(5.0, 1) == 1  # hysteresis band: streak wiped
        assert p.decide(50.0, 1) == 1
        assert p.decide(50.0, 1) == 1
        assert p.decide(50.0, 1) == 2  # needs a fresh run of `patience`

    def test_cooldown_suppresses_followup_action(self):
        p = self._policy(patience=1, cooldown=2)
        assert p.decide(50.0, 1) == 2
        assert p.decide(50.0, 2) == 2  # cooling
        assert p.decide(50.0, 2) == 2  # cooling
        assert p.decide(50.0, 2) == 3  # cooldown elapsed

    def test_bounds_are_hard(self):
        p = self._policy(patience=1, max_workers=2)
        assert p.decide(50.0, 2) == 2   # at max: no grow
        assert p.decide(0.1, 1) == 1    # at min: no shrink

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="min_workers"):
            AutoscalePolicy(min_workers=0)
        with pytest.raises(ValueError, match="max_workers"):
            AutoscalePolicy(min_workers=3, max_workers=2)
        with pytest.raises(ValueError, match="hysteresis"):
            AutoscalePolicy(low_ms=10.0, high_ms=10.0)


# -- scheduler self-healing seam -------------------------------------------------


class TestRunReadyQueueRecovery:
    def test_recovered_item_is_requeued_and_completes(self):
        deps = {"a": [], "b": ["a"]}
        calls = {"a": 0, "b": 0}

        def runner(n):
            calls[n] += 1
            if n == "a" and calls["a"] == 1:
                raise RuntimeError("boom")
            return 1.0

        healed = []
        out = run_ready_queue(deps, runner, 2,
                              recover=lambda n, e: healed.append(n) or True)
        assert out == {"a": 1.0, "b": 1.0}
        assert healed == ["a"]
        assert calls == {"a": 2, "b": 1}  # dependent ran exactly once, after

    def test_retries_are_bounded(self):
        calls = {"a": 0}

        def runner(n):
            calls[n] += 1
            raise RuntimeError("always broken")

        with pytest.raises(RuntimeError, match="always broken"):
            run_ready_queue({"a": []}, runner, 2,
                            recover=lambda n, e: True, max_retries=2)
        assert calls["a"] == 3  # initial + max_retries

    def test_declined_recovery_raises(self):
        def runner(n):
            raise RuntimeError("fatal")

        with pytest.raises(RuntimeError, match="fatal"):
            run_ready_queue({"a": []}, runner, 2, recover=lambda n, e: False)


# -- attach validation + snapshot-mode resolution --------------------------------


class TestAttach:
    def test_supervisor_rejects_non_pool_backend(self):
        with pytest.raises(ValueError, match="worker-pool backend"):
            WorkerSupervisor(resolve_backend("dryrun"))

    def test_supervisor_rejects_unknown_snapshot_mode(self):
        be = MultiprocBackend(workers=1, worker_plane="dry")
        try:
            with pytest.raises(ValueError, match="snapshot_mode"):
                WorkerSupervisor(be, snapshot_mode="telepathy")
        finally:
            be.close()

    def test_autoscaler_rejects_non_resizable_backend(self):
        with pytest.raises(ValueError, match="resizable worker pool"):
            Autoscaler(resolve_backend("dryrun"))

    def test_autoscaler_rejects_policy_plus_kwargs(self):
        be = MultiprocBackend(workers=1, worker_plane="dry")
        try:
            with pytest.raises(ValueError, match="not both"):
                Autoscaler(be, policy=AutoscalePolicy(), high_ms=9.0)
        finally:
            be.close()

    def test_auto_snapshot_mode_resolves_to_spill_on_local_launcher(self):
        be = MultiprocBackend(workers=1, worker_plane="dry")
        try:
            WorkerSupervisor(be)
            assert be.snapshot_mode == "spill"
            assert be.self_heal
            assert not be.shadow_states  # no per-step wire encodes
        finally:
            be.close()

    def test_wire_mode_arms_shadow_snapshots(self):
        be = MultiprocBackend(workers=1, worker_plane="dry")
        try:
            WorkerSupervisor(be, snapshot_mode="wire")
            assert be.snapshot_mode == "wire"
            assert be.shadow_states
        finally:
            be.close()


# -- crash recovery conformance --------------------------------------------------


class TestKillRecoveryConformance:
    @pytest.mark.parametrize("snapshot_mode", ["spill", "wire"])
    def test_fig1_counts_survive_mid_trace_kill(self, snapshot_mode):
        ref, _, _ = _run_fig1(MultiprocBackend(workers=2, worker_plane="dry"))
        got, kinds, respawns = _run_fig1(
            MultiprocBackend(workers=2, worker_plane="dry"),
            kill_at=4,
            supervise=dict(heartbeat_interval=5.0, snapshot_mode=snapshot_mode),
        )
        assert {n: {s: d["count"] for s, d in v.items()} for n, v in got.items()} == {
            n: {s: d["count"] for s, d in v.items()} for n, v in ref.items()
        }
        assert respawns >= 1
        assert WORKER_RESPAWNED in kinds
        assert SEGMENT_REDEPLOYED in kinds

    def test_opmw_rw1_slice_kill_at_seeded_random_step(self):
        """The PR acceptance shape: kill a worker at a randomized (seeded)
        trace step of the OPMW rw1 trace; sink counts must be identical to
        the uninterrupted run. The CI job replays this in both step modes."""
        from repro.workloads import opmw_workload, rw_trace

        dags = {d.name: d for d in opmw_workload()}
        events = [(ev.op, ev.name) for ev in rw_trace(dags.values(), seed=11)][:16]
        kill_at = random.Random(117).randrange(2, len(events) - 2)

        def run(kill):
            system = StreamSystem(
                strategy="signature",
                backend=MultiprocBackend(workers=2, worker_plane="dry"),
                step_mode=STEP_MODE, max_workers=MAX_WORKERS,
            )
            sup = WorkerSupervisor(system.backend, heartbeat_interval=5.0).start()
            for i, (op, name) in enumerate(events):
                _apply(system, dags, op, name)
                if kill and i == kill_at:
                    be = system.backend
                    os.kill(be._procs[1].pid, signal.SIGKILL)
                system.step()
            counts = _counts(system)
            respawns = len(system.backend.respawns)
            sup.stop()
            system.close()
            return counts, respawns

        ref, _ = run(kill=False)
        got, respawns = run(kill=True)
        assert got == ref
        assert respawns >= 1

    @pytest.mark.slow
    def test_jit_plane_kill_digests_identical_to_inprocess(self):
        """Counts AND checksums: the supervised jit worker plane recovers
        a SIGKILLed worker bit-identically to the in-process jit plane."""
        dags = {d.name: d for d in fig1()}
        system = StreamSystem(strategy="signature", backend="inprocess",
                              step_mode=STEP_MODE, max_workers=MAX_WORKERS)
        for op, name in FIG1_OPS:
            _apply(system, dags, op, name)
            system.step()
        for _ in range(3):
            system.step()
        ref = _digests(system)
        system.close()

        got, _, respawns = _run_fig1(
            resolve_backend("multiproc", workers=2),
            kill_at=4, supervise=dict(heartbeat_interval=5.0),
        )
        assert got == ref
        assert respawns >= 1


# -- elasticity ------------------------------------------------------------------


class TestResizePool:
    def test_grow_and_shrink_preserve_counts(self):
        def run(resize):
            be = MultiprocBackend(workers=2, worker_plane="dry")
            system = StreamSystem(strategy="none", backend=be,
                                  step_mode=STEP_MODE, max_workers=MAX_WORKERS)
            for i in range(5):
                system.submit(
                    chain_df(f"R{i}", "urban", [("kalman", {"q": float(i)})])
                )
            for _ in range(2):
                system.step()
            if resize:
                be.resize_pool(4)
            for _ in range(2):
                system.step()
            if resize:
                be.resize_pool(1)
                assert set(be.device_of.values()) == {0}
            for _ in range(2):
                system.step()
            counts = _counts(system)
            kinds = [e.kind for e in be.worker_events]
            system.close()
            return counts, kinds

        ref, _ = run(resize=False)
        got, kinds = run(resize=True)
        assert got == ref
        assert POOL_GROWN in kinds and POOL_SHRUNK in kinds

    def test_resize_validation(self):
        be = MultiprocBackend(workers=1, worker_plane="dry")
        try:
            with pytest.raises(ValueError, match=">= 1"):
                be.resize_pool(0)
        finally:
            be.close()


class TestAutoscalerEndToEnd:
    def test_forced_pressure_grows_then_shrinks_pool(self, monkeypatch):
        be = MultiprocBackend(workers=1, worker_plane="dry")
        system = StreamSystem(strategy="none", backend=be,
                              step_mode=STEP_MODE, max_workers=MAX_WORKERS)
        for i in range(4):
            system.submit(chain_df(f"A{i}", "urban", [("kalman", {"q": float(i)})]))
        system.step()
        scaler = Autoscaler(be, min_workers=1, max_workers=3,
                            high_ms=10.0, low_ms=1.0, patience=2, cooldown=0)
        monkeypatch.setattr(scaler, "pressure", lambda: 100.0)
        for _ in range(4):
            system.step()
            scaler.observe()
        assert be.n_workers > 1
        monkeypatch.setattr(scaler, "pressure", lambda: 0.01)
        for _ in range(6):
            system.step()
            scaler.observe()
        assert be.n_workers == 1
        assert [(a["from"], a["to"]) for a in scaler.actions][0] == (1, 2)
        # the resized pool still serves a correct step
        report = system.step()
        assert report.live_tasks == be.live_task_count
        system.close()

    def test_system_autoscale_knob_binds_and_reports(self):
        be = MultiprocBackend(workers=1, worker_plane="dry")
        system = StreamSystem(
            strategy="none", backend=be, step_mode=STEP_MODE,
            max_workers=MAX_WORKERS,
            autoscale={"min_workers": 1, "max_workers": 2,
                       "high_ms": 1e9, "low_ms": 1e-9},
        )
        system.submit(chain_df("K0", "urban", [("kalman", {"q": 1.0})]))
        system.step()  # observe() runs inside step()
        health = system.worker_health()
        assert health["autoscale"]["max_workers"] == 2
        assert health["autoscale"]["actions"] == []
        system.close()


class TestHeartbeatAndHealth:
    def test_heartbeat_detects_idle_crash(self):
        be = MultiprocBackend(workers=2, worker_plane="dry")
        system = StreamSystem(strategy="none", backend=be,
                              step_mode=STEP_MODE, max_workers=MAX_WORKERS)
        for i in range(2):
            system.submit(chain_df(f"H{i}", "urban", [("kalman", {"q": float(i)})]))
        system.step()
        sup = WorkerSupervisor(be, heartbeat_interval=0.05).start()
        os.kill(be._procs[1].pid, signal.SIGKILL)
        deadline = time.monotonic() + 10.0
        while not be.respawns and time.monotonic() < deadline:
            time.sleep(0.02)  # no step issued: only the heartbeat can notice
        assert be.respawns, "heartbeat never recovered the idle crash"
        assert HEARTBEAT_MISSED in [e.kind for e in be.worker_events]
        assert be.worker_alive(1)
        system.step()  # recovered pool keeps stepping
        sup.stop()
        system.close()

    def test_check_is_synchronous(self):
        be = MultiprocBackend(workers=2, worker_plane="dry")
        system = StreamSystem(strategy="none", backend=be,
                              step_mode=STEP_MODE, max_workers=MAX_WORKERS)
        system.submit(chain_df("C0", "urban", [("kalman", {"q": 1.0})]))
        system.step()
        sup = WorkerSupervisor(be)  # not started: no background thread
        os.kill(be._procs[0].pid, signal.SIGKILL)
        time.sleep(0.1)
        assert sup.check() == [0]
        assert be.worker_alive(0)
        system.close()

    def test_supervise_knob_surfaces_worker_health(self):
        system = StreamSystem(
            strategy="none",
            backend=MultiprocBackend(workers=2, worker_plane="dry"),
            step_mode=STEP_MODE, max_workers=MAX_WORKERS,
            supervise=True,
        )
        system.submit(chain_df("W0", "urban", [("kalman", {"q": 1.0})]))
        system.step()
        health = system.worker_health()
        assert health["workers"] == 2
        assert health["alive"] == [True, True]
        assert health["supervised"] is True
        assert health["snapshot_mode"] in ("spill", "wire")
        assert "spill_ms_per_step" in health
        assert health["heartbeat_running"] is True
        system.close()  # stops the supervisor thread
        assert system._supervisor.running is False

    def test_inprocess_backends_have_no_worker_health(self):
        system = StreamSystem(strategy="none", backend="dryrun")
        assert system.worker_health() is None
        with pytest.raises(ValueError, match="worker-pool backend"):
            StreamSystem(strategy="none", backend="dryrun", supervise=True)
        system.close()

    def test_event_hook_receives_pool_events(self):
        seen = []
        be = MultiprocBackend(workers=1, worker_plane="dry")
        system = StreamSystem(strategy="none", backend=be,
                              step_mode=STEP_MODE, max_workers=MAX_WORKERS,
                              on_worker_event=seen.append)
        system.submit(chain_df("E0", "urban", [("kalman", {"q": 1.0})]))
        system.step()
        be.resize_pool(2)
        be.resize_pool(1)
        kinds = [e.kind for e in seen]
        assert POOL_GROWN in kinds and POOL_SHRUNK in kinds
        system.close()


class TestSubprocessLauncher:
    def test_end_to_end_counts_match_local_launcher(self):
        ref, _, _ = _run_fig1(
            MultiprocBackend(workers=2, worker_plane="dry"),
            ops=FIG1_OPS[:4], tail_steps=1,
        )
        be = MultiprocBackend(workers=2, worker_plane="dry",
                              launcher="subprocess")
        assert be.launcher.supports_spill  # same host, no command_prefix
        got, _, _ = _run_fig1(be, ops=FIG1_OPS[:4], tail_steps=1)
        assert {n: {s: d["count"] for s, d in v.items()} for n, v in got.items()} == {
            n: {s: d["count"] for s, d in v.items()} for n, v in ref.items()
        }


# -- one process per TPU chip ---------------------------------------------------


class TestJitWorkerChipPinning:
    """The environment a local launcher gives each jit-plane worker."""

    @pytest.fixture
    def tpu_host(self, monkeypatch):
        from repro.cluster import launcher

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(launcher, "local_tpu_chips", lambda: 4)
        monkeypatch.setattr(launcher, "_holds_tpu", lambda: False)
        return launcher

    def test_worker_i_gets_chip_i(self, tpu_host):
        env = tpu_host.jit_worker_env(2)
        assert env["TPU_VISIBLE_CHIPS"] == "2"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert env["ALLOW_MULTIPLE_LIBTPU_LOAD"] == "1"
        ports = {tpu_host.jit_worker_env(i)["TPU_PROCESS_PORT"] for i in range(4)}
        assert len(ports) == 4

    def test_more_workers_than_chips_refused(self, tpu_host):
        with pytest.raises(RuntimeError, match="at most 4 workers"):
            tpu_host.jit_worker_env(4)

    def test_parent_holding_the_tpu_refused_before_spawning(self, tpu_host, monkeypatch, tmp_path):
        monkeypatch.setattr(tpu_host, "_holds_tpu", lambda: True)
        with pytest.raises(RuntimeError, match="holds the TPU"):
            tpu_host.LocalProcessLauncher().launch(
                0, {"kind": "inproc"}, "jit", str(tmp_path / "w.log")
            )

    def test_cpu_platform_and_chipless_host_leave_env_alone(self, tpu_host, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert tpu_host.jit_worker_env(7) == {}
        monkeypatch.delenv("JAX_PLATFORMS")
        monkeypatch.setattr(tpu_host, "local_tpu_chips", lambda: 0)
        assert tpu_host.jit_worker_env(7) == {}

    def test_chips_counted_from_device_nodes(self, tmp_path):
        from repro.cluster.launcher import local_tpu_chips

        assert local_tpu_chips(str(tmp_path)) == 0
        (tmp_path / "vfio").mkdir()
        for name in ("vfio/3", "vfio/vfio", "tty0"):  # one v5e chip, no others
            (tmp_path / name).write_text("")
        assert local_tpu_chips(str(tmp_path)) == 1
        for name in ("accel0", "accel1"):  # v4 / v5p nodes
            (tmp_path / name).write_text("")
        assert local_tpu_chips(str(tmp_path)) == 3
