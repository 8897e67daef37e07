"""Unified telemetry plane tests (``repro.obs`` + its runtime wiring).

Five layers:
  * primitives: counter/gauge/histogram semantics, registry get-or-create,
    snapshot/merge (the multiproc aggregation path), the null twin,
    collectors, Prometheus render/parse round-trips;
  * tracing: span recording, stride sampling, ring-buffer bounds, error
    spans, Chrome trace-event export, the bridge onto the profiler's
    clock (host annotations nested as a step runs them);
  * system wiring: merge/unmerge/step spans, reuse-savings metrics
    cross-checked against manager/ledger ground truth, ``configure_obs``
    registry swaps, the canonical ``segment_latency_ms()`` accessor vs the
    raw ``StepReport.segment_ms`` history (the double-source fix);
  * cluster/durability: worker-health staleness marking through serving
    ``status()``, the ``report_history`` ring buffer surviving a multiproc
    checkpoint/restore, cross-process span harvest;
  * serving: the ``metrics`` wire verb end-to-end over TCP, serve gauges
    matching the tenant ledgers.

The CI observability job re-runs this module with ``REPRO_TEST_STEP_MODE``
sync and concurrent; results must be mode-invariant.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from contextlib import contextmanager

import pytest

from repro.obs import (
    DEFAULT_MS_BUCKETS,
    MetricsRegistry,
    NULL_REGISTRY,
    NullRegistry,
    Tracer,
    chrome_trace_json,
    merge_snapshots,
    parse_prometheus,
    render_prometheus,
    write_chrome_trace,
)
from repro.runtime.system import StreamSystem

from helpers import fig1

STEP_MODE = os.environ.get("REPRO_TEST_STEP_MODE") or "sync"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sample(families, name, **labels):
    want = {k: str(v) for k, v in labels.items()}
    for lbls, value in families.get(name, []):
        if lbls == want:
            return value
    return None


def snap_value(snapshot, name, **labels):
    """Scalar of one labelset in a registry snapshot, or None."""
    entry = snapshot.get(name)
    if entry is None:
        return None
    want = {k: str(v) for k, v in labels.items()}
    for lbls, value in entry["values"]:
        if lbls == want:
            return value
    return None


# -- primitives -------------------------------------------------------------------


class TestMetricsPrimitives:
    def test_counter_inc_labels_and_clamped_set_total(self):
        m = MetricsRegistry()
        c = m.counter("ops_total", "ops")
        c.inc()
        c.inc(2.5)
        c.inc(1, op="merge")
        assert c.value() == 3.5
        assert c.value(op="merge") == 1.0
        c.set_total(10.0)
        assert c.value() == 10.0
        c.set_total(4.0)  # clamped: counters never decrease
        assert c.value() == 10.0
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        g = MetricsRegistry().gauge("depth", "queue depth")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value() == 6.0

    def test_histogram_buckets_sum_count(self):
        m = MetricsRegistry()
        h = m.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
        for v in (0.5, 5.0, 50.0, 10.0):  # 10.0 lands in le=10 (inclusive)
            h.observe(v)
        cell = snap_value(m.snapshot(), "lat_ms")
        assert cell["counts"] == [1, 2, 1]
        assert cell["count"] == 4
        assert cell["sum"] == pytest.approx(65.5)
        assert m.histogram("lat_ms").buckets == (1.0, 10.0)
        assert DEFAULT_MS_BUCKETS == tuple(sorted(DEFAULT_MS_BUCKETS))

    def test_registry_get_or_create_and_kind_mismatch(self):
        m = MetricsRegistry()
        assert m.counter("x") is m.counter("x")
        with pytest.raises(ValueError):
            m.gauge("x")

    def test_merge_adds_counters_and_histogram_cells(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        for m, n in ((a, 2), (b, 3)):
            m.counter("steps_total").inc(n)
            m.gauge("live").set(n)
            m.histogram("ms", buckets=(1.0,)).observe(0.5)
        merged = merge_snapshots([a.snapshot(), b.snapshot()])
        assert snap_value(merged, "steps_total") == 5.0
        assert snap_value(merged, "live") == 5.0  # worker gauges sum pool-wide
        cell = snap_value(merged, "ms")
        assert cell["count"] == 2 and cell["counts"] == [2, 0]

    def test_null_registry_is_inert(self):
        assert isinstance(NULL_REGISTRY, NullRegistry)
        NULL_REGISTRY.counter("whatever").inc(5)
        NULL_REGISTRY.add_collector(lambda: 1 / 0)
        assert NULL_REGISTRY.snapshot() == {}

    def test_collectors_run_at_snapshot_and_failures_are_swallowed(self):
        m = MetricsRegistry()
        m.add_collector(lambda: m.gauge("mirrored").set(42))
        m.add_collector(lambda: 1 / 0)  # must not kill the scrape
        assert snap_value(m.snapshot(), "mirrored") == 42.0


class TestPrometheusText:
    def test_render_parse_round_trip(self):
        m = MetricsRegistry()
        m.counter("req_total", "requests").inc(3, tenant="a/b", code="200")
        m.gauge("temp").set(-1.5)
        m.histogram("ms", buckets=(1.0, 5.0)).observe(0.2)
        text = render_prometheus(m.snapshot())
        fams = parse_prometheus(text)
        assert sample(fams, "req_total", tenant="a/b", code="200") == 3.0
        assert sample(fams, "temp") == -1.5
        assert sample(fams, "ms_count") == 1.0
        assert sample(fams, "ms_bucket", le="1") == 1.0
        assert sample(fams, "ms_bucket", le="+Inf") == 1.0

    def test_label_escaping_survives_round_trip(self):
        m = MetricsRegistry()
        m.counter("c").inc(1, topic='we"ird\\label\nx')
        fams = parse_prometheus(render_prometheus(m.snapshot()))
        assert sample(fams, "c", topic='we"ird\\label\nx') == 1.0

    def test_parse_rejects_malformed_lines(self):
        with pytest.raises(ValueError):
            parse_prometheus("this is not exposition format\n")


# -- tracing ----------------------------------------------------------------------


class TestTracer:
    def test_disabled_records_nothing(self):
        t = Tracer(enabled=False)
        with t.span("x"):
            pass
        assert t.drain() == []

    def test_span_shape(self):
        t = Tracer(enabled=True)
        with t.span("step", "step", step=3):
            pass
        (s,) = t.drain()
        assert s["name"] == "step" and s["cat"] == "step" and s["ph"] == "X"
        assert s["dur"] >= 1 and s["args"] == {"step": 3}
        assert s["pid"] == os.getpid()

    def test_stride_sampling_per_name(self):
        t = Tracer(enabled=True, sample_stride=3)
        for _ in range(9):
            with t.span("a"):
                pass
        for _ in range(2):
            with t.span("b"):
                pass
        names = [s["name"] for s in t.drain()]
        assert names.count("a") == 3  # every 3rd
        assert names.count("b") == 1  # stride state is per name

    def test_ring_buffer_drops_oldest(self):
        t = Tracer(enabled=True, capacity=4)
        for i in range(10):
            with t.span("s", i=i):
                pass
        kept = [s["args"]["i"] for s in t.drain()]
        assert kept == [6, 7, 8, 9]

    def test_error_span_recorded_and_raises(self):
        t = Tracer(enabled=True)
        with pytest.raises(RuntimeError):
            with t.span("boom"):
                raise RuntimeError("x")
        (s,) = t.drain()
        assert s["args"]["error"] == "RuntimeError"

    def test_chrome_trace_export(self, tmp_path):
        t = Tracer(enabled=True)
        with t.span("work", "segment"):
            pass
        doc = chrome_trace_json(t.spans())
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert len(metas) == 1 and metas[0]["args"]["name"].startswith("repro pid")
        path = write_chrome_trace(str(tmp_path / "trace.json"), t.drain())
        loaded = json.load(open(path))
        assert any(e["ph"] == "X" for e in loaded["traceEvents"])


class TestProfilerBridge:
    """``Tracer.annotate``: each recorded span also opens a host annotation
    (the jit backends install ``jax.profiler.TraceAnnotation``)."""

    @staticmethod
    def _recording_tracer(**kw):
        calls = []

        @contextmanager
        def annotate(name, **args):
            calls.append(("open", name, args))
            yield
            calls.append(("close", name))

        t = Tracer(**kw)
        t.annotate = annotate
        return t, calls

    def test_enabled_span_annotates_repro_name(self):
        t, calls = self._recording_tracer(enabled=True)
        with t.span("fetch", "transport", segment="seg1", topics=2):
            assert calls == [("open", "repro.fetch", {})]
        assert calls[-1] == ("close", "repro.fetch")
        assert [s["name"] for s in t.drain()] == ["fetch"]  # ring buffer as before

    def test_segment_span_annotates_fixed_name_with_segment_arg(self):
        t, calls = self._recording_tracer(enabled=True)
        with t.span("seg17", "segment", step=4):
            pass
        assert calls == [("open", "repro.segment", {"segment": "seg17"}),
                         ("close", "repro.segment")]

    def test_disabled_span_annotates_nothing(self):
        t, calls = self._recording_tracer(enabled=False)
        with t.span("step"):
            pass
        assert calls == [] and t.drain() == []

    def test_sampled_out_span_annotates_nothing(self):
        t, calls = self._recording_tracer(enabled=True, sample_stride=2)
        for _ in range(4):
            with t.span("dispatch", "device"):
                pass
        assert [c for c in calls if c[0] == "open"] == [("open", "repro.dispatch", {})] * 2

    def test_annotation_closes_when_the_span_raises(self):
        t, calls = self._recording_tracer(enabled=True)
        with pytest.raises(RuntimeError):
            with t.span("wait", "device"):
                raise RuntimeError("x")
        assert calls == [("open", "repro.wait", {}), ("close", "repro.wait")]

    def test_obs_imports_without_jax(self):
        code = ("import sys, repro.obs; "
                "t = repro.obs.Tracer(enabled=True); "
                "assert t.annotate is None and 'jax' not in sys.modules")
        env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]

    def test_jit_backends_install_the_profiler_annotation(self):
        import jax

        jit = _fig1_system(backend="inprocess", base_batch=8)
        dry = _fig1_system()
        try:
            assert jit.backend.tracer.annotate is jax.profiler.TraceAnnotation
            assert dry.backend.tracer.annotate is None
        finally:
            jit.close()
            dry.close()


# -- system wiring ----------------------------------------------------------------


def _fig1_system(**kw):
    kw.setdefault("strategy", "signature")
    kw.setdefault("backend", "dryrun")
    kw.setdefault("step_mode", STEP_MODE)
    system = StreamSystem(**kw)
    for df in fig1():
        system.submit(df.copy())
    return system


class TestSystemObs:
    def test_control_and_step_spans(self):
        system = _fig1_system()
        system.configure_obs(trace=True)
        system.submit(fig1()[1].copy("B2"))
        system.step()
        system.remove("B2")
        spans = system.drain_spans()
        names = {s["name"] for s in spans}
        assert {"merge", "unmerge", "step"} <= names
        cats = {s["cat"] for s in spans}
        assert {"control", "step", "segment"} <= cats
        system.close()

    def test_reuse_savings_metrics_match_manager_ground_truth(self):
        system = _fig1_system()
        system.run(3)
        system.remove("B")
        snap = system.metrics_snapshot()
        mgr = system.manager
        saved = mgr.submitted_task_count - mgr.running_task_count
        assert snap_value(snap, "repro_reuse_tasks_saved") == saved
        oc = mgr.op_counts
        assert snap_value(snap, "repro_reuse_tasks_submitted_total") == oc["tasks_submitted"]
        assert snap_value(snap, "repro_reuse_tasks_reused_total") == oc["tasks_reused"]
        assert snap_value(snap, "repro_merge_events_total") == oc["merge_events"]
        assert snap_value(snap, "repro_unmerge_events_total") == 1.0
        # tasks_submitted decomposes exactly: reused + created
        assert oc["tasks_submitted"] == oc["tasks_reused"] + oc["tasks_created"]
        # core·steps avoided accrues only while sharing exists
        assert snap_value(snap, "repro_reuse_core_steps_avoided_total") > 0
        system.close()

    def test_op_counts_survive_journal_replay(self, tmp_path):
        from repro.core import ReuseManager

        journal = str(tmp_path / "journal.jsonl")
        system = _fig1_system(journal_path=journal)
        system.remove("A")
        want = dict(system.manager.op_counts)
        system.close()
        replayed = ReuseManager.restore(journal, strategy="signature")
        assert replayed.op_counts == want

    def test_configure_obs_registry_swap_keeps_collectors(self):
        system = _fig1_system()
        assert snap_value(system.metrics_snapshot(), "repro_reuse_tasks_saved") is not None
        system.configure_obs(metrics=False)
        assert system.metrics_snapshot() == {}
        assert system.prometheus_text() == "\n"
        system.configure_obs(metrics=True)  # fresh registry, collector re-wired
        assert snap_value(system.metrics_snapshot(), "repro_reuse_tasks_saved") is not None
        system.close()

    def test_registry_swap_moves_the_minted_core_steps_counter(self):
        system = _fig1_system()
        system.step()
        system.configure_obs(metrics=False)
        system.step()  # the null registry's counter takes the increment
        system.configure_obs(metrics=True)
        assert snap_value(system.metrics_snapshot(),
                          "repro_reuse_core_steps_avoided_total") is None
        system.step()
        assert snap_value(system.metrics_snapshot(),
                          "repro_reuse_core_steps_avoided_total") > 0
        system.close()

    @pytest.mark.parametrize("step_mode", ["sync", "concurrent"])
    def test_disabled_tracer_opens_no_span_while_stepping(self, step_mode, monkeypatch):
        """Every hot-path span site (step, wave_dispatch, segment, fetch,
        dispatch, wait, publish, account) is guarded by ``tracer.enabled``."""
        system = _fig1_system(backend="inprocess", base_batch=8, step_mode=step_mode)

        def no_span(*a, **k):
            raise AssertionError(f"span {a[:1]} built with tracing off")

        monkeypatch.setattr(system.backend.tracer, "span", no_span)
        try:
            system.run(2)
        finally:
            system.close()

    def test_segment_latency_accessor_matches_report_history(self):
        """Satellite: segment_latency_ms() is THE accessor — its digest must
        agree exactly with the raw StepReport.segment_ms history that also
        feeds latency_samples() (no second EWMA-based source)."""
        system = _fig1_system(report_history=64)
        system.run(6)
        stats = system.segment_latency_ms()
        reports = system.backend.reports
        assert stats and reports
        for name, cell in stats.items():
            series = [r.segment_ms[name] for r in reports if name in r.segment_ms]
            assert cell["samples"] == len(series)
            assert cell["mean_ms"] == pytest.approx(sum(series) / len(series))
            assert cell["last_ms"] == pytest.approx(series[-1])
            assert cell["max_ms"] == pytest.approx(max(series))
        # same sample population as the dry-run calibrator feed
        n_samples = sum(c["samples"] for c in stats.values())
        assert len(system.backend.latency_samples()) == n_samples
        system.close()

    def test_checkpoint_metrics_and_spans(self, tmp_path):
        system = _fig1_system(checkpoint_dir=str(tmp_path / "ck"))
        system.configure_obs(trace=True)
        system.run(2)
        system.checkpoint()
        snap = system.metrics_snapshot()
        assert snap_value(snap, "repro_checkpoints_total") == 1.0
        hist = snap_value(snap, "repro_checkpoint_save_ms")
        assert hist["count"] == 1
        names = {s["name"] for s in system.drain_spans() if s["cat"] == "checkpoint"}
        assert {"ckpt_encode", "ckpt_fsync"} <= names
        system.close()

    def test_transport_counters_mirrored(self):
        system = _fig1_system(backend="inprocess")
        system.run(3)
        snap = system.metrics_snapshot()
        transport = system.backend.transport
        assert snap_value(snap, "repro_transport_publishes_total") == transport.counters()["publishes"]
        assert snap_value(snap, "repro_transport_fetches_total") == transport.fetch_count
        assert snap_value(snap, "repro_transport_fetches_total") > 0
        system.close()


# -- cluster / durability ---------------------------------------------------------


class TestWorkerHealthStaleness:
    def test_health_has_monotonic_staleness_fields(self):
        system = _fig1_system(backend="multiproc", workers=2,
                              backend_options={"worker_plane": "dry"})
        try:
            system.run(2)
            health = system.backend.worker_health()
            assert health["stale_after_ms"] > 0
            assert set(health["stale"]) == {"0", "1"}
            for w in ("0", "1"):
                t = health["last_ok_monotonic"][w]
                assert t is not None and t <= health["now_monotonic"]
                assert health["stale"][w] is False  # just replied
            # shrink the window to zero: every worker's last reply is stale
            system.backend.stale_after_ms = 0.0
            assert all(system.backend.worker_health()["stale"].values())
        finally:
            system.close()

    def test_staleness_surfaces_through_serving_status(self):
        from repro.api import ReuseSession
        from repro.serve.frontend import ServeFrontend

        session = ReuseSession(
            strategy="signature", execute=True, backend="multiproc",
            workers=1, step_mode=STEP_MODE,
            backend_options={"worker_plane": "dry"},
        )
        frontend = ServeFrontend(session=session)
        try:
            frontend.submit("alice", fig1()[0].copy("alice/A"))
            frontend.step()
            health = frontend.status()["worker_health"]
            assert health["stale"]["0"] is False
            assert health["last_ok_monotonic"]["0"] is not None
            assert health["stale_after_ms"] > 0
        finally:
            frontend.close()


class TestReportHistoryCheckpoint:
    def test_report_ring_survives_multiproc_checkpoint_restore(self, tmp_path):
        """Satellite: the opt-in StepReport ring buffer is part of the
        durable state — a restored system resumes with the pre-crash
        trajectory, trimmed to the ring limit."""
        limit = 5
        system = _fig1_system(
            backend="multiproc", workers=2,
            backend_options={"worker_plane": "dry"},
            report_history=limit, checkpoint_dir=str(tmp_path / "ck"),
        )
        try:
            system.run(limit + 3)  # overflow the ring before checkpointing
            assert len(system.backend.reports) == limit
            want = [(r.step, r.cost, r.segment_ms) for r in system.backend.reports]
            path = system.checkpoint()
        finally:
            system.close()
        restored = StreamSystem.restore(
            path, backend="multiproc",
            backend_options={"worker_plane": "dry"},
        )
        try:
            assert restored.backend.history_limit == limit
            got = [(r.step, r.cost, r.segment_ms) for r in restored.backend.reports]
            assert got == want
            restored.run(limit)  # ring keeps enforcing the limit post-restore
            assert len(restored.backend.reports) == limit
            assert restored.backend.reports[-1].step > want[-1][0]
        finally:
            restored.close()


class TestProfilerTrace:
    def test_step_phases_nest_on_the_profiler_clock(self, tmp_path):
        """A traced ``inprocess`` step under ``jax.profiler``: the program's
        spans appear as host annotations, nested as the step runs them."""
        import jax
        from jax.profiler import ProfileData

        system = _fig1_system(backend="inprocess", base_batch=8, step_mode="sync")
        try:
            system.step()  # compile outside the trace
            system.configure_obs(trace=True)
            jax.profiler.start_trace(str(tmp_path))
            try:
                system.run(2)
            finally:
                jax.profiler.stop_trace()
        finally:
            system.close()
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        lines = [[(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
                 for plane in ProfileData.from_file(str(path)).planes for line in plane.lines]
        lines = [[ev for ev in line if ev[0].startswith("repro.")] for line in lines]
        (events,) = [line for line in lines if line]  # one host thread steps

        def inside(child, parent_name):
            return any(p[0] == parent_name and p[1] <= child[1] and child[2] <= p[2]
                       for p in events)

        names = [ev[0] for ev in events]
        assert names.count("repro.step") == 2
        assert names.count("repro.account") == 2
        for phase in ("repro.fetch", "repro.dispatch", "repro.wait", "repro.publish"):
            assert names.count(phase) == names.count("repro.segment") > 0
        for ev in events:
            if ev[0] in ("repro.segment", "repro.account"):
                assert inside(ev, "repro.step"), ev
            elif ev[0] != "repro.step":
                assert inside(ev, "repro.segment"), ev


class TestCrossChipCounters:
    def test_one_device_moves_nothing_across_chips(self):
        """The counters exist from the start and stay at zero while every
        segment shares the consumer's device (the four-device case runs in
        ``test_distributed_subprocess.py``)."""
        system = _fig1_system(backend="sharded", base_batch=8)
        try:
            system.run(2)
            snap = system.metrics_snapshot()
            for name in ("repro_transport_cross_chip_fetches_total",
                         "repro_transport_cross_chip_bytes_total"):
                assert snap[name]["kind"] == "counter"
                assert snap_value(snap, name) is None  # never incremented
            assert snap_value(snap, "repro_transport_fetches_total") > 0
        finally:
            system.close()


class TestMultiprocObsHarvest:
    def test_worker_metrics_and_spans_harvested(self):
        system = _fig1_system(backend="multiproc", workers=2,
                              backend_options={"worker_plane": "dry"})
        try:
            system.configure_obs(trace=True)
            system.run(3)
            snap = system.metrics_snapshot()
            # worker families are distinct from coordinator ones: no
            # double-count on merge
            worker_steps = snap.get("repro_worker_segment_steps_total")
            assert worker_steps is not None
            total = sum(v for _lbls, v in worker_steps["values"])
            assert total > 0
            spans = system.drain_spans()
            seg_pids = {s["pid"] for s in spans if s["cat"] == "segment"}
            assert len(seg_pids) >= 2  # spans from >1 worker process
            assert os.getpid() not in seg_pids  # segments ran in workers
            rpc_spans = [s for s in spans if s["cat"] == "rpc"]
            assert rpc_spans and all(s["pid"] == os.getpid() for s in rpc_spans)
        finally:
            system.close()


# -- serving ----------------------------------------------------------------------


class TestServeMetricsVerb:
    def test_metrics_verb_over_tcp_matches_ledgers(self):
        from repro.serve.client import ServeClient
        from repro.serve.frontend import ServeFrontend

        frontend = ServeFrontend(slots=64, backend="dryrun")
        host, port = frontend.start()
        try:
            with ServeClient((host, port)) as client:
                a, b, *_ = fig1()
                assert client.submit("alice", a.copy("alice/A"))["status"] == "ADMITTED"
                assert client.submit("bob", b.copy("bob/B"))["status"] == "ADMITTED"
                client.step(2)
                out = client.metrics()
                fams = parse_prometheus(out["text"])  # validates the format
                stats = frontend.stats()
                assert sample(fams, "repro_serve_slots") == 64.0
                assert sample(fams, "repro_serve_slots_used") == stats["slots_used"]
                assert sample(fams, "repro_serve_naive_slots") == stats["naive_slots"]
                assert sample(fams, "repro_serve_effective_capacity") == pytest.approx(
                    stats["effective_capacity"]
                )
                for tenant in ("alice", "bob"):
                    ledger = stats["ledgers"][tenant]
                    assert sample(fams, "repro_serve_slots_held", tenant=tenant) == ledger["slots_held"]
                    assert sample(fams, "repro_serve_slots_saved", tenant=tenant) == ledger["slots_saved"]
                    assert sample(fams, "repro_serve_cost_total", tenant=tenant) == pytest.approx(
                        ledger["cost_total"]
                    )
                # snapshot side of the reply carries the raw registry JSON
                assert snap_value(out["snapshot"], "repro_serve_slots") == 64.0
        finally:
            frontend.close()

    def test_metrics_http_listener(self):
        import urllib.request

        from repro.serve.frontend import ServeFrontend

        frontend = ServeFrontend(slots=16, backend="dryrun")
        try:
            frontend.submit("alice", fig1()[0].copy("alice/A"))
            frontend.step()
            host, port = frontend.start_metrics_http(port=0)
            body = urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10
            ).read().decode("utf-8")
            fams = parse_prometheus(body)
            assert sample(fams, "repro_serve_slots_used") == frontend.slots_used
            with pytest.raises(Exception):
                urllib.request.urlopen(f"http://{host}:{port}/nope", timeout=10)
        finally:
            frontend.close()

    def test_control_plane_session_metrics_are_empty(self):
        from repro.api import ReuseSession
        from repro.serve.frontend import ServeFrontend

        frontend = ServeFrontend(session=ReuseSession(execute=False))
        try:
            out = frontend.metrics()
            assert out["ok"] and out["text"] == "" and out["snapshot"] == {}
        finally:
            frontend.close()
