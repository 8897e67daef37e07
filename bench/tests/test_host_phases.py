"""The readers of the program's host phases and cross-chip bytes
(``metrics/backend.host_ms_per_step.py``, ``backend.account_ms_per_step.py``,
``transport.cross_chip_bytes_per_step.py``) on hand-made runs. Runs on the
CPU.

    python -m pytest bench/tests/test_host_phases.py
"""
import importlib.util
import os
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader(metric):
    spec = importlib.util.spec_from_file_location(
        metric, os.path.join(BENCH, "metrics", f"{metric}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


host_ms = _reader("backend.host_ms_per_step")
account_ms = _reader("backend.account_ms_per_step")
cross_chip_bytes = _reader("transport.cross_chip_bytes_per_step")


def span(name, cat, ts, dur):
    return {"name": name, "cat": cat, "ph": "X", "ts": ts, "dur": dur, "args": {}}


def traced_step(ts, dur, waits, account):
    """A step span from ``ts`` with ``waits`` (offset, dur) and an account
    span at its end."""
    out = [span("step", "step", ts, dur)]
    out += [span("wait", "device", ts + off, d) for off, d in waits]
    out.append(span("account", "step", ts + dur - account, account))
    return out


def ctx(spans, start_us=1000):
    return NS(spans=spans, start_us=start_us)


RUN = (traced_step(0, 400, [(10, 300)], 20)  # set-up: outside the window
       + traced_step(1000, 200, [(10, 50), (100, 60)], 10)
       + traced_step(1300, 300, [(20, 100)], 30))


def test_host_time_is_the_step_less_its_waits():
    # window steps: 200 - 110 = 90 us and 300 - 100 = 200 us
    assert host_ms(ctx(RUN)) == pytest.approx((90 + 200) / 2 / 1e3)


def test_account_time_per_window_step():
    assert account_ms(ctx(RUN)) == pytest.approx((10 + 30) / 2 / 1e3)


def test_program_without_the_phase_spans_reads_nothing():
    # a program that records steps and segments but no wait or account span
    old = [s for s in RUN if s["name"] not in ("wait", "account")]
    assert host_ms(ctx(old)) is None
    assert account_ms(ctx(old)) is None
    assert host_ms(ctx([])) is None and account_ms(ctx([])) is None


def snapshot_session(snapshot):
    return NS(metrics_snapshot=lambda: snapshot)


def test_cross_chip_bytes_over_every_step():
    snap = {"repro_transport_cross_chip_bytes_total":
            {"kind": "counter", "help": "", "values": [[{}, 15 * 524288.0 * 10]]}}
    c = NS(session=snapshot_session(snap), run=NS(steps=10))
    assert cross_chip_bytes(c) == pytest.approx(15 * 524288.0)


def test_cross_chip_counter_never_incremented_reads_zero():
    snap = {"repro_transport_cross_chip_bytes_total": {"kind": "counter", "help": "", "values": []}}
    assert cross_chip_bytes(NS(session=snapshot_session(snap), run=NS(steps=3))) == 0


def test_program_without_the_counter_reads_nothing():
    assert cross_chip_bytes(NS(session=snapshot_session({}), run=NS(steps=3))) is None
