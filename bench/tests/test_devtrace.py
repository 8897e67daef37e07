"""The trace reduction on a small trace recorded on the chip
(``record_trace.py``: one traced step of ``riot21.steady`` at 8 events per
source per step, TPU v5 lite, gzip-compressed), checked against plain sweeps
over the same events. Runs on the CPU.

    python -m pytest bench/tests/test_devtrace.py
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
FIXTURE = os.path.join(BENCH, "data", "riot21_b8.xplane.pb.gz")
sys.path.insert(0, BENCH)

from lib import devtrace  # noqa: E402


def covered(intervals):
    """Length covered by a set of intervals, by a sweep over their ends."""
    edges = sorted([(s, 1) for s, e in intervals] + [(e, -1) for s, e in intervals])
    total, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


@pytest.fixture(scope="module")
def planes():
    return devtrace.load_planes(FIXTURE)


@pytest.fixture(scope="module")
def window(planes):
    spans = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
             for p in planes if not p.name.startswith("/device:")
             for line in p.lines for ev in line.events if ev.name == devtrace.WINDOW]
    assert len(spans) == 1
    return spans[0]


@pytest.fixture(scope="module")
def device_events(planes, window):
    w0, w1 = window
    (plane,) = [p for p in planes if p.name == "/device:TPU:0"]
    out = []
    for line in plane.lines:
        if line.name == "XLA Ops":
            for ev in line.events:
                s, e = max(int(ev.start_ns), w0), min(int(ev.start_ns + ev.duration_ns), w1)
                if e > s:
                    out.append((ev.name, s, e))
    return out


def test_fixture_is_small():
    assert os.path.getsize(FIXTURE) < 1 << 20


def test_busy_and_idle(planes, window, device_events):
    got = devtrace.reduce_trace(planes, [0])
    busy = covered([(s, e) for _, s, e in device_events]) * 1e-9
    assert got["window_s"] == pytest.approx((window[1] - window[0]) * 1e-9)
    assert got["busy_s"] == pytest.approx(busy, abs=1e-8)  # ns rounding of nested ends
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["steps"] == 1


def test_loop_time(planes, device_events):
    got = devtrace.reduce_trace(planes, [0])
    loops = [(s, e) for name, s, e in device_events if devtrace.op_name(name) == "while"]
    assert loops, "the riot21 scans run as while loops"
    assert got["loop_s"] == pytest.approx(covered(loops) * 1e-9, abs=1e-8)
    assert 0 < got["loop_s"] <= got["busy_s"]


def test_top_ops_are_self_time(planes):
    got = devtrace.reduce_trace(planes, [0])
    ops = got["device_ops"]
    assert 0 < len(ops) <= 10
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    # the self times of properly nested ops add up to the busy time
    assert got["op_self_s"] == pytest.approx(got["busy_s"], rel=1e-3)
    assert sum(t for _, t in ops) <= got["op_self_s"] * (1 + 1e-9)


def test_idle_gaps_named(planes):
    got = devtrace.reduce_trace(planes, [0])
    gaps = got["idle_gaps"]
    assert gaps and all(isinstance(n, str) and t > 0 for n, t in gaps)
    idle = got["window_s"] - got["busy_s"]
    assert sum(t for _, t in gaps) <= idle * (1 + 1e-9)


def test_missing_chip_is_an_error(planes):
    with pytest.raises(ValueError):
        devtrace.reduce_trace(planes, [0, 1])


def test_gap_found_inside_a_long_host_span():
    # gaps are named by the deepest host event at their middle, found even
    # after thousands of short host events inside one long span
    from types import SimpleNamespace as NS

    def ev(name, s, e):
        return NS(name=name, start_ns=s, duration_ns=e - s)
    host = [ev(devtrace.WINDOW, 0, 100_000), ev(devtrace.STEP, 10, 90_000)]
    host += [ev("dispatch", 20 + 10 * i, 25 + 10 * i) for i in range(5000)]  # to 50_015
    ops = [ev(f"%fusion.{i} = f32[8] fusion()", s, e)
           for i, (s, e) in enumerate([(5, 8), (50_020, 50_030), (80_000, 80_100)])]
    planes = [NS(name="/host:CPU", lines=[NS(name="python3", events=host)]),
              NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])]
    got = dict(devtrace.reduce_trace(planes, [0])["idle_gaps"])
    assert got == pytest.approx({"dispatch": (50_020 - 8) * 1e-9,
                                 devtrace.STEP: (80_000 - 50_030) * 1e-9,
                                 "(no host span)": (5 + 100_000 - 80_100) * 1e-9})
