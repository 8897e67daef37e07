"""The step's roofline share (``metrics/ops.step_roofline.py``) against its
definition: least bytes over the bandwidth of the chips used, over the busy
time per step, which the trace reduction averages over the chips. Runs on the
CPU.

    python -m pytest bench/tests/test_roofline.py
"""
import importlib.util
import os
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "step_roofline", os.path.join(BENCH, "metrics", "ops.step_roofline.py"))
roofline = importlib.util.module_from_spec(spec)
spec.loader.exec_module(roofline)

KIND = "TPU v5 lite"
SINKS, BATCH = 21, 16384


def ctx(chips: int, busy_s: float, steps: int = 1):
    return NS(devtrace={"busy_s": busy_s, "steps": steps}, batch=BATCH,
              session=NS(names=[f"f{i}" for i in range(SINKS)]),
              run=NS(devices=[NS(device_kind=KIND)] * chips))


def test_one_chip():
    least_s = SINKS * BATCH * 32 / 819e9
    assert roofline.read(ctx(1, 0.160)) == pytest.approx(100 * least_s / 0.160)


def test_work_spread_over_four_chips_reads_the_same_share():
    # the same device work, spread evenly: each chip is busy a quarter as long
    assert roofline.read(ctx(4, 0.040)) == pytest.approx(roofline.read(ctx(1, 0.160)))


def test_nothing_to_read():
    assert roofline.read(NS(devtrace=None)) is None
    assert roofline.read(ctx(1, 0.0)) is None


def test_unknown_device_is_an_error():
    c = ctx(1, 0.1)
    c.run.devices = [NS(device_kind="no such chip")]
    with pytest.raises(KeyError):
        roofline.read(c)
