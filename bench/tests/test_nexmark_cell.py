"""``nexmark.steady`` judged end to end on the CPU at a small size: a sound
run is correct, and each planted fault is not: Q5 counts one too high, pane
and window boundaries one event late, and the bfloat16 reference in the
program's place. Runs at 128 events per step with the window tasks' rate
at 26 events/s (130-event panes), so a window closes about every step and
the last ones weigh in the sinks' checksums; the seed starts the source at
counter 29, where a row's window index and auctions are small against a
count of one. Each case runs ``nexmark_run.py`` in a process of its own.

    python -m pytest bench/tests/test_nexmark_cell.py
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BATCH, RATE, SECONDS, SEED = 128, 26, 1.5, 2147484434


@pytest.mark.parametrize("fault,correct", [("none", True), ("count_off", False),
                                           ("pane_late", False), ("control", False)])
def test_nexmark_steady_is_judged(fault, correct):
    out = subprocess.run([sys.executable, os.path.join(HERE, "nexmark_run.py"), fault,
                          str(BATCH), str(RATE), str(SECONDS), str(SEED)],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is correct, result["checks"]
