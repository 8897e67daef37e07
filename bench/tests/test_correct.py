"""The comparison that decides ``correct``, driven end to end on the CPU at
a small size: a sound run of each cell comes out correct, and the same run
with the timed path broken underneath (each fault the cell can have), or
with the bfloat16 reference in the program's place, comes out not correct.

    python -m pytest bench/tests
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BATCH, SECONDS, SEED = 128, 1.5, 2**31 + 11


def run(cell: str, fault: str, *draw: int) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_run.py"), cell, fault,
         str(BATCH), str(SECONDS), str(SEED), *map(str, draw)],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


CASES = [
    ("riot21.steady", "none", (), True),
    ("riot21.steady", "state_unchanged", (), False),
    ("riot21.steady", "half_batch", (), False),
    ("riot21.steady", "altered", (), False),
    ("riot21.steady", "control", (), False),
    ("opmw35.churn", "none", (), True),
    ("opmw35.churn", "state_unchanged", (), False),
    ("opmw35.churn", "half_batch", (), False),
    ("opmw35.churn", "altered", (), False),
    ("opmw35.churn", "control", (), False),
    ("riot21.churn", "none", (), True),
    ("riot21.churn", "state_unchanged", (), False),
    ("riot21.churn", "half_batch", (), False),
    ("riot21.churn", "altered", (), False),
    ("riot21.churn", "control", (), False),
    ("riot21_x4.steady", "none", (), True),
    ("riot21_x4.steady", "no_exchange", (), False),
    # The churn cell fixes its draws of the swaps; the check holds on others.
    *[("opmw35.churn", fault, (draw,), fault == "none")
      for draw in (1, 2, 3) for fault in ("none", "control")],
]


@pytest.mark.parametrize("cell,fault,draw,correct", CASES)
def test_correct(cell, fault, draw, correct):
    result = run(cell, fault, *draw)
    assert result["correct"] is correct, result["checks"]
