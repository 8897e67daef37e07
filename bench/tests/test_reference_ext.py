"""A configuration's own reference semantics (its ``"reference"`` module):
the plain reference walks chains of the module's types as it walks its own,
refuses a module that redefines one of its types, and judges a whole run of
such a configuration on the CPU (``toy_run.py``). No reference module
imports the program. Runs on the CPU.

    python -m pytest bench/tests/test_reference_ext.py
"""
import ast
import glob
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import reference  # noqa: E402
from lib.cell import reference_module  # noqa: E402

TOY = reference_module({"name": "toy", "reference": "bench/tests/toy_reference.py"})
BATCH = 64
FLOWS = {
    "a": reference.Flow("a", "ramp", [("toy_drift", {}), ("toy_scale", {"f": 2})], "store"),
    "b": reference.Flow("b", "ramp", [("toy_drift", {}), ("toy_scale", {"f": 3})], "store"),
    "d": reference.Flow("d", "ramp:2", [("toy_drift", {})], "store"),
}
# b joins a's running prefix (source and drift) at step 3 and keeps it after
# a leaves; d is removed and submitted again, which starts it anew.
LOG = [(0, "submit", "a"), (3, "submit", "b"), (5, "submit", "d"), (6, "remove", "a"),
       (8, "remove", "d"), (8, "submit", "d")]
STEPS = 12
STARTS = {("ramp", 0): 1000, ("ramp:2", 5): 77, ("ramp:2", 8): 5}


def plain(flows, log, steps, batch, starts):
    """The toy semantics event by event in float64, (B, 8) per batch: every
    running task keyed by its prefix, stepped once per step."""
    users, state, born, running, sinks = {}, {}, {}, {}, {}
    c = np.arange(1, 6)
    for s in range(steps):
        for step, op, name in log:
            if step != s:
                continue
            keys = flows[name].prefixes()
            if op == "submit":
                running[name] = keys
                for k in keys:
                    if users.get(k, 0) == 0:
                        born[k], state[k] = s, None
                    users[k] = users.get(k, 0) + 1
            else:
                del running[name]
                for k in keys:
                    users[k] -= 1
                    if users[k] == 0:
                        del born[k], state[k]
                        sinks.pop(k, None)
        out = {}
        for keys in running.values():
            for k in keys:
                if k in out:
                    continue
                typ, cfg = k[-1]
                if typ == "source":
                    counter = starts.get((cfg, born[k]), 0) + s - born[k]
                    t = counter + np.arange(batch) / batch
                    x = np.zeros((batch, 8))
                    x[:, 0], x[:, 1:6], x[:, 6] = t, c * np.sin(0.01 * c * t[:, None]), 1.0
                    x[:, 7] = counter * batch + np.arange(batch)
                    out[k] = x
                    continue
                x = out[k[:-1]].copy()
                if typ == "sink":
                    n, total, _ = sinks.get(k, (0, 0.0, None))
                    sinks[k] = (n + 1, total * 0.5 + x.sum(), x)
                elif typ == "toy_scale":
                    x[:, 1:6] *= json.loads(cfg)["f"]
                elif typ == "toy_drift":
                    state[k] = (0.0 if state[k] is None else state[k]) + x[:, 1:6].mean(axis=0)
                    x[:, 1:6] += state[k]
                out[k] = x
    return {name: sinks[keys[-1]] for name, keys in running.items()}


@pytest.mark.parametrize("block", [4, 16])
def test_run_reference_follows_the_module_semantics(block):
    want = plain(FLOWS, LOG, STEPS, BATCH, STARTS)
    got = reference.run_reference(FLOWS, LOG, STEPS, BATCH, STARTS, block=block, extra=TOY)
    assert sorted(got) == sorted(want) == ["b", "d"]
    for name, (count, checksum, last) in want.items():
        assert got[name]["count"] == count
        assert got[name]["checksum"] == pytest.approx(checksum, rel=1e-5)
        np.testing.assert_allclose(got[name]["last"], last, rtol=1e-5, atol=1e-3)
    assert (want["b"][0], want["d"][0]) == (9, 4)


def test_without_the_module_the_types_are_unknown():
    with pytest.raises(KeyError, match="toy_drift"):
        reference.run_reference(FLOWS, LOG, STEPS, BATCH, STARTS)


@pytest.mark.parametrize("extra", [
    NS(TASKS={"kalman": TOY.toy_scale}),
    NS(TASKS={}, SOURCES={"urban": TOY.ramp}),
    NS(TASKS={}, SOURCES={"taxi:nyc": TOY.ramp}),
], ids=["task", "source", "source_suffix"])
def test_a_module_may_not_redefine_a_known_type(extra):
    with pytest.raises(ValueError, match="redefines"):
        reference.run_reference(FLOWS, LOG, STEPS, BATCH, STARTS, extra=extra)


@pytest.mark.parametrize("fault,correct", [("none", True), ("off", False)])
def test_a_run_is_judged_by_the_module(fault, correct):
    out = subprocess.run([sys.executable, os.path.join(HERE, "toy_run.py"), fault, "1.5",
                          str(2**31 + 29)], capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["run"]["swaps_in_window"] > 0
    assert result["correct"] is correct, result["checks"]


def _reference_files():
    files = [os.path.join(BENCH, "lib", "reference.py"), os.path.join(HERE, "toy_reference.py")]
    for path in sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))):
        with open(path) as f:
            named = json.load(f).get("reference")
        if named:
            files.append(os.path.join(ROOT, named))
    return files


@pytest.mark.parametrize("path", _reference_files(), ids=lambda p: os.path.relpath(p, ROOT))
def test_a_reference_imports_nothing_of_the_program(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n == "repro" or n.startswith("repro.")], names
