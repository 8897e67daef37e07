import os
import re
import shutil
import sys

import pytest

# Make `repro` importable without installation (PYTHONPATH=src also works).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Keep the default 1-device view for smoke tests and benches. The multi-pod
# dry-run (launch/dryrun.py) sets XLA_FLAGS itself in a fresh process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The jit planes turn on JAX's persistent compilation cache inside the
# checkout; tests (and the worker processes they start) write none of it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


@pytest.fixture
def ckpt_dir(tmp_path, request):
    """Checkpoint directory for the recovery tests.

    Defaults to a per-test tmp dir. With ``REPRO_CKPT_ARTIFACT_DIR`` set
    (CI does this), checkpoints land under that root keyed by test id, so
    a failing run's checkpoint files can be uploaded as a CI artifact for
    post-mortem restore."""
    base = os.environ.get("REPRO_CKPT_ARTIFACT_DIR")
    if not base:
        return str(tmp_path / "ckpts")
    safe = re.sub(r"[^A-Za-z0-9_.-]+", "_", request.node.nodeid)[-120:]
    path = os.path.join(base, safe)
    # Hermetic per run: drop checkpoints left by a previous invocation (CI
    # runs the recovery slice and then the full fast sweep against the same
    # root) while keeping this run's files around for post-failure upload.
    if os.path.isdir(path):
        shutil.rmtree(path)
    os.makedirs(path)
    return path
