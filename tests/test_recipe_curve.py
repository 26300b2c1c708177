"""Golden-curve harness tests (VERDICT r3 #7): the recipe_curve tool's
record/check cycle is deterministic on CPU, and the committed PTB
fixture replays within tolerance (on the chip the same tool replays
BOTH legs with the fused kernels)."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "recipe_curve.py")


def _run(args):
    return subprocess.run(
        [sys.executable, _TOOL] + args, cwd=_REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=540,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


@pytest.mark.slow
def test_record_check_cycle_deterministic(tmp_path):
    """Same seeds -> identical trajectory -> check passes at tight tol.

    slow: records a 20-step ResNet recipe leg in a subprocess — several
    hundred seconds on a CPU-only box, the long-running-accuracy class
    the marker exists for."""
    fx = str(tmp_path / "fixtures")
    r = _run(["--record", "--leg", "resnet", "--steps", "20",
              "--fixtures", fx])
    assert r.returncode == 0, r.stdout[-1500:]
    with open(os.path.join(fx, "recipe_resnet.json")) as f:
        assert len(json.load(f)["losses"]) == 20
    c = _run(["--check", "--leg", "resnet", "--steps", "20",
              "--fixtures", fx, "--tol", "0.02"])
    assert c.returncode == 0, c.stdout[-1500:]
    assert "resnet curve OK" in c.stdout


@pytest.mark.slow
def test_committed_ptb_fixture_replays():
    """The committed short-horizon PTB perplexity checkpoint is
    reproducible on the CPU reference path."""
    c = _run(["--check", "--leg", "ptb", "--tol", "0.1"])
    assert c.returncode == 0, c.stdout[-1500:]
    assert "FAIL" not in c.stdout
