"""The four-chip cell's mesh path on four virtual CPU devices: the trainer
is built, drawn and stepped on a mesh, its tables sharded by rows and each
batch laid out over the devices; the run follows the reference at the
global batch, and each fault the cell can have (a step that leaves the
state unchanged, half of the batch left out, the exchange between chips
left out) ends with ``correct`` false."""
import json
import os
import subprocess
import sys

import pytest

from .conftest import CHECKOUT


def run_child(fault=None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [CHECKOUT, os.path.join(CHECKOUT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.mesh_child"]
        + ([fault] if fault else []),
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mesh_run_matches_reference():
    out = run_child()
    assert out["chips"] == 4 and out["batch"] == 4 * 64
    assert out["correct"], out["checks"]
    # each device holds a quarter of every table, and a quarter of a batch
    assert out["table_devices"] == 4
    assert out["table_rows"] == [1250] * 4
    assert out["batch_rows"] == [64] * 4
    w = out["window"]
    assert w["steps"] > 0 and w["compiles"] == 0
    assert out["samples_per_s"] == pytest.approx(
        w["steps"] * 256 / w["seconds"])


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "no_exchange"])
def test_mesh_run_with_planted_fault(fault):
    out = run_child(fault)
    assert out["correct"] is False, out["checks"]
