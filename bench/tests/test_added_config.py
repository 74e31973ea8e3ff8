"""A configuration added as files alone: an entry in ``BENCHMARK.json``, a
configuration whose ``fields`` list mixes dense device tables with a
host_lru table behind a device cache, each field with its own rows and
widest bag, and a tower module of its own named by ``reference_tower``.
Written into a copy of the benchmark and run there, in a process of its
own (``python -m bench.tests.test_added_config`` from the copy), it runs
program against reference with nothing under ``bench/harness/`` edited."""
import json
import os
import shutil
import subprocess
import sys

import jax

from bench import run as R
from bench.harness import spec

from .conftest import CPU_LIMITS, CPU_PEAKS

SEED = 2**31 + 29
FIELDS = [{"rows": 3000, "hot": 1, "backend": "dense"},
          {"rows": 5000, "hot": 5, "backend": "host_lru", "cache_rows": 520},
          {"rows": 2000, "hot": 5, "backend": "dense"}]


def add_files(root: str):
    """Copy the benchmark to ``root`` and add a cell, its configuration and
    its tower there, as a later change would add them."""
    bench = os.path.join(root, "bench")
    shutil.copytree(spec.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "mixed-dlrm", "source": "a test",
                          "file": "bench/configs/mixed-dlrm.json",
                          "reduced": [], "why": "per-field placement"})
    bm["workloads"].append({"name": "mixed-fields", "config": "mixed-dlrm",
                            "traffic": "zipf1.2-b4096", "chips": 1,
                            "why": "dense and host_lru fields in one step"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    with open(os.path.join(bench, "configs", "criteo-dlrm.json")) as f:
        cfg = json.load(f)
    for k in ("rows_per_field", "emb_rows", "emb_params"):
        del cfg[k]
    del cfg["tables"]["backend"]
    cfg.update(name="mixed-dlrm", fields=FIELDS,
               reference_tower="mlp_by_name")
    cfg["model"].update(n_id_fields=3, ids_per_field=5, emb_dim=16,
                        mlp_dims=[64, 32])
    # eight compared steps touch ~730 rows of the host_lru field, any four
    # in a row (the steps a put spends in the tau = 3 queue) under 470:
    # its 520-slot cache evicts, and never a row with a put still queued
    cfg["check"].update(steps=8, limits=dict(CPU_LIMITS))
    with open(os.path.join(bench, "configs", "mixed-dlrm.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(bench, "towers", "mlp.py"),
                os.path.join(bench, "towers", "mlp_by_name.py"))


def main() -> dict:
    """The added cell, run from the checkout this module was imported
    from: its layout, and the verdict of program against reference."""
    cell = spec.resolve("mixed-fields")
    cell.traffic = dict(cell.traffic, batch_per_chip=128)
    b = cell.batches(SEED, 0, 1)[0]
    out = R.execute(cell, SEED, 0.3, False, jax.devices()[:1], CPU_PEAKS)
    return {"checkout": spec.CHECKOUT, "tower": cell.tower.__file__,
            "backends": [f["backend"] for f in spec.fields(cell.config)],
            "ids_shape": list(b["ids"].shape),
            "correct": out["correct"], "checks": out["checks"],
            "steps": out["window"]["steps"],
            "leaves_left_out": out["compared"]["leaves_left_out"],
            "stored_rows": out["compared"]["stored_rows"]}


def test_config_added_as_files_runs_against_reference(tmp_path):
    root = str(tmp_path)
    add_files(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(spec.CHECKOUT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.test_added_config"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert os.path.realpath(out["checkout"]) == os.path.realpath(root)
    assert os.path.realpath(out["tower"]) == os.path.realpath(
        os.path.join(root, "bench", "towers", "mlp_by_name.py"))
    assert out["backends"] == ["dense", "host_lru", "dense"]
    assert out["ids_shape"] == [128, 3, 5]
    assert out["correct"], out["checks"]
    assert out["steps"] > 0
    assert out["leaves_left_out"] == []
    # the host_lru field's cache overflowed: rows were written back to its
    # host store and compared from there
    assert out["stored_rows"] > 0


if __name__ == "__main__":
    print(json.dumps(main()))
