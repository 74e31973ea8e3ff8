"""The check fails what it must fail, at a size a CPU test run holds.

* the control: the reference computed in bfloat16, put in the program's
  place, fails at least one of each configuration's limits;
* planted faults (``bench/control.py``): a whole run, with everything but
  the look for a chip, over a program whose step leaves the state
  unchanged, or averages the loss over half of the batch, or (host_lru)
  whose host store drops the rows written back to it, ends with
  ``correct`` false; the sound program, in the same run, ends with
  ``correct`` true.
"""
import jax
import jax.numpy as jnp
import pytest

from bench import control
from bench import run as R
from bench.harness import compare, reference

from .conftest import CPU_PEAKS, small_cell

SEED = 4_000_000_011
CELLS = ["criteo-dense-zipf", "kwai-hostlru-zipf"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("case", [{"dtype": jnp.bfloat16},
                                  {"half_batch": True}],
                         ids=["control_bf16", "half_batch"])
def test_reference_variant_fails_the_limits(name, case):
    cell = small_cell(name, batch=512, rows=20000)
    cfg = cell.config
    batches = cell.batches(SEED, 0, cfg["check"]["steps"])
    ref = reference.Reference(cfg, SEED, cell.tower).run(batches)
    got = reference.Reference(cfg, SEED, cell.tower, **case).run(batches)
    ok, checks = compare.verdict(compare.gaps(got, ref),
                                 cfg["check"]["limits"])
    assert not ok, checks


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in CELLS
    for fault in (None, "unchanged_state", "half_batch")
] + [("kwai-hostlru-zipf", "drop_writeback")],
    ids=lambda x: x or "sound")
def test_run_with_planted_fault(name, fault, monkeypatch):
    hostlru = "hostlru" in name
    cell = small_cell(name, rows=5000, batch=64 if hostlru else 128,
                      cache_rows=700 if hostlru else None)
    if fault is not None:
        control.FAULTS[fault](monkeypatch.setattr)
    out = R.execute(cell, SEED, 0.3, False, jax.devices()[:1], CPU_PEAKS)
    assert out["correct"] is (fault is None), out["checks"]
