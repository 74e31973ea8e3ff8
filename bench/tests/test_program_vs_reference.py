"""The program against the plain reference on the CPU, at a small size, on
each path a cell drives: the fused dense step, and the pipelined host_lru
trainer with a cache smaller than the rows its compared steps touch."""
import jax
import numpy as np

from bench import run as R
from bench.harness import compare, program, reference

from .conftest import CPU_LIMITS, CPU_PEAKS, small_cell

SEED = 2**31 + 101
# the compared steps touch about 750 rows per table: the last four steps'
# rows fit this cache, the twelve steps' do not
CACHE_ROWS = 700


def test_fused_dense_matches_reference():
    cell = small_cell("criteo-dense-zipf", limits=CPU_LIMITS)
    out = R.execute(cell, SEED, 0.5, False, jax.devices()[:1], CPU_PEAKS)
    assert out["correct"], out["checks"]
    assert out["window"]["steps"] > 0
    assert out["window"]["compiles"] == 0
    assert out["compared"]["leaves_left_out"] == []
    assert out["compared"]["stored_rows"] == 0


def test_host_lru_fault_in_and_write_back_match_reference():
    """The compared steps, at tau = 3, overflow the device cache: rows are
    faulted in, the least recent are evicted and written back to the host
    store once their queued puts have been applied, and both the tables and
    the rows read back from the host store follow the reference."""
    cell = small_cell("kwai-hostlru-zipf", rows=5000, cache_rows=CACHE_ROWS,
                      batch=64, limits=CPU_LIMITS)
    cfg = cell.config
    assert cfg["tables"]["staleness"] == 3
    batches = cell.batches(SEED, 0, cfg["check"]["steps"])
    system = program.System(cfg, 64)
    system.init(SEED, batches[0])
    prog = R.compared_steps(system, batches, cfg["model"]["n_id_fields"])
    c = system.counters()
    assert c["writebacks"] > 0 and c["faults"] > c["writebacks"]
    stored = {k: len(x) for k, (_, x) in prog["subsets"].items()}
    assert len(stored) == cfg["model"]["n_id_fields"]
    assert min(stored.values()) > 0
    system.free()
    ref = reference.Reference(cfg, SEED, cfg["rows_per_field"]).run(
        batches, prog["subsets"])
    g = compare.gaps(prog, ref)
    assert all(ref["change"][k] > 0 for k in prog["subsets"])
    ok, checks = compare.verdict(g, cfg["check"]["limits"])
    assert ok, checks


def test_pipelined_host_lru_run_matches_reference():
    cell = small_cell("kwai-hostlru-zipf", rows=5000, cache_rows=CACHE_ROWS,
                      batch=64, limits=CPU_LIMITS)
    out = R.execute(cell, SEED, 0.5, False, jax.devices()[:1], CPU_PEAKS)
    assert out["correct"], out["checks"]
    assert out["window"]["steps"] > 0
    assert out["compared"]["stored_rows"] > 0


def test_reference_subset_is_the_rows_change():
    """A subset leaf of the reference is the change of just those rows."""
    cell = small_cell("kwai-hostlru-zipf", rows=5000, batch=64)
    cfg = cell.config
    batches = cell.batches(SEED, 0, 4)
    ids = reference.touched(batches, cfg["model"]["n_id_fields"])
    every = ids[1]
    half = every[::2]
    ref = reference.Reference(cfg, SEED, cfg["rows_per_field"]).run(
        batches, {"all": (1, every), "half": (1, half), "none": (1, half[:0])})
    assert np.isclose(ref["change"]["all"], ref["change"]["emb/field_01"])
    assert 0 < ref["change"]["half"] < ref["change"]["all"]
    assert ref["change"]["none"] == 0.0
