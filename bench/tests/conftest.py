"""Helpers for the benchmark's CPU tests: the program on the path, and the
cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds.

    PYTHONPATH=src python -m pytest bench/tests -q
"""
import copy
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (CHECKOUT, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# CPU limits for the tests: the program and the reference both run float32
# on the CPU, where matmuls are exact float32, so they agree to rounding.
CPU_LIMITS = {"loss_gap": 1e-5, "grad1_gap": 1e-4, "change_gap": 1e-4}


def small_cell(name: str, fields: int = 3,
               rows: int = 5000, cache_rows: int | None = None,
               batch: int = 128, limits: dict | None = None,
               max_inflight: int | None = None):
    """A cell of ``BENCHMARK.json`` with its configuration's shapes cut:
    fewer fields, a small tower, few rows, a small batch."""
    from bench.harness import spec
    cell = spec.resolve(name)
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(n_id_fields=fields, mlp_dims=[64, 32])
    cfg["rows_per_field"] = rows
    if "cache_rows" in cfg:
        cfg["cache_rows"] = cache_rows or rows // 2
    if max_inflight is not None:
        cfg["trainer"]["max_inflight"] = max_inflight
    if limits is not None:
        cfg["check"]["limits"] = dict(limits)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, batch_per_chip=batch)
    return cell


CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
