"""Helpers for the benchmark's CPU tests: the program on the path, and the
cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds.

    PYTHONPATH=src python -m pytest bench/tests -q
"""
import copy
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
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


def fixture_cell(config: str, mix: str, batch: int, rows: int | None = None,
                 fields: list | None = None, mix_keys: dict | None = None,
                 limits: dict | None = None):
    """A cell of a configuration and a mix under ``bench/tests/fixtures/``
    (not in ``BENCHMARK.json``), on one chip with a small tower: only the
    fields at the indices ``fields``, each table cut to ``rows`` rows."""
    from bench.harness import spec
    with open(os.path.join(FIXTURES, config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(FIXTURES, mix + ".json")) as f:
        traffic = json.load(f)
    if fields is not None:
        cfg["fields"] = [cfg["fields"][i] for i in fields]
    for f in cfg["fields"]:
        f["rows"] = min(f["rows"], rows or f["rows"])
    cfg["model"].update(n_id_fields=len(cfg["fields"]), mlp_dims=[64, 32])
    if limits is not None:
        cfg["check"]["limits"] = dict(limits)
    traffic.update(mix_keys or {}, batch_per_chip=batch)
    return spec.Cell(name=cfg["name"], chips=1, config=cfg, traffic=traffic,
                     end_to_end=[], per_layer=[])


CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
