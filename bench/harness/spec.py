"""What one cell is: its entry in ``BENCHMARK.json`` and the files it names.

A cell names a configuration (``bench/configs/<file>``) and a traffic mix
(``bench/traffic/<traffic>.json``, which names its generator, a module
beside it); the metrics that apply to it are the
entries of ``BENCHMARK.json`` that list it, or that list no cells at all.
Nothing here knows any cell by name.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    def batches(self, seed: int, start: int, n: int) -> list:
        """Batches ``start .. start + n - 1`` of this cell's stream, drawn
        by the generator its traffic mix names (``bench/traffic/<name>.py``)
        at the configuration's shapes."""
        gen = importlib.import_module(
            f"bench.traffic.{self.traffic['generator']}")
        cfg = self.config
        return gen.batches(cfg["model"], int(cfg["rows_per_field"]),
                           self.traffic,
                           int(self.traffic["batch_per_chip"]) * self.chips,
                           seed, start, n)


def load_benchmark(root: str = CHECKOUT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def resolve(name: str, root: str = CHECKOUT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)
