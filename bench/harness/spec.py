"""What one cell is: its entry in ``BENCHMARK.json`` and the files it names.

A cell names a configuration (``bench/configs/<file>``) and a traffic mix
(``bench/traffic/<traffic>.json``, which names its generator, a module
beside it); the configuration names its tower's plain reference
(``bench/towers/<reference_tower>.py``, ``mlp`` where it names none); the
metrics that apply to it are the entries of ``BENCHMARK.json`` that list
it, or that list no cells at all. Nothing here knows any cell by name.

A configuration describes its sparse fields either by one list,
``"fields": [{"rows", "hot", "backend", "cache_rows"}, ...]``, one object
per field in field order, or by the uniform keys ``rows_per_field``,
``model.ids_per_field``, ``tables.backend`` and ``cache_rows``, which
``fields`` expands into that list.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
DEFAULT_TOWER = "mlp"


def fields(config: dict) -> list:
    """One ``{"rows", "hot", "backend", "cache_rows"}`` per sparse field:
    the configuration's ``fields`` list, or its uniform keys expanded.
    ``hot`` is the field's largest bag; ``cache_rows`` is None for a table
    that keeps every row on the device."""
    if "fields" in config:
        out = [{"rows": int(f["rows"]), "hot": int(f["hot"]),
                "backend": f["backend"],
                "cache_rows": None if f.get("cache_rows") is None
                else int(f["cache_rows"])} for f in config["fields"]]
        if len(out) != config["model"]["n_id_fields"]:
            raise ValueError(f"{config['name']}: {len(out)} fields listed, "
                             f"model.n_id_fields is "
                             f"{config['model']['n_id_fields']}")
        return out
    cache = config.get("cache_rows")
    return [{"rows": int(config["rows_per_field"]),
             "hot": int(config["model"]["ids_per_field"]),
             "backend": config["tables"]["backend"],
             "cache_rows": None if cache is None else int(cache)}
            for _ in range(config["model"]["n_id_fields"])]


def field_ids(ids, f: int):
    """Field ``f``'s bags, ``(B, L_f)``, of a batch's ``ids``: either one
    ``(B, F, L)`` array (bags padded with -1 to one width) or a list of
    per-field ``(B, L_f)`` arrays."""
    if isinstance(ids, (list, tuple)):
        return ids[f]
    return ids[:, f]


def tower_input(config: dict) -> int:
    """Width of the tower's input: the pooled fields and the dense
    features, concatenated."""
    m = config["model"]
    return m["n_id_fields"] * m["emb_dim"] + m["n_dense_features"]


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` (a generator, a tower, a
    metric's reader), found by its name."""
    return importlib.import_module(f"bench.{kind}.{name}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def batch(self) -> int:
        """Samples per step over all the cell's chips."""
        return int(self.traffic["batch_per_chip"]) * self.chips

    @property
    def tower(self):
        """The module of the tower's plain reference and work count."""
        return load("towers", self.config.get("reference_tower",
                                              DEFAULT_TOWER))

    def batches(self, seed: int, start: int, n: int) -> list:
        """Batches ``start .. start + n - 1`` of this cell's stream, drawn
        by the generator its traffic mix names (``bench/traffic/<name>.py``)
        at the configuration's shapes and the cell's global batch."""
        gen = load("traffic", self.traffic["generator"])
        return gen.batches(self.config, self.traffic, self.batch, seed,
                           start, n)


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
