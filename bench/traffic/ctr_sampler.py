"""The one generator behind every CTR traffic mix.

A mix is a data file beside this one (``<name>.json``) that sets the batch
per chip, the id skew and the seed of the labelling truth; the model's
shape (dense features, tasks, and each field's rows and widest bag, as
``bench/harness/spec.fields`` reads them) comes from the configuration.
The arithmetic is a copy of the program's synthetic CTR stream
(``src/repro/data/ctr.py``: ``PlantedTruth`` and ``CTRDataset.sampler``),
kept here so that a change to the program cannot move the traffic it is
measured on:

* ids: bounded Zipf(``zipf_a``) ranks over each field's own rows, drawn by
  the inverse CDF, one id space per field;
* ragged multi-hot bags: each (sample, field) keeps a length uniform in
  ``1..hot`` of its field, the tail padded with -1 to the widest field's
  ``hot`` (``(B, F, L)``, the layout the program takes);
* dense features: standard normal;
* labels: Bernoulli draws from a planted logistic truth over hashed ids and
  dense features, keyed to ``truth_seed`` alone, so every stream labels
  from the same model.

A run's traffic is an unending stream: ``batches`` draws its batch ``i``
from a generator of its own, seeded with ``(seed, i)``, so any stretch of
the stream can be drawn ahead, in parallel, and the same seed gives the
same batch ``i`` however far a run gets.
"""
from __future__ import annotations

import concurrent.futures
import os

import numpy as np

from bench.harness import spec


class PlantedTruth:
    """Bucket effects over ids mod 256 plus dense-feature effects, squashed
    through a sigmoid with bias ``bias`` (about 25% positives at 1.0)."""

    def __init__(self, seed: int, n_fields: int, n_dense: int,
                 n_tasks: int = 1, bias: float = 1.0):
        rng = np.random.default_rng(seed)     # draw order fixes the weights
        self.w_buckets = rng.standard_normal((n_fields, 256)) \
            .astype(np.float32)
        self.w_dense = rng.standard_normal((max(n_dense, 1), n_tasks)) \
            .astype(np.float32)
        self.w_field = rng.standard_normal((n_fields, n_tasks)) \
            .astype(np.float32)
        self.bias = float(bias)

    def prob(self, ids: np.ndarray, dense: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        n_fields = self.w_buckets.shape[0]
        mask = ids >= 0
        bucket = self.w_buckets[np.arange(n_fields)[None, :, None],
                                np.where(mask, ids, 0) % 256]
        bucket = np.where(mask, bucket, 0.0)
        sig = (bucket.sum(-1) @ self.w_field) / np.sqrt(n_fields)
        nd = self.w_dense.shape[0]
        sig = sig + (np.asarray(dense, np.float32)[:, :nd]
                     @ self.w_dense) / np.sqrt(nd)
        return 1.0 / (1.0 + np.exp(-(sig - self.bias)))


def truth(model: dict, mix: dict) -> PlantedTruth:
    return PlantedTruth(int(mix["truth_seed"]), model["n_id_fields"],
                        model["n_dense_features"], model["n_tasks"])


def draw(rng, truth: PlantedTruth, model: dict, fields: list, mix: dict,
         batch: int) -> dict:
    """One batch ``{"ids" (B, F, L) int32, "labels" (B, T) float32,
    "dense" (B, n_dense) float32}`` from ``rng``; ``fields`` as
    ``spec.fields`` gives them."""
    n_fields = len(fields)
    rows = _per_field([f["rows"] for f in fields], (1, n_fields, 1))
    hot = _per_field([f["hot"] for f in fields], (1, n_fields))
    width = max(f["hot"] for f in fields)
    n_dense = model["n_dense_features"]
    n_tasks = model["n_tasks"]
    a = float(mix["zipf_a"])
    u = rng.random((batch, n_fields, width))
    ranks = np.floor(((rows ** (1 - a) - 1) * u + 1) ** (1 / (1 - a)) - 1)
    ids = np.clip(ranks, 0, rows - 1).astype(np.int64)
    lens = rng.integers(1, hot + 1, (batch, n_fields))
    mask = np.arange(width)[None, None, :] < lens[:, :, None]
    ids = np.where(mask, ids, -1)
    dense = rng.standard_normal((batch, max(n_dense, 1))).astype(np.float32)
    prob = truth.prob(ids, dense)
    labels = (rng.random((batch, n_tasks)) < prob).astype(np.float32)
    out = {"ids": ids.astype(np.int32), "labels": labels}
    if n_dense:
        out["dense"] = dense[:, :n_dense]
    return out


def _per_field(values: list, shape: tuple):
    """One number where every field has the same, else one per field,
    shaped to broadcast over ``(B, F, L)``; both draw the same bits
    (``bench/tests/test_sampler.py``). The scalar is kept for set-up:
    numpy's scalar paths of ``clip`` and ``integers`` draw a 4096-sample
    batch in 22 ms against 28 ms with per-field arrays at criteo-dlrm's
    shapes, and 83 against 94 ms at kwai-dlrm's (one core of an Intel
    Xeon, medians of 60 batches). Drop one branch once a configuration
    with per-field values shows what the arrays cost there."""
    if len(set(values)) == 1:
        return values[0]
    return np.array(values, np.int64).reshape(shape)


def stream(config: dict, mix: dict, batch: int, seed):
    """Infinite generator of batches from one generator seeded with
    ``seed``: the program's own synthetic stream, draw for draw."""
    t = truth(config["model"], mix)
    fields = spec.fields(config)
    rng = np.random.default_rng(seed)
    while True:
        yield draw(rng, t, config["model"], fields, mix, batch)


def batches(config: dict, mix: dict, batch: int, seed: int, start: int,
            n: int) -> list:
    """Batches ``start .. start + n - 1`` of the run's stream; batch ``i``
    is the first batch of ``stream(..., seed=[seed, i])``. Drawn on a few
    threads (numpy lets go of the interpreter lock inside its array
    operations)."""
    t = truth(config["model"], mix)
    fields = spec.fields(config)

    def one(i):
        return draw(np.random.default_rng([int(seed), int(i)]), t,
                    config["model"], fields, mix, batch)

    workers = max(1, min(8, (os.cpu_count() or 1) - 1, n))
    with concurrent.futures.ThreadPoolExecutor(workers) as ex:
        return list(ex.map(one, range(start, start + n)))
