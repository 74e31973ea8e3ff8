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
* multi-hot bags, by the mix's ``bags``: ``"ragged"`` (the default) keeps
  for each (sample, field) a length uniform in ``1..hot`` of its field;
  ``"fixed"`` fills every bag to its field's ``hot`` and draws no length;
* the layout, by the mix's ``layout``: ``"padded"`` (the default) gives one
  ``(B, F, L)`` array, each bag's tail padded with -1 to the widest field's
  ``hot`` (the layout the program takes); ``"per_field"``, with fixed bags
  only, gives a list of ``(B, hot_f)`` arrays, each field drawn at its own
  width, in field order;
* dense features: standard normal;
* labels: Bernoulli draws from a planted logistic truth over hashed ids and
  dense features, keyed to ``truth_seed`` alone, so every stream labels
  from the same model, each field's effects summed over its own bags.

A mix without ``bags`` and ``layout`` draws what the program's stream
draws. A run's traffic is an unending stream: ``batches`` draws its batch
``i`` from a generator of its own, seeded with ``(seed, i)``, so any stretch
of the stream can be drawn ahead, in parallel, and the same seed gives the
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

    def prob(self, ids, dense: np.ndarray) -> np.ndarray:
        """Click probability per sample and task. ``ids`` is one
        ``(B, F, L)`` array, summed in whole-array operations, or a list of
        per-field ``(B, L_f)`` arrays, each field summed at its own width:
        padding DLRM-DCNv2's 214 ids to 2,600 slots for the array's path
        takes 268.7 against 15.1 ms a 4096-sample batch (one core of an
        Intel Xeon), and a loop over fields on arrays holds the interpreter
        lock on small operations that the drawing threads otherwise run in
        parallel (twice the time of 300 criteo-dlrm batches on 7 threads)."""
        n_fields = self.w_buckets.shape[0]
        if isinstance(ids, (list, tuple)):
            bucket = np.empty((len(dense), n_fields), np.float32)
            for f, x in enumerate(ids):
                x = np.asarray(x, np.int64)
                bucket[:, f] = np.where(
                    x >= 0, self.w_buckets[f, np.where(x >= 0, x, 0) % 256],
                    0.0).sum(-1)
        else:
            ids = np.asarray(ids, np.int64)
            mask = ids >= 0
            bucket = self.w_buckets[np.arange(n_fields)[None, :, None],
                                    np.where(mask, ids, 0) % 256]
            bucket = np.where(mask, bucket, 0.0).sum(-1)
        sig = (bucket @ self.w_field) / np.sqrt(n_fields)
        nd = self.w_dense.shape[0]
        sig = sig + (np.asarray(dense, np.float32)[:, :nd]
                     @ self.w_dense) / np.sqrt(nd)
        return 1.0 / (1.0 + np.exp(-(sig - self.bias)))


def truth(model: dict, mix: dict) -> PlantedTruth:
    return PlantedTruth(int(mix["truth_seed"]), model["n_id_fields"],
                        model["n_dense_features"], model["n_tasks"])


# (layout, bags) pairs a mix may name; per-field lists come with full bags
DRAWS = (("padded", "ragged"), ("padded", "fixed"), ("per_field", "fixed"))


def draw(rng, truth: PlantedTruth, model: dict, fields: list, mix: dict,
         batch: int) -> dict:
    """One batch ``{"ids", "labels" (B, T) float32, "dense" (B, n_dense)
    float32}`` from ``rng``; ``ids`` int32 in the mix's layout, bags by its
    ``bags``; ``fields`` as ``spec.fields`` gives them."""
    layout = mix.get("layout", "padded")
    bags = mix.get("bags", "ragged")
    if (layout, bags) not in DRAWS:
        raise ValueError(f"mix {mix.get('name')!r}: layout {layout!r} with "
                         f"bags {bags!r}; (layout, bags) one of {DRAWS}")
    a = float(mix["zipf_a"])
    if layout == "padded":
        n_fields = len(fields)
        rows = _per_field([f["rows"] for f in fields], (1, n_fields, 1))
        hot = _per_field([f["hot"] for f in fields], (1, n_fields))
        width = max(f["hot"] for f in fields)
        # ``u`` lives to the end of the draw: freed inside ``_zipf``
        # instead, 300 criteo-dlrm batches took 15-20% longer on 7 threads
        u = rng.random((batch, n_fields, width))
        ids = _zipf(u, rows, a)
        if bags == "ragged":
            lens = rng.integers(1, hot + 1, (batch, n_fields))
        else:
            lens = np.broadcast_to(hot, (batch, n_fields))
        mask = np.arange(width)[None, None, :] < lens[:, :, None]
        ids = np.where(mask, ids, -1)
    else:
        ids = [_zipf(rng.random((batch, f["hot"])), f["rows"], a)
               for f in fields]
    n_dense = model["n_dense_features"]
    dense = rng.standard_normal((batch, max(n_dense, 1))).astype(np.float32)
    prob = truth.prob(ids, dense)
    labels = (rng.random((batch, model["n_tasks"])) < prob) \
        .astype(np.float32)
    out = {"ids": ids.astype(np.int32) if layout == "padded"
           else [x.astype(np.int32) for x in ids], "labels": labels}
    if n_dense:
        out["dense"] = dense[:, :n_dense]
    return out


def _zipf(u: np.ndarray, rows, a: float) -> np.ndarray:
    """Bounded Zipf(``a``) ranks over ``rows`` from uniforms ``u``, by the
    inverse CDF."""
    ranks = np.floor(((rows ** (1 - a) - 1) * u + 1) ** (1 / (1 - a)) - 1)
    return np.clip(ranks, 0, rows - 1).astype(np.int64)


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
