"""The benchmark's copy of the traffic generator draws what the program's
own synthetic stream draws for the same seed."""
import json
import os

import numpy as np
import pytest

from bench.harness import spec
from bench.traffic import ctr_sampler

BENCH = os.path.dirname(os.path.dirname(__file__))


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("config", ["criteo-dlrm", "kwai-dlrm"])
@pytest.mark.parametrize("seed", [0, 1234, 2**31 + 17])
def test_same_batches_as_program_stream(config, seed):
    from repro.data.ctr import CTRDataset
    cfg = load("configs", config + ".json")
    mix = load("traffic", "zipf1.2-b4096.json")
    m = cfg["model"]
    rows = cfg["rows_per_field"]
    ds = CTRDataset("bench", n_rows=rows * m["n_id_fields"],
                    n_fields=m["n_id_fields"],
                    ids_per_field=m["ids_per_field"],
                    n_dense=m["n_dense_features"], n_tasks=m["n_tasks"],
                    zipf_a=mix["zipf_a"], seed=mix["truth_seed"])
    assert ds.rows_per_field == rows
    theirs = ds.sampler(256, seed=seed)
    ours = ctr_sampler.stream(cfg, mix, 256, seed)
    for _ in range(3):
        a, b = next(theirs), next(ours)
        assert sorted(a) == sorted(b)
        for k in a:
            assert a[k].dtype == b[k].dtype
            np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("seed", [7, 2**31 + 17])
def test_stream_positions_are_program_streams(seed):
    """Batch ``i`` of a run's stream is the first batch of the program's
    stream seeded with ``[seed, i]``, whatever stretch it is drawn in."""
    from repro.data.ctr import CTRDataset
    cfg = load("configs", "kwai-dlrm.json")
    mix = load("traffic", "zipf1.2-b4096.json")
    m = cfg["model"]
    rows = cfg["rows_per_field"]
    ds = CTRDataset("bench", n_rows=rows * m["n_id_fields"],
                    n_fields=m["n_id_fields"],
                    ids_per_field=m["ids_per_field"],
                    n_dense=m["n_dense_features"], n_tasks=m["n_tasks"],
                    zipf_a=mix["zipf_a"], seed=mix["truth_seed"])
    ours = ctr_sampler.batches(cfg, mix, 64, seed, 3, 4)
    assert len(ours) == 4
    for i, b in zip(range(3, 7), ours):
        a = next(ds.sampler(64, seed=[seed, i]))
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    again = ctr_sampler.batches(cfg, mix, 64, seed, 5, 1)[0]
    np.testing.assert_array_equal(again["ids"], ours[2]["ids"])
    assert not np.array_equal(ours[0]["ids"], ours[1]["ids"])


def test_traffic_shapes():
    cfg = load("configs", "criteo-dlrm.json")
    mix = load("traffic", "zipf1.2-b4096.json")
    b = next(ctr_sampler.stream(cfg, mix, 64, 5))
    assert b["ids"].shape == (64, 26, 2)
    assert b["dense"].shape == (64, 13)
    assert b["labels"].shape == (64, 1)
    valid = b["ids"][b["ids"] >= 0]
    assert valid.max() < cfg["rows_per_field"]
    # every bag keeps at least its first id
    assert np.all(b["ids"][:, :, 0] >= 0)


@pytest.mark.parametrize("name", ["criteo-dense-zipf", "kwai-hostlru-zipf",
                                  "criteo-dense-zipf-4chip"])
@pytest.mark.parametrize("seed", [0, 1])
def test_cell_batches_are_program_streams(name, seed):
    """A cell's stream, drawn through ``spec.Cell.batches`` from the whole
    configuration at the cell's global batch (the batch per chip times its
    chips), is batch for batch the program's stream at that batch: what
    the harness drew before it read per-field tables."""
    from repro.data.ctr import CTRDataset
    cell = spec.resolve(name)
    cell.traffic = dict(cell.traffic, batch_per_chip=32)
    cfg, mix = cell.config, cell.traffic
    m = cfg["model"]
    ds = CTRDataset("bench", n_rows=cfg["rows_per_field"] * m["n_id_fields"],
                    n_fields=m["n_id_fields"],
                    ids_per_field=m["ids_per_field"],
                    n_dense=m["n_dense_features"], n_tasks=m["n_tasks"],
                    zipf_a=mix["zipf_a"], seed=mix["truth_seed"])
    ours = cell.batches(seed, 0, 3)
    assert cell.batch == 32 * cell.chips
    for i, b in enumerate(ours):
        a = next(ds.sampler(cell.batch, seed=[seed, i]))
        assert sorted(a) == sorted(b)
        for k in a:
            assert a[k].dtype == b[k].dtype
            np.testing.assert_array_equal(a[k], b[k])


def test_per_field_rows_and_bags():
    """A ``fields`` list gives each field its own id space and its own
    widest bag, padded to the widest field's."""
    cfg = load("configs", "criteo-dlrm.json")
    mix = load("traffic", "zipf1.2-b4096.json")
    cfg["model"].update(n_id_fields=3, ids_per_field=5)
    cfg["fields"] = [{"rows": 7, "hot": 1, "backend": "dense"},
                     {"rows": 300, "hot": 5, "backend": "host_lru",
                      "cache_rows": 64},
                     {"rows": 40, "hot": 3, "backend": "dense"}]
    b = ctr_sampler.batches(cfg, mix, 512, 9, 0, 1)[0]
    ids = b["ids"]
    assert ids.shape == (512, 3, 5)
    for f, (rows, hot) in enumerate([(7, 1), (300, 5), (40, 3)]):
        x = spec.field_ids(ids, f)
        assert np.all(x[:, hot:] == -1)
        assert np.all(x[:, 0] >= 0) and x.max() < rows
        lens = (x >= 0).sum(axis=1)
        assert set(np.unique(lens)) == set(range(1, hot + 1))


@pytest.mark.parametrize("config", ["criteo-dlrm", "kwai-dlrm"])
def test_per_field_arrays_draw_the_scalar_bits(config, monkeypatch):
    """Uniform fields draw through numpy's scalar paths; one value per
    field, as a ``fields`` list with values that differ is drawn, gives
    the same batch bit for bit."""
    cfg = load("configs", config + ".json")
    mix = load("traffic", "zipf1.2-b4096.json")
    scalar = ctr_sampler.batches(cfg, mix, 64, 2**31 + 3, 0, 2)
    monkeypatch.setattr(ctr_sampler, "_per_field",
                        lambda values, shape: np.array(
                            values, np.int64).reshape(shape))
    arrays = ctr_sampler.batches(cfg, mix, 64, 2**31 + 3, 0, 2)
    for a, b in zip(scalar, arrays):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
