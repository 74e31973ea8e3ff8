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


DCN_CONFIG = ("tests", "fixtures", "dlrm-dcnv2.json")
DCN_MIX = ("tests", "fixtures", "zipf1.2-b4096-fixed-bags.json")


def padded_ids(ids: list) -> np.ndarray:
    """Per-field ``(B, L_f)`` bags padded with -1 to one ``(B, F, L)``."""
    width = max(x.shape[1] for x in ids)
    return np.stack([np.pad(x, ((0, 0), (0, width - x.shape[1])),
                            constant_values=-1) for x in ids], axis=1)


def test_default_mix_keys_draw_the_same_bits():
    """A mix that names the default layout and bags draws what a mix that
    names neither draws."""
    cfg = load("configs", "kwai-dlrm.json")
    mix = load("traffic", "zipf1.2-b4096.json")
    named = dict(mix, layout="padded", bags="ragged")
    for a, b in zip(ctr_sampler.batches(cfg, mix, 64, 2**31 + 9, 0, 2),
                    ctr_sampler.batches(cfg, named, 64, 2**31 + 9, 0, 2)):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("config", ["criteo-dlrm", "kwai-dlrm"])
def test_prob_sums_padded_bags_as_one_array(config):
    """The truth read field by field gives the bits of the sum over the
    whole ``(B, F, L)`` array, the labels' formula before per-field lists."""
    cfg = load("configs", config + ".json")
    mix = load("traffic", "zipf1.2-b4096.json")
    truth = ctr_sampler.truth(cfg["model"], mix)
    b = ctr_sampler.batches(cfg, mix, 512, 2**31 + 23, 0, 1)[0]
    ids = b["ids"].astype(np.int64)
    f = np.arange(ids.shape[1])[None, :, None]
    bucket = np.where(ids >= 0, truth.w_buckets[f, np.where(ids >= 0, ids, 0)
                                                % 256], 0.0).sum(-1)
    nd = truth.w_dense.shape[0]
    sig = (bucket @ truth.w_field) / np.sqrt(ids.shape[1]) \
        + (b["dense"][:, :nd] @ truth.w_dense) / np.sqrt(nd)
    np.testing.assert_array_equal(
        truth.prob(b["ids"], b["dense"]),
        1.0 / (1.0 + np.exp(-(sig - truth.bias))))


@pytest.mark.parametrize("keys", [{"bags": "poisson"},
                                  {"layout": "ragged_rows"},
                                  {"layout": "per_field"},
                                  {"layout": "per_field", "bags": "ragged"}])
def test_mix_keys_outside_the_draws_raise(keys):
    """Unknown values, and per-field lists with ragged bags, which no mix
    draws, are refused."""
    cfg = load(*DCN_CONFIG)
    mix = {k: v for k, v in load(*DCN_MIX).items()
           if k not in ("layout", "bags")}
    with pytest.raises(ValueError):
        ctr_sampler.batches(cfg, dict(mix, **keys), 8, 0, 0, 1)


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_per_field_layout_with_fixed_bags(seed):
    """DLRM-DCNv2's traffic: one ``(B, hot_f)`` int32 array per field, every
    bag full, every id inside its field's rows; the labels are the Bernoulli
    draws of the planted truth, which reads the lists as it reads the same
    ids padded to ``(B, F, L)``, to float32 rounding."""
    cfg = load(*DCN_CONFIG)
    mix = load(*DCN_MIX)
    fields = spec.fields(cfg)
    b = ctr_sampler.batches(cfg, mix, 256, seed, 5, 1)[0]
    ids = b["ids"]
    assert isinstance(ids, list) and len(ids) == 26
    for x, f in zip(ids, fields):
        assert x.dtype == np.int32 and x.shape == (256, f["hot"])
        assert x.min() >= 0 and x.max() < f["rows"]
    assert b["dense"].shape == (256, 13) and b["labels"].shape == (256, 1)
    # the same draws, in the order the generator makes them
    rng = np.random.default_rng([seed, 5])
    for f in fields:
        rng.random((256, f["hot"]))
    dense = rng.standard_normal((256, 13)).astype(np.float32)
    u = rng.random((256, 1))
    truth = ctr_sampler.truth(cfg["model"], mix)
    np.testing.assert_array_equal(b["dense"], dense)
    prob = truth.prob(ids, dense)
    np.testing.assert_allclose(prob, truth.prob(padded_ids(ids), dense),
                               rtol=1e-6)
    np.testing.assert_array_equal(b["labels"], (u < prob).astype(np.float32))


def test_padded_layout_with_fixed_bags():
    """Fixed bags in the padded layout the program takes: every bag full to
    its field's hot, the tail -1, every id inside its field's rows."""
    cfg = load(*DCN_CONFIG)
    mix = dict(load(*DCN_MIX), layout="padded")
    ids = ctr_sampler.batches(cfg, mix, 512, 11, 0, 1)[0]["ids"]
    assert isinstance(ids, np.ndarray) and ids.shape == (512, 26, 100)
    for f, field in enumerate(spec.fields(cfg)):
        x = ids[:, f]
        hot = field["hot"]
        assert np.all(x[:, hot:] == -1) and x.max() < field["rows"]
        assert np.all(x[:, :hot] >= 0)


def test_per_field_layout_by_position():
    """Batch ``i`` of a per-field stream is the same whatever stretch it is
    drawn in, and differs from its neighbours."""
    cfg = load(*DCN_CONFIG)
    mix = load(*DCN_MIX)
    ours = ctr_sampler.batches(cfg, mix, 64, 2**31 + 5, 2, 3)
    again = ctr_sampler.batches(cfg, mix, 64, 2**31 + 5, 3, 1)[0]
    for x, y in zip(ours[1]["ids"], again["ids"]):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(ours[1]["labels"], again["labels"])
    assert not np.array_equal(ours[0]["ids"][20], ours[1]["ids"][20])
