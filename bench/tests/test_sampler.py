"""The benchmark's copy of the traffic generator draws what the program's
own synthetic stream draws for the same seed."""
import json
import os

import numpy as np
import pytest

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
    ours = ctr_sampler.stream(m, rows, mix, 256, seed)
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
    ours = ctr_sampler.batches(m, rows, mix, 64, seed, 3, 4)
    assert len(ours) == 4
    for i, b in zip(range(3, 7), ours):
        a = next(ds.sampler(64, seed=[seed, i]))
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    again = ctr_sampler.batches(m, rows, mix, 64, seed, 5, 1)[0]
    np.testing.assert_array_equal(again["ids"], ours[2]["ids"])
    assert not np.array_equal(ours[0]["ids"], ours[1]["ids"])


def test_traffic_shapes():
    cfg = load("configs", "criteo-dlrm.json")
    mix = load("traffic", "zipf1.2-b4096.json")
    b = next(ctr_sampler.stream(cfg["model"], cfg["rows_per_field"], mix,
                                64, 5))
    assert b["ids"].shape == (64, 26, 2)
    assert b["dense"].shape == (64, 13)
    assert b["labels"].shape == (64, 1)
    valid = b["ids"][b["ids"] >= 0]
    assert valid.max() < cfg["rows_per_field"]
    # every bag keeps at least its first id
    assert np.all(b["ids"][:, :, 0] >= 0)
