"""Work counts and peaks, pinned to numbers worked out by hand."""
import json
import os

import numpy as np
import pytest

from bench.harness import device, spec, work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def tower_flops(name):
    """The tower's FLOPs per sample, by the module the configuration names
    (neither names one: ``mlp``)."""
    cfg = config(name)
    tower = spec.load("towers", cfg.get("reference_tower", "mlp"))
    return tower.flops_per_sample(cfg["model"], spec.tower_input(cfg))


def test_criteo_tower_flops():
    # d_in = 26*128 + 13 = 3341; MACs 3341*4096 + 4096*2048 + 2048*1024
    # + 1024*512 + 512*256 + 256*1 = 24,826,112; x2 fwd, x3 fwd+bwd
    assert spec.tower_input(config("criteo-dlrm")) == 3341
    assert tower_flops("criteo-dlrm") == 148_956_672
    assert tower_flops("criteo-dlrm") / 1e6 == \
        pytest.approx(148.96, abs=0.005)


def test_kwai_tower_flops():
    # d_in = 32*128 + 16 = 4112; the last layer has 4 tasks
    assert spec.tower_input(config("kwai-dlrm")) == 4112
    assert tower_flops("kwai-dlrm") == 167_909_376
    assert tower_flops("kwai-dlrm") / 1e6 == \
        pytest.approx(167.9, abs=0.05)


def test_emb_bytes_by_hand():
    # one table, dim 128: 10 unique ids now, 6 popped. Lookup 10*512,
    # push 10*(512+4), pop 6*(512+4), adagrad 6*2*(512+4)
    assert work.emb_bytes(np.array([10]), np.array([6]), 128) == \
        10 * 512 + 10 * 516 + 6 * 516 + 6 * 2 * 516


def test_unique_counts():
    ids = np.array([[[1, 1], [2, -1]], [[1, 3], [-1, -1]]])   # (B=2, F=2, 2)
    assert list(work.unique_counts(ids, 2)) == [2, 1]
    # the same bags as a list of per-field arrays, the second field 1-hot
    per_field = [ids[:, 0], ids[:, 1, :1]]
    assert list(work.unique_counts(per_field, 2)) == [2, 1]


@pytest.mark.parametrize("name", ["criteo-dlrm", "kwai-dlrm"])
def test_uniform_keys_expand_to_fields(name):
    cfg = config(name)
    f = spec.fields(cfg)
    assert len(f) == cfg["model"]["n_id_fields"]
    assert {x["rows"] for x in f} == {cfg["rows_per_field"]}
    assert {x["hot"] for x in f} == {cfg["model"]["ids_per_field"]}
    assert {x["backend"] for x in f} == {cfg["tables"]["backend"]}
    assert {x["cache_rows"] for x in f} == {cfg.get("cache_rows")}


def test_v5e_peaks():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("cpu")
