"""Work counts and peaks, pinned to numbers worked out by hand."""
import json
import os

import numpy as np
import pytest

from bench.harness import device, work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def model(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["model"]


def test_criteo_tower_flops():
    # d_in = 26*128 + 13 = 3341; MACs 3341*4096 + 4096*2048 + 2048*1024
    # + 1024*512 + 512*256 + 256*1 = 24,826,112; x2 fwd, x3 fwd+bwd
    assert work.tower_flops_per_sample(model("criteo-dlrm")) == 148_956_672
    assert work.tower_flops_per_sample(model("criteo-dlrm")) / 1e6 == \
        pytest.approx(148.96, abs=0.005)


def test_kwai_tower_flops():
    # d_in = 32*128 + 16 = 4112; the last layer has 4 tasks
    assert work.tower_flops_per_sample(model("kwai-dlrm")) == 167_909_376
    assert work.tower_flops_per_sample(model("kwai-dlrm")) / 1e6 == \
        pytest.approx(167.9, abs=0.05)


def test_emb_bytes_by_hand():
    # one table, dim 128: 10 unique ids now, 6 popped. Lookup 10*512,
    # push 10*(512+4), pop 6*(512+4), adagrad 6*2*(512+4)
    assert work.emb_bytes(np.array([10]), np.array([6]), 128) == \
        10 * 512 + 10 * 516 + 6 * 516 + 6 * 2 * 516


def test_unique_counts():
    ids = np.array([[[1, 1], [2, -1]], [[1, 3], [-1, -1]]])   # (B=2, F=2, 2)
    assert list(work.unique_counts(ids)) == [2, 1]


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
