"""The readers of the host prepare's phases, and a reader for every
per-layer metric of ``BENCHMARK.json``."""
import importlib
import types

import pytest

from bench.harness import spec

PHASES = ("plan", "lock_wait", "slots", "evict_d2h", "store", "fault_h2d")


def _run(counters, steps=4):
    return types.SimpleNamespace(counters=counters,
                                 window=types.SimpleNamespace(steps=steps))


@pytest.mark.parametrize("phase", PHASES)
def test_phase_reader_ms_per_step(phase):
    reader = importlib.import_module(f"bench.metrics.prepare_{phase}_ms")
    run = _run({f"pipeline/prepare/{phase}_s": 0.5,
                "pipeline/prepare/busy_s": 2.0})
    assert reader.read(run) == pytest.approx(125.0)
    # the fused path reports no pipeline counters, and no step: nothing
    assert reader.read(_run({"faults": 3.0})) is None
    assert reader.read(_run({f"pipeline/prepare/{phase}_s": 0.5},
                            steps=0)) is None


def test_every_per_layer_metric_has_a_reader():
    for m in spec.load_benchmark()["per_layer"]:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(reader.read), m["name"]
