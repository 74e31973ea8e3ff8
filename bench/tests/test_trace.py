"""The trace reduction, on a recorded v5e trace and on hand-made events."""
import os

import pytest

from bench.harness import trace as T

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "criteo_v5e_2steps.json.gz")
TPU0 = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    """Two fused CRITEO steps (26 dense tables of 1M rows, batch 4096) on a
    v5e, cut from a profiler trace: device ops with their HLO text up to
    the fusion kind, the host's C++ spans, and a ``bench:window`` span."""
    return T.reduce(T.TraceData.from_json(FIXTURE), steps=2)


def test_recorded_busy_and_idle(recorded):
    d = recorded["devices"][TPU0]
    assert recorded["window_s"] == pytest.approx(0.0745)
    assert d["busy_s"] == pytest.approx(0.069068725)
    assert d["idle_share"] == pytest.approx(1 - 0.069068725 / 0.0745)


def test_recorded_classes(recorded):
    d = recorded["devices"][TPU0]
    assert d["matmul_s"] == pytest.approx(0.007009668)
    assert d["embedding_s"] == pytest.approx(0.05521629)
    assert d["other_s"] == pytest.approx(0.006842767)
    assert d["collective_s"] == 0.0
    assert d["exposed_collective_s"] == 0.0
    # the classes partition the ops; overlap makes busy the smaller
    total = d["matmul_s"] + d["embedding_s"] + d["other_s"]
    assert total >= d["busy_s"]


def test_recorded_breakdown(recorded):
    ops = recorded["device_ops"]
    assert len(ops) == 10
    assert ops[0][0] == "embedding fusion kCustom f32[1000000,128]"
    assert ops[0][1] == pytest.approx(0.035153278)
    assert [o[1] for o in ops] == sorted((o[1] for o in ops), reverse=True)
    gaps = recorded["idle_gaps"]
    assert gaps[0][0] == "tpu::System::Execute=>Done"
    assert sum(g[1] for g in gaps) <= recorded["window_s"] * (
        recorded["devices"][TPU0]["idle_share"]) + 1e-12


@pytest.mark.parametrize("text,cls", [
    ("%convolution_add_fusion = f32[4096,4096]{1,0} fusion(bf16[4096,3341] "
     "%a, f32[3341,4096] %b), kind=kOutput", "matmul"),
    ("%multiply_reduce_fusion = (f32[]{:T(128)}, f32[3341,4096]{1,0}) "
     "fusion(bf16[4096,3341] %a, bf16[4096,4096] %b), kind=kOutput",
     "matmul"),
    ("%dot.3 = f32[8,8]{1,0} dot(f32[8,4] %a, f32[4,8] %b)", "matmul"),
    ("%fusion.143 = f32[1000000,128]{1,0} fusion(f32[1000000,128] %t, "
     "s32[8192] %i, f32[8192,128] %u), kind=kCustom", "embedding"),
    ("%gather.5 = f32[2048,128]{1,0} gather(f32[9,128] %t, s32[2048] %i)",
     "embedding"),
    ("%all-gather-start.2 = (s32[2048]{0}, s32[8192]{0}) "
     "all-gather-start(s32[2048]{0} %p)", "collective"),
    ("%all-reduce.7 = f32[8192,128]{1,0} all-reduce(f32[8192,128] %x)",
     "collective"),
    ("%fusion.9 = f32[4096,128]{1,0} fusion(f32[4096,128] %x), kind=kLoop",
     "other"),
    ("%copy.4 = f32[8]{0} copy(f32[8]{0} %x)", "other"),
])
def test_classify(text, cls):
    assert T.classify(text) == cls


def test_exposed_collective_and_idle():
    """One device: compute on [0,10) and [20,30); an all-reduce on [5,25)
    is hidden on [5,10) and [20,25) and exposed on [10,20)."""
    ar = "%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %x)"
    mm = "%dot.1 = f32[4,4]{1,0} dot(f32[4,4] %a, f32[4,4] %b)"
    td = T.TraceData(
        devices={TPU0: {T.OPS_LINE: [(0.0, 10.0, mm), (20.0, 10.0, mm)],
                        T.ASYNC_LINE: [(5.0, 20.0, ar)]}},
        host=[(0.0, 40.0, T.WINDOW), (12.0, 4.0, "prepare")])
    r = T.reduce(td, steps=1)
    d = r["devices"][TPU0]
    assert d["busy_s"] == pytest.approx(20e-9)
    assert d["idle_share"] == pytest.approx(0.5)
    assert d["exposed_collective_s"] == pytest.approx(10e-9)
    assert d["collective_s"] == pytest.approx(20e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["prepare"] == pytest.approx(10e-9)       # the gap [10, 20)
    assert gaps["no host span"] == pytest.approx(10e-9)  # the tail [30, 40)


def test_collective_exposed_ms():
    """The worst chip's exposed collective time per traced step: TPU:0 as
    in the test above (10 ns exposed), TPU:1 with its all-gather wholly
    under compute; over two steps. A trace without collectives reads
    nothing."""
    import types
    from bench.metrics import collective_exposed_ms
    ar = "%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %x)"
    ag = "%all-gather.2 = s32[8]{0} all-gather(s32[2]{0} %i)"
    mm = "%dot.1 = f32[4,4]{1,0} dot(f32[4,4] %a, f32[4,4] %b)"
    td = T.TraceData(
        devices={TPU0: {T.OPS_LINE: [(0.0, 10.0, mm), (20.0, 10.0, mm)],
                        T.ASYNC_LINE: [(5.0, 20.0, ar)]},
                 "/device:TPU:1": {T.OPS_LINE: [(0.0, 30.0, mm),
                                                (10.0, 5.0, ag)]}},
        host=[(0.0, 40.0, T.WINDOW)])
    r = T.reduce(td, steps=2)
    assert r["devices"]["/device:TPU:1"]["exposed_collective_s"] == 0.0
    run = types.SimpleNamespace(trace=r,
                                traced=types.SimpleNamespace(steps=2))
    assert collective_exposed_ms.read(run) == pytest.approx(1e3 * 10e-9 / 2)
    quiet = T.reduce(T.TraceData(
        devices={TPU0: {T.OPS_LINE: [(0.0, 10.0, mm)]}},
        host=[(0.0, 10.0, T.WINDOW)]), steps=1)
    assert collective_exposed_ms.read(types.SimpleNamespace(
        trace=quiet, traced=types.SimpleNamespace(steps=1))) is None
    assert collective_exposed_ms.read(types.SimpleNamespace(
        trace=None, traced=None)) is None


def test_interval_arithmetic():
    assert T.union([(3, 5), (0, 2), (1, 4)]) == [[0, 5]]
    assert T.subtract([[0, 10]], [[2, 3], [5, 6]]) == [[0, 2], [3, 5],
                                                       [6, 10]]
    assert T.length(T.clip([[0, 10], [20, 30]], 5, 25)) == 10
