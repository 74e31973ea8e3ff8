"""PipelinedTrainer (core/pipeline.py): max_inflight=1 determinism vs the
serial decomposed step for every mode x backend, the bounded-staleness
backpressure invariant under seeded random stage delays, ordered/lossless
put application, stage-failure propagation, per-stage metrics, and the
HostLRUBackend.prepare thread-safety regression."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import adapters
from repro.core.backend import create_backend
from repro.core.embedding_ps import EmbeddingSpec
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.core.pipeline import (PipelinedTrainer, PipelineStageError,
                                 STAGES)
from repro.data.ctr import CTRDataset
from repro.optim.optimizers import OptConfig

F, RPF, D = 3, 128, 8      # fields x rows-per-field x dim

CFG = ModelConfig(name="pl", arch_type="recsys", n_id_fields=F,
                  ids_per_field=3, emb_dim=D, emb_rows=F * RPF,
                  n_dense_features=4, mlp_dims=(16,), n_tasks=1)
DS = CTRDataset("pl", n_rows=F * RPF, n_fields=F, ids_per_field=3, n_dense=4)


def _batches(n, batch=32, seed=0):
    it = DS.sampler(batch, seed=seed)
    return [{k: jnp.asarray(v) for k, v in next(it).items()}
            for _ in range(n)]


def _trainer(backend="dense", cache_rows=None, mode=None):
    coll = adapters.ctr_collection(CFG, lr=5e-2, field_rows=DS.field_rows())
    if backend != "dense":
        coll = coll.with_backend(backend, cache_rows)
    ad = adapters.recsys_adapter(CFG, field_rows=DS.field_rows(),
                                 collection=coll)
    return PersiaTrainer(ad, mode or TrainMode.hybrid(3),
                         OptConfig(kind="adam", lr=5e-3))


def _assert_states_equal(sa, sb, exact=True):
    cmp = (np.testing.assert_array_equal if exact
           else lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5))
    for n in sa.emb:
        cmp(np.asarray(sa.emb[n]["table"]), np.asarray(sb.emb[n]["table"]))
        if "acc" in sa.emb[n]:
            cmp(np.asarray(sa.emb[n]["acc"]), np.asarray(sb.emb[n]["acc"]))
    for a, b in zip(jax.tree.leaves(sa.dense), jax.tree.leaves(sb.dense)):
        cmp(np.asarray(a), np.asarray(b))
    assert int(sa.step) == int(sb.step)


# ---------------------------------------------------------------------------
# determinism: max_inflight=1 == serial decomposed_step, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.timeout(240)
@pytest.mark.parametrize("backend,cache", [("dense", None),
                                           ("host_lru", RPF)],
                         ids=["dense", "host_lru"])
@pytest.mark.parametrize("mode", [TrainMode.sync(), TrainMode.hybrid(3),
                                  TrainMode.async_(3, 3)],
                         ids=["sync", "hybrid", "async"])
def test_inflight1_bit_exact_with_serial(backend, cache, mode):
    """The determinism contract: one permit pins the exact serial dispatch
    order, so 25 pipelined steps equal 25 decomposed_step calls bit for
    bit — dense params, every table, adagrad accs, losses."""
    batches = _batches(25)
    ta = _trainer(backend, cache, mode)
    sa = ta.init(jax.random.PRNGKey(0), batches[0])
    sa, ms_a = ta.run(sa, batches)
    tb = _trainer(backend, cache, mode)
    engine = PipelinedTrainer(tb, max_inflight=1)
    sb, ms_b = engine.run(tb.init(jax.random.PRNGKey(0), batches[0]),
                          batches)
    assert len(ms_a) == len(ms_b) == 25
    assert [float(m["loss"]) for m in ms_a] == \
        [float(m["loss"]) for m in ms_b]
    _assert_states_equal(sa, sb)


@pytest.mark.timeout(240)
def test_deep_pipeline_trains_and_preserves_order():
    """max_inflight > 1: results arrive complete and in batch order, puts
    apply FIFO per table, and the run still learns (loss finite)."""
    batches = _batches(20)
    tr = _trainer("host_lru", RPF)
    engine = PipelinedTrainer(tr, max_inflight=4)
    state = engine.init(jax.random.PRNGKey(0), batches[0])
    state, ms = engine.run(state, batches)
    assert len(ms) == 20
    assert engine.applied_order == list(range(20))     # no drop, no reorder
    assert all(np.isfinite(float(m["loss"])) for m in ms)
    assert int(state.step) == 20
    # the engine is reusable: a second run continues from the final state
    state, ms2 = engine.run(state, _batches(5, seed=7))
    assert len(ms2) == 5 and int(state.step) == 25


# ---------------------------------------------------------------------------
# stress: random stage delays, staleness invariant, failure propagation
# ---------------------------------------------------------------------------

@pytest.mark.timeout(240)
@pytest.mark.parametrize("seed", [0, 1])
def test_stress_random_delays_hold_invariants(seed):
    """Seeded random per-stage sleeps skew every stage's relative speed;
    the bounded-staleness invariant (outstanding puts <= min(max_inflight,
    tau) per table), order preservation and loss parity with a clean run
    must all survive the skew."""
    rng = np.random.default_rng(seed)
    delays = {(s, i): float(rng.uniform(0, 0.004))
              for s in STAGES for i in range(16)}

    def delay_fn(stage, idx):
        return delays.get((stage, idx), 0.0)

    batches = _batches(16)
    tau, inflight = 2, 3
    tr = _trainer("host_lru", RPF, TrainMode.hybrid(tau))
    engine = PipelinedTrainer(tr, max_inflight=inflight, delay_fn=delay_fn)
    state = engine.run(engine.init(jax.random.PRNGKey(0), batches[0]),
                       batches)[0]
    assert engine.applied_order == list(range(16))
    for n, peak in engine.max_outstanding.items():
        assert 1 <= peak <= min(inflight, tau), (n, peak)
    assert int(state.step) == 16
    # delays change timing only, never results: an undelayed pipelined run
    # with the same window reaches the identical staleness interleavings?
    # no — interleavings may differ with inflight>1; what must match is the
    # serial reference when the window is 1:
    tr1 = _trainer("host_lru", RPF, TrainMode.hybrid(tau))
    e1 = PipelinedTrainer(tr1, max_inflight=1, delay_fn=delay_fn)
    s1 = e1.run(e1.init(jax.random.PRNGKey(0), batches[0]), batches)[0]
    tr2 = _trainer("host_lru", RPF, TrainMode.hybrid(tau))
    s2, _ = tr2.run(tr2.init(jax.random.PRNGKey(0), batches[0]), batches)
    _assert_states_equal(s1, s2)


@pytest.mark.timeout(120)
@pytest.mark.parametrize("stage", ["loader", "prepare", "lookup", "dense",
                                   "put"])
def test_stage_exception_surfaces_without_hanging(stage):
    """A failure in ANY stage must abort the whole pipeline and re-raise
    from run() promptly (stop-event-aware queue waits), naming the stage."""
    batches = _batches(12)

    def delay_fn(s, idx):
        if s == stage and idx == 4:
            raise RuntimeError(f"injected-{stage}")
        return 0.0

    tr = _trainer("dense")
    engine = PipelinedTrainer(tr, max_inflight=3, delay_fn=delay_fn)
    state = engine.init(jax.random.PRNGKey(0), batches[0])
    t0 = time.monotonic()
    with pytest.raises(PipelineStageError, match=stage) as ei:
        engine.run(state, batches)
    assert time.monotonic() - t0 < 60
    assert ei.value.stage == stage and ei.value.step == 4
    assert isinstance(ei.value.original, RuntimeError)


@pytest.mark.timeout(120)
def test_sync_tables_never_read_past_unapplied_put():
    """tau=0 forces the put window to 1 even with a deep pipeline: sync
    semantics admit no pipeline-induced staleness, so inflight=4 sync must
    stay bit-exact with the serial sync run."""
    batches = _batches(12)
    ta = _trainer("dense", mode=TrainMode.sync())
    sa, _ = ta.run(ta.init(jax.random.PRNGKey(0), batches[0]), batches)
    tb = _trainer("dense", mode=TrainMode.sync())
    engine = PipelinedTrainer(tb, max_inflight=4)
    assert all(engine.put_window(n) == 1 for n in tb.collection.names)
    sb, _ = engine.run(engine.init(jax.random.PRNGKey(0), batches[0]),
                       batches)
    for n in engine.max_outstanding:
        assert engine.max_outstanding[n] == 1
    _assert_states_equal(sa, sb)


# ---------------------------------------------------------------------------
# metrics and guardrails
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
def test_pipeline_metrics_schema_and_occupancy():
    batches = _batches(8)
    tr = _trainer("host_lru", RPF)
    engine = PipelinedTrainer(
        tr, max_inflight=3,
        delay_fn=lambda s, i: 0.003 if s == "prepare" else 0.0)
    engine.run(engine.init(jax.random.PRNGKey(0), batches[0]), batches)
    pm = engine.pipeline_metrics()
    for stage in STAGES:
        assert pm[f"pipeline/{stage}/busy_s"] >= 0.0
        assert 0.0 <= pm[f"pipeline/{stage}/occupancy"] <= 1.0 + 1e-6
        assert pm[f"pipeline/{stage}/items"] == 8.0
    for stage in ("prepare", "lookup", "dense", "put"):
        assert pm[f"pipeline/{stage}/queue_depth_max"] <= 3.0
    assert pm["pipeline/prepare/busy_s"] >= 8 * 0.003
    assert pm["pipeline/steps"] == 8.0 and pm["pipeline/steps_per_s"] > 0
    for n in tr.collection.names:
        assert pm[f"pipeline/outstanding_puts_max/{n}"] >= 1.0


def test_engine_rejects_bad_construction():
    with pytest.raises(TypeError, match="PersiaTrainer"):
        PipelinedTrainer(object())
    tr = _trainer()
    with pytest.raises(ValueError, match="max_inflight"):
        PipelinedTrainer(tr, max_inflight=0)


@pytest.mark.timeout(120)
def test_run_steps_cap_and_delegated_surface(tmp_path):
    batches = _batches(10)
    tr = _trainer("dense", mode=TrainMode.hybrid(2))
    engine = PipelinedTrainer(tr, max_inflight=2)
    state = engine.init(jax.random.PRNGKey(0), batches[0])
    state, ms = engine.run(state, batches, steps=6)
    assert len(ms) == 6 and int(state.step) == 6
    # the delegated serial surface keeps working on the pipelined state
    m = engine.eval(state, batches[0])
    assert np.isfinite(float(m["loss"]))
    engine.save(str(tmp_path), state)
    restored = engine.restore(str(tmp_path))
    assert int(restored.step) == 6
    state2, _ = engine.run(restored, batches[6:])
    assert int(state2.step) == 10


# ---------------------------------------------------------------------------
# slot pinning: deep pipelines must never fault-recycle in-flight rows
# ---------------------------------------------------------------------------

def test_host_lru_pinned_slots_survive_fault_in():
    """While a batch is in flight (pinned), a later fault-in must evict
    around its slots — or raise when it can't — never recycle them."""
    spec = EmbeddingSpec(rows=64, dim=4, mode="full", optimizer="sgd",
                         backend="host_lru", cache_rows=8)
    bk = create_backend(spec)
    state = bk.init(jax.random.PRNGKey(0))
    state, dev0 = bk.prepare(state, np.arange(0, 6))        # batch 0: 6 slots
    bk.pin_slots(dev0)
    # 2 unpinned slots remain; a 2-id disjoint batch fits around the pins
    state, dev1 = bk.prepare(state, np.array([10, 11]))
    assert not set(np.asarray(dev1).tolist()) & \
        set(np.asarray(dev0).tolist())
    smap = bk.slot_map()
    for i in range(6):                          # batch 0 still resident
        assert smap[i] == int(np.asarray(dev0)[i])
        assert int(bk._slot_arr[i]) == int(np.asarray(dev0)[i])
    # ... but a batch needing more than the unpinned residue must raise,
    # not silently recycle pinned rows (batch 1's slots are unpinned, so 2
    # are evictable; 3 disjoint ids need one pinned victim -> refused)
    with pytest.raises(ValueError, match="pinned"):
        bk.prepare(state, np.array([20, 21, 22]))
    bk.unpin_slots(dev0)
    state, _ = bk.prepare(state, np.array([20, 21, 22]))    # now fine
    assert bk._pin_count.sum() == 0


@pytest.mark.timeout(240)
def test_deep_pipeline_pins_inflight_rows_host_lru():
    """A deep pipeline with a slow put stage keeps several batches in
    flight; with a cache sized near one batch's working set the engine
    must either run correctly (pins make later fault-ins evict around
    in-flight rows) or fail loudly — and with a roomy cache the run must
    stay consistent with sequential application of every batch."""
    batches = _batches(10, batch=8)
    tr = _trainer("host_lru", RPF, TrainMode.hybrid(2))
    engine = PipelinedTrainer(
        tr, max_inflight=3,
        delay_fn=lambda s, i: 0.02 if s == "put" else 0.0)
    state, ms = engine.run(engine.init(jax.random.PRNGKey(0), batches[0]),
                           batches)
    assert len(ms) == 10
    assert engine.applied_order == list(range(10))
    for n in tr.collection.names:                  # every pin released
        assert tr.backends[n]._pin_count.sum() == 0, n


# ---------------------------------------------------------------------------
# HostLRUBackend.prepare thread-safety regression (satellite fix)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
def test_host_lru_prepare_is_thread_safe():
    """Two threads hammering prepare on one backend: the slot bookkeeping
    must stay an exact bijection and never raise. Before the RLock fix the
    interleaved dict/array mutation corrupts the slot map (two ids on one
    slot) or dies with 'dictionary changed size during iteration'."""
    spec = EmbeddingSpec(rows=512, dim=4, mode="full", optimizer="sgd",
                         backend="host_lru", cache_rows=96)
    bk = create_backend(spec)
    state0 = bk.init(jax.random.PRNGKey(0))
    errors = []
    go = threading.Event()

    def hammer(seed):
        rng = np.random.default_rng(seed)
        go.wait()
        try:
            for _ in range(60):
                ids = rng.integers(0, spec.rows, 24)
                _, dev = bk.prepare(state0, ids)
                dev = np.asarray(dev)
                assert ((dev >= 0) & (dev < spec.cache_rows)).all()
        except Exception as e:   # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(s,)) for s in (1, 2)]
    for t in threads:
        t.start()
    go.set()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    # bijection: id->slot and slot->id agree, no slot serves two ids
    smap = bk.slot_map()
    assert len(set(smap.values())) == len(smap)
    for k, s in smap.items():
        assert int(bk._id_for_slot[s]) == k
        assert int(bk._slot_arr[k]) == s
    occupied = {int(s) for s in np.nonzero(bk._id_for_slot >= 0)[0]}
    assert occupied == set(smap.values())
    assert int(np.count_nonzero(bk._slot_arr >= 0)) == len(smap)
