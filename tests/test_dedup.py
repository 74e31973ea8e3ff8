"""Worker-side batch dedup (core/dedup.py): bit-exactness of the
unique-width lookup/queue/put path vs the occurrence-width PR-4 path
(sync/hybrid/async x dense/host_lru x shards x pipeline inflight),
narrowed-queue checkpoint round-trips (incl. old full-width blob
migration), the consolidated dedup capacity rule, and plan invariants."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import adapters
from repro.core import backend as BK
from repro.core import dedup as D
from repro.core import embedding_ps as PS
from repro.core.compression import dedup_put
from repro.core.dedup import dedup_cap, make_plan
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.core.pipeline import PipelinedTrainer
from repro.data.ctr import CTRDataset
from repro.optim.optimizers import OptConfig

F, RPF, DIM = 2, 64, 8

CFG = ModelConfig(name="dd", arch_type="recsys", n_id_fields=F,
                  ids_per_field=3, emb_dim=DIM, emb_rows=F * RPF,
                  n_dense_features=4, mlp_dims=(16,), n_tasks=1)
DS = CTRDataset("dd", n_rows=F * RPF, n_fields=F, ids_per_field=3, n_dense=4)

MODES = {"sync": TrainMode.sync(), "hybrid": TrainMode.hybrid(3),
         "async": TrainMode.async_(3, 3)}


def _batches(n, batch=16, seed=None):
    it = DS.sampler(batch, seed=seed)
    return [{k: jnp.asarray(v) for k, v in next(it).items()}
            for _ in range(n)]


def _trainer(mode, backend="dense", shards=1, dedup=True, cache=None):
    coll = adapters.ctr_collection(CFG, lr=5e-2, field_rows=DS.field_rows())
    if backend != "dense":
        coll = coll.with_backend(backend, cache or RPF)
    if shards != 1:
        coll = coll.with_shards(shards)
    ad = adapters.recsys_adapter(CFG, field_rows=DS.field_rows(),
                                 collection=coll)
    return PersiaTrainer(ad, MODES[mode] if isinstance(mode, str) else mode,
                         OptConfig(kind="adam", lr=5e-3), batch_dedup=dedup)


def _logical_tables(trainer, state):
    """Logical (row-ordered) table+acc per table — slot layouts may differ
    between runs (fault order), logical content must not."""
    out = {}
    for n in trainer.collection.names:
        bk = BK.unwrap(trainer.backends[n])
        spec = trainer.collection[n]
        base = "host_lru" if "host_lru" in (spec.backend or "dense") \
            else "dense"
        blob = bk.state_for_checkpoint(state.emb[n])
        out[n] = BK.extract_logical_rows(blob, spec, base)
    return out


def _assert_logical_equal(ta, sa, tb, sb):
    la, lb = _logical_tables(ta, sa), _logical_tables(tb, sb)
    for n in la:
        np.testing.assert_array_equal(la[n][0], lb[n][0], err_msg=f"{n} vec")
        if la[n][1] is not None:
            np.testing.assert_array_equal(la[n][1], lb[n][1],
                                          err_msg=f"{n} acc")


# ---------------------------------------------------------------------------
# the consolidated dedup capacity rule (one helper, three former mirrors)
# ---------------------------------------------------------------------------

def test_dedup_cap_matches_legacy_rule_and_is_idempotent():
    from repro.utils import round_up
    for n_put in (1, 2, 7, 48, 100, 1024, 1500, 4096, 9999):
        for rows in (1, 3, 64, 512, 1500, 4096, 100_000):
            want = round_up(min(n_put, rows), min(1024, n_put))  # PR-2 rule
            got = dedup_cap(n_put, rows)
            assert got == want, (n_put, rows)
            assert dedup_cap(got, rows) == got, (n_put, rows)  # idempotent
            assert got >= min(n_put, rows)


def test_cap_rule_shared_across_modules():
    """The three former mirrors all route through core/dedup.dedup_cap."""
    assert not hasattr(BK, "_dedup_cap")          # backend mirror deleted
    assert "dedup_cap" in inspect.getsource(PS.apply_put)
    # wire + dense + sharded queue widths all derive from the one rule
    spec = PS.EmbeddingSpec(rows=512, dim=4, mode="full", staleness=2)
    assert BK.create_backend(spec).queue_width(4096) == dedup_cap(4096, 512)
    wire = BK.create_backend(
        PS.EmbeddingSpec(rows=512, dim=4, mode="full", staleness=2,
                         backend="dense+compressed"))
    assert wire.queue_width(4096) == dedup_cap(4096, 512)
    lru = BK.create_backend(
        PS.EmbeddingSpec(rows=512, dim=4, mode="full", staleness=2,
                         backend="host_lru", cache_rows=128))
    assert lru.queue_width(4096) == dedup_cap(4096, 128)


# ---------------------------------------------------------------------------
# plan invariants
# ---------------------------------------------------------------------------

def test_make_plan_roundtrip_and_counts():
    rng = np.random.default_rng(0)
    ids = rng.integers(-2, 40, (8, 5))
    u, inv, counts, info = make_plan(ids, 40, dedup_cap(40, 40))
    valid = (ids >= 0) & (ids < 40)
    # inverse maps every valid occurrence back to its id
    np.testing.assert_array_equal(u[inv[valid]], ids[valid])
    assert np.all(inv[~valid] == -1)
    assert counts.sum() == valid.sum() == info["n_occ"]
    assert (u >= 0).sum() == info["n_unique"]
    assert info["dup_factor"] == pytest.approx(
        info["n_occ"] / info["n_unique"])
    # unique set is exactly np.unique of the valid ids
    np.testing.assert_array_equal(u[u >= 0], np.unique(ids[valid]))


def test_plan_segment_sum_matches_dedup_put_sums():
    """Pre-queue segment-sum == the old post-queue sort-based dedup, row
    for row (the commutation the bit-exactness contract rests on)."""
    rng = np.random.default_rng(1)
    ids = rng.integers(-1, 10, 64)
    g = jnp.asarray(rng.standard_normal((64, 4)).astype(np.float32))
    cap = dedup_cap(64, 10)
    u, inv, _, _ = make_plan(ids, 10, cap)
    g_u = D.plan_segment_sum(jnp.asarray(inv), g, int(u.shape[0]))
    old_u, old_g = dedup_put(jnp.asarray(np.where(ids >= 0, ids, -1),
                                         jnp.int32), g, cap)
    old = {int(i): np.asarray(r) for i, r in zip(old_u, old_g) if i >= 0}
    new = {int(i): np.asarray(r) for i, r in zip(u, g_u) if i >= 0}
    assert set(old) == set(new)
    for k in old:
        np.testing.assert_array_equal(old[k], new[k], err_msg=str(k))


def test_plan_scatter_matches_direct_lookup():
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.standard_normal((32, 6)).astype(np.float32))
    ids = rng.integers(-1, 32, (4, 5))
    u, inv, _, _ = make_plan(ids, 32, dedup_cap(20, 32))
    dev = jnp.asarray(u, jnp.int32)
    acts_u = table[jnp.clip(dev, 0)] * (dev >= 0)[:, None]
    got = D.plan_scatter(acts_u, jnp.asarray(inv))
    want = table[np.where(ids >= 0, ids, 0)] * \
        jnp.asarray((ids >= 0)[..., None], jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# bit-exactness sweep: unique-width path vs the PR-4 occurrence path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["sync", "hybrid", "async"])
@pytest.mark.parametrize("backend,shards", [("dense", 1), ("dense", 4),
                                            ("host_lru", 1),
                                            ("host_lru", 4)])
def test_dedup_bit_exact_vs_occurrence_path(mode, backend, shards):
    batches = _batches(6)
    t_new = _trainer(mode, backend, shards, dedup=True)
    t_old = _trainer(mode, backend, shards, dedup=False)
    s_new = t_new.init(jax.random.PRNGKey(0), batches[0])
    s_old = t_old.init(jax.random.PRNGKey(0), batches[0])
    for b in batches:
        s_new, m_new = t_new.decomposed_step(s_new, b)
        s_old, _ = t_old.decomposed_step(s_old, b)
    _assert_logical_equal(t_new, s_new, t_old, s_old)
    for a, b_ in zip(jax.tree.leaves(s_new.dense),
                     jax.tree.leaves(s_old.dense)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    # the dedup gauges only exist on the dedup path
    assert any(k.startswith("dedup/") and k.endswith("dup_factor")
               for k in m_new)


def test_dedup_fused_matches_decomposed_and_eval_parity():
    batches = _batches(5)
    t_f = _trainer("hybrid")
    t_d = _trainer("hybrid")
    s_f = t_f.init(jax.random.PRNGKey(0), batches[0])
    s_d = t_d.init(jax.random.PRNGKey(0), batches[0])
    for b in batches:
        s_f, _ = t_f.step(s_f, b)
        s_d, _ = t_d.decomposed_step(s_d, b)
    for n in s_f.emb:
        np.testing.assert_array_equal(np.asarray(s_f.emb[n]["table"]),
                                      np.asarray(s_d.emb[n]["table"]))
    # eval through plans == eval through the occurrence path
    t_old = _trainer("hybrid", dedup=False)
    s_old = t_old.init(jax.random.PRNGKey(0), batches[0])
    for b in batches:
        s_old, _ = t_old.decomposed_step(s_old, b)
    eb = _batches(1, seed=99)[0]
    m_new, m_old = t_d.eval(s_d, eb), t_old.eval(s_old, eb)
    assert float(m_new["loss"]) == float(m_old["loss"])


@pytest.mark.parametrize("shards", [1, 4])
def test_pipeline_inflight1_bit_exact_and_deep_runs(shards):
    """max_inflight=1 over the plan path == the occurrence-path serial
    trainer; a deep pipeline completes in order with the plan payloads."""
    batches = _batches(8)
    t_old = _trainer("hybrid", "host_lru", shards, dedup=False)
    s_old = t_old.init(jax.random.PRNGKey(0), batches[0])
    for b in batches:
        s_old, _ = t_old.decomposed_step(s_old, b)

    t_new = _trainer("hybrid", "host_lru", shards, dedup=True)
    engine = PipelinedTrainer(t_new, max_inflight=1)
    s_new = engine.init(jax.random.PRNGKey(0), batches[0])
    s_new, ms = engine.run(s_new, batches)
    assert len(ms) == len(batches)
    _assert_logical_equal(t_new, s_new, t_old, s_old)

    t_deep = _trainer("hybrid", "host_lru", shards, dedup=True)
    deep = PipelinedTrainer(t_deep, max_inflight=4)
    s_deep = deep.init(jax.random.PRNGKey(0), batches[0])
    s_deep, ms_deep = deep.run(s_deep, batches)
    assert deep.applied_order == list(range(len(batches)))
    assert all(np.isfinite(float(m["loss"])) for m in ms_deep)
    assert any(k.endswith("dup_factor") for k in ms_deep[0])


# ---------------------------------------------------------------------------
# narrowed queues + checkpoint round-trips (incl. old full-width blobs)
# ---------------------------------------------------------------------------

# a geometry where the cap actually bites: n_occ = 128*16 = 2048 per table,
# rows = 256 -> queue width 1024 (2x narrower than occurrence width)
NCFG = ModelConfig(name="nw", arch_type="recsys", n_id_fields=1,
                   ids_per_field=16, emb_dim=4, emb_rows=256,
                   n_dense_features=2, mlp_dims=(8,), n_tasks=1)
NDS = CTRDataset("nw", n_rows=256, n_fields=1, ids_per_field=16, n_dense=2)


def _narrow_trainer(dedup=True, backend="dense"):
    coll = adapters.ctr_collection(NCFG, lr=5e-2, field_rows=(256,))
    if backend != "dense":
        coll = coll.with_backend(backend, 256)
    ad = adapters.recsys_adapter(NCFG, field_rows=(256,), collection=coll)
    return PersiaTrainer(ad, TrainMode.hybrid(2),
                         OptConfig(kind="adam", lr=5e-3), batch_dedup=dedup)


def _narrow_batches(n, seed=None):
    it = NDS.sampler(128, seed=seed)
    return [{k: jnp.asarray(v) for k, v in next(it).items()}
            for _ in range(n)]


def test_queue_width_is_the_dedup_cap():
    batches = _narrow_batches(1)
    tr = _narrow_trainer(dedup=True)
    st = tr.init(jax.random.PRNGKey(0), batches[0])
    q = st.emb_queue["field_00"]
    assert q["ids"].shape == (2, dedup_cap(128 * 16, 256)) == (2, 1024)
    legacy = _narrow_trainer(dedup=False)
    sl = legacy.init(jax.random.PRNGKey(0), batches[0])
    assert sl.emb_queue["field_00"]["ids"].shape == (2, 2048)


@pytest.mark.parametrize("backend", ["dense", "host_lru"])
def test_old_full_width_queue_blob_migrates_on_restore(tmp_path, backend):
    """A checkpoint written by the occurrence-width trainer (tau pending
    full-width puts in flight) restores into a batch-dedup trainer: the
    queue narrows to the cap and training continues bit-exactly with the
    old trainer's own continuation."""
    batches = _narrow_batches(6)
    t_old = _narrow_trainer(dedup=False, backend=backend)
    s_old = t_old.init(jax.random.PRNGKey(0), batches[0])
    for b in batches[:3]:
        s_old, _ = t_old.decomposed_step(s_old, b)
    t_old.save(str(tmp_path / "ck"), s_old)

    t_new = _narrow_trainer(dedup=True, backend=backend)
    s_new = t_new.restore(str(tmp_path / "ck"))
    q = s_new.emb_queue["field_00"]
    assert np.shape(q["ids"])[1] == 1024          # migrated, was 2048
    # the pending puts survived the migration (filled FIFO, warmup done)
    assert int(np.asarray(q["filled"])) == 2
    for b in batches[3:]:
        s_new, _ = t_new.decomposed_step(s_new, b)
        s_old, _ = t_old.decomposed_step(s_old, b)
    _assert_logical_equal(t_new, s_new, t_old, s_old)


def test_same_geometry_dedup_resume_is_bit_identical(tmp_path):
    batches = _narrow_batches(6)
    t_a = _narrow_trainer(dedup=True)
    s_a = t_a.init(jax.random.PRNGKey(0), batches[0])
    for b in batches[:3]:
        s_a, _ = t_a.decomposed_step(s_a, b)
    t_a.save(str(tmp_path / "ck"), s_a)
    t_b = _narrow_trainer(dedup=True)
    s_b = t_b.restore(str(tmp_path / "ck"))
    # narrow blob into a narrow trainer: no migration, bit-identical queue
    np.testing.assert_array_equal(np.asarray(s_a.emb_queue["field_00"]["ids"]),
                                  np.asarray(s_b.emb_queue["field_00"]["ids"]))
    for b in batches[3:]:
        s_a, _ = t_a.decomposed_step(s_a, b)
        s_b, _ = t_b.decomposed_step(s_b, b)
    for n in s_a.emb:
        np.testing.assert_array_equal(np.asarray(s_a.emb[n]["table"]),
                                      np.asarray(s_b.emb[n]["table"]))


def test_migrate_queue_blob_dedups_each_slot():
    q = {"ids": np.array([[3, 3, 5, -1], [7, -1, 7, 7]], np.int32),
         "grads": np.arange(24, dtype=np.float32).reshape(2, 4, 3),
         "ptr": np.int32(1), "filled": np.int32(2)}
    out = D.migrate_queue_blob(q, 2)
    np.testing.assert_array_equal(out["ids"], [[3, 5], [7, -1]])
    np.testing.assert_array_equal(out["grads"][0, 0],
                                  q["grads"][0, 0] + q["grads"][0, 1])
    np.testing.assert_array_equal(out["grads"][0, 1], q["grads"][0, 2])
    np.testing.assert_array_equal(
        out["grads"][1, 0],
        q["grads"][1, 0] + q["grads"][1, 2] + q["grads"][1, 3])
    assert int(out["ptr"]) == 1 and int(out["filled"]) == 2


# ---------------------------------------------------------------------------
# metrics + host-LRU plan consumption (no second np.unique in the fault path)
# ---------------------------------------------------------------------------

def test_step_metrics_carry_dedup_gauges():
    batches = _batches(2)
    tr = _trainer("hybrid", "host_lru")
    st = tr.init(jax.random.PRNGKey(0), batches[0])
    st, m = tr.step(st, batches[0])
    for n in tr.collection.names:
        assert f"dedup/{n}/dup_factor" in m
        assert f"dedup/{n}/unique_rows" in m
        assert f"dedup/{n}/bytes_saved" in m
        assert m[f"dedup/{n}/dup_factor"] >= 1.0


def test_host_lru_prepare_consumes_plan_uniques():
    """assume_unique skips the backend's own np.unique: feeding the raw
    (duplicated) stream with assume_unique=False and the deduped stream
    with assume_unique=True must produce identical slot maps."""
    spec = PS.EmbeddingSpec(rows=32, dim=4, mode="full",
                            backend="host_lru", cache_rows=16)
    a, b = BK.create_backend(spec), BK.create_backend(spec)
    sa = a.init(jax.random.PRNGKey(0))
    sb = b.init(jax.random.PRNGKey(0))
    ids = np.array([5, 5, 9, 2, 9, -1])
    sa, dev_a = a.prepare(sa, ids)
    uniq = np.unique(ids[ids >= 0])
    sb, dev_b = b.prepare(sb, uniq, assume_unique=True)
    assert a.slot_map() == b.slot_map()
    np.testing.assert_array_equal(a._slot_arr, b._slot_arr)
    assert a.faults == b.faults == 3


def test_cache_overflow_raises_actionable_error():
    """A batch whose unique working set exceeds the host_lru device cache
    must fail with the raise-cache_rows guidance (the plan's capacity is
    bounded by the cache, so the overflow surfaces at plan time)."""
    spec = PS.EmbeddingSpec(rows=1024, dim=4, mode="full",
                            backend="host_lru", cache_rows=8)
    bk = BK.create_backend(spec)
    st = bk.init(jax.random.PRNGKey(0))
    ids = np.arange(16)          # 16 unique > 8 cache slots
    with pytest.raises(ValueError, match="cache_rows"):
        BK.prepare_all({"t": bk}, {"t": st}, {"t": ids})


def test_sharded_imbalance_gauge_still_sees_occurrence_traffic():
    """Dedup must NOT blind the hot-key gauge: counts ride the plan, so
    routed traffic is still measured per occurrence."""
    coll = adapters.ctr_collection(CFG, lr=5e-2, field_rows=DS.field_rows())
    coll = coll.with_backend("host_lru", RPF).with_shards(4)
    ad = adapters.recsys_adapter(CFG, field_rows=DS.field_rows(),
                                 collection=coll)
    tr = PersiaTrainer(ad, TrainMode.sync(), OptConfig(kind="adam", lr=5e-3))
    rng = np.random.default_rng(0)

    def skewed():
        ids = rng.integers(0, RPF, (16, F, 3))
        ids = np.where(rng.random((16, F, 3)) < 0.9, 7, ids)
        return {"ids": jnp.asarray(ids, jnp.int32),
                "dense": jnp.asarray(rng.standard_normal((16, 4)),
                                     jnp.float32),
                "labels": jnp.asarray(rng.random((16, 1)) < 0.3,
                                      jnp.float32)}

    st = tr.init(jax.random.PRNGKey(0), skewed())
    for _ in range(4):
        st, m = tr.decomposed_step(st, skewed())
    gauges = [v for k, v in m.items() if k.endswith("imbalance")]
    assert gauges and all(float(v) > 2.0 for v in gauges)
