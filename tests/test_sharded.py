"""ShardedBackend router (core/backend.py): sharded vs single-shard bit
parity (dense AND host_lru), N->M reshard checkpoint round-trips
(row-exact for N, M in {1, 2, 4}), concurrent two-thread prepare bijection
under the per-shard locks, pinned-slot survival under the deep pipeline,
the hot-key load-imbalance gauge, and shard-mapping validation."""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import checkpoint_shard_layout
from repro.configs.base import ModelConfig
from repro.core import adapters
from repro.core import backend as BK
from repro.core.backend import (CompressedWireBackend, DenseBackend,
                                HostLRUBackend, ShardedBackend,
                                create_backend)
from repro.core.embedding_ps import EmbeddingSpec
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.core.pipeline import PipelinedTrainer
from repro.data.ctr import CTRDataset
from repro.optim.optimizers import OptConfig

F, RPF, D = 2, 64, 8       # fields x rows-per-field x dim

CFG = ModelConfig(name="sh", arch_type="recsys", n_id_fields=F,
                  ids_per_field=3, emb_dim=D, emb_rows=F * RPF,
                  n_dense_features=4, mlp_dims=(16,), n_tasks=1)
DS = CTRDataset("sh", n_rows=F * RPF, n_fields=F, ids_per_field=3, n_dense=4)


def _batches(n, batch=16, seed=None):
    it = DS.sampler(batch, seed=seed)
    return [{k: jnp.asarray(v) for k, v in next(it).items()}
            for _ in range(n)]


def _trainer(backend="dense", cache_rows=None, shards=1, tau=2):
    coll = adapters.ctr_collection(CFG, lr=5e-2, field_rows=DS.field_rows())
    if backend != "dense":
        coll = coll.with_backend(backend, cache_rows)
    if shards != 1:
        coll = coll.with_shards(shards)
    ad = adapters.recsys_adapter(CFG, field_rows=DS.field_rows(),
                                 collection=coll)
    return PersiaTrainer(ad, TrainMode.hybrid(tau),
                         OptConfig(kind="adam", lr=5e-3))


def _probe_all_rows(trainer, state, chunk=8):
    """Logical full-table view through each backend's own prepare+lookup
    path, chunked so small (per-shard) caches can stream it."""
    out = {}
    for n in trainer.collection.names:
        bk = trainer.backends[n]
        rows = []
        for lo in range(0, RPF, chunk):
            ids = jnp.arange(lo, min(lo + chunk, RPF), dtype=jnp.int32)
            st, dev = bk.prepare(state.emb[n], ids)
            state.emb = {**state.emb, n: st}
            acts, _ = bk.lookup(st, dev)
            rows.append(np.asarray(acts))
        out[n] = np.concatenate(rows)
    return out


# ---------------------------------------------------------------------------
# factory: shards=1 stays the plain backend, checkpoint bytes unchanged
# ---------------------------------------------------------------------------

def test_factory_shards1_is_plain_and_router_composes():
    spec = EmbeddingSpec(rows=64, dim=4, mode="full")
    assert isinstance(create_backend(spec), DenseBackend)
    assert isinstance(create_backend(
        dataclasses.replace(spec, emb_shards=4)), ShardedBackend)
    h = create_backend(dataclasses.replace(spec, backend="host_lru",
                                           cache_rows=16, emb_shards=2))
    assert isinstance(h, ShardedBackend)
    assert all(isinstance(s, HostLRUBackend) for s in h.shard_backends)
    # the wire wraps OUTSIDE the router (one wire per table)
    w = create_backend(dataclasses.replace(spec, backend="dense+compressed",
                                           emb_shards=2))
    assert isinstance(w, CompressedWireBackend)
    assert isinstance(w.inner, ShardedBackend)
    with pytest.raises(ValueError, match="shards"):
        ShardedBackend(spec, n_shards=1)
    from repro.core.collection import EmbeddingCollection
    with pytest.raises(ValueError, match="emb_shards"):
        EmbeddingCollection.single(
            "t", dataclasses.replace(spec, emb_shards=0))


def test_shards1_dense_checkpoint_bytes_unchanged(tmp_path):
    """emb_shards=1 must keep the plain dense path — including the exact
    bytes a checkpoint writes (the on-disk format is the compat surface)."""
    b = _batches(1)[0]
    ta = _trainer("dense")            # spec default emb_shards=1
    sa = ta.init(jax.random.PRNGKey(0), b)
    pa = ta.save(str(tmp_path / "a"), sa)
    tb = _trainer("dense")
    sb = tb.init(jax.random.PRNGKey(0), b)
    pb = tb.save(str(tmp_path / "b"), sb)
    raw_a = open(f"{pa}/emb/data.bin", "rb").read()
    raw_b = open(f"{pb}/emb/data.bin", "rb").read()
    assert raw_a == raw_b and len(raw_a) > 0


# ---------------------------------------------------------------------------
# bit parity: k shards == 1 shard, dense and host_lru, all pipelines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,cache", [("dense", None),
                                           ("host_lru", RPF)],
                         ids=["dense", "host_lru"])
def test_sharded_bit_parity_with_single_shard(backend, cache):
    """4-shard router == plain backend bit for bit: per-step losses, every
    logical table row, and eval — through both the decomposed and the
    fused pipeline. (Affine routing is a bijection and every row lives in
    exactly one shard, so the math must be identical.)"""
    batches = _batches(6)
    t1, t4 = _trainer(backend, cache), _trainer(backend, cache, shards=4)
    tf = _trainer(backend, cache, shards=4)
    s1 = t1.init(jax.random.PRNGKey(0), batches[0])
    s4 = t4.init(jax.random.PRNGKey(0), batches[0])
    sf = tf.init(jax.random.PRNGKey(0), batches[0])
    for b in batches:
        s1, m1 = t1.decomposed_step(s1, b)
        s4, m4 = t4.decomposed_step(s4, b)
        sf, _ = tf.step(sf, b)                       # fused path
        assert float(m1["loss"]) == float(m4["loss"])
    rows1, rows4 = _probe_all_rows(t1, s1), _probe_all_rows(t4, s4)
    rowsf = _probe_all_rows(tf, sf)
    for n in rows1:
        np.testing.assert_array_equal(rows1[n], rows4[n], err_msg=n)
        np.testing.assert_array_equal(rows1[n], rowsf[n], err_msg=n)
    np.testing.assert_allclose(float(t1.eval(s1, batches[0])["loss"]),
                               float(t4.eval(s4, batches[0])["loss"]))


def test_init_emb_shards_routes_host_backed_tables():
    """PersiaTrainer.init(emb_shards=k) used to raise for host_lru tables;
    it now routes them through the router (and keeps legacy dense
    semantics untouched)."""
    batches = _batches(3)
    tr = _trainer("host_lru", RPF)                  # spec emb_shards=1
    state = tr.init(jax.random.PRNGKey(0), batches[0], emb_shards=2)
    for n in tr.collection.names:
        assert isinstance(tr.backends[n], ShardedBackend)
        assert tr.backends[n].n_shards == 2
    for b in batches:
        state, m = tr.decomposed_step(state, b)
    assert np.isfinite(float(m["loss"]))
    # parity with a spec-sharded trainer: same routing, same numbers
    t2 = _trainer("host_lru", RPF, shards=2)
    s2 = t2.init(jax.random.PRNGKey(0), batches[0])
    for b in batches:
        s2, m2 = t2.decomposed_step(s2, b)
    assert float(m["loss"]) == float(m2["loss"])


# ---------------------------------------------------------------------------
# resharding checkpoints: N-shard save -> M-shard restore, row-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,cache", [("dense", None),
                                           ("host_lru", RPF // 2)],
                         ids=["dense", "host_lru"])
def test_reshard_checkpoint_roundtrip_row_exact(backend, cache, tmp_path):
    """Save with N shards, restore with M, for N, M in {1, 2, 4}: every
    logical row (including through host-store + device-cache overlay)
    comes back bit-exactly, the shard layout is inspectable on disk, and
    training continues."""
    batches = _batches(3, batch=8)
    for N in (1, 2, 4):
        tN = _trainer(backend, cache, shards=N)
        s = tN.init(jax.random.PRNGKey(0), batches[0])
        for b in batches:
            s, _ = tN.decomposed_step(s, b)
        rows_src = _probe_all_rows(tN, s)
        d = str(tmp_path / f"{backend}_n{N}")
        tN.save(d, s)
        assert all(v == N for v in checkpoint_shard_layout(d).values())
        for M in (1, 2, 4):
            tM = _trainer(backend, cache, shards=M)
            r = tM.restore(d)
            assert int(r.step) == 3
            rows_dst = _probe_all_rows(tM, r)
            for n in rows_src:
                np.testing.assert_array_equal(rows_src[n], rows_dst[n],
                                              err_msg=f"N={N} M={M} {n}")
            if N != M:          # resharded: queues restart empty (warmup)
                for n in tM.collection.names:
                    q = r.emb_queue[n]
                    leaf = q["ids"] if "ids" in q else q["s0"]["ids"]
                    assert int(np.asarray(leaf).max()) == -1
            r, m = tM.decomposed_step(r, batches[0])
            assert np.isfinite(float(m["loss"]))


def test_same_geometry_sharded_restore_is_bit_identical(tmp_path):
    """N == M restore is the non-reshard path: identical continuation,
    matching the plain backend's bit-exact resume contract."""
    batches = _batches(6, batch=8)
    mk = lambda: _trainer("host_lru", RPF // 2, shards=2)  # noqa: E731
    ta = mk()
    s = ta.init(jax.random.PRNGKey(0), batches[0])
    for b in batches[:3]:
        s, _ = ta.decomposed_step(s, b)
    ta.save(str(tmp_path), s)
    for b in batches[3:]:
        s, _ = ta.decomposed_step(s, b)
    tb = mk()
    r = tb.restore(str(tmp_path))
    for n in tb.collection.names:
        assert not BK.unwrap(tb.backends[n]).last_restore_resharded
    for b in batches[3:]:
        r, _ = tb.decomposed_step(r, b)
    rows_a, rows_b = _probe_all_rows(ta, s), _probe_all_rows(tb, r)
    for n in rows_a:
        np.testing.assert_array_equal(rows_a[n], rows_b[n], err_msg=n)


def test_reshard_rejects_cross_backend_and_row_mismatch(tmp_path):
    tr = _trainer("host_lru", RPF // 2, shards=2, tau=0)
    b = _batches(1, batch=8)[0]
    tr.save(str(tmp_path), tr.init(jax.random.PRNGKey(0), b))
    # a dense router cannot adopt a host_lru sharded checkpoint
    td = _trainer("dense", shards=4, tau=0)
    with pytest.raises(ValueError, match="backend"):
        td.restore(str(tmp_path))


# ---------------------------------------------------------------------------
# concurrency: two-thread prepare bijection under the per-shard locks
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
def test_sharded_prepare_is_thread_safe():
    """Two threads hammering the router's concurrent prepare: every shard's
    slot bookkeeping must stay an exact bijection, and returned device ids
    must decode into their shard's slot range."""
    spec = EmbeddingSpec(rows=512, dim=4, mode="full", optimizer="sgd",
                         backend="host_lru", cache_rows=192, emb_shards=4)
    bk = create_backend(spec)
    state0 = bk.init(jax.random.PRNGKey(0))
    errors = []
    go = threading.Event()

    def hammer(seed):
        rng = np.random.default_rng(seed)
        go.wait()
        try:
            for _ in range(40):
                ids = rng.integers(0, spec.rows, 24)
                _, dev = bk.prepare(state0, ids)
                dev = np.asarray(dev)
                assert ((dev >= 0) & (dev < bk.dev_rows)).all()
        except Exception as e:   # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(s,)) for s in (1, 2)]
    for t in threads:
        t.start()
    go.set()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    for s, sub in enumerate(bk.shard_backends):
        smap = sub.slot_map()
        assert len(set(smap.values())) == len(smap)
        for k, slot in smap.items():
            assert int(sub._id_for_slot[slot]) == k, (s, k)
            assert int(sub._slot_arr[k]) == slot, (s, k)
        occupied = {int(x) for x in np.nonzero(sub._id_for_slot >= 0)[0]}
        assert occupied == set(smap.values())
        assert int(np.count_nonzero(sub._slot_arr >= 0)) == len(smap)


# ---------------------------------------------------------------------------
# pipelined execution over a sharded table
# ---------------------------------------------------------------------------

@pytest.mark.timeout(240)
def test_pipelined_inflight1_bit_exact_over_sharded_host_lru():
    batches = _batches(12)
    ta = _trainer("host_lru", RPF, shards=2)
    sa = ta.init(jax.random.PRNGKey(0), batches[0])
    sa, ms_a = ta.run(sa, batches)
    tb = _trainer("host_lru", RPF, shards=2)
    engine = PipelinedTrainer(tb, max_inflight=1)
    sb, ms_b = engine.run(tb.init(jax.random.PRNGKey(0), batches[0]),
                          batches)
    assert [float(m["loss"]) for m in ms_a] == \
        [float(m["loss"]) for m in ms_b]


@pytest.mark.timeout(240)
def test_deep_pipeline_pins_survive_sharded_eviction_pressure():
    """max_inflight > 1 over a sharded host_lru table with real eviction
    pressure: per-shard pins must keep every in-flight batch's rows
    resident (no wrong-row reads, no dropped puts), order preserved."""
    it = DS.sampler(4)
    batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
               for _ in range(15)]
    tr = _trainer("host_lru", RPF // 2, shards=2, tau=2)
    engine = PipelinedTrainer(tr, max_inflight=3)
    state = engine.init(jax.random.PRNGKey(0), batches[0])
    state, ms = engine.run(state, batches)
    assert len(ms) == 15
    assert engine.applied_order == list(range(15))
    assert all(np.isfinite(float(m["loss"])) for m in ms)
    # hybrid sharded tables charge EVERY shard's window, so the per-table
    # outstanding-puts bound min(max_inflight, tau) must still hold — the
    # staleness-contract regression for per-shard backpressure
    for n, v in engine.max_outstanding.items():
        assert v <= min(3, 2), (n, v)
    faults = sum(int(s.faults)
                 for n in tr.collection.names
                 for s in BK.unwrap(tr.backends[n]).shard_backends)
    assert faults > 0


# ---------------------------------------------------------------------------
# hot-key skew: the load-imbalance gauge fires
# ---------------------------------------------------------------------------

def test_hot_key_skew_fires_imbalance_gauge():
    """90% of the id traffic hammering one key must land on one shard and
    push max/mean traffic well above 1 — the gauge that makes hot-key skew
    visible in step metrics."""
    tr = _trainer("host_lru", RPF, shards=4, tau=0)
    rng = np.random.default_rng(0)
    B, L = 16, 3

    def skewed_batch():
        ids = rng.integers(0, RPF, (B, F, L))
        hot = rng.random((B, F, L)) < 0.9
        ids = np.where(hot, 7, ids)
        return {"ids": jnp.asarray(ids, jnp.int32),
                "dense": jnp.asarray(rng.standard_normal((B, 4)),
                                     jnp.float32),
                "labels": jnp.asarray(rng.random((B, 1)) < 0.3,
                                      jnp.float32)}

    state = tr.init(jax.random.PRNGKey(0), skewed_batch())
    for _ in range(4):
        state, m = tr.decomposed_step(state, skewed_batch())
    gauges = {k: float(v) for k, v in m.items() if k.endswith("imbalance")}
    assert gauges and all(v > 2.0 for v in gauges.values()), gauges
    # per-shard gauges are present for every shard
    name = tr.collection.names[0]
    for s in range(4):
        assert f"shard/{name}/{s}/hit_rate" in m
        assert f"shard/{name}/{s}/faults" in m
        assert f"shard/{name}/{s}/rows" in m
        assert f"shard/{name}/{s}/bytes" in m
    # a balanced stream keeps the gauge near 1
    tb = _trainer("host_lru", RPF, shards=4, tau=0)
    bs = _batches(5, batch=16)
    sb = tb.init(jax.random.PRNGKey(0), bs[0])
    for b in bs:
        sb, mb = tb.decomposed_step(sb, b)
    assert all(float(v) < 2.0 for k, v in mb.items()
               if k.endswith("imbalance"))


# ---------------------------------------------------------------------------
# shard-mapping validation (typo'd table names must fail loudly)
# ---------------------------------------------------------------------------

def test_shard_mapping_validates_table_names():
    coll = adapters.ctr_collection(CFG, lr=5e-2, field_rows=DS.field_rows())
    with pytest.raises(ValueError, match="unknown tables"):
        coll.with_shards({"field_typo": 4})
    with pytest.raises(ValueError, match="unknown tables"):
        coll.init(jax.random.PRNGKey(0), shards={"field_typo": 4})
    with pytest.raises(ValueError, match=">= 1"):
        coll.with_shards({"field_00": 0})
    tr = _trainer("host_lru", RPF)
    with pytest.raises(ValueError, match="unknown tables"):
        tr.init(jax.random.PRNGKey(0), _batches(1)[0],
                emb_shards={"field_typo": 2})
    # a valid mapping shards only the named table
    tr2 = _trainer("host_lru", RPF)
    tr2.init(jax.random.PRNGKey(0), _batches(1)[0],
             emb_shards={"field_00": 2})
    assert isinstance(tr2.backends["field_00"], ShardedBackend)
    assert isinstance(tr2.backends["field_01"], HostLRUBackend)
