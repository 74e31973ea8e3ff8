"""EmbeddingBackend protocol (core/backend.py): dense PS vs host-LRU
out-of-core parity, eviction/write-back behavior, the compressed wire's
bytes-moved accounting, and full checkpoint round-trips (vectors + adagrad
accumulators + LRU recency order)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import adapters, embedding_ps as PS
import repro.core.backend as BK
from repro.core.backend import (CompressedWireBackend, DenseBackend,
                                HostLRUBackend, _lru_victims, create_backend,
                                parse_backend_name)
from repro.core.collection import EmbeddingCollection
from repro.core.embedding_ps import EmbeddingSpec
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.data.ctr import CTRDataset
from repro.optim.optimizers import OptConfig

F, RPF, D = 3, 128, 8      # fields x rows-per-field x dim

CFG = ModelConfig(name="bk", arch_type="recsys", n_id_fields=F,
                  ids_per_field=3, emb_dim=D, emb_rows=F * RPF,
                  n_dense_features=4, mlp_dims=(16,), n_tasks=1)
DS = CTRDataset("bk", n_rows=F * RPF, n_fields=F, ids_per_field=3, n_dense=4)


def _batches(n, batch=32):
    it = DS.sampler(batch)
    return [{k: jnp.asarray(v) for k, v in next(it).items()}
            for _ in range(n)]


def _trainer(backend, cache_rows=None, tau=2):
    coll = adapters.ctr_collection(CFG, lr=5e-2, field_rows=DS.field_rows())
    coll = coll.with_backend(backend, cache_rows)
    ad = adapters.recsys_adapter(CFG, field_rows=DS.field_rows(),
                                 collection=coll)
    return PersiaTrainer(ad, TrainMode.hybrid(tau),
                         OptConfig(kind="adam", lr=5e-3))


def _probe_all_rows(trainer, state):
    """Bit-exact full-table view through the backend's own lookup path,
    chunked so host-LRU caches smaller than the table can stream it."""
    out = {}
    for n in trainer.collection.names:
        bk = trainer.backends[n]
        chunk = getattr(bk, "cache_rows", None) or RPF
        chunk = getattr(getattr(bk, "inner", None), "cache_rows", chunk)
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
# factory / spec validation
# ---------------------------------------------------------------------------

def test_backend_name_parsing():
    assert parse_backend_name("dense") == ("dense", False)
    assert parse_backend_name(None) == ("dense", False)
    assert parse_backend_name("host_lru") == ("host_lru", False)
    assert parse_backend_name("dense+compressed") == ("dense", True)
    assert parse_backend_name("host_lru+compressed") == ("host_lru", True)
    assert parse_backend_name("compressed") == ("dense", True)
    for bad in ("sparse", "host_lru+gzip", "dense+"):
        with pytest.raises(ValueError):
            parse_backend_name(bad)


def test_backend_factory_and_spec_validation():
    spec = EmbeddingSpec(rows=64, dim=4, mode="full")
    assert isinstance(create_backend(spec), DenseBackend)
    b = create_backend(dataclasses.replace(spec, backend="host_lru",
                                           cache_rows=16))
    assert isinstance(b, HostLRUBackend)
    c = create_backend(dataclasses.replace(spec,
                                           backend="host_lru+compressed",
                                           cache_rows=16))
    assert isinstance(c, CompressedWireBackend)
    assert isinstance(c.inner, HostLRUBackend)
    with pytest.raises(ValueError, match="cache_rows"):
        create_backend(dataclasses.replace(spec, backend="host_lru"))
    # collections fail fast on hostile backend strings
    with pytest.raises(ValueError, match="backend"):
        EmbeddingCollection.single(
            "t", dataclasses.replace(spec, backend="nope"))


def test_dense_backend_is_the_ps_unchanged():
    spec = EmbeddingSpec(rows=64, dim=4, mode="full", optimizer="adagrad",
                         lr=0.1)
    b = create_backend(spec)
    key = jax.random.PRNGKey(3)
    st_a, st_b = b.init(key), PS.ps_init(key, spec)
    np.testing.assert_array_equal(np.asarray(st_a["table"]),
                                  np.asarray(st_b["table"]))
    ids = jnp.asarray([0, 5, -1, 63, 5], jnp.int32)
    acts, m = b.lookup(st_a, ids)
    assert m == {}
    np.testing.assert_array_equal(np.asarray(acts),
                                  np.asarray(PS.lookup(st_b, spec, ids)))
    g = jnp.ones((5, 4), jnp.float32)
    new_a, _ = b.apply_put(st_a, ids, g)
    new_b = PS.apply_put(st_b, spec, ids, g)
    np.testing.assert_array_equal(np.asarray(new_a["table"]),
                                  np.asarray(new_b["table"]))
    np.testing.assert_array_equal(np.asarray(new_a["acc"]),
                                  np.asarray(new_b["acc"]))


# ---------------------------------------------------------------------------
# host-LRU: parity, out-of-core training, queue guard
# ---------------------------------------------------------------------------

def test_host_lru_bit_exact_with_dense_when_working_set_fits():
    """cache_rows == rows: nothing ever evicts, so the out-of-core tier must
    reproduce the dense PS bit for bit through BOTH pipelines (tau=2)."""
    batches = _batches(6)
    td, th = _trainer("dense"), _trainer("host_lru", cache_rows=RPF)
    tf = _trainer("host_lru", cache_rows=RPF)
    sd = td.init(jax.random.PRNGKey(0), batches[0])
    sh = th.init(jax.random.PRNGKey(0), batches[0])
    sf = tf.init(jax.random.PRNGKey(0), batches[0])
    for b in batches:
        sd, md = td.decomposed_step(sd, b)
        sh, mh = th.decomposed_step(sh, b)
        sf, _ = tf.step(sf, b)                       # fused path
    assert float(md["loss"]) == float(mh["loss"])
    rows_d, rows_h = _probe_all_rows(td, sd), _probe_all_rows(th, sh)
    rows_f = _probe_all_rows(tf, sf)
    for n in rows_d:
        np.testing.assert_array_equal(rows_d[n], rows_h[n], err_msg=n)
        np.testing.assert_array_equal(rows_d[n], rows_f[n], err_msg=n)
    # eval agrees too (and faults rows without desyncing the slot maps)
    np.testing.assert_allclose(float(td.eval(sd, batches[0])["loss"]),
                               float(th.eval(sh, batches[0])["loss"]))


def test_host_lru_bit_exact_with_dense_on_a_criteo_sized_field():
    """One field of 20,769 rows (a Criteo Table-1 field): the dense PS must
    give every id its own row, as the host-LRU tier does, so the fused
    hybrid runs stay bit-exact past the first stale apply."""
    rows = 20_769
    cfg = ModelConfig(name="bk_big", arch_type="recsys", n_id_fields=1,
                      ids_per_field=2, emb_dim=D, emb_rows=rows,
                      n_dense_features=4, mlp_dims=(16,), n_tasks=1)
    ds = CTRDataset("bk_big", n_rows=rows, n_fields=1, ids_per_field=2,
                    n_dense=4)
    it = ds.sampler(512)
    batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
               for _ in range(5)]
    runs = {}
    for backend in ("dense", "host_lru"):
        coll = adapters.ctr_collection(cfg, lr=5e-2,
                                       field_rows=ds.field_rows())
        coll = coll.with_backend(backend, 2048)
        tr = PersiaTrainer(adapters.recsys_adapter(
            cfg, field_rows=ds.field_rows(), collection=coll),
            TrainMode.hybrid(2), OptConfig(kind="adam", lr=5e-3))
        st = tr.init(jax.random.PRNGKey(0), batches[0])
        losses = []
        for b in batches:
            st, m = tr.step(st, b)
            losses.append(float(m["loss"]))
        name = tr.collection.names[0]
        seen = jnp.asarray(np.unique(np.concatenate(
            [np.asarray(b["ids"]).reshape(-1) for b in batches[:3]])))
        bk = tr.backends[name]
        emb, dev = bk.prepare(st.emb[name], seen)
        runs[backend] = losses, np.asarray(bk.lookup(emb, dev)[0])
    assert runs["dense"][0] == runs["host_lru"][0]
    np.testing.assert_array_equal(runs["dense"][1], runs["host_lru"][1])


def test_dense_restore_moves_rows_into_this_mesh_geometry():
    """A table saved under a 4-device mesh (padded to 20,772 rows) restores
    on one device with every logical id reading its own row again."""
    spec = EmbeddingSpec(rows=20_769, dim=4, mode="full")
    key = jax.random.PRNGKey(0)
    padded = jax.tree.map(np.asarray, PS.ps_init(key, spec, 4))
    assert padded["table"].shape[0] == 20_772
    bk = create_backend(spec)
    restored = bk.restore_from_checkpoint(padded)
    assert restored["table"].shape[0] == spec.rows
    ids = jnp.arange(spec.rows, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(bk.lookup(restored, ids)[0]),
        np.asarray(PS.lookup(PS.ps_init(key, spec), spec, ids)))


def test_host_lru_trains_beyond_device_cache():
    """The acceptance scenario: logical rows 8x the device cache, training
    end-to-end through decomposed_step with real evictions/write-backs."""
    cache = RPF // 8
    # narrow batches so the per-step working set fits the small cache
    it = DS.sampler(4)
    batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
               for _ in range(10)]
    tr = _trainer("host_lru", cache_rows=cache, tau=1)
    state = tr.init(jax.random.PRNGKey(0), batches[0])
    t0 = _probe_all_rows(tr, state)
    for b in batches:
        state, m = tr.decomposed_step(state, b)
    assert np.isfinite(float(m["loss"]))
    name = tr.collection.names[0]
    bk = tr.backends[name]
    assert bk.spec.rows == 8 * bk.cache_rows
    assert bk.faults > cache            # refaulted rows => out-of-core traffic
    assert bk.writebacks > 0            # dirty rows went back to the host
    t1 = _probe_all_rows(tr, state)
    assert any(not np.array_equal(t0[n], t1[n]) for n in t0)
    # device cache holds cache_rows slots; host store holds all logical rows
    assert bk.device_bytes(state.emb[name]) < bk.host_bytes()


def test_host_lru_rejects_oversized_working_set():
    tr = _trainer("host_lru", cache_rows=4, tau=0)
    b = _batches(1, batch=64)[0]
    state = tr.init(jax.random.PRNGKey(0), b)
    with pytest.raises(ValueError, match="working set"):
        tr.decomposed_step(state, b)


def test_host_lru_stale_put_to_recycled_slot_is_dropped():
    """tau-stale puts whose cache slot was recycled for another row must be
    dropped (the paper's tolerated lost put), not applied to the new row."""
    spec = EmbeddingSpec(rows=4, dim=2, mode="full", optimizer="sgd", lr=1.0,
                         staleness=1, backend="host_lru", cache_rows=2)
    bk = create_backend(spec)
    state = bk.init(jax.random.PRNGKey(0))
    queue = bk.queue_init((2,))              # fixed put width: 2 ids/step
    g = jnp.full((2, 2), 7.0)
    state, dev = bk.prepare(state, np.array([0, -1]))
    state, queue, _ = bk.hybrid_update(state, queue, dev, g)   # queued put(0)
    # fault ids 1,2 into the 2-slot cache: id 0 must get evicted
    state, dev12 = bk.prepare(state, np.array([1, 2]))
    assert 0 not in bk.slot_map()
    assert int(bk._slot_arr[0]) == -1
    before = np.asarray(state["table"]).copy()
    zero = jnp.zeros((2, 2))
    # the pop of put(0) happens here; its slot now belongs to id 1 or 2
    state, queue, _ = bk.hybrid_update(state, queue, dev12, zero)
    np.testing.assert_array_equal(np.asarray(state["table"]), before)
    # control: without the recycle, the tau=1 put lands on id 0's row
    bk2 = create_backend(dataclasses.replace(spec, cache_rows=4))
    st2 = bk2.init(jax.random.PRNGKey(0))
    q2 = bk2.queue_init((2,))
    st2, dev0 = bk2.prepare(st2, np.array([0, -1]))
    st2, q2, _ = bk2.hybrid_update(st2, q2, dev0, g)
    st2, dev0 = bk2.prepare(st2, np.array([0, -1]))
    row_before = np.asarray(bk2.lookup(st2, dev0)[0][0]).copy()
    st2, q2, _ = bk2.hybrid_update(st2, q2, dev0, jnp.zeros((2, 2)))
    st2, dev0 = bk2.prepare(st2, np.array([0, -1]))
    row_after = np.asarray(bk2.lookup(st2, dev0)[0][0])
    np.testing.assert_allclose(row_after, row_before - 7.0, atol=1e-6)


# ---------------------------------------------------------------------------
# checkpoint round-trip (vectors + acc + LRU recency order)
# ---------------------------------------------------------------------------

def test_host_lru_checkpoint_roundtrip_bit_identical(tmp_path):
    it = DS.sampler(8)
    batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
               for _ in range(7)]
    cache = RPF // 4

    def make():
        return _trainer("host_lru", cache_rows=cache, tau=2)

    tr_a = make()
    state = tr_a.init(jax.random.PRNGKey(0), batches[0])
    for b in batches[:4]:
        state, _ = tr_a.decomposed_step(state, b)
    tr_a.save(str(tmp_path), state)
    for b in batches[4:]:
        state, _ = tr_a.decomposed_step(state, b)

    tr_b = make()
    resumed = tr_b.restore(str(tmp_path))
    assert int(resumed.step) == 4
    # the host tier came back: store contents AND recency order
    name = tr_a.collection.names[0]
    ba, bb = tr_a.backends[name], tr_b.backends[name]
    assert bb.store.size == ba.store.size
    for b in batches[4:]:
        resumed, _ = tr_b.decomposed_step(resumed, b)

    # identical continuation: device caches, host stores, recency, counters
    for n in tr_a.collection.names:
        x, y = tr_a.backends[n], tr_b.backends[n]
        assert x.recency_order() == y.recency_order(), n
        assert (x.faults, x.writebacks) == (y.faults, y.writebacks), n
        sa, sb = x.store.serialize(), y.store.serialize()
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k], err_msg=f"{n}/{k}")
    rows_a = _probe_all_rows(tr_a, state)
    rows_b = _probe_all_rows(tr_b, resumed)
    for n in rows_a:
        np.testing.assert_array_equal(rows_a[n], rows_b[n], err_msg=n)


def test_host_lru_restore_rejects_mismatches(tmp_path):
    tr = _trainer("host_lru", cache_rows=RPF // 4, tau=0)
    b = _batches(1, batch=8)[0]
    state = tr.init(jax.random.PRNGKey(0), b)
    tr.save(str(tmp_path), state)
    # different cache geometry is refused
    tr2 = _trainer("host_lru", cache_rows=RPF // 2, tau=0)
    with pytest.raises(ValueError, match="cache_rows"):
        tr2.restore(str(tmp_path))
    # a dense trainer cannot adopt a host_lru checkpoint
    td = _trainer("dense", tau=0)
    with pytest.raises(ValueError, match="backend"):
        td.restore(str(tmp_path))
    # ... nor the reverse
    td.save(str(tmp_path / "dense"), td.init(jax.random.PRNGKey(0), b))
    tr3 = _trainer("host_lru", cache_rows=RPF // 4, tau=0)
    with pytest.raises(ValueError, match="backend"):
        tr3.restore(str(tmp_path / "dense"))


# ---------------------------------------------------------------------------
# compressed wire
# ---------------------------------------------------------------------------

def test_compressed_wire_reduces_bytes_and_stays_close():
    """Acceptance: >= 1.8x bytes-moved reduction at AUC-neutral settings
    (blockscale fp16 max rel err ~2^-11, so training stays close to the
    uncompressed run)."""
    batches = _batches(6)
    tc = _trainer("dense+compressed")
    td = _trainer("dense")
    sc = tc.init(jax.random.PRNGKey(0), batches[0])
    sd = td.init(jax.random.PRNGKey(0), batches[0])
    raw = wire = 0.0
    for b in batches:
        sc, m = tc.decomposed_step(sc, b)
        sd, _ = td.decomposed_step(sd, b)
        raw += sum(float(v) for k, v in m.items()
                   if k.startswith("wire/") and k.endswith("bytes_raw"))
        wire += sum(float(v) for k, v in m.items()
                    if k.startswith("wire/") and k.endswith("bytes_wire"))
    assert raw / wire >= 1.8, f"wire ratio {raw / wire:.2f}x < 1.8x"
    pc = np.asarray(tc.predict(sc, batches[0]))
    pd = np.asarray(td.predict(sd, batches[0]))
    np.testing.assert_allclose(pc, pd, atol=5e-2)


def test_compressed_wire_over_host_lru_and_kernel_path():
    batches = _batches(4, batch=16)
    tr = _trainer("host_lru+compressed", cache_rows=RPF, tau=1)
    state = tr.init(jax.random.PRNGKey(0), batches[0])
    for b in batches:
        state, m = tr.decomposed_step(state, b)
    assert any(k.endswith("put_bytes_wire") for k in m)
    assert np.isfinite(float(m["loss"]))
    # the Pallas kernel path is selectable per spec
    spec = EmbeddingSpec(rows=32, dim=16, mode="full",
                         backend="dense+compressed", wire_kernel=True)
    bk = create_backend(spec)
    st = bk.init(jax.random.PRNGKey(0))
    acts, m = bk.lookup(st, jnp.arange(8, dtype=jnp.int32))
    assert np.isfinite(np.asarray(acts)).all()
    with pytest.raises(ValueError, match="block"):
        create_backend(dataclasses.replace(spec, wire_block=64))


def test_compressed_queue_holds_deduped_puts():
    """The staleness queue lives PS-side, after the wire: what gets queued
    is the losslessly deduped put (one summed row per unique id)."""
    spec = EmbeddingSpec(rows=16, dim=4, mode="full", optimizer="sgd",
                         staleness=1, backend="dense+compressed")
    bk = create_backend(spec)
    state = bk.init(jax.random.PRNGKey(0))
    queue = bk.queue_init((6,))
    ids = jnp.asarray([3, 3, 5, 5, 5, -1], jnp.int32)
    g = jnp.ones((6, 4), jnp.float32)
    state, queue, m = bk.hybrid_update(state, queue, ids, g)
    qids = np.asarray(queue["ids"][0])
    assert sorted(qids[qids >= 0].tolist()) == [3, 5]      # deduped
    qg = {int(i): np.asarray(row) for i, row in
          zip(queue["ids"][0], queue["grads"][0]) if i >= 0}
    np.testing.assert_allclose(qg[3], 2 * np.ones(4), rtol=1e-3)
    np.testing.assert_allclose(qg[5], 3 * np.ones(4), rtol=1e-3)
    assert float(m["put_bytes_wire"]) < float(m["put_bytes_raw"])


# ---------------------------------------------------------------------------
# host_lru slot bookkeeping on arrays: victim choice and pin counts are
# exactly the rules they replace (the old rules kept here as oracles)
# ---------------------------------------------------------------------------

def _argsort_victims(clock, cand_slots, n):
    return cand_slots[np.argsort(clock[cand_slots], kind="stable")[:n]]


@pytest.mark.parametrize("ties", [2, 7, 10**6])
@pytest.mark.parametrize("n", ["one", "some", "all"])
def test_lru_victims_is_the_stable_argsort(ties, n):
    rng = np.random.default_rng(ties)
    clock = rng.integers(0, ties, 5000).astype(np.int64)
    cand = np.nonzero(rng.random(5000) < 0.7)[0]
    k = {"one": 1, "some": cand.size // 3, "all": cand.size}[n]
    np.testing.assert_array_equal(_lru_victims(clock, cand, k),
                                  _argsort_victims(clock, cand, k))


@pytest.mark.parametrize("region", ["pool", "main", "bypass"])
@pytest.mark.parametrize("n_evict", ["one", "some", "all"])
def test_free_slots_victims_match_stable_argsort(region, n_evict):
    """_free_slots takes the region's empty unpinned slots first, then the
    oldest evictable ones in the stable argsort's order: clocks full of
    ties, protected (batch-hit) and pinned slots, both admission regions."""
    spec = EmbeddingSpec(rows=512, dim=4, mode="full", optimizer="adagrad",
                         backend="host_lru", cache_rows=96,
                         admit_threshold=2.0, bypass_rows=32)
    bk = create_backend(spec)
    state = bk.init(jax.random.PRNGKey(0))
    n = bk.dev_slots
    rng = np.random.default_rng(["pool", "main", "bypass"].index(region))
    occ = np.nonzero(rng.random(n) < 0.85)[0]
    ids = rng.choice(spec.rows, occ.size, replace=False)
    bk._id_for_slot[occ] = ids
    bk._slot_arr[ids] = occ
    bk._slot_clock[:] = rng.integers(0, 5, n)
    bk._pin_count[:] = np.where(rng.random(n) < 0.1,
                                rng.integers(1, 4, n), 0)
    protected = rng.choice(occ, occ.size // 6, replace=False)
    lo, hi = {"pool": (0, n), "main": (0, bk.cache_rows),
              "bypass": (bk.cache_rows, n)}[region]
    in_region = np.zeros(n, bool)
    in_region[lo:hi] = True
    unpinned = bk._pin_count == 0
    free = np.nonzero((bk._id_for_slot < 0) & unpinned & in_region)[0]
    cand = in_region & unpinned & (bk._id_for_slot >= 0)
    cand[protected] = False
    cand = np.nonzero(cand)[0]
    k = {"one": 1, "some": cand.size // 2, "all": cand.size}[n_evict]
    want = np.concatenate([free, _argsort_victims(bk._slot_clock, cand, k)])
    ev_ids = bk._id_for_slot[want[free.size:]].copy()
    got = bk._free_slots(protected, free.size + k, dict(state), lo=lo,
                         hi=None if region == "pool" else hi)
    np.testing.assert_array_equal(got, want)
    assert bk.writebacks == k
    assert (bk._id_for_slot[want] == -1).all()
    assert (bk._slot_arr[ev_ids] == -1).all()


def test_bincount_pins_match_add_at():
    spec = EmbeddingSpec(rows=256, dim=4, mode="full", optimizer="sgd",
                         backend="host_lru", cache_rows=64)
    bk = create_backend(spec)
    state = bk.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    want = np.zeros(bk.dev_slots, np.int32)
    batches = [rng.integers(-3, 70, 300) for _ in range(4)]   # repeats, pads
    for b in batches:
        bk.pin_slots(jnp.asarray(b, jnp.int32))
        np.add.at(want, b[(b >= 0) & (b < bk.dev_slots)], 1)
    np.testing.assert_array_equal(bk._pin_count, want)
    for b in batches[:3] + [rng.integers(0, 64, 500)]:   # the last over-unpins
        bk.unpin_slots(b)
        np.subtract.at(want, b[(b >= 0) & (b < bk.dev_slots)], 1)
        np.maximum(want, 0, out=want)
        np.testing.assert_array_equal(bk._pin_count, want)
    # the serve path pins its hit slots across its gather, then releases
    state, _ = bk.prepare(state, np.array([3, 3, 9, 40]))
    bk.pin_slots(np.array([5, 5, 7]))
    before = bk._pin_count.copy()
    bk.read_rows(state, np.array([[3, 9], [9, 200]]))
    np.testing.assert_array_equal(bk._pin_count, before)


def _zipf_ids(rng, rows, n, a=1.2):
    u = rng.random(n)
    r = ((rows ** (1 - a) - 1) * u + 1) ** (1 / (1 - a))
    return np.minimum(np.floor(r).astype(np.int64) - 1, rows - 1)


def _run_host_lru_zipf(steps=40):
    spec = EmbeddingSpec(rows=4096, dim=8, mode="full", optimizer="adagrad",
                         lr=0.1, staleness=1, backend="host_lru",
                         cache_rows=384)
    bk = create_backend(spec)
    state = bk.init(jax.random.PRNGKey(0))
    queue = bk.queue_init((256,))
    rng = np.random.default_rng(7)
    inflight, evictions = None, []
    for _ in range(steps):
        ids = _zipf_ids(rng, spec.rows, 256)
        wb = bk.writebacks
        state, dev = bk.prepare(state, ids)
        evictions.append(bk.writebacks - wb)
        bk.pin_slots(dev)
        if inflight is not None:
            bk.unpin_slots(inflight)
        inflight = dev
        g = jnp.asarray(rng.standard_normal((256, spec.dim)), jnp.float32)
        state, queue, _ = bk.hybrid_update(state, queue, dev, g)
    return bk, state, evictions


def test_host_lru_array_bookkeeping_bit_exact_with_argsort_rule(monkeypatch):
    """A Zipf stream that evicts every step once the cache is full, run
    with the linear-time victim choice and again with the stable argsort:
    slot maps, clocks, counters, device cache and host store all equal."""
    bk, state, evictions = _run_host_lru_zipf()
    with monkeypatch.context() as m:
        m.setattr(BK, "_lru_victims", _argsort_victims)
        ref, ref_state, _ = _run_host_lru_zipf()
    assert min(evictions[5:]) > 0, evictions
    for a in ("_slot_arr", "_id_for_slot", "_slot_clock", "_pin_count"):
        np.testing.assert_array_equal(getattr(bk, a), getattr(ref, a),
                                      err_msg=a)
    assert (bk.faults, bk.writebacks, bk.hits) == \
        (ref.faults, ref.writebacks, ref.hits)
    assert bk.slot_map() == ref.slot_map()
    for k in state:
        np.testing.assert_array_equal(np.asarray(state[k]),
                                      np.asarray(ref_state[k]), err_msg=k)
    sa, sb = bk.store.serialize(), ref.store.serialize()
    for k in sa:
        np.testing.assert_array_equal(sa[k], sb[k], err_msg=k)
