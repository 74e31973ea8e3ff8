"""Fused embedding backward + store_dtype, backend/trainer level (ISSUE 9).

The one-pass ``_put_plan`` / ``_hybrid_plan`` fused path (the new default,
jnp oracle) must be BIT-exact vs the decomposed segment-sum-then-apply
dispatches it replaced, across optimizer x staleness x backend — same
sweep discipline as test_dedup.py. The Pallas kernel flag sits in the
documented ~1e-7 reduction-order class, hence allclose. store_dtype gets
trainer-level trajectory-closeness plus spec validation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import adapters
from repro.core import backend as BK
from repro.core import dedup as D
from repro.core.dedup import DedupPlan
from repro.core.embedding_ps import EmbeddingSpec
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.data.ctr import CTRDataset
from repro.optim.optimizers import OptConfig


def _tree_eq(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _plan(rng, rows, cap, shape=(4, 6)):
    ids = rng.integers(-1, rows, shape)
    u_pad, inv, counts, _ = D.make_plan(ids, rows, cap, floor=8)
    return DedupPlan(dev=jnp.asarray(u_pad, jnp.int32),
                     inv=jnp.asarray(inv, jnp.int32)), counts, u_pad


def _decomposed_put(b, state, plan, grads):
    g_u = D.plan_segment_sum(plan.inv, grads, int(plan.dev.shape[0]))
    return b._put_unique(state, plan.dev, g_u)


def _decomposed_hybrid(b, state, queue, plan, grads):
    g_u = D.plan_segment_sum(plan.inv, grads, int(plan.dev.shape[0]))
    return b._hybrid_unique(state, queue, plan.dev, g_u)


@pytest.mark.parametrize("opt,tau", [("adagrad", 0), ("adagrad", 3),
                                     ("sgd", 0), ("sgd", 3)])
def test_dense_fused_matches_decomposed(opt, tau):
    rng = np.random.default_rng(hash((opt, tau)) % 2**31)
    spec = EmbeddingSpec(rows=257, dim=16, optimizer=opt, lr=3e-2,
                         staleness=tau, backend="dense")
    b = BK.DenseBackend(spec)
    state = b.init(jax.random.PRNGKey(0))
    queue = b.queue_init((4, 6))
    q2 = None if queue is None else jax.tree.map(jnp.copy, queue)
    for step in range(5):
        cap = D.dedup_cap(24, spec.rows)
        plan, _, _ = _plan(rng, spec.rows, cap)
        grads = jnp.asarray(
            rng.standard_normal((4, 6, 16)).astype(np.float32))
        st1, q1, _ = b.hybrid_update(state, queue, plan, grads)
        st2, q2, _ = _decomposed_hybrid(b, state, q2, plan, grads)
        _tree_eq(st1, st2)
        _tree_eq(q1, q2)
        sp1, _ = b.apply_put(state, plan, grads)
        sp2, _ = _decomposed_put(b, state, plan, grads)
        _tree_eq(sp1, sp2)
        state, queue = st1, q1


@pytest.mark.parametrize("opt,tau", [("adagrad", 2), ("adagrad", 0),
                                     ("sgd", 2)])
def test_host_lru_fused_matches_decomposed(opt, tau):
    rng = np.random.default_rng(hash((opt, tau, 1)) % 2**31)
    spec = EmbeddingSpec(rows=300, dim=16, optimizer=opt, lr=3e-2,
                         staleness=tau, backend="host_lru", cache_rows=64)
    b, b2 = BK.HostLRUBackend(spec), BK.HostLRUBackend(spec)
    state, state2 = b.init(jax.random.PRNGKey(1)), b2.init(
        jax.random.PRNGKey(1))
    queue = b.queue_init((4, 6))
    q2 = None if queue is None else jax.tree.map(jnp.copy, queue)
    for step in range(5):
        cap = D.dedup_cap(24, b.dedup_rows())
        ids = rng.integers(-1, spec.rows, (4, 6))
        u_pad, inv, counts, _ = D.make_plan(ids, spec.rows, cap, floor=8)
        state, dev_u = b.prepare(state, u_pad, assume_unique=True,
                                 counts=counts)
        state2, dev_u2 = b2.prepare(state2, u_pad, assume_unique=True,
                                    counts=counts)
        np.testing.assert_array_equal(np.asarray(dev_u), np.asarray(dev_u2))
        plan = DedupPlan(dev=jnp.asarray(dev_u, jnp.int32),
                         inv=jnp.asarray(inv, jnp.int32))
        grads = jnp.asarray(
            rng.standard_normal((4, 6, 16)).astype(np.float32))
        st1, q1, _ = b.hybrid_update(state, queue, plan, grads)
        st2, q2, _ = _decomposed_hybrid(b2, state2, q2, plan, grads)
        _tree_eq(st1, st2)
        _tree_eq(q1, q2)
        state, queue, state2 = st1, q1, st2


def test_backward_kernel_flag_matches_oracle():
    """backward_kernel=True routes through the Pallas kernel — same
    trajectory as the oracle default to reduction-order tolerance."""
    rng = np.random.default_rng(7)
    mk = lambda kernel: EmbeddingSpec(rows=257, dim=16, lr=3e-2,
                                      staleness=3, backend="dense",
                                      backward_kernel=kernel)
    bk, bo = BK.DenseBackend(mk(True)), BK.DenseBackend(mk(False))
    state_k = bk.init(jax.random.PRNGKey(2))
    state_o = jax.tree.map(jnp.copy, state_k)
    qk = bk.queue_init((4, 6))
    qo = jax.tree.map(jnp.copy, qk)
    for step in range(4):
        cap = D.dedup_cap(24, 257)
        plan, _, _ = _plan(rng, 257, cap)
        grads = jnp.asarray(
            rng.standard_normal((4, 6, 16)).astype(np.float32))
        state_k, qk, _ = bk.hybrid_update(state_k, qk, plan, grads)
        state_o, qo, _ = bo.hybrid_update(state_o, qo, plan, grads)
    for x, y in zip(jax.tree.leaves((state_k, qk)),
                    jax.tree.leaves((state_o, qo))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("backend", ["dense", "host_lru"])
def test_backward_kernel_refuses_an_optimizer_it_cannot_apply(backend):
    """The kernel applies adagrad only: any other optimizer is refused when
    the backend is built, never run through the jnp oracle instead."""
    spec = EmbeddingSpec(rows=64, dim=16, optimizer="sgd", backend=backend,
                         cache_rows=32, backward_kernel=True)
    with pytest.raises(ValueError, match="adagrad only"):
        BK.create_backend(spec)


# ---------------------------------------------------------------------------
# store_dtype at trainer level
# ---------------------------------------------------------------------------

def _trainer(store_dtype):
    ds = CTRDataset("fbw", n_rows=2 * 1024, n_fields=2, ids_per_field=2,
                    n_dense=13)
    cfg = ModelConfig(name="fbw", arch_type="recsys", n_id_fields=2,
                      ids_per_field=2, emb_dim=32, emb_rows=2 * 1024,
                      n_dense_features=13, mlp_dims=(32, 16), n_tasks=1)
    coll = adapters.ctr_collection(cfg, lr=5e-2, field_rows=ds.field_rows())
    coll = coll.with_backend("host_lru", 256)
    if store_dtype != "fp32":
        coll = coll.with_store_dtype(store_dtype)
    adapter = adapters.recsys_adapter(cfg, field_rows=ds.field_rows(),
                                      collection=coll)
    return ds, PersiaTrainer(adapter, TrainMode.hybrid(2),
                             OptConfig(kind="adam", lr=1e-3))


def test_trainer_store_dtype_trajectory_close():
    """blockscale16 cold rows move the hybrid training trajectory by at
    most the codec's quantisation noise — far under the 2e-3 bar the
    benchmarks pin."""
    losses = {}
    for sd in ("fp32", "blockscale16"):
        ds, tr = _trainer(sd)
        it = ds.sampler(32)
        bs = [{k: jnp.asarray(v) for k, v in next(it).items()}
              for _ in range(6)]
        st = tr.init(jax.random.PRNGKey(0), bs[0])
        out = []
        for bt in bs:
            st, m = tr.decomposed_step(st, bt)
            out.append(float(m["loss"]))
        losses[sd] = out
    delta = max(abs(a - b) for a, b in
                zip(losses["fp32"], losses["blockscale16"]))
    assert delta < 2e-3, delta


def test_trainer_store_dtype_payload_shrinks():
    _, tr32 = _trainer("fp32")
    _, tr16 = _trainer("blockscale16")
    b = {"ids": jnp.zeros((4, 2, 2), jnp.int32),
         "dense": jnp.zeros((4, 13)), "labels": jnp.zeros((4, 1))}
    tr32.init(jax.random.PRNGKey(0), b)
    tr16.init(jax.random.PRNGKey(0), b)
    p32 = sum(bk.store.payload_bytes() for bk in tr32.backends.values())
    p16 = sum(bk.store.payload_bytes() for bk in tr16.backends.values())
    assert p32 / p16 > 1.8                       # dim 32: 128 B vs 68 B/row


def test_dense_rejects_blockscale():
    """Dense tables are device-resident — there is no host store to
    compress; the spec must fail fast."""
    spec = EmbeddingSpec(rows=64, dim=8, backend="dense",
                         store_dtype="blockscale16")
    with pytest.raises(ValueError, match="store_dtype"):
        BK.DenseBackend(spec)


def test_bad_store_dtype_rejected():
    spec = EmbeddingSpec(rows=64, dim=8, backend="host_lru", cache_rows=16,
                         store_dtype="fp8")
    with pytest.raises(ValueError, match="store_dtype"):
        BK.HostLRUBackend(spec)


def test_hostenv_tuned_env_pure_and_idempotent():
    """tuned_env is a pure dict: merges caller XLA_FLAGS, never doubles
    the host-device pin, and carries the tcmalloc/TF silencers."""
    from repro.launch import hostenv
    env = hostenv.tuned_env(4, "--foo")
    assert env["XLA_FLAGS"] == \
        "--foo --xla_force_host_platform_device_count=4"
    again = hostenv.tuned_env(1, env["XLA_FLAGS"])
    assert again["XLA_FLAGS"] == env["XLA_FLAGS"]
    assert env["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] == "60000000000"
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "4"
    # find_tcmalloc never raises — None (graceful no-op) or a real path
    lib = hostenv.find_tcmalloc()
    assert lib is None or hostenv.os.path.exists(lib)
