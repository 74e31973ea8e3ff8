"""Per-kernel validation: shape/dtype sweeps + hypothesis properties, all
against the pure-jnp ref.py oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # optional dep: property tests get a fixed sweep
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref
from repro.kernels import blockscale as bs


# ---------------------------------------------------------------------------
# blockscale
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [256, 512, 1024])
def test_blockscale_matches_ref(rows):
    key = jax.random.PRNGKey(rows)
    v = jax.random.normal(key, (rows, 128)) * jnp.exp(
        jax.random.normal(key, (rows, 1)) * 4)
    c, s = ops.blockscale_compress(v)
    cr, sr = ref.blockscale_compress_ref(v)
    assert jnp.all(c == cr)
    np.testing.assert_allclose(s, sr, rtol=1e-6)
    out = ops.blockscale_decompress(c, s)
    np.testing.assert_allclose(out, ref.blockscale_decompress_ref(cr, sr),
                               rtol=1e-6)


def _blockscale_error_bound_case(a, b, logscale):
    """Property: per-block relative error <= fp16 quantisation of the
    block's L_inf (the paper's non-uniform-mapping guarantee)."""
    rng = np.random.default_rng(a * 1000 + b)
    v = (rng.standard_normal((a, b)) * np.exp(logscale)).astype(np.float32)
    out = np.asarray(ops.blockscale_roundtrip(jnp.asarray(v)))
    linf = np.abs(v).max() if v.size else 0.0
    # fp16 has 11 mantissa bits; values scaled to ~kappa so relative
    # error per element is <= linf * 2^-10 (conservative)
    assert np.all(np.abs(out - v) <= linf * 2 ** -10 + 1e-12)


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=25)
    @given(st.integers(1, 5), st.integers(1, 300), st.floats(-8, 8))
    def test_blockscale_roundtrip_error_bound(a, b, logscale):
        _blockscale_error_bound_case(a, b, logscale)
else:
    @pytest.mark.parametrize("a,b,logscale",
                             [(1, 1, 0.0), (2, 37, -8.0), (5, 300, 8.0),
                              (3, 128, 3.5)])
    def test_blockscale_roundtrip_error_bound(a, b, logscale):
        _blockscale_error_bound_case(a, b, logscale)


def test_blockscale_zero_block():
    v = jnp.zeros((256, 128))
    out = ops.blockscale_roundtrip(v)
    assert jnp.all(out == 0)


# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("V,D,B,L", [(64, 128, 4, 6), (128, 256, 8, 3),
                                     (32, 128, 1, 1), (256, 128, 16, 12)])
def test_embedding_bag_sweep(V, D, B, L):
    key = jax.random.PRNGKey(V + D + B + L)
    table = jax.random.normal(key, (V, D))
    ids = jax.random.randint(key, (B, L), -3, V)
    got = ops.embedding_bag(table, ids)
    want = ref.embedding_bag_ref(table, ids)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_embedding_bag_bf16():
    key = jax.random.PRNGKey(0)
    table = jax.random.normal(key, (64, 128)).astype(jnp.bfloat16)
    ids = jax.random.randint(key, (4, 5), -1, 64)
    got = ops.embedding_bag(table, ids)
    want = ref.embedding_bag_ref(table, ids)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=1e-1)


def test_embedding_bag_all_padding():
    table = jnp.ones((16, 128))
    ids = jnp.full((2, 3), -1, jnp.int32)
    assert jnp.all(ops.embedding_bag(table, ids) == 0)


def _embedding_bag_case(B, L, V):
    rng = np.random.default_rng(B * 100 + L * 10 + V)
    table = jnp.asarray(rng.standard_normal((V, 128)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-2, V, (B, L)).astype(np.int32))
    got = ops.embedding_bag(table, ids)
    want = ref.embedding_bag_ref(table, ids)
    np.testing.assert_allclose(got, want, atol=1e-5)


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=20)
    @given(st.integers(1, 8), st.integers(1, 10), st.integers(8, 64))
    def test_embedding_bag_property(B, L, V):
        _embedding_bag_case(B, L, V)
else:
    @pytest.mark.parametrize("B,L,V", [(1, 1, 8), (4, 7, 33), (8, 10, 64)])
    def test_embedding_bag_property(B, L, V):
        _embedding_bag_case(B, L, V)


# ---------------------------------------------------------------------------
# unique_bag (worker-side batch dedup: fused gather + inverse + sum pool)
# ---------------------------------------------------------------------------

def _unique_bag_inputs(V, D, B, L, U, seed):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((V, D)).astype(np.float32))
    n_live = max(U // 2, 1)                      # half the plan is padding
    dev = np.full(U, -1, np.int32)
    dev[:n_live] = rng.permutation(V)[:n_live]
    inv = rng.integers(-1, U, (B, L))            # hits padding slots too
    return table, jnp.asarray(dev, jnp.int32), jnp.asarray(inv, jnp.int32)


@pytest.mark.parametrize("V,D,B,L,U", [(64, 128, 4, 6, 16),
                                       (128, 256, 8, 3, 32),
                                       (32, 128, 1, 1, 4),
                                       (256, 128, 16, 12, 64)])
def test_unique_bag_sweep(V, D, B, L, U):
    table, dev, inv = _unique_bag_inputs(V, D, B, L, U, V + B + L)
    got = ops.unique_bag(table, dev, inv)
    want = ref.unique_bag_ref(table, dev, inv)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_unique_bag_all_duplicates():
    """Every occurrence of the bag resolves to the SAME unique position —
    the hot-key regime batch dedup exists for: the pool must be L * row."""
    table = jnp.asarray(np.arange(8 * 128, dtype=np.float32).reshape(8, 128))
    dev = jnp.asarray([5, -1, -1, -1], jnp.int32)
    inv = jnp.zeros((2, 7), jnp.int32)           # all 14 occurrences -> u=0
    out = ops.unique_bag(table, dev, inv)
    np.testing.assert_allclose(out, np.tile(np.asarray(table[5]) * 7,
                                            (2, 1)), atol=1e-4)


def test_unique_bag_all_padding():
    """inv=-1 (multi-hot padding) and dev=-1 (plan padding) both pool to
    exact zeros."""
    table = jnp.ones((16, 128))
    dev = jnp.full((4,), -1, jnp.int32)
    assert jnp.all(ops.unique_bag(table, dev,
                                  jnp.full((2, 3), -1, jnp.int32)) == 0)
    # inv points at live positions of an all-padding plan
    assert jnp.all(ops.unique_bag(table, dev,
                                  jnp.zeros((2, 3), jnp.int32)) == 0)


def test_unique_bag_matches_unfused_plan_lookup():
    """The kernel computes exactly pool(scatter(gather(table, dev), inv)) —
    the three-step jnp lowering of the dedup-plan lookup."""
    from repro.core import dedup as D_
    rng = np.random.default_rng(3)
    V, D, B, L = 64, 128, 8, 5
    table = jnp.asarray(rng.standard_normal((V, D)).astype(np.float32))
    ids = rng.integers(-1, V, (B, L))
    u_pad, inv, _, _ = D_.make_plan(ids, V, D_.dedup_cap(B * L, V), floor=4)
    dev = jnp.asarray(u_pad, jnp.int32)
    inv = jnp.asarray(inv, jnp.int32)
    acts_u = table[jnp.clip(dev, 0)] * (dev >= 0)[:, None]
    want = jnp.sum(D_.plan_scatter(acts_u, inv), axis=1)
    got = ops.unique_bag(table, dev, inv)
    np.testing.assert_allclose(got, want, atol=1e-5)


# ---------------------------------------------------------------------------
# embedding_sgd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 4, 17])
def test_embedding_sgd(T):
    key = jax.random.PRNGKey(T)
    table = jax.random.normal(key, (64, 128))
    # unique ids (kernel contract: pre-deduped puts)
    ids = jnp.asarray(np.random.default_rng(T).permutation(64)[:T],
                      jnp.int32)
    ids = ids.at[0].set(-1) if T > 2 else ids
    grads = jax.random.normal(key, (T, 128))
    got = ops.embedding_sgd(table, ids, grads, lr=0.05)
    want = ref.embedding_sgd_ref(table, ids, grads, lr=0.05)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention (Pallas fwd kernel vs jnp oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window,dtype",
                         [(True, 0, jnp.float32), (True, 24, jnp.float32),
                          (False, 0, jnp.float32), (True, 0, jnp.bfloat16)])
def test_flash_kernel_matches_naive(causal, window, dtype):
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models.layers import _attn_naive
    key = jax.random.PRNGKey(0)
    B, Hq, Hkv, S, Dh = 2, 4, 2, 64, 32
    q = jax.random.normal(key, (B, Hq, S, Dh)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Hkv, S, Dh)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, S, Dh)).astype(dtype)
    o, lse = flash_attention_fwd(q, k, v, scale=0.2, causal=causal,
                                 window=window, qblk=16, kblk=16,
                                 interpret=True)
    qg = q.reshape(B, Hkv, Hq // Hkv, S, Dh).transpose(0, 3, 1, 2, 4)
    on = _attn_naive(qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                     scale=0.2, causal=causal, window=window, q_offset=0)
    on = on.transpose(0, 2, 3, 1, 4).reshape(B, Hq, S, Dh)
    atol = 1e-5 if dtype == jnp.float32 else 0.04
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(on, np.float32), atol=atol)


@pytest.mark.parametrize("S,qblk,kblk", [(128, 32, 64), (96, 16, 32)])
def test_flash_kernel_block_shapes(S, qblk, kblk):
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models.layers import _attn_naive
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 2, S, 16))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 2, S, 16))
    v = jax.random.normal(jax.random.PRNGKey(5), (1, 2, S, 16))
    o, _ = flash_attention_fwd(q, k, v, scale=0.25, qblk=qblk, kblk=kblk,
                               interpret=True)
    qg = q.transpose(0, 2, 1, 3)[:, :, :, None]
    on = _attn_naive(qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                     scale=0.25, causal=True, window=0, q_offset=0)
    on = on[:, :, :, 0].transpose(0, 2, 1, 3)
    np.testing.assert_allclose(o, on, atol=1e-5)


def test_embedding_sgd_untouched_rows_preserved():
    table = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128)
    ids = jnp.array([5], jnp.int32)
    grads = jnp.ones((1, 128))
    out = ops.embedding_sgd(table, ids, grads, lr=1.0)
    assert jnp.all(out[6:] == table[6:])
    assert jnp.all(out[:5] == table[:5])
    np.testing.assert_allclose(out[5], table[5] - 1.0)


# ---------------------------------------------------------------------------
# fused_backward (one-pass dedup segment-sum + adagrad apply + queue payload)
# ---------------------------------------------------------------------------

def _fused_backward_case(R, Dm, U, n_occ, seed, apply_self=False):
    """Kernel vs jnp oracle. The queue payload (pure segment-sum) is
    bit-exact; table/acc sit in the documented ~1e-7 reduction-order
    class, hence allclose."""
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((R, Dm)).astype(np.float32))
    acc = jnp.asarray(rng.random(R).astype(np.float32))
    inv = jnp.asarray(rng.integers(-1, U, n_occ), jnp.int32)
    grads = jnp.asarray(rng.standard_normal((n_occ, Dm)).astype(np.float32))
    n_live = min(max(U // 2, 1), R)              # half the plan is padding
    apply_idx = np.full(U, -1, np.int32)
    apply_idx[:n_live] = rng.permutation(R)[:n_live]
    apply_idx = jnp.asarray(apply_idx)
    apply_g = jnp.zeros((U, Dm)) if apply_self else jnp.asarray(
        rng.standard_normal((U, Dm)).astype(np.float32))
    want = ref.fused_backward_ref(table, acc, inv, grads, apply_idx,
                                  apply_g, cap=U, lr=5e-2, eps=1e-8,
                                  apply_self=apply_self)
    got = ops.fused_backward(table, acc, inv, grads, apply_idx, apply_g,
                             lr=5e-2, eps=1e-8, apply_self=apply_self)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("R,Dm,U,n_occ,apply_self",
                         [(64, 16, 8, 24, False), (128, 32, 16, 96, False),
                          (257, 64, 32, 128, True), (32, 8, 4, 4, True)])
def test_fused_backward_sweep(R, Dm, U, n_occ, apply_self):
    _fused_backward_case(R, Dm, U, n_occ, R + n_occ, apply_self)


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=20)
    @given(st.integers(8, 80), st.sampled_from([8, 16, 32, 64]),
           st.sampled_from([4, 8, 16, 32]), st.integers(1, 128),
           st.booleans())
    def test_fused_backward_property(R, Dm, U, n_occ, apply_self):
        _fused_backward_case(R, Dm, U, n_occ, R * 7 + n_occ, apply_self)
else:
    @pytest.mark.parametrize("R,Dm,U,n_occ,apply_self",
                             [(8, 8, 4, 1, False), (80, 64, 32, 128, True),
                              (33, 16, 8, 50, False)])
    def test_fused_backward_property(R, Dm, U, n_occ, apply_self):
        _fused_backward_case(R, Dm, U, n_occ, R * 7 + n_occ, apply_self)


def test_fused_backward_all_padding():
    """inv=-1 (padding occurrences) and apply_idx=-1 (plan padding) leave
    the table/acc untouched and push exact zeros."""
    table = jnp.ones((16, 8))
    acc = jnp.ones((16,))
    got = ops.fused_backward(
        table, acc, jnp.full((6,), -1, jnp.int32), jnp.ones((6, 8)),
        jnp.full((4,), -1, jnp.int32), jnp.ones((4, 8)),
        lr=0.1, eps=1e-8)
    assert jnp.all(got[0] == table) and jnp.all(got[1] == acc)
    assert jnp.all(got[2] == 0)


def test_fused_backward_ref_sgd():
    """acc=None selects plain SGD: applied rows move by exactly
    -lr * summed grad, untouched rows are preserved bit-exact."""
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    inv = jnp.asarray([0, 0, 1, -1], jnp.int32)
    grads = jnp.asarray(rng.standard_normal((4, 8)).astype(np.float32))
    apply_idx = jnp.asarray([5, 9, -1], jnp.int32)
    new_t, new_acc, push = ref.fused_backward_ref(
        table, None, inv, grads, apply_idx, None, cap=3, lr=0.5, eps=1e-8,
        apply_self=True)
    assert new_acc is None
    np.testing.assert_array_equal(np.asarray(push[0]),
                                  np.asarray(grads[0] + grads[1]))
    np.testing.assert_array_equal(np.asarray(push[1]), np.asarray(grads[2]))
    np.testing.assert_array_equal(np.asarray(new_t[5]),
                                  np.asarray(table[5] - 0.5 * push[0]))
    np.testing.assert_array_equal(np.asarray(new_t[9]),
                                  np.asarray(table[9] - 0.5 * push[1]))
    untouched = np.setdiff1d(np.arange(32), [5, 9])
    np.testing.assert_array_equal(np.asarray(new_t[untouched]),
                                  np.asarray(table[untouched]))


# ---------------------------------------------------------------------------
# embedding_sgd duplicate-id contract (ISSUE 9 satellite)
# ---------------------------------------------------------------------------

def test_embedding_sgd_duplicate_ids_raise():
    """Since the PR-5 unique path, puts are pre-aggregated: occurrence-width
    ids must fail loudly instead of silently last-write-winning."""
    table = jnp.ones((16, 8))
    ids = jnp.asarray([3, 3, 7], jnp.int32)
    grads = jnp.ones((3, 8))
    with pytest.raises(ValueError, match="unique"):
        ops.embedding_sgd(table, ids, grads, lr=0.1)


def test_embedding_sgd_assume_unique_skips_guard():
    table = jnp.ones((16, 8))
    ids = jnp.asarray([3, 3, 7], jnp.int32)
    grads = jnp.ones((3, 8))
    out = ops.embedding_sgd(table, ids, grads, lr=0.1, assume_unique=True)
    assert out.shape == table.shape


def test_embedding_sgd_padding_duplicates_allowed():
    """-1 padding repeats freely — only valid ids are checked."""
    table = jnp.ones((16, 8))
    ids = jnp.asarray([-1, -1, 5], jnp.int32)
    grads = jnp.zeros((3, 8))
    out = ops.embedding_sgd(table, ids, grads, lr=0.1)
    assert jnp.all(out == table)
