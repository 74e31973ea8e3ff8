"""Embedding PS semantics: lookup/put vs a dense oracle, uniform-shuffle
balance, bounded-staleness queue behaviour (Assumption 1: t - D(t) = tau)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # optional dep: only the property test needs it
    HAVE_HYPOTHESIS = False

from repro.core import embedding_ps as PS


def _spec(**kw):
    base = dict(rows=64, dim=8, mode="model", optimizer="sgd", lr=0.5,
                staleness=0)
    base.update(kw)
    return PS.EmbeddingSpec(**base)


def test_lookup_returns_rows_and_masks_invalid():
    spec = _spec()
    st_ = PS.ps_init(jax.random.PRNGKey(0), spec)
    ids = jnp.array([0, 5, -1, 63, 64], jnp.int32)   # 64 out of range
    out = PS.lookup(st_, spec, ids)
    pos = PS.shuffle_pos(jnp.array([0, 5, 63]), 64)
    np.testing.assert_allclose(out[0], st_["table"][pos[0]])
    np.testing.assert_allclose(out[1], st_["table"][pos[1]])
    assert jnp.all(out[2] == 0) and jnp.all(out[4] == 0)
    np.testing.assert_allclose(out[3], st_["table"][pos[2]])


def test_put_sgd_matches_oracle():
    spec = _spec(optimizer="sgd", lr=0.1)
    st_ = PS.ps_init(jax.random.PRNGKey(1), spec)
    ids = jnp.array([3, 3, 7, -1], jnp.int32)
    grads = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((4, 8)).astype(np.float32))
    new = PS.apply_put(st_, spec, ids, grads)
    # oracle: duplicate ids accumulate, -1 dropped
    before3 = PS.lookup(st_, spec, jnp.array([3]))[0]
    after3 = PS.lookup(new, spec, jnp.array([3]))[0]
    np.testing.assert_allclose(after3, before3 - 0.1 * (grads[0] + grads[1]),
                               atol=1e-5)
    before7 = PS.lookup(st_, spec, jnp.array([7]))[0]
    after7 = PS.lookup(new, spec, jnp.array([7]))[0]
    np.testing.assert_allclose(after7, before7 - 0.1 * grads[2], atol=1e-5)


def test_adagrad_put_scales_by_accumulator():
    spec = _spec(optimizer="adagrad", lr=1.0, eps=0.0)
    st_ = PS.ps_init(jax.random.PRNGKey(1), spec)
    ids = jnp.array([3], jnp.int32)
    g = jnp.ones((1, 8))
    new = PS.apply_put(st_, spec, ids, g)
    # acc = mean(g^2) = 1 -> step = g / sqrt(1) = 1
    d = PS.lookup(st_, spec, ids)[0] - PS.lookup(new, spec, ids)[0]
    np.testing.assert_allclose(d, jnp.ones(8), atol=1e-5)
    new2 = PS.apply_put(new, spec, ids, g)
    d2 = PS.lookup(new, spec, ids)[0] - PS.lookup(new2, spec, ids)[0]
    np.testing.assert_allclose(d2, jnp.ones(8) / np.sqrt(2), atol=1e-5)


def test_uniform_shuffle_balances_hot_range():
    """Paper §4.2.3: a contiguous hot feature group spreads over shards."""
    rows = 4096
    n_shards = 16
    ids = jnp.arange(256)              # one hot 'feature group'
    pos = np.asarray(PS.shuffle_pos(ids, rows))
    shard_of = pos // (rows // n_shards)
    counts = np.bincount(shard_of, minlength=n_shards)
    assert counts.max() <= 3 * max(counts.mean(), 1)


@pytest.mark.parametrize("rows", [64, 4295, 20_769, 1_000_033])
def test_shuffle_pos_is_a_permutation(rows):
    """Every logical id owns its own storage row, also where id * MULT
    leaves uint32 (the Criteo fields' 20,769 rows)."""
    pos = np.asarray(PS.shuffle_pos(jnp.arange(rows), rows))
    assert pos.min() >= 0 and pos.max() < rows
    assert np.unique(pos).size == rows
    # the exact affine map, as Python integers
    ids = np.array([0, 1, min(4294, rows - 1), rows // 2, rows - 1],
                   np.int64)
    want = (ids * PS._SHUFFLE_MULT + PS._SHUFFLE_ADD) % rows
    np.testing.assert_array_equal(pos[ids], want)


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=15)
    @given(st.integers(0, 1 << 20), st.integers(4, 1000))
    def test_shuffle_pos_in_range(i, rows):
        p = int(PS.shuffle_pos(jnp.array([i]), rows)[0])
        assert 0 <= p < rows
else:
    @pytest.mark.parametrize("i,rows", [(0, 4), (1, 7), (123_456, 1000),
                                        ((1 << 20) - 1, 997)])
    def test_shuffle_pos_in_range(i, rows):
        p = int(PS.shuffle_pos(jnp.array([i]), rows)[0])
        assert 0 <= p < rows


# ---------------------------------------------------------------------------
# staleness queue: lookup at t must see updates through t - tau exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tau", [1, 2, 4])
def test_queue_delays_updates_by_tau(tau):
    spec = _spec(optimizer="sgd", lr=1.0, staleness=tau)
    state = PS.ps_init(jax.random.PRNGKey(0), spec)
    table0 = state["table"].copy()
    queue = PS.queue_init(spec, (1,), spec.dim)
    target = jnp.array([5], jnp.int32)
    for t in range(2 * tau + 2):
        g = jnp.full((1, spec.dim), float(t + 1))
        state, queue = PS.hybrid_emb_update(state, queue, spec, target, g)
        got = PS.lookup(state, spec, target)[0]
        # applied puts are those from steps <= t - tau:
        applied = sum(s + 1 for s in range(t - tau + 1)) if t >= tau else 0.0
        want = PS.lookup({"table": table0}, spec, target)[0] - applied
        np.testing.assert_allclose(got, want, atol=1e-4,
                                   err_msg=f"t={t} tau={tau}")


def test_tau_zero_is_synchronous():
    spec = _spec(optimizer="sgd", lr=1.0, staleness=0)
    state = PS.ps_init(jax.random.PRNGKey(0), spec)
    before = PS.lookup(state, spec, jnp.array([1]))[0]
    state, q = PS.hybrid_emb_update(state, None, spec, jnp.array([1]),
                                    jnp.ones((1, spec.dim)))
    after = PS.lookup(state, spec, jnp.array([1]))[0]
    np.testing.assert_allclose(before - after, jnp.ones(spec.dim), atol=1e-5)
