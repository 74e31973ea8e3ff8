"""jit'd public wrappers around the Pallas kernels.

Each wrapper decides when it is traced how its kernel runs: compiled by
Mosaic when JAX's default backend is a TPU, through ``interpret=True`` on
any other backend (the CPU test suite). Nothing is decided at import, so
importing this module initialises no backend. Each wrapper also handles
padding/reshaping to the kernels' aligned layouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import blockscale as _bs
from repro.kernels import embedding_bag as _bag
from repro.kernels import embedding_sgd as _sgd
from repro.kernels import fused_backward as _fb
from repro.kernels import unique_bag as _ub


def interpret_mode() -> bool:
    """True when the kernels must run through the Pallas interpreter: the
    default backend is not a TPU, so Mosaic has nothing to compile for."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block",))
def blockscale_roundtrip(v, block: int = 128):
    """Compress+decompress arbitrary-shaped fp32 v (the comm boundary)."""
    assert block == _bs.BLOCK
    flat = v.reshape(-1).astype(jnp.float32)
    n = flat.size
    blocks = jnp.pad(flat, (0, -n % _bs.BLOCK)).reshape(-1, _bs.BLOCK)
    comp, scales = _bs.compress(blocks, interpret=interpret_mode())
    out = _bs.decompress(comp, scales, interpret=interpret_mode())
    return out.reshape(-1)[:n].reshape(v.shape)


@jax.jit
def blockscale_compress(v_blocks):
    return _bs.compress(v_blocks, interpret=interpret_mode())


@jax.jit
def blockscale_decompress(comp, scales):
    return _bs.decompress(comp, scales, interpret=interpret_mode())


@jax.jit
def embedding_bag(table, ids):
    """(V,D) x (B,L) -> (B,D) fused gather+pool."""
    return _bag.embedding_bag(table, ids, interpret=interpret_mode())


@jax.jit
def unique_bag(table, dev, inv):
    """(V,D) x (U,) unique dev ids x (B,L) inverse -> (B,D): the dedup-plan
    lookup (unique gather + inverse scatter + bag pool) in one fused pass."""
    return _ub.unique_bag(table, dev, inv, interpret=interpret_mode())


def embedding_sgd(table, ids, grads, lr: float = 1e-2,
                  assume_unique: bool = False):
    """Row-wise SGD scatter-apply. The kernel does not sum duplicate ids,
    so callers must pass pre-aggregated unique rows; unless
    ``assume_unique`` vouches for that, concrete (non-traced) ids are
    checked and duplicates raise instead of silently dropping grads."""
    if not assume_unique:
        _sgd.check_unique(ids)
    return _embedding_sgd_jit(table, ids, grads, lr)


@functools.partial(jax.jit, static_argnames=("lr",))
def _embedding_sgd_jit(table, ids, grads, lr: float):
    return _sgd.embedding_sgd(table, ids, grads, lr=lr,
                              interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("lr", "eps", "apply_self"))
def fused_backward(table, acc, inv, grads, apply_idx, apply_g, *,
                   lr: float, eps: float, apply_self: bool = False):
    """Fused embedding backward: dedup segment-sum + adagrad apply + queue
    payload in one pass -> (table, acc, g_push). Oracle:
    ``ref.fused_backward_ref``."""
    return _fb.fused_backward(table, acc, inv, grads, apply_idx, apply_g,
                              lr=lr, eps=eps, apply_self=apply_self,
                              interpret=interpret_mode())
