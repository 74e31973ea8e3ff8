"""Pallas TPU kernel for Persia §4.2.3 lossy value compression.

Non-uniform fp32 -> fp16: each 128-wide block v is scaled by kappa/||v||_inf
before the cast (decompress divides it back out), so the fp16 mantissa covers
the block's actual dynamic range instead of clipping outliers.

TPU adaptation: data is viewed as (n_blocks, 128) — the 128 lane dimension is
exactly one vreg row, the per-block L_inf reduction is a lane reduction, and
tiles of TILE_ROWS blocks are staged through VMEM. TILE_ROWS is 1024 because
XLA tiles a 1-D fp32 array of that length or more in units of 1024, and the
per-block scales are such an array; inputs are zero-padded to a whole tile.
The kernels compute in fp32 on both sides of the codec; the fp32<->fp16
casts run in XLA around them, because Mosaic cannot lower an in-kernel
f32->f16 pack for v5e. Round-to-nearest-even is the same in either place,
so the payload is bit-identical to an in-kernel cast.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

KAPPA = 32_768.0
BLOCK = 128          # elements per scale block == one vreg of lanes
TILE_ROWS = 1024     # blocks per grid step


def _compress_kernel(v_ref, scaled_ref, scale_ref):
    v = v_ref[...]                                     # (TILE_ROWS, BLOCK) f32
    linf = jnp.max(jnp.abs(v), axis=-1, keepdims=True)
    scale = KAPPA / jnp.maximum(linf, 1e-30)
    scaled_ref[...] = v * scale
    scale_ref[...] = scale[:, 0]


def _decompress_kernel(comp_ref, scale_ref, out_ref):
    out_ref[...] = comp_ref[...] / scale_ref[...][:, None]


def _pad_rows(a, fill=0.0):
    pad = -a.shape[0] % TILE_ROWS
    widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill)


def compress(v_blocks: jax.Array, *, interpret: bool = False):
    """v_blocks: (n_blocks, BLOCK) fp32.

    Returns (comp fp16 (n_blocks, BLOCK), scales fp32 (n_blocks,)).
    """
    n, b = v_blocks.shape
    assert b == BLOCK, (n, b)
    v = _pad_rows(v_blocks.astype(jnp.float32))
    m = v.shape[0]
    scaled, scales = pl.pallas_call(
        _compress_kernel,
        grid=(m // TILE_ROWS,),
        in_specs=[pl.BlockSpec((TILE_ROWS, BLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((TILE_ROWS, BLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((TILE_ROWS,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((m, BLOCK), jnp.float32),
                   jax.ShapeDtypeStruct((m,), jnp.float32)],
        interpret=interpret,
    )(v)
    return scaled[:n].astype(jnp.float16), scales[:n]


def decompress(comp: jax.Array, scales: jax.Array, *, interpret: bool = False):
    n, b = comp.shape
    assert b == BLOCK, (n, b)
    c = _pad_rows(comp.astype(jnp.float32))
    m = c.shape[0]
    out = pl.pallas_call(
        _decompress_kernel,
        grid=(m // TILE_ROWS,),
        in_specs=[pl.BlockSpec((TILE_ROWS, BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((TILE_ROWS,), lambda i: (i,))],
        out_specs=pl.BlockSpec((TILE_ROWS, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, BLOCK), jnp.float32),
        interpret=interpret,
    )(c, _pad_rows(scales, 1.0))
    return out[:n]
