"""Pallas TPU kernel: fused multi-hot embedding gather + sum pool — the
embedding-worker "aggregation" hot spot (paper §4.1 step 4: pool the bag's
rows *before* shipping activations to the NN worker).

TPU adaptation: the GPU pattern (one warp per bag, random-access loads from
HBM) has no direct TPU analogue. Instead the bag ids are *scalar-prefetched*
into SMEM and the table stays in HBM (``memory_space=pl.ANY``): each grid
step owns ``BAGS`` output rows, starts one single-row DMA HBM->VMEM per id
occurrence of those bags, waits for them all, and pools the rows into the
VMEM-resident output block. Invalid ids (< 0, padding) are fetched as row 0
and masked by a 0/1 weight, so an all-padding bag pools to exact zeros.
A ``(1, D)`` BlockSpec row block would be simpler, but Mosaic requires the
last two block dims to be divisible by 8 and 128.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BAGS = 8             # output rows per grid step (one f32 sublane tile)


def gather_pool(row_of, ids_refs, table_hbm, out_ref, rows, sem, *,
                bag_len: int):
    """Shared body of the bag kernels: ``row_of(k) -> (row, valid)`` maps
    the block's k-th occurrence to its table row; every row is DMA'd into
    ``rows`` before any is pooled."""
    n = BAGS * bag_len
    base = pl.program_id(0) * n

    def copy(k):
        row, _ = row_of(ids_refs, base + k)
        return pltpu.make_async_copy(table_hbm.at[pl.ds(row, 1)],
                                     rows.at[pl.ds(k, 1)], sem.at[0])

    def start(k, c):
        copy(k).start()
        return c

    def wait(k, c):
        copy(k).wait()
        return c

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def pool(k, c):
        _, valid = row_of(ids_refs, base + k)
        b = k // bag_len
        out_ref[pl.ds(b, 1), :] += rows[pl.ds(k, 1), :] * valid.astype(
            rows.dtype)
        return c

    jax.lax.fori_loop(0, n, pool, 0)


def bag_call(kernel, n_prefetch: int, prefetch, table, n_bags: int,
             bag_len: int, interpret: bool):
    """pallas_call plumbing shared by the bag kernels: ``n_bags`` must be a
    multiple of BAGS."""
    V, D = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(n_bags // BAGS,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((BAGS, D), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((BAGS * bag_len, D), table.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_bags, D), table.dtype),
        interpret=interpret,
    )(*prefetch, table)


def pad_bags(a: jax.Array) -> jax.Array:
    """Pad (B, L) ids with -1 bags up to a multiple of BAGS, flattened."""
    B = a.shape[0]
    return jnp.pad(a, ((0, -B % BAGS), (0, 0)),
                   constant_values=-1).reshape(-1).astype(jnp.int32)


def embedding_bag(table: jax.Array, ids: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """table: (V, D); ids: (B, L) int32 with -1 padding -> (B, D) sum-pooled.

    D should be a multiple of 128 (lane width) for the non-interpret path.
    """
    B, L = ids.shape
    V = table.shape[0]

    def row_of(refs, i):
        (ids_ref,) = refs
        t = ids_ref[i]
        return jnp.clip(t, 0, V - 1), t >= 0

    def kernel(ids_ref, table_hbm, out_ref, rows, sem):
        gather_pool(row_of, (ids_ref,), table_hbm, out_ref, rows, sem,
                    bag_len=L)

    flat = pad_bags(ids)
    out = bag_call(kernel, 1, (flat,), table, flat.shape[0] // L, L,
                   interpret)
    return out[:B]
