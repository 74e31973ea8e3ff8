"""Pallas TPU kernel: fused row-wise embedding update (the PS-side 'put' +
optimizer apply, paper Alg. 1 backward). Each grid step takes ``ROWS``
gradient rows: for every valid id, the owning table row is DMA'd from HBM
to VMEM (driven by scalar-prefetched ids), updated, and DMA'd back in
place (the table is aliased input -> output in ``memory_space=pl.ANY``) —
no dense (V, D) gradient is ever built. Each write-back completes before
the next row is read.

Rows must be pre-aggregated (core.compression.dedup_put or a DedupPlan)
when ids repeat within a put: the kernel is a plain row-wise apply, not a
segment-sum, and the jnp oracle sums duplicates before it applies.
Since PR 5 the unique data path guarantees pre-aggregated rows —
``check_unique`` turns an occurrence-width call into a loud error
instead (``ops.embedding_sgd`` runs it unless ``assume_unique`` vouches).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8             # gradient rows per grid step (one f32 sublane tile)


def check_unique(ids) -> None:
    """Raise ValueError when concrete ``ids`` contain duplicates among the
    valid (>= 0) entries — the occurrence-width misuse this kernel cannot
    honor. Traced ids (inside jit) are skipped: the check needs host
    values, and the jitted callers are the vetted unique-width paths."""
    if isinstance(ids, jax.core.Tracer):
        return
    host = np.asarray(ids).reshape(-1)
    valid = host[host >= 0]
    if valid.size != np.unique(valid).size:
        uniq, counts = np.unique(valid, return_counts=True)
        dups = uniq[counts > 1][:8]
        raise ValueError(
            "embedding_sgd requires pre-aggregated unique ids (a row-wise "
            "apply does not sum duplicate grads); got duplicates "
            f"{dups.tolist()} among {valid.size} valid ids. Segment-sum "
            "via a DedupPlan / compression.dedup_put first, or pass "
            "assume_unique=True if the rows are already aggregated.")


def _sgd_kernel(ids_ref, grad_ref, table_in, table_hbm, row, sem, *,
                lr: float, n_rows: int):
    del table_in                             # aliased: read via the out ref
    base = pl.program_id(0) * ROWS
    for k in range(ROWS):
        t = ids_ref[base + k]

        @pl.when((t >= 0) & (t < n_rows))
        def _():
            cp_in = pltpu.make_async_copy(table_hbm.at[pl.ds(t, 1)], row,
                                          sem.at[0])
            cp_in.start()
            cp_in.wait()
            row[...] = row[...] - lr * grad_ref[pl.ds(k, 1), :]
            cp_out = pltpu.make_async_copy(row, table_hbm.at[pl.ds(t, 1)],
                                           sem.at[0])
            cp_out.start()
            cp_out.wait()


def embedding_sgd(table: jax.Array, ids: jax.Array, grads: jax.Array, *,
                  lr: float, interpret: bool = False) -> jax.Array:
    """table: (V, D); ids: (T,) int32 (-1 = no-op); grads: (T, D).

    Returns the updated table (aliased in place on TPU).
    """
    T, D = grads.shape
    V, _ = table.shape
    pad = -T % ROWS
    ids = jnp.pad(ids.astype(jnp.int32), (0, pad), constant_values=-1)
    grads = jnp.pad(grads.astype(table.dtype), ((0, pad), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((T + pad) // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, D), lambda i, ids_pref: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((1, D), table.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        functools.partial(_sgd_kernel, lr=lr, n_rows=V),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((V, D), table.dtype),
        input_output_aliases={2: 0},      # table (arg idx incl. prefetch) -> out
        interpret=interpret,
    )(ids, grads, table)
