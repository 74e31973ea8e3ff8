"""Pallas TPU kernel: fused embedding backward (paper Alg. 1 PS-side put).

One kernel invocation, two phases, with the table and its adagrad
accumulator left in HBM (``memory_space=pl.ANY``) and aliased in place:

* phase A — segment-sum the occurrence-width grads into the ``(cap, D)``
  queue payload, which stays resident in VMEM for the whole call, driven
  by the dedup-plan inverse (``core.dedup.DedupPlan.inv``, scalar-
  prefetched into SMEM); -1 inverse entries (padding) are skipped. Grads
  stream in from HBM one ``(CHUNK, D)`` tile per DMA;
* phase B — per unique row ``j`` whose physical row ``apply_idx[j]`` is
  live: DMA the table row and the 128-lane accumulator group holding its
  adagrad entry into VMEM, apply the row-wise adagrad update, DMA both
  back. Each row's write-back completes before the next row is read, so
  rows that share an accumulator group observe each other's updates.

Rows move by explicit single-row DMAs because a ``(1, D)`` block of a 2-D
table is not a legal Mosaic block shape (the last two block dims must be
divisible by 8 and 128) and output blocks are never read back from HBM.

No full-width ``(U, D)`` gradient intermediate is ever materialized in
HBM: the decomposed path's segment-sum output and its padded queue copy
both collapse into the single ``(cap, D)`` payload output.

The jnp oracle is ``kernels.ref.fused_backward_ref`` (bit-identical to
``core.embedding_ps._apply_sparse`` + ``core.dedup.plan_segment_sum``).
The kernel matches it to the fp32 regroup class (~1e-7 relative): the
oracle's ``(cap, D)`` row-mean reduction is tiled differently from the
kernel's per-row ``(1, D)`` reduction, so the adagrad ``mean(g^2)`` sums
in a different order — the payload and the scatter structure are exact.
Live ``apply_idx`` rows must be distinct, as a dedup plan's are.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 8            # occurrence rows per grads DMA (one f32 sublane tile)
LANES = 128          # accumulator entries per DMA'd group row
# the (cap, D) payload lives in VMEM for the whole call; v5e has 128 MiB
_VMEM_CEILING = 100 * 2 ** 20


def _kernel(idx_ref, inv_ref, grads_hbm, applyg_hbm, table_in, acc_in,
            table_hbm, acc_hbm, push_ref, gbuf, grow, tbuf, abuf, sem, *,
            n_chunks: int, cap: int, n_rows: int, lr: float, eps: float,
            apply_self: bool):
    del table_in, acc_in                     # aliased: read via the out refs
    push_ref[...] = jnp.zeros_like(push_ref)

    def accumulate(c, carry):
        base = c * CHUNK
        cp = pltpu.make_async_copy(grads_hbm.at[pl.ds(base, CHUNK)], gbuf,
                                   sem.at[0])
        cp.start()
        cp.wait()
        for k in range(CHUNK):
            u = inv_ref[base + k]

            @pl.when(u >= 0)
            def _():
                push_ref[pl.ds(u, 1), :] += gbuf[pl.ds(k, 1), :]
        return carry

    jax.lax.fori_loop(0, n_chunks, accumulate, 0)

    def apply(j, carry):
        row = idx_ref[j]

        @pl.when((row >= 0) & (row < n_rows))
        def _():
            grp = row // LANES
            t_in = pltpu.make_async_copy(table_hbm.at[pl.ds(row, 1)], tbuf,
                                         sem.at[0])
            a_in = pltpu.make_async_copy(acc_hbm.at[pl.ds(grp, 1)], abuf,
                                         sem.at[1])
            t_in.start()
            a_in.start()
            if apply_self:
                g = push_ref[pl.ds(j, 1), :]
            else:
                g_in = pltpu.make_async_copy(applyg_hbm.at[pl.ds(j, 1)],
                                             grow, sem.at[2])
                g_in.start()
                g_in.wait()
                g = grow[...]
            t_in.wait()
            a_in.wait()
            inc = jnp.mean(jnp.square(g), axis=-1, keepdims=True)   # (1, 1)
            hit = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) \
                == row % LANES
            new_grp = abuf[...] + jnp.where(hit, inc, 0.0)
            abuf[...] = new_grp
            new_acc = jnp.sum(jnp.where(hit, new_grp, 0.0), axis=-1,
                              keepdims=True)
            step = g * jax.lax.rsqrt(new_acc + eps)
            upd = (-lr * step).astype(tbuf.dtype)
            # the self-equality select blocks XLA/LLVM from contracting the
            # -lr multiply into an fma with the row add: the decomposed
            # path's scatter-add rounds the product first, and bit-exactness
            # vs that path is the contract (optimization_barrier does not
            # survive interpret-mode lowering)
            upd = jnp.where(upd == upd, upd, jnp.zeros_like(upd))
            tbuf[...] = tbuf[...] + upd
            t_out = pltpu.make_async_copy(tbuf, table_hbm.at[pl.ds(row, 1)],
                                          sem.at[0])
            a_out = pltpu.make_async_copy(abuf, acc_hbm.at[pl.ds(grp, 1)],
                                          sem.at[1])
            t_out.start()
            a_out.start()
            t_out.wait()
            a_out.wait()
        return carry

    jax.lax.fori_loop(0, cap, apply, 0)


def vmem_bytes(cap: int, dim: int) -> int:
    """VMEM the kernel holds: the resident (cap, D) payload plus the
    per-row staging buffers."""
    return 4 * (cap * dim + (CHUNK + 2) * dim + LANES)


def fused_backward(table: jax.Array, acc: jax.Array, inv: jax.Array,
                   grads: jax.Array, apply_idx: jax.Array,
                   apply_g: jax.Array, *, lr: float, eps: float,
                   apply_self: bool = False,
                   interpret: bool = False):
    """table: (R, D) fp32; acc: (R,) adagrad accumulator; inv: occurrence
    -> unique position (-1 pad, any leading shape); grads: occurrence
    grads; apply_idx: (cap,) physical rows to update (-1 = no-op, live
    rows distinct); apply_g: (cap, D) grads applied at apply_idx unless
    ``apply_self`` routes the freshly summed payload into the update
    (sync / staleness-0).

    Returns (table, acc, g_push) with table/acc aliased in place on TPU
    and g_push: (cap, D) fp32 the queue-ready payload.
    """
    flat = inv.reshape(-1).astype(jnp.int32)
    n_occ = int(flat.shape[0])
    D = int(table.shape[1])
    R = int(table.shape[0])
    cap = int(apply_idx.shape[0])
    need = vmem_bytes(cap, D)
    if need > _VMEM_CEILING:
        raise ValueError(
            f"fused_backward keeps the (cap={cap}, dim={D}) payload in "
            f"VMEM: {need / 2**20:.0f} MiB exceeds the "
            f"{_VMEM_CEILING / 2**20:.0f} MiB budget — shrink the batch")
    n_pad = -(-max(n_occ, 1) // CHUNK) * CHUNK
    g_occ = grads.reshape(n_occ, D).astype(jnp.float32)
    g_occ = jnp.pad(g_occ, ((0, n_pad - n_occ), (0, 0)))
    flat = jnp.pad(flat, (0, n_pad - n_occ), constant_values=-1)
    r_pad = -(-R // LANES) * LANES
    acc2 = jnp.pad(acc.astype(jnp.float32), (0, r_pad - R)).reshape(-1, LANES)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[hbm, hbm, hbm, hbm],        # grads, apply_g, table, acc
        out_specs=[hbm, hbm,                  # table, acc (aliased)
                   pl.BlockSpec(memory_space=pltpu.VMEM)],   # payload
        scratch_shapes=[pltpu.VMEM((CHUNK, D), jnp.float32),
                        pltpu.VMEM((1, D), jnp.float32),
                        pltpu.VMEM((1, D), table.dtype),
                        pltpu.VMEM((1, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((3,))],
    )
    new_table, new_acc, g_push = pl.pallas_call(
        functools.partial(_kernel, n_chunks=n_pad // CHUNK, cap=cap,
                          n_rows=R, lr=lr, eps=eps, apply_self=apply_self),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, D), table.dtype),
            jax.ShapeDtypeStruct(acc2.shape, jnp.float32),
            jax.ShapeDtypeStruct((cap, D), jnp.float32),
        ],
        input_output_aliases={4: 0, 5: 1},   # arg idx incl. prefetch args
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=need + 8 * 2 ** 20),
        interpret=interpret,
    )(apply_idx.astype(jnp.int32), flat, g_occ,
      apply_g.astype(jnp.float32), table, acc2)
    return new_table, new_acc.reshape(-1)[:R].astype(acc.dtype), g_push
