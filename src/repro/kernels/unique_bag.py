"""Pallas TPU kernel: fused unique-gather + inverse-scatter + sum pool —
the worker-side-dedup lookup hot spot (paper §4.2.3 + §4.1 step 4).

With batch dedup the embedding worker holds the batch as a *dedup plan*:
``dev`` (one device row id per unique id) and ``inv`` (occurrence -> unique
position). The naive lowering materialises the (U, D) unique gather, then
the (B, L, D) inverse scatter, then the (B, D) bag pool — three HBM-sized
intermediates. This kernel fuses all three: both arrays are
scalar-prefetched into SMEM, each grid step resolves the double
indirection ``dev[inv[i]]`` for its bags' occurrences, DMAs exactly those
table rows HBM->VMEM and pools them into the VMEM-resident output block
(the gather/pool body is shared with ``embedding_bag``). Nothing unique- or
occurrence-width ever touches HBM.

Invalid occurrences (``inv[i] < 0``, multi-hot padding) and plan padding
(``dev[u] < 0``) are fetched as row 0 and masked by a 0/1 weight inside
the kernel, so an all-padding bag pools to exact zeros.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag import bag_call, gather_pool, pad_bags


def unique_bag(table: jax.Array, dev: jax.Array, inv: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """table: (V, D); dev: (U,) int32 unique row ids (-1 padding);
    inv: (B, L) int32 occurrence -> unique position (-1 padding)
    -> (B, D) sum-pooled bags of ``table[dev[inv[b, l]]]``.

    D should be a multiple of 128 (lane width) for the non-interpret path.
    """
    B, L = inv.shape
    V = table.shape[0]

    def row_of(refs, i):
        inv_ref, dev_ref = refs
        u = inv_ref[i]
        row = dev_ref[jnp.maximum(u, 0)]
        return jnp.clip(row, 0, V - 1), (u >= 0) & (row >= 0)

    def kernel(inv_ref, dev_ref, table_hbm, out_ref, rows, sem):
        gather_pool(row_of, (inv_ref, dev_ref), table_hbm, out_ref, rows,
                    sem, bag_len=L)

    flat = pad_bags(inv)
    out = bag_call(kernel, 2, (flat, dev.astype(jnp.int32)), table,
                   flat.shape[0] // L, L, interpret)
    return out[:B]
