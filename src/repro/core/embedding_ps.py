"""Sharded embedding parameter server — the TPU-native mapping of Persia's
embedding PS tier (paper §4.1/§4.2).

Two sharding modes:

* ``mode='model'`` — table rows sharded over the ``model`` mesh axis only
  (replicated over batch axes). Used for LM vocab tables. Lookup: each model
  rank gathers its owned rows, ``psum('model')`` combines. Update: per-shard
  dense delta, ``psum`` over batch axes (every replica applies the same
  delta).
* ``mode='full'`` — rows sharded over *all* mesh axes flattened (the 512-way
  "PS node" set). Used for the paper's own massive recsys tables where
  replication over the batch axes is impossible. Lookup: ids are
  ``all_gather``-ed over the batch axes so every PS shard sees every id,
  partial rows are ``psum``-ed over all axes, each batch shard slices its
  tokens back out. Update: row-wise scatter into the locally-owned rows from
  the (already gathered) global id/grad set — the PS shard applying its own
  puts, no extra traffic.

Row placement uses the paper's *uniform shuffle* (§4.2.3 workload balance): a
fixed affine hash permutes row ids before mod-N placement, so hot feature
groups spread evenly across shards.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.utils import _mesh_axis_names, bspec_axes, round_up

# Affine permutation constants (a prime multiplier => bijection mod any row
# count it does not divide)
_SHUFFLE_MULT = 1_000_003
_SHUFFLE_ADD = 12_345


@dataclass(frozen=True)
class EmbeddingSpec:
    rows: int                       # logical rows (vocab size / total id space)
    dim: int
    mode: str = "model"             # 'model' | 'full'
    optimizer: str = "adagrad"      # 'adagrad' | 'sgd'
    lr: float = 1e-2
    eps: float = 1e-8
    staleness: int = 0              # tau; 0 = synchronous embedding updates
    dtype: Any = jnp.float32
    # -- storage backend (core/backend.py) ------------------------------------
    # 'dense' | 'host_lru' | 'host_lru+disk', optionally with a
    # '+compressed' wire decorator (e.g. 'host_lru+compressed',
    # 'host_lru+disk+compressed'). 'dense' is the device-resident PS
    # shard; 'host_lru' keeps `rows` host-side behind a device hot-cache
    # of `cache_rows` slots (paper §4.2.2 out-of-core tier); '+disk'
    # stacks a memory-mapped disk tier under a host LRU of `host_rows`,
    # so logical rows can exceed host RAM (core/mmap_store.py).
    backend: str = "dense"
    cache_rows: int = 0             # host_lru: device-resident hot slots
    wire_block: int = 128           # +compressed: blockscale block size
    wire_kernel: bool = False       # +compressed: Pallas kernel vs jnp ref
    # -- fused backward (kernels/fused_backward.py) ---------------------------
    # True routes the plan-driven put through the Pallas fused-backward
    # kernel (segment-sum + adagrad apply + queue payload in one pass);
    # False (default) keeps the jnp oracle on the same fused code path —
    # bit-identical to the decomposed plan_segment_sum + _apply_sparse.
    # The kernel path needs optimizer='adagrad' (any other optimizer is
    # refused when the backend is built) and applies to the single-shard
    # dense / host_lru puts.
    backward_kernel: bool = False
    # -- host-store row format (core/lru.py, core/mmap_store.py) --------------
    # 'fp32' (default) keeps cold host/disk rows at full precision;
    # 'blockscale16' stores them blockscale-compressed (fp16 payload +
    # one fp32 scale per <=128-wide block — the wire codec applied at
    # rest), roughly halving host bytes per row. Rows are decompressed on
    # fault-in and recompressed on write-back, so the device cache and
    # the optimizer math stay fp32. host_lru backends only.
    store_dtype: str = "fp32"
    # -- frequency-aware admission (core/hotness.py) --------------------------
    # > 0 enables the decayed count-min admission filter on host_lru
    # caches: a faulting id whose estimated hotness is below the
    # threshold is served from `bypass_rows` scratch slots instead of
    # claiming (and possibly evicting) a hot cache row. 0 = recency-only
    # admission, bit-identical to the pre-admission backend.
    admit_threshold: float = 0.0
    bypass_rows: int = 0            # scratch slots (0 = cache_rows // 4)
    # -- '+disk' tier sizing (core/mmap_store.py) -----------------------------
    host_rows: int = 0              # host LRU tier rows (0 = rows // 4)
    disk_path: str | None = None    # mmap backing dir (None = tempdir)
    # -- sharded PS router (core/backend.py ShardedBackend) -------------------
    # number of independent embedding-PS shards this table is hash-partitioned
    # over (paper §4.1: each embedding worker owns a partition of every
    # table). 1 = the plain single backend; k > 1 routes ids over k
    # per-shard backends with per-shard stores/locks and concurrent fault-in.
    emb_shards: int = 1
    # -- worker-side batch dedup (core/dedup.py) ------------------------------
    # True (default): the trainer's prepare phase computes a per-batch
    # DedupPlan and the whole lookup/queue/put path runs at unique width
    # (one row per unique id; staleness queues sized at the dedup cap).
    # False: the pre-dedup occurrence-width data path (PR-4 behavior),
    # kept for apples-to-apples benchmarking and old-format checkpoints.
    batch_dedup: bool = True

    def padded_rows(self, n_shards: int) -> int:
        return round_up(self.rows, max(n_shards, 1))


def _axes_for(mode: str) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(shard_axes, batch_axes) present in the ambient mesh."""
    names = _mesh_axis_names()
    batch = tuple(a for a in ("pod", "data") if a in names)
    if mode == "model":
        shard_axes = ("model",) if "model" in names else ()
    else:
        shard_axes = tuple(a for a in ("pod", "data", "model") if a in names)
    return shard_axes, batch


def _n_shards(shard_axes) -> int:
    if not shard_axes:
        return 1
    mesh = jax.sharding.get_abstract_mesh()
    n = 1
    for a in shard_axes:
        n *= mesh.shape[a]
    return n


def _addmod(x, y, n: int):
    s = x + y                        # x, y < n <= 2^31: no uint32 wrap
    return jnp.where(s >= n, s - n, s)


def shuffle_pos(ids, padded_rows: int):
    """Uniform-shuffle storage position for a row id: the affine map
    ``(id * MULT + ADD) mod padded_rows``, a permutation of
    ``[0, padded_rows)`` because the prime MULT does not divide it.

    The product is reduced by double-and-add so no intermediate leaves
    uint32: a plain ``id * MULT`` wraps for ids above 4,294, and the
    wrapped map sends distinct ids of a larger table to one row (20,769
    rows would land on 13,831)."""
    n = int(padded_rows)
    if not 0 < n <= 2 ** 31 or n % _SHUFFLE_MULT == 0:
        raise ValueError(f"shuffle_pos needs 0 < rows <= 2^31 not divisible "
                         f"by {_SHUFFLE_MULT}; got {n}")
    a = ids.astype(jnp.uint32) % n
    pos = jnp.full(a.shape, _SHUFFLE_ADD % n, jnp.uint32)
    m = _SHUFFLE_MULT % n
    while m:
        if m & 1:
            pos = _addmod(pos, a, n)
        a = _addmod(a, a, n)
        m >>= 1
    return pos


def mesh_shards(spec: EmbeddingSpec) -> int:
    """PS shards of ``spec``'s table in the ambient mesh (1 without one)."""
    return _n_shards(_axes_for(spec.mode)[0])


def ps_init(key, spec: EmbeddingSpec, n_shards: int = 1, scale: float = 0.02):
    """Embedding PS state: table + row-wise optimizer accumulator, padded
    to ``padded_rows(n_shards)``.

    The draw is made in logical-id order and then laid out: logical id
    ``i`` reads the same row whatever ``n_shards`` pads the table to, so a
    table sharded over a mesh starts from the same model as the unsharded
    one. Padding rows are zero and never addressed."""
    rows = spec.padded_rows(n_shards)
    table = (jax.random.normal(key, (spec.rows, spec.dim), jnp.float32)
             * scale).astype(spec.dtype)
    if rows != spec.rows:
        ids = jnp.arange(spec.rows)
        table = jnp.zeros((rows, spec.dim), spec.dtype).at[
            shuffle_pos(ids, rows)].set(table[shuffle_pos(ids, spec.rows)])
    state = {"table": table}
    if spec.optimizer == "adagrad":
        state["acc"] = jnp.zeros((rows,), jnp.float32)
    return state


def ps_init_on_host(key, spec: EmbeddingSpec, n_shards: int = 1,
                    scale: float = 0.02):
    """``ps_init`` computed on the host CPU backend, whatever the default
    device or the ambient mesh, returned as NumPy arrays for ``place``:
    one seed draws the same rows on every platform and mesh (the TPU's
    normal sampler differs from the CPU's in the last bits), and the full
    table never has to fit device memory. Under a mesh, jitted draws run
    on the mesh's devices, so the draw runs under a one-device mesh of the
    host instead. A traced key (an abstract init under ``jax.eval_shape``)
    has no device to choose."""
    if isinstance(key, jax.core.Tracer):
        return ps_init(key, spec, n_shards, scale)
    cpu = jax.devices("cpu")[0]
    host = jax.sharding.Mesh(np.array([cpu]), ("host",))
    with jax.sharding.set_mesh(host), jax.default_device(cpu):
        state = ps_init(jax.device_put(key, cpu), spec, n_shards, scale)
        return jax.tree.map(np.asarray, state)


def place(state, spec: EmbeddingSpec):
    """Lay a PS state out for the ambient mesh: rows split over the
    table's shard axes (each device receives only its own rows), or on
    the default device when there is one shard."""
    shard_axes, _ = _axes_for(spec.mode)
    if _n_shards(shard_axes) == 1:
        return jax.tree.map(jax.device_put, state)
    return jax.tree.map(
        lambda x: jax.device_put(x, P(shard_axes, *([None] * (x.ndim - 1)))),
        state)


def table_spec(spec: EmbeddingSpec) -> P:
    if spec.mode == "model":
        return P("model", None)
    return P(("pod", "data", "model"), None)


# ---------------------------------------------------------------------------
# Lookup (Persia Alg.1 forward: get(x_ID))
# ---------------------------------------------------------------------------

def lookup(state, spec: EmbeddingSpec, ids):
    """ids: (...,) int32 -> (..., dim). Out-of-range ids return zeros
    (used as padding in multi-hot bags)."""
    shape = ids.shape
    flat = ids.reshape(-1)
    shard_axes, batch_axes = _axes_for(spec.mode)
    n = _n_shards(shard_axes)
    rows = spec.padded_rows(n)
    valid = (flat >= 0) & (flat < spec.rows)
    pos = shuffle_pos(jnp.where(valid, flat, 0), rows)

    if n == 1:
        out = state["table"][pos] * valid[:, None].astype(state["table"].dtype)
        return out.reshape(*shape, spec.dim)

    rows_local = rows // n
    baxes = bspec_axes(pos.shape[0])
    bspec = P(baxes)

    if spec.mode == "model":
        @partial(jax.shard_map,
                 in_specs=(P("model", None), bspec, bspec),
                 out_specs=P(baxes, None),
                 check_vma=False)
        def _lk(tbl, pos_blk, valid_blk):
            me = jax.lax.axis_index("model")
            owner = pos_blk // rows_local
            local = pos_blk % rows_local
            mine = (owner == me) & valid_blk
            vals = tbl[local] * mine[:, None].astype(tbl.dtype)
            return jax.lax.psum(vals, "model")

        out = _lk(state["table"], pos, valid)
    else:
        all_axes = shard_axes

        @partial(jax.shard_map,
                 in_specs=(P(all_axes, None), bspec, bspec),
                 out_specs=P(baxes, None),
                 check_vma=False)
        def _lk(tbl, pos_blk, valid_blk):
            me = _flat_index(all_axes)
            # every shard must see every id: gather ids over the batch axes
            if baxes:
                pos_all = jax.lax.all_gather(pos_blk, baxes, tiled=True)
                valid_all = jax.lax.all_gather(valid_blk, baxes, tiled=True)
            else:
                pos_all, valid_all = pos_blk, valid_blk
            owner = pos_all // rows_local
            local = pos_all % rows_local
            mine = (owner == me) & valid_all
            vals = tbl[local] * mine[:, None].astype(tbl.dtype)
            vals = jax.lax.psum(vals, all_axes)                    # (T_glob, D)
            # slice this batch shard's tokens back out
            if baxes:
                t_local = pos_blk.shape[0]
                off = _flat_index(baxes) * t_local
                vals = jax.lax.dynamic_slice(
                    vals, (off, 0), (t_local, vals.shape[1]))
            return vals

        out = _lk(state["table"], pos, valid)

    return out.reshape(*shape, spec.dim)


def _flat_index(axes):
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _axes_size(axes):
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


# ---------------------------------------------------------------------------
# Gradient put + optimizer apply (Persia Alg.1 backward)
# ---------------------------------------------------------------------------

def apply_put(state, spec: EmbeddingSpec, ids, grads, assume_unique=False):
    """Apply activation gradients to the table (put + PS-side optimizer).

    ids: (T,) int32; grads: (T, dim) — gradients of the *looked-up
    activations* (Persia's F^emb'), exactly what NN workers send back.

    ``assume_unique=True`` declares the put pre-deduplicated (the
    worker-side batch-dedup path, core/dedup.py: ids are a DedupPlan's
    unique set, grads already segment-summed) and skips the on-device
    sort-based dedup — the row-sparse apply is exact on unique ids.
    """
    from repro.core.compression import dedup_put
    from repro.core.dedup import dedup_cap
    shard_axes, batch_axes = _axes_for(spec.mode)
    n = _n_shards(shard_axes)
    rows = spec.padded_rows(n)
    flat = ids.reshape(-1)
    grads = grads.reshape(-1, spec.dim)
    valid = (flat >= 0) & (flat < spec.rows)
    pos = shuffle_pos(jnp.where(valid, flat, 0), rows)
    g = jnp.where(valid[:, None], grads, 0.0).astype(jnp.float32)

    # the embedding worker aggregates a put before it crosses the wire
    # (paper §4.1 step 4 + the §4.2.3 lossless index compression): duplicate
    # rows are segment-summed so the gathered put is one row per unique id.
    # ONLY the gather-based paths (full mode / single-shard sparse apply)
    # dedup — model mode's dense-delta scatter aggregates duplicates exactly
    # without a sort (a global jit-level sort of the LM-scale (T, D) put
    # measured +2.7x peak memory; see EXPERIMENTS.md §Perf I13).
    # capacity is rounded up so the deduped arrays still shard over the
    # batch axes on any production mesh (up to 1024 batch shards).
    pos_signed = jnp.where(valid, pos.astype(jnp.int32), -1)

    def _dedup():
        if assume_unique:
            return pos_signed, g
        return dedup_put(pos_signed, g,
                         dedup_cap(int(pos.shape[0]), rows))

    if n == 1:
        pos_u, g_u = _dedup()
        return _apply_sparse(state, spec,
                             jnp.where(pos_u >= 0, pos_u, rows), g_u, rows)

    rows_local = rows // n
    baxes = bspec_axes(pos.shape[0])
    bspec = P(baxes)
    bspec2 = P(baxes, None)

    if spec.mode == "model":
        in_tree = (jax.tree.map(lambda _: P("model", None)
                                if _.ndim == 2 else P("model"), state),
                   bspec, bspec2)

        @partial(jax.shard_map, in_specs=in_tree,
                 out_specs=jax.tree.map(lambda x: P("model", None)
                                        if x.ndim == 2 else P("model"), state),
                 check_vma=False)
        def _put(st, pos_blk, g_blk):
            me = jax.lax.axis_index("model")
            owner = jnp.where(pos_blk >= 0, pos_blk // rows_local, -1)
            local = jnp.where(owner == me, pos_blk % rows_local, rows_local)
            delta = jnp.zeros((rows_local + 1, spec.dim), jnp.float32)
            delta = delta.at[local].add(g_blk)[:rows_local]
            cnt = jnp.zeros((rows_local + 1,), jnp.float32)
            cnt = cnt.at[local].add((owner == me).astype(jnp.float32))[:rows_local]
            if baxes:
                delta = jax.lax.psum(delta, baxes)
                cnt = jax.lax.psum(cnt, baxes)
            return _apply_delta(st, spec, delta, cnt)

        return _put(state, pos_signed, g)

    all_axes = shard_axes
    st_spec = jax.tree.map(lambda x: P(all_axes, None) if x.ndim == 2
                           else P(all_axes), state)

    # the deduped put is what crosses the wire (paper's index compression
    # applied to the gradient traffic): gather over batch shards, each PS
    # shard applies its own rows sparsely
    pos_u, g_u = _dedup()
    baxes = bspec_axes(pos_u.shape[0])
    bspec = P(baxes)
    bspec2 = P(baxes, None)

    @partial(jax.shard_map, in_specs=(st_spec, bspec, bspec2),
             out_specs=st_spec, check_vma=False)
    def _put(st, uniq_blk, g_blk):
        from repro.core.compression import dedup_put as _dedup
        me = _flat_index(all_axes)
        if baxes:
            uniq_all = jax.lax.all_gather(uniq_blk, baxes, tiled=True)
            g_all = jax.lax.all_gather(g_blk, baxes, tiled=True)
            # a row can arrive from several batch shards: aggregate once more
            # so the adagrad accumulator sees one summed put per row
            uniq_all, g_all = _dedup(uniq_all, g_all,
                                     min(int(uniq_all.shape[0]), rows))
        else:
            uniq_all, g_all = uniq_blk, g_blk
        owner = jnp.where(uniq_all >= 0, uniq_all // rows_local, -1)
        local = jnp.where(owner == me, uniq_all % rows_local, rows_local)
        return _apply_sparse(st, spec, local, g_all, rows_local)

    return _put(state, pos_u, g_u)


def _apply_delta(st, spec: EmbeddingSpec, delta, cnt):
    """PS-shard-local optimizer step given a dense per-shard delta
    (model-mode tables: V_local x D is small, psum-friendly)."""
    new = dict(st)
    if spec.optimizer == "adagrad":
        acc = st["acc"] + jnp.mean(jnp.square(delta), axis=-1)
        step = delta * jax.lax.rsqrt(acc + spec.eps)[:, None]
        new["acc"] = acc
    else:
        step = delta
    new["table"] = (st["table"].astype(jnp.float32)
                    - spec.lr * step).astype(st["table"].dtype)
    return new


def _apply_sparse(st, spec: EmbeddingSpec, idx, g, n_rows):
    """Row-sparse optimizer apply: O(#puts), never O(rows).

    idx: (U,) local row indices; entries == n_rows (or any >= n_rows) are
    dropped via a sacrificial padding row. Duplicate rows accumulate — the
    paper's lock-free put semantics (acc sees all increments before the
    scaled step is taken, batch-style adagrad).
    """
    new = dict(st)
    live = (idx >= 0) & (idx < n_rows)
    safe = jnp.clip(idx, 0, n_rows - 1)
    g = jnp.where(live[:, None], g.astype(jnp.float32), 0.0)
    if spec.optimizer == "adagrad":
        inc = jnp.where(live, jnp.mean(jnp.square(g), axis=-1), 0.0)
        acc = st["acc"].at[safe].add(inc)
        new["acc"] = acc
        step = g * jax.lax.rsqrt(acc[safe] + spec.eps)[:, None]
    else:
        step = g
    new["table"] = st["table"].at[safe].add(
        (-spec.lr * step).astype(st["table"].dtype))
    return new


# ---------------------------------------------------------------------------
# Bounded-staleness queue (the async relaxation; Assumption 1, t - D(t) <= tau)
# ---------------------------------------------------------------------------

def queue_init(spec: EmbeddingSpec, put_ids_shape, put_dim):
    """FIFO of tau pending puts. Each slot holds (ids, grads). Grads are
    held in the table's dtype (bf16 on the big configs — the queue is the
    largest transient of the hybrid algorithm at LM scale)."""
    tau = spec.staleness
    if tau <= 0:
        return None
    gdtype = spec.dtype
    return {
        "ids": jnp.full((tau,) + tuple(put_ids_shape), -1, jnp.int32),
        "grads": jnp.zeros((tau,) + tuple(put_ids_shape) + (put_dim,),
                           gdtype),
        "ptr": jnp.zeros((), jnp.int32),
        "filled": jnp.zeros((), jnp.int32),
    }


def queue_push_pop(queue, ids, grads):
    """Push this step's put; pop the put from tau steps ago (or an empty put
    with ids=-1 during warmup, which apply_put treats as a no-op)."""
    ptr = queue["ptr"]
    old_ids = jnp.take(queue["ids"], ptr, axis=0)
    old_grads = jnp.take(queue["grads"], ptr, axis=0)
    tau = queue["ids"].shape[0]
    new_q = {
        "ids": jax.lax.dynamic_update_index_in_dim(
            queue["ids"], ids.astype(jnp.int32), ptr, 0),
        "grads": jax.lax.dynamic_update_index_in_dim(
            queue["grads"], grads.astype(queue["grads"].dtype), ptr, 0),
        "ptr": (ptr + 1) % tau,
        "filled": jnp.minimum(queue["filled"] + 1, tau),
    }
    return new_q, old_ids, old_grads


def hybrid_emb_update(state, queue, spec: EmbeddingSpec, ids, grads):
    """One hybrid-algorithm embedding update: enqueue this step's put, apply
    the (tau-stale) put that pops out. tau=0 applies immediately (sync)."""
    if spec.staleness <= 0 or queue is None:
        return apply_put(state, spec, ids, grads), queue
    queue, old_ids, old_grads = queue_push_pop(queue, ids, grads)
    state = apply_put(state, spec, old_ids, old_grads)
    return state, queue
