"""Pluggable embedding storage backends — the memory hierarchy behind the PS.

Persia's 100T-parameter capacity claim (paper §4.2.2/§4.2.3) rests on the
embedding tier being *bigger than device memory*: PS nodes keep tables in
host RAM behind an LRU array-list cache and move rows over a compressed
wire. This module makes that a first-class storage choice: every table in an
:class:`~repro.core.collection.EmbeddingCollection` selects its backend via
``EmbeddingSpec.backend``:

* ``DenseBackend`` — the device-sharded PS of :mod:`repro.core.embedding_ps`
  re-housed behind the protocol, numerically unchanged.
* ``HostLRUBackend`` — the out-of-core tier: a device-resident hot-cache of
  ``spec.cache_rows`` slots backed by a host :class:`LRUEmbeddingStore`
  holding all ``spec.rows`` (vectors **and** adagrad accumulators, the
  paper's array-item layout). ``prepare`` faults missing rows host→device
  and writes evicted dirty rows back, so logical ``rows`` can exceed device
  memory.
* ``CompressedWireBackend`` — a decorator over either backend applying the
  paper's §4.2.3 wire compression: lossless unique-id dedup on puts plus
  lossy blockscale fp16 on get/put payloads, surfacing bytes-moved metrics.
* ``ShardedBackend`` — the sharded parameter-server router (paper §4.1:
  every embedding worker owns a hash partition of every table). Wraps
  ``spec.emb_shards`` independent per-shard backends (dense or host_lru)
  behind this same protocol: deterministic affine-hash ``id -> shard``
  routing, per-shard slot maps / LRU stores / staleness queues / locks, a
  thread-pool ``prepare`` that faults all shards **concurrently** (host
  fault-in latency drops near-linearly with shards on miss-heavy
  workloads), shard-tagged checkpoints that **reshard on restore** (save
  with N shards, restore with M — row-exact), and per-shard traffic/hit
  metrics plus a max/mean load-imbalance gauge. Composable under the
  compressed wire (wire outside, router inside).

All backends speak the worker-side batch-dedup protocol (core/dedup.py):
the trainer's prepare phase hands the traceable ops a per-batch
``DedupPlan`` (unique device ids + occurrence inverse) instead of raw id
arrays, so lookups gather one row per *unique* id and puts are
segment-summed to unique width before they reach the staleness queue —
queue memory, device puts and wire bytes all shrink by the batch's
duplication factor (``EmbeddingSpec.batch_dedup=False`` restores the
occurrence-width PR-4 path).

The protocol splits host-level from traceable ops:

  host-level (never traced; may mutate backend-owned host state):
    ``init / prepare / queue_init / state_for_checkpoint /
    restore_from_checkpoint``
  traceable (pure, jit-safe, operate on *device ids* — raw ids for dense,
  cache-slot indices for host_lru — produced by ``prepare``):
    ``lookup / apply_put / hybrid_update``

``lookup`` returns ``(acts, metrics)`` and the put ops return their updated
state plus a metrics dict (empty except for the compressed wire), so wire
traffic flows out through the trainer's per-step metrics.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as C
from repro.core import dedup as D
from repro.core import embedding_ps as PS
from repro.core.dedup import DedupPlan
from repro.core.embedding_ps import EmbeddingSpec
from repro.core.hotness import HotnessSketch
from repro.core.lru import LRUEmbeddingStore, STORE_DTYPES
from repro.core.mmap_store import TieredHostStore
from repro.core.spans import span
from repro.utils import round_up


def _prod(shape) -> int:
    return math.prod(int(s) for s in shape)


# dedup capacity + jit-shape bucketing both live in core/dedup.py now —
# one shared rule for the PS apply, the queue sizing, the wire and the
# fault path (a drifted mirror would make one layer drop rows another
# layer still ships)
_pow2_bucket = D.pow2_bucket


# the fault path's device ops, fused and jitted (cached per bucket shape):
# one dispatch per table instead of one per array keeps the host prepare
# phase off the dispatch-overhead treadmill

@jax.jit
def _fault_apply(table, slot_ids, vslots, vecs, ids):
    with jax.named_scope("persia/fault"):
        return (table.at[vslots].set(vecs.astype(table.dtype)),
                slot_ids.at[vslots].set(ids))


@jax.jit
def _fault_apply_acc(table, slot_ids, acc, vslots, vecs, ids, accs):
    with jax.named_scope("persia/fault"):
        return (table.at[vslots].set(vecs.astype(table.dtype)),
                slot_ids.at[vslots].set(ids),
                acc.at[vslots].set(accs))


def _lru_victims(clock: np.ndarray, cand_slots: np.ndarray,
                 n: int) -> np.ndarray:
    """The ``n`` least-recently-touched of ``cand_slots`` (ascending slot
    indices into ``clock``), oldest first, ties broken by slot index:
    exactly ``cand_slots[np.argsort(clock[cand_slots], kind="stable")[:n]]``
    in linear time. ``clock * len(clock) + slot`` is unique per slot and
    orders like (clock, slot), so a partition finds the ``n`` smallest and
    only those are sorted; the slot is the key's remainder."""
    size = clock.size
    key = np.partition(clock[cand_slots] * size + cand_slots, n - 1)[:n]
    key.sort()
    return key % size


@jax.jit
def _gather_rows(table, eslots):
    return table[eslots].astype(jnp.float32)


@jax.jit
def _gather_rows_acc(table, acc, eslots):
    return (table[eslots].astype(jnp.float32),
            acc[eslots].astype(jnp.float32))


class EmbeddingBackend:
    """Protocol base. Subclasses own one table's storage (device arrays are
    threaded through as pytrees; anything host-resident lives on ``self``).
    ``requires_prepare`` tells the trainer whether ``prepare`` does real work
    (host fault-in) and therefore must run outside jit every step.

    The traceable ops accept device ids in two forms: a raw id array (the
    pre-dedup occurrence-width path, one row per occurrence) or a
    :class:`~repro.core.dedup.DedupPlan` (the worker-side batch-dedup path:
    ``dev`` unique device ids + ``inv`` occurrence->unique inverse). The
    base class dispatches on the form; subclasses implement the ``_flat``
    (occurrence) and ``_unique`` (plan) variants. With a plan, ``lookup``
    gathers unique rows and scatters through the inverse, and the puts
    segment-sum occurrence grads to unique width ONCE at the outermost
    layer — everything downstream (queues, wire, optimizer apply) runs at
    unique width."""

    spec: EmbeddingSpec
    requires_prepare: bool = False
    # set by restore_from_checkpoint when the restored blob had a different
    # shard geometry than this backend (caches flushed, queues invalidated)
    last_restore_resharded: bool = False

    @functools.cached_property
    def span_s(self) -> dict[str, float]:
        """Seconds of this table's host spans by name (``prepare/plan``,
        ``prepare/slots``, ...; core/spans.py), over the backend's life."""
        return {}

    # -- host-level ----------------------------------------------------------
    def init(self, key, shards: int = 1, scale: float = 0.02):
        raise NotImplementedError

    def prepare(self, state, ids, assume_unique: bool = False, counts=None):
        """(state, ids) -> (state, device_ids). Host-level, once per step.
        ``assume_unique`` marks ids as an already-deduped set (a plan's
        unique ids — backends skip their own np.unique); ``counts`` carries
        the per-unique occurrence counts for traffic accounting."""
        return state, ids

    def prepare_submit(self, state, ids, assume_unique: bool = False,
                       counts=None):
        """Two-phase prepare: submit now, collect later. Returns a thunk
        producing ``(state, device_ids)``. The split exists so a caller
        preparing several tables can submit them all before collecting any
        — remote backends buffer the submit into one coalesced RPC frame
        per endpoint and only the collect waits. The in-process default
        just defers the blocking :meth:`prepare`."""
        return lambda: self.prepare(state, ids, assume_unique, counts)

    def read_rows(self, state, ids):
        """Serve-path read: LOGICAL ids -> ``(rows, info)`` where ``rows``
        is fp32 of shape ``ids.shape + (dim,)`` and ``info`` carries the
        read gauges ``reads`` (unique ids resolved), ``hits`` (served from
        device-resident rows) and ``misses`` (served from the host tier).

        Unlike ``prepare`` + ``lookup`` this is **read-only**: no row is
        faulted into the device cache, no slot is evicted, no host
        bookkeeping changes — so a serving thread can call it concurrently
        with a trainer stepping on the same backend. Host-cached
        implementations resolve residency against the *caller's* state
        snapshot (whose table and slot map can never desync), take the
        backend lock for the host-tier reads, and pin the slots they
        gather from so a concurrent fault-in never recycles a row
        mid-inference. Invalid ids (< 0 or >= rows) read as zero rows.

        The device-resident default gathers through the backend's own
        lookup (every read is a hit)."""
        if self.requires_prepare:
            raise NotImplementedError
        arr = np.asarray(ids, np.int64)
        acts, _ = self._lookup_flat(state, jnp.asarray(arr, jnp.int32))
        flat = arr.reshape(-1)
        n = int(np.unique(flat[(flat >= 0) & (flat < self.spec.rows)]).size)
        rows = np.asarray(acts.astype(jnp.float32)).reshape(
            arr.shape + (self.spec.dim,))
        return rows, {"reads": n, "hits": n, "misses": 0}

    # -- worker-side dedup sizing --------------------------------------------

    def dedup_rows(self) -> int:
        """Upper bound on distinct device ids one batch can produce — the
        denominator of the dedup capacity rule for this backend."""
        return self.spec.rows

    def queue_width(self, n_occ: int) -> int:
        """Width of this table's staleness-queue slots for a batch of
        ``n_occ`` id occurrences: the dedup cap under batch dedup, the raw
        occurrence count on the legacy path."""
        if self.spec.batch_dedup:
            return D.dedup_cap(n_occ, self.dedup_rows())
        return int(n_occ)

    # slot pinning: a pipelined caller pins a batch's device slots between
    # its prepare and its applied put, so a later batch's fault-in cannot
    # recycle rows still in flight. No-ops for device-resident backends
    # (device ids ARE logical ids — nothing is ever recycled).
    def pin_slots(self, dev_ids):
        pass

    def unpin_slots(self, dev_ids):
        pass

    def reset_pins(self):
        pass

    # -- shard introspection (pipelined callers, metrics) --------------------
    # Unsharded backends are one PS "shard": all puts land on shard 0.
    # ShardedBackend overrides both so the pipeline can run per-shard
    # put backpressure and the trainer can surface per-shard metrics.
    def n_put_shards(self) -> int:
        return 1

    def put_shards(self, dev_ids) -> tuple[int, ...]:
        return (0,)

    def shard_metrics(self) -> dict:
        return {}

    def cache_metrics(self) -> dict:
        """Per-step cache-admission gauges (keys are relative: the prepare
        driver prefixes ``cache/<table>/``). Empty for backends without an
        admission policy."""
        return {}

    def queue_init(self, ids_shape):
        raise NotImplementedError

    def state_for_checkpoint(self, state):
        raise NotImplementedError

    def restore_from_checkpoint(self, blob):
        raise NotImplementedError

    # -- traceable -----------------------------------------------------------
    #
    # Public ops dispatch on the dev_ids form (raw array vs DedupPlan);
    # subclasses implement the _flat/_unique variants. The plan path
    # segment-sums occurrence grads to unique width here, exactly once.

    def lookup(self, state, dev_ids):
        if D.is_plan(dev_ids):
            acts_u, m = self._lookup_unique(state, dev_ids.dev)
            return D.plan_scatter(acts_u, dev_ids.inv), m
        return self._lookup_flat(state, dev_ids)

    def apply_put(self, state, dev_ids, grads):
        if D.is_plan(dev_ids):
            return self._put_plan(state, dev_ids, grads)
        return self._put_flat(state, dev_ids, grads)

    def hybrid_update(self, state, queue, dev_ids, grads):
        if D.is_plan(dev_ids):
            return self._hybrid_plan(state, queue, dev_ids, grads)
        return self._hybrid_flat(state, queue, dev_ids, grads)

    def _put_plan(self, state, plan, grads):
        """Plan-driven put. Default: decompose into the plan's segment-sum
        then the unique-width put. Dense/HostLRU override with the fused
        backward (segment-sum + optimizer apply + queue payload in one
        pass, kernels/fused_backward.py); the shard router keeps the
        decomposition (one segment-sum reused across every shard) and the
        compressed wire bypasses this dispatch entirely."""
        g_u = D.plan_segment_sum(plan.inv, grads, int(plan.dev.shape[0]))
        return self._put_unique(state, plan.dev, g_u)

    def _hybrid_plan(self, state, queue, plan, grads):
        g_u = D.plan_segment_sum(plan.inv, grads, int(plan.dev.shape[0]))
        return self._hybrid_unique(state, queue, plan.dev, g_u)

    def _lookup_flat(self, state, dev_ids):
        raise NotImplementedError

    def _lookup_unique(self, state, dev_u):
        """(U,) unique device ids -> ((U, dim) rows, metrics). Default:
        the flat lookup already handles any id shape."""
        return self._lookup_flat(state, dev_u)

    def _put_flat(self, state, dev_ids, grads):
        raise NotImplementedError

    def _put_unique(self, state, dev_u, g_u):
        """Pre-deduped put: (U,) unique device ids + (U, dim) fp32 summed
        grads — no on-device sort/dedup needed."""
        raise NotImplementedError

    def _hybrid_flat(self, state, queue, dev_ids, grads):
        raise NotImplementedError

    def _hybrid_unique(self, state, queue, dev_u, g_u):
        raise NotImplementedError

    # -- capacity accounting (benchmarks) ------------------------------------
    def device_bytes(self, state) -> int:
        return sum(int(x.size) * x.dtype.itemsize
                   for x in jax.tree.leaves(state))

    def host_bytes(self) -> int:
        return 0


def _fused_backward(spec, state, inv, grads, apply_idx, apply_g, *,
                    apply_self=False):
    """One-pass plan-driven put: segment-sum the occurrence grads via the
    dedup-plan inverse, apply the optimizer row-wise at ``apply_idx``
    (-1 = no-op), return ``(new_state, g_push)`` with ``g_push`` the
    queue-ready unique-width payload.

    ``spec.backward_kernel`` selects the Pallas kernel (adagrad only — the
    accumulator update is built into the pass, and ``create_backend``
    refuses the flag with any other optimizer); the default jnp oracle is
    bit-identical to ``plan_segment_sum`` + ``PS._apply_sparse``.
    """
    if apply_g is None:
        apply_g = jnp.zeros((int(apply_idx.shape[0]), spec.dim),
                            jnp.float32)
    acc = state.get("acc") if spec.optimizer == "adagrad" else None
    if spec.backward_kernel:
        from repro.kernels import ops as K
        table, acc, g_push = K.fused_backward(
            state["table"], acc, inv, grads, apply_idx, apply_g,
            lr=spec.lr, eps=spec.eps, apply_self=apply_self)
    else:
        from repro.kernels import ref as KR
        table, acc, g_push = KR.fused_backward_ref(
            state["table"], acc, inv, grads, apply_idx, apply_g,
            cap=int(apply_idx.shape[0]), lr=spec.lr, eps=spec.eps,
            apply_self=apply_self)
    new = dict(state)
    new["table"] = table
    if acc is not None:
        new["acc"] = acc
    return new, g_push


# ===========================================================================
# DenseBackend — today's device-sharded PS behind the protocol
# ===========================================================================

class DenseBackend(EmbeddingBackend):
    """Device-resident PS shard; every op delegates to embedding_ps with no
    numerical change (device ids ARE the logical ids)."""

    requires_prepare = False

    def __init__(self, spec: EmbeddingSpec):
        if spec.store_dtype != "fp32":
            raise ValueError(
                f"store_dtype={spec.store_dtype!r} compresses cold HOST "
                "rows — the dense backend is fully device-resident; use a "
                "host_lru backend (or drop store_dtype)")
        self.spec = spec

    def init(self, key, shards: int = 1, scale: float = 0.02):
        """Draw the table on the host CPU backend — the same seed gives the
        same rows on every platform, and the same rows the host_lru
        backend draws there — padded and placed for the ambient mesh, the
        geometry ``lookup``/``apply_put`` address. ``shards`` is unused:
        the mesh, not the caller, fixes a dense table's layout."""
        del shards
        return PS.place(PS.ps_init_on_host(
            key, self.spec, PS.mesh_shards(self.spec), scale), self.spec)

    def queue_init(self, ids_shape):
        if self.spec.staleness <= 0:
            return None
        return self._queue_init_width(self.queue_width(_prod(ids_shape)))

    def _queue_init_width(self, width: int):
        return PS.queue_init(self.spec, (int(width),), self.spec.dim)

    def _lookup_flat(self, state, dev_ids):
        return PS.lookup(state, self.spec, dev_ids), {}

    def _put_flat(self, state, dev_ids, grads):
        return PS.apply_put(state, self.spec, dev_ids.reshape(-1),
                            grads.reshape(-1, self.spec.dim)), {}

    def _put_unique(self, state, dev_u, g_u):
        return PS.apply_put(state, self.spec, dev_u, g_u,
                            assume_unique=True), {}

    def _logical_to_pos(self, ids):
        """Logical id (-1 = no-op) -> physical shuffled row, -1 preserved —
        the assume_unique translation inside PS.apply_put, hoisted so the
        fused pass can scatter rows directly."""
        spec = self.spec
        valid = (ids >= 0) & (ids < spec.rows)
        pos = PS.shuffle_pos(jnp.where(valid, ids, 0), spec.padded_rows(1))
        return jnp.where(valid, pos.astype(jnp.int32), -1)

    def _fusable(self) -> bool:
        # the fused pass is the single-PS-shard sparse apply; mesh-sharded
        # tables keep the decomposed shard_map path
        return PS.mesh_shards(self.spec) == 1

    def _put_plan(self, state, plan, grads):
        if not self._fusable():
            return super()._put_plan(state, plan, grads)
        new, _ = _fused_backward(self.spec, state, plan.inv, grads,
                                 self._logical_to_pos(plan.dev), None,
                                 apply_self=True)
        return new, {}

    def _hybrid_plan(self, state, queue, plan, grads):
        spec = self.spec
        if spec.staleness <= 0 or queue is None:
            st, m = self._put_plan(state, plan, grads)
            return st, queue, m
        if not self._fusable():
            return super()._hybrid_plan(state, queue, plan, grads)
        # pop the tau-stale put first (it reads the pre-push queue), fuse
        # its apply with this step's segment-sum, then push the fresh
        # payload into the popped slot — the queue_push_pop ordering
        cap = int(queue["ids"].shape[1])
        ptr = queue["ptr"]
        old_ids = jnp.take(queue["ids"], ptr, axis=0)
        old_g = jnp.take(queue["grads"], ptr, axis=0)
        new, g_push = _fused_backward(spec, state, plan.inv, grads,
                                      self._logical_to_pos(old_ids), old_g)
        tau = queue["ids"].shape[0]
        new_q = {
            "ids": jax.lax.dynamic_update_index_in_dim(
                queue["ids"],
                D.pad_axis0(plan.dev.astype(jnp.int32), cap, -1), ptr, 0),
            "grads": jax.lax.dynamic_update_index_in_dim(
                queue["grads"], g_push.astype(queue["grads"].dtype),
                ptr, 0),
            "ptr": (ptr + 1) % tau,
            "filled": jnp.minimum(queue["filled"] + 1, tau),
        }
        return new, new_q, {}

    def _hybrid_flat(self, state, queue, dev_ids, grads):
        spec = self.spec
        flat = dev_ids.reshape(-1)
        g = grads.reshape(-1, spec.dim)
        if spec.staleness <= 0 or queue is None or not spec.batch_dedup:
            # legacy path: occurrence-width queue, dedup at apply time
            st, q = PS.hybrid_emb_update(state, queue, spec, flat, g)
            return st, q, {}
        # unique-width queue: the occurrence put must dedup BEFORE the push
        # (same summed rows the post-queue dedup would produce, so mixing
        # this path with plan-driven steps keeps the queue invariant: every
        # queued put is one row per unique id)
        valid = (flat >= 0) & (flat < spec.rows)
        ids_signed = jnp.where(valid, flat.astype(jnp.int32), -1)
        gm = jnp.where(valid[:, None], g, 0.0).astype(jnp.float32)
        uniq, g_u = C.dedup_put(ids_signed, gm, int(queue["ids"].shape[1]))
        return self._hybrid_unique(state, queue, uniq, g_u)

    def _hybrid_unique(self, state, queue, dev_u, g_u):
        spec = self.spec
        if spec.staleness <= 0 or queue is None:
            st, m = self._put_unique(state, dev_u, g_u)
            return st, queue, m
        cap = int(queue["ids"].shape[1])
        ids_cap = D.pad_axis0(dev_u.astype(jnp.int32), cap, -1)
        g_cap = D.pad_axis0(g_u, cap, 0)
        queue, old_ids, old_g = PS.queue_push_pop(queue, ids_cap, g_cap)
        st = PS.apply_put(state, spec, old_ids, old_g, assume_unique=True)
        return st, queue, {}

    def state_for_checkpoint(self, state):
        return jax.tree.map(np.asarray, state)

    def restore_from_checkpoint(self, blob):
        spec = self.spec
        self.last_restore_resharded = False
        if isinstance(blob, dict) and "shard_meta" in blob:
            # a sharded-router checkpoint restored into a single-shard
            # trainer: gather the logical rows and rebuild (N -> 1 reshard)
            vec, acc = extract_logical_rows(blob, spec, "dense")
            self.last_restore_resharded = True
            return _dense_state_from_logical(spec, spec.rows, vec, acc)
        table = blob.get("table") if isinstance(blob, dict) else None
        if table is None:
            raise ValueError(
                "checkpoint blob has no 'table' — it was not written by the "
                "dense backend (restoring across backends is not supported)")
        if table.shape[1] != spec.dim or table.shape[0] < spec.rows:
            raise ValueError(
                f"checkpoint table has shape {tuple(table.shape)} but this "
                f"table's spec wants >= ({spec.rows}, {spec.dim}) — "
                "collection changed since the save?")
        rows = spec.padded_rows(PS.mesh_shards(spec))
        if table.shape[0] != rows:
            # saved under a mesh that padded the table differently: move
            # every logical row to its place in this mesh's geometry (the
            # queue holds logical ids, so its pending puts stay valid)
            vec, acc = extract_logical_rows(blob, spec, "dense")
            return _dense_state_from_logical(spec, rows, vec, acc)
        return blob


# ===========================================================================
# HostLRUBackend — the out-of-core tier (paper §4.2.2)
# ===========================================================================

class HostLRUBackend(EmbeddingBackend):
    """Device hot-cache of ``spec.cache_rows`` slots over a host
    :class:`LRUEmbeddingStore` holding all ``spec.rows``.

    ``prepare`` is the fault path: it resolves the batch's unique ids
    against the slot map, writes the LRU victims' (vector, acc) back to the
    host store, loads the missing rows device-side, and returns the batch
    translated to cache-slot indices. The traceable ops then run entirely on
    the device cache — lookups gather slots, puts apply the PS-side
    optimizer to slots via the same dedup + row-sparse apply as the dense
    backend, so a working set that fits in cache is bit-exact with dense.

    Staleness queues store ``(slot, logical id)`` pairs; a popped put whose
    slot has been recycled for another id since it was enqueued is dropped
    (the paper's tolerated lost put). Note this includes recycling caused by
    *read-path* fault-ins: an eval/lookup batch near the cache's capacity
    can evict a slot with a put still pending in the queue — unlike the
    dense backend, eval is then not perfectly side-effect-free. Alg.1's
    lock-free semantics tolerate the loss; size ``cache_rows`` above the
    combined train+eval working set where that matters.

    The host tier (slot map, clock, LRU store) is guarded by an RLock:
    ``prepare`` may be called from a pipeline's prepare-stage thread while
    another thread (eval, checkpointing) touches the same backend, and the
    slot bookkeeping must stay a bijection under that interleaving. Callers
    are still responsible for sequencing the *device-array* state they
    thread through prepare/put (the pipeline's table-store lock does this).
    """

    requires_prepare = True

    def __init__(self, spec: EmbeddingSpec):
        if spec.cache_rows <= 0:
            raise ValueError(
                "host_lru backend needs EmbeddingSpec.cache_rows > 0 "
                f"(got {spec.cache_rows})")
        if spec.optimizer not in ("adagrad", "sgd"):
            raise ValueError(spec.optimizer)
        if spec.store_dtype not in STORE_DTYPES:
            raise ValueError(
                f"unknown store_dtype {spec.store_dtype!r}: one of "
                f"{STORE_DTYPES}")
        self.spec = spec
        self.cache_rows = int(spec.cache_rows)
        # three-tier variant: the host store becomes a TieredHostStore
        # (host LRU over mmap disk) instead of an all-rows LRU store
        self._disk = "disk" in (spec.backend or "").split("+")
        # frequency-aware admission (MixCache-style): a decayed count-min
        # sketch scores each unique id; ids below admit_threshold are
        # served from BYPASS slots — a small scratch region appended after
        # the main cache — so a once-seen cold id never evicts a hot
        # resident. admit_threshold <= 0 disables the sketch entirely and
        # keeps the pre-admission behaviour bit-identical.
        self.admit_threshold = float(spec.admit_threshold)
        if self.admit_threshold > 0:
            self.bypass_rows = (int(spec.bypass_rows)
                                or max(1, self.cache_rows // 4))
            self._sketch: HotnessSketch | None = HotnessSketch()
        else:
            self.bypass_rows = 0
            self._sketch = None
        self.dev_slots = self.cache_rows + self.bypass_rows
        self.store: LRUEmbeddingStore | TieredHostStore | None = None
        self._lock = threading.RLock()
        # the slot map lives on two arrays only, kept inverse to each
        # other: id -> cache slot (-1 = absent) and slot -> id (-1 = empty);
        # fault-ins and evictions update both with one scatter each
        self._slot_arr = np.full(spec.rows, -1, np.int32)
        self._id_for_slot = np.full(self.dev_slots, -1, np.int64)
        self._slot_clock = np.zeros(self.dev_slots, np.int64)
        self._pin_count = np.zeros(self.dev_slots, np.int32)
        self._tick = 0
        self.faults = 0          # rows moved host -> device
        self.writebacks = 0      # rows moved device -> host
        self.hits = 0            # unique ids resolved without a fault
        self.admits = 0          # faults granted a main-cache slot
        self.bypasses = 0        # faults served from the bypass region
        self.promotes = 0        # bypass rows re-admitted once hot
        self.last_admit = 0      # per-step versions of the three above
        self.last_bypass = 0
        self.last_promote = 0

    # -- host-level ----------------------------------------------------------

    def init(self, key, shards: int = 1, scale: float = 0.02):
        if shards != 1:
            raise ValueError(
                "HostLRUBackend is one PS shard; to run a host-backed table "
                f"over {shards} shards set EmbeddingSpec.emb_shards (or pass "
                "emb_shards to PersiaTrainer.init), which routes through the "
                "ShardedBackend router")
        with self._lock:
            return self._init_locked(key, scale)

    def _init_locked(self, key, scale: float):
        spec = self.spec
        # draw the SAME init values the dense backend would, then park them
        # host-side: host row for id i is what a dense lookup of i would
        # read (table[shuffle_pos(i)]) — this is what makes dense and
        # host_lru bit-exact when the working set fits in cache
        dense = PS.ps_init_on_host(
            key, dataclasses.replace(spec, backend="dense"), 1, scale)
        table = np.asarray(dense["table"], np.float32)
        pos = np.asarray(PS.shuffle_pos(jnp.arange(spec.rows),
                                        spec.padded_rows(1)))
        return self._init_with_rows_locked(np.arange(spec.rows), table[pos])

    def _init_with_rows(self, ids, vecs, accs=None):
        """Fresh run seeded with explicit host rows (the sharded router's
        init/reshard path): ids land in the host store, the device cache
        starts empty, all slot bookkeeping is reset."""
        with self._lock:
            return self._init_with_rows_locked(ids, vecs, accs)

    def _make_store(self):
        """Build the host tier: a plain all-rows LRU store (never evicts —
        skip per-access recency upkeep on the fault path), or, under
        ``+disk``, the tiered host-over-mmap hierarchy whose host tier
        genuinely evicts (spilling to disk)."""
        spec = self.spec
        if self._disk:
            host_rows = int(spec.host_rows) or max(1024, spec.rows // 4)
            return TieredHostStore(spec.rows, spec.dim,
                                   host_rows=host_rows,
                                   path=spec.disk_path,
                                   store_dtype=spec.store_dtype)
        return LRUEmbeddingStore(spec.rows, spec.dim, track_recency=False,
                                 store_dtype=spec.store_dtype)

    def _init_with_rows_locked(self, ids, vecs, accs=None):
        spec = self.spec
        self.store = self._make_store()
        self.store.preload(np.asarray(ids, np.int64),
                           np.asarray(vecs, np.float32), accs)
        # a (re-)init starts a fresh run: drop any previous slot bookkeeping
        self._slot_arr = np.full(spec.rows, -1, np.int32)
        self._id_for_slot = np.full(self.dev_slots, -1, np.int64)
        self._slot_clock = np.zeros(self.dev_slots, np.int64)
        self._pin_count = np.zeros(self.dev_slots, np.int32)
        self._tick = 0
        self.faults = self.writebacks = self.hits = 0
        self.admits = self.bypasses = self.promotes = 0
        self.last_admit = self.last_bypass = self.last_promote = 0
        if self._sketch is not None:
            self._sketch = HotnessSketch()
        state = {
            "table": jnp.zeros((self.dev_slots, spec.dim), spec.dtype),
            "slot_ids": jnp.full((self.dev_slots,), -1, jnp.int32),
        }
        if spec.optimizer == "adagrad":
            state["acc"] = jnp.zeros((self.dev_slots,), jnp.float32)
        return state

    def prepare(self, state, ids, assume_unique: bool = False, counts=None):
        """Fault the batch's rows into the device cache; translate ids to
        cache-slot indices (-1 for padding / out-of-range). Thread-safe:
        the whole fault-in (slot map + LRU store + clock) is one critical
        section, so concurrent callers see consistent slot bookkeeping.
        ``assume_unique=True`` (the batch-dedup plan path) skips the
        np.unique — the caller already deduped the batch."""
        with self._lock:
            return self._prepare_locked(state, ids, assume_unique, counts)

    def _split_admission(self, missing: np.ndarray,
                         hit_slots: np.ndarray) -> tuple[np.ndarray,
                                                         np.ndarray]:
        """Partition this step's missing ids into (admitted, bypassed) by
        sketch hotness. Bypassed faults are capped by the bypass slots
        actually free this step (unpinned and not holding a row the batch
        also hits) — the overflow is admitted, deterministically from the
        front of the bypass list, so a cold burst can still be served."""
        hot = self._sketch.estimate(missing) >= self.admit_threshold
        admit, bypass = missing[hot], missing[~hot]
        if bypass.size:
            avail = np.ones(self.dev_slots, bool)
            avail[: self.cache_rows] = False
            avail[self._pin_count > 0] = False
            avail[hit_slots] = False
            room = int(np.count_nonzero(avail))
            if bypass.size > room:
                admit = np.concatenate([admit, bypass[room:]])
                bypass = bypass[:room]
        return admit, bypass

    def _prepare_locked(self, state, ids, assume_unique: bool = False,
                        counts=None):
        # each stretch runs inside one span of core/spans.PREPARE_PHASES;
        # the eviction calls between them time their own phases
        spec, t = self.spec, self.span_s
        with span("prepare/slots", t):
            flat = np.asarray(ids, np.int64).reshape(-1)
            valid = (flat >= 0) & (flat < spec.rows)
            uniq = flat[valid] if assume_unique else np.unique(flat[valid])
            if uniq.size > self.cache_rows:
                raise ValueError(
                    f"batch working set ({uniq.size} unique ids) exceeds "
                    f"the device cache ({self.cache_rows} slots) — raise "
                    "EmbeddingSpec.cache_rows or shrink the batch")
            self._tick += 1
            if self._sketch is not None:
                c = None
                if counts is not None:
                    c = np.asarray(counts, np.float64).reshape(-1)
                    c = c[valid] if c.size == flat.size else None
                self._sketch.update(uniq, c)
            uslots = self._slot_arr[uniq].astype(np.int64)
            self.last_admit = self.last_bypass = self.last_promote = 0
            promo = self._promotions(uniq, uslots)
        if promo is not None:
            # promote bypass-resident rows that have become hot: write the
            # device copy (the freshest) back to the host store, free the
            # bypass slot, and let the normal fault path re-admit them into
            # the main cache this same step
            state = dict(state)
            self._evict_slots(uslots[promo], state)
            uslots[promo] = -1
            self.last_promote = int(promo.sum())
            self.promotes += self.last_promote
        hit_slots = uslots[uslots >= 0]
        missing = uniq[uslots < 0]
        self.hits += int(hit_slots.size)
        if missing.size:
            state = dict(state)
            if self._sketch is not None:
                with span("prepare/slots", t):
                    admit, bypass = self._split_admission(missing,
                                                          hit_slots)
                v_main = self._free_slots(hit_slots, admit.size, state,
                                          hi=self.cache_rows)
                v_byp = self._free_slots(hit_slots, bypass.size, state,
                                         lo=self.cache_rows)
                missing = np.concatenate([admit, bypass])
                victims = np.concatenate([v_main, v_byp])
                self.admits += int(admit.size)
                self.bypasses += int(bypass.size)
                self.last_admit = int(admit.size)
                self.last_bypass = int(bypass.size)
            else:
                victims = self._free_slots(hit_slots, missing.size, state)
                self.admits += int(missing.size)
                self.last_admit = int(missing.size)
            m = int(missing.size)
            with span("prepare/store", t, rows=m):
                vecs, accs = self.store.read_rows(missing)
            self.faults += m
            with span("prepare/fault_h2d", t, rows=m):
                self._fault_rows(state, victims, missing, vecs, accs)
            with span("prepare/slots", t):
                self._slot_arr[missing] = victims
                self._id_for_slot[victims] = missing
                touched = np.concatenate([hit_slots, victims])
        else:
            touched = hit_slots
        with span("prepare/slots", t):
            self._slot_clock[touched] = self._tick
            dev = np.where(valid,
                           self._slot_arr[np.where(valid, flat, 0)].astype(
                               np.int64), -1)
            return state, jnp.asarray(dev.reshape(np.shape(ids)), jnp.int32)

    def _promotions(self, uniq: np.ndarray, uslots: np.ndarray):
        """Mask over ``uniq`` of bypass-resident rows hot enough to move
        into the main cache (pinned slots — in-flight pipelined batches —
        wait for a later step), or None."""
        if self._sketch is None:
            return None
        in_byp = uslots >= self.cache_rows
        if not in_byp.any():
            return None
        hot = self._sketch.estimate(uniq) >= self.admit_threshold
        safe = np.clip(uslots, 0, self.dev_slots - 1)
        promo = in_byp & hot & (self._pin_count[safe] == 0)
        return promo if promo.any() else None

    def _fault_rows(self, state, victims, missing, vecs, accs):
        """Copy the faulted rows to the device and scatter them into their
        victim slots of ``state`` (updated in place)."""
        # bucket the scatter shape (see _pow2_bucket): pad slots index one
        # past the cache — an out-of-bounds scatter update, which JAX
        # drops — so padding never touches a real row
        m, bucket = missing.size, _pow2_bucket(missing.size)
        pad_slots = np.full(bucket, self.dev_slots, np.int64)
        pad_slots[:m] = victims
        pad_vecs = np.zeros((bucket, self.spec.dim), np.float32)
        pad_vecs[:m] = vecs
        pad_ids = np.full(bucket, -1, np.int64)
        pad_ids[:m] = missing
        vslots = jnp.asarray(pad_slots, jnp.int32)
        vecs_j = jnp.asarray(pad_vecs, jnp.float32)
        ids_j = jnp.asarray(pad_ids, jnp.int32)
        if "acc" in state:
            pad_accs = np.zeros(bucket, np.float32)
            pad_accs[:m] = accs
            state["table"], state["slot_ids"], state["acc"] = \
                _fault_apply_acc(state["table"], state["slot_ids"],
                                 state["acc"], vslots, vecs_j, ids_j,
                                 jnp.asarray(pad_accs, jnp.float32))
        else:
            state["table"], state["slot_ids"] = _fault_apply(
                state["table"], state["slot_ids"], vslots, vecs_j, ids_j)

    def _free_slots(self, protected: np.ndarray, need: int, state,
                    lo: int = 0, hi: int | None = None):
        """Pick ``need`` victim slots inside ``[lo, hi)`` (the full slot
        pool by default; the admission path carves it into the main cache
        ``[0, cache_rows)`` and the bypass region ``[cache_rows,
        dev_slots)``): empty slots first, then the least-recently-touched
        occupied slots outside the current batch (never a pinned slot —
        those hold rows of in-flight pipelined batches); evicted rows
        (vector + acc) are written back to the host store."""
        if hi is None:
            hi = self.dev_slots
        if need <= 0:
            return np.zeros(0, np.int64)
        with span("prepare/slots", self.span_s):
            in_region = np.zeros(self.dev_slots, bool)
            in_region[lo:hi] = True
            pinned = self._pin_count > 0
            free = np.nonzero((self._id_for_slot < 0) & ~pinned
                              & in_region)[0][:need]
            n_evict = need - free.size
            if n_evict <= 0:
                return free
            cand = in_region.copy()
            cand[self._id_for_slot < 0] = False
            cand[protected] = False
            cand[pinned] = False
            cand_slots = np.nonzero(cand)[0]
            if cand_slots.size < n_evict:
                raise ValueError(
                    f"fault-in needs {n_evict} eviction victims but only "
                    f"{cand_slots.size} unpinned slots are evictable: the "
                    f"combined working set of in-flight pipelined batches "
                    f"exceeds the device cache ({hi - lo} slots in "
                    f"[{lo}, {hi}), {int(pinned.sum())} pinned) — lower "
                    "max_inflight or raise EmbeddingSpec.cache_rows")
            evict = _lru_victims(self._slot_clock, cand_slots, n_evict)
        self._evict_slots(evict, state)
        return np.concatenate([free, evict])

    def _evict_slots(self, evict: np.ndarray, state):
        """Write the given occupied slots' rows (vector + acc — the device
        copy is the freshest) back to the host store and clear their slot
        bookkeeping. Callers pick the victims; this does the writeback."""
        t = self.span_s
        n_evict = int(evict.size)
        ev_ids = self._id_for_slot[evict]
        with span("prepare/evict_d2h", t, rows=n_evict):
            # bucketed gather (see _pow2_bucket); pad rows are sliced off
            idx = np.zeros(_pow2_bucket(n_evict), np.int64)
            idx[:n_evict] = evict
            eslots = jnp.asarray(idx, jnp.int32)
            if "acc" in state:
                vecs_j, accs_j = _gather_rows_acc(state["table"],
                                                  state["acc"], eslots)
                accs = np.asarray(accs_j)[:n_evict]
            else:
                vecs_j, accs = _gather_rows(state["table"], eslots), None
            vecs = np.asarray(vecs_j)[:n_evict]
        with span("prepare/store", t, rows=n_evict):
            self.store.write_rows(ev_ids, vecs, accs)
        self.writebacks += n_evict
        with span("prepare/slots", t):
            self._slot_arr[ev_ids] = -1
            self._id_for_slot[evict] = -1

    # -- slot pinning (pipelined callers) ------------------------------------
    #
    # Between a batch's prepare and its applied put, a deep pipeline must
    # keep that batch's cache slots resident: a later batch's fault-in that
    # recycled them would make the pending lookup read the WRONG row (not a
    # stale one) and silently drop the put. Pins are reference counts; a
    # fault-in that cannot find enough unpinned victims raises (the
    # combined in-flight working set must fit the cache). A batch's slots
    # repeat, so counts are added as one bincount over the slot pool.

    def _slot_counts(self, dev_ids) -> np.ndarray:
        slots = np.asarray(dev_ids, np.int64).reshape(-1)
        slots = slots[(slots >= 0) & (slots < self.dev_slots)]
        return np.bincount(slots, minlength=self.dev_slots)

    def pin_slots(self, dev_ids):
        counts = self._slot_counts(dev_ids)
        with self._lock:
            self._pin_count += counts

    def unpin_slots(self, dev_ids):
        counts = self._slot_counts(dev_ids)
        with self._lock:
            self._pin_count -= counts
            np.maximum(self._pin_count, 0, out=self._pin_count)

    def reset_pins(self):
        with self._lock:
            self._pin_count[:] = 0

    # -- serve-path read (read-only, thread-safe) ----------------------------

    def read_rows(self, state, ids):
        """Read rows without faulting them in (see the base-class doc).

        Residency is resolved against the CALLER's state snapshot — its
        ``slot_ids`` array, not the backend's live slot map — so the gather
        and the residency decision come from the same immutable snapshot
        and a concurrent trainer fault-in/evict can never make this read
        return the wrong row. Misses are read straight from the host store
        (under the backend lock), quantized through the cache dtype so a
        served row is bit-identical whether it happens to be cached or
        not. Hit slots are pinned across the gather: on a server whose
        state IS mutated in place between ops (repro.net.ps_server), the
        pin keeps an interleaved fault-in from recycling the slot
        mid-read."""
        spec = self.spec
        arr = np.asarray(ids, np.int64)
        flat = arr.reshape(-1)
        valid = (flat >= 0) & (flat < spec.rows)
        uniq = np.unique(flat[valid])
        slot_of = np.asarray(state["slot_ids"], np.int64)   # slot -> id
        if uniq.size:
            order = np.argsort(slot_of, kind="stable")
            pos = np.clip(np.searchsorted(slot_of, uniq, sorter=order),
                          0, self.dev_slots - 1)
            cand = order[pos]
            hit = slot_of[cand] == uniq
        else:
            cand = np.zeros(0, np.int64)
            hit = np.zeros(0, bool)
        hit_slots = cand[hit]
        missing = uniq[~hit]
        with self._lock:
            if missing.size:
                m_vecs, _ = self.store.read_rows(missing)
                m_vecs = np.asarray(
                    jnp.asarray(m_vecs, jnp.float32).astype(spec.dtype)
                    .astype(jnp.float32))
            else:
                m_vecs = np.zeros((0, spec.dim), np.float32)
            self._pin_count += self._slot_counts(hit_slots)
        try:
            if hit_slots.size:
                idx = np.zeros(_pow2_bucket(hit_slots.size), np.int64)
                idx[:hit_slots.size] = hit_slots
                h_vecs = np.asarray(_gather_rows(
                    state["table"],
                    jnp.asarray(idx, jnp.int32)))[:hit_slots.size]
            else:
                h_vecs = np.zeros((0, spec.dim), np.float32)
        finally:
            self.unpin_slots(hit_slots)
        rows_u = np.zeros((uniq.size, spec.dim), np.float32)
        rows_u[hit] = h_vecs
        rows_u[~hit] = m_vecs
        out = np.zeros((flat.size, spec.dim), np.float32)
        if uniq.size:
            out[valid] = rows_u[np.searchsorted(uniq, flat[valid])]
        return (out.reshape(arr.shape + (spec.dim,)),
                {"reads": int(uniq.size), "hits": int(hit_slots.size),
                 "misses": int(missing.size)})

    def dedup_rows(self) -> int:
        # a batch's unique set must fit the device cache (prepare raises
        # otherwise), so the cache bounds the distinct device ids too
        return min(self.spec.rows, self.cache_rows)

    def queue_init(self, ids_shape):
        if self.spec.staleness <= 0:
            return None
        return self._queue_init_width(self.queue_width(_prod(ids_shape)))

    def _queue_init_width(self, width: int):
        spec = self.spec
        tau, n_ids = spec.staleness, int(width)
        return {
            "slots": jnp.full((tau, n_ids), -1, jnp.int32),
            "ids": jnp.full((tau, n_ids), -1, jnp.int32),
            "grads": jnp.zeros((tau, n_ids, spec.dim), spec.dtype),
            "ptr": jnp.zeros((), jnp.int32),
            "filled": jnp.zeros((), jnp.int32),
        }

    # -- traceable -----------------------------------------------------------

    def _lookup_flat(self, state, dev_ids):
        shape = dev_ids.shape
        flat = dev_ids.reshape(-1)
        valid = (flat >= 0) & (flat < self.dev_slots)
        safe = jnp.clip(flat, 0, self.dev_slots - 1)
        out = state["table"][safe] * valid[:, None].astype(
            state["table"].dtype)
        return out.reshape(*shape, self.spec.dim), {}

    def _put_flat(self, state, dev_ids, grads):
        spec = self.spec
        flat = dev_ids.reshape(-1)
        grads = grads.reshape(-1, spec.dim)
        valid = (flat >= 0) & (flat < self.dev_slots)
        g = jnp.where(valid[:, None], grads, 0.0).astype(jnp.float32)
        slot_signed = jnp.where(valid, flat.astype(jnp.int32), -1)
        cap = D.dedup_cap(int(flat.shape[0]), self.dev_slots)
        uniq, g_u = C.dedup_put(slot_signed, g, cap)
        return self._put_unique(state, uniq, g_u)

    def _put_unique(self, state, slots_u, g_u):
        new = PS._apply_sparse(
            state, self.spec,
            jnp.where(slots_u >= 0, slots_u, self.dev_slots),
            g_u.astype(jnp.float32), self.dev_slots)
        return new, {}

    def _hybrid_flat(self, state, queue, dev_ids, grads):
        spec = self.spec
        flat = dev_ids.reshape(-1)
        g = grads.reshape(-1, spec.dim)
        if spec.staleness <= 0 or queue is None:
            st, m = self._put_flat(state, flat, g)
            return st, queue, m
        valid = (flat >= 0) & (flat < self.dev_slots)
        if not spec.batch_dedup:
            # legacy path: occurrence-width queue slots
            return self._hybrid_flat_legacy(state, queue, flat, g, valid)
        # unique-width queue: dedup by slot before the push
        gm = jnp.where(valid[:, None], g, 0.0).astype(jnp.float32)
        slot_signed = jnp.where(valid, flat.astype(jnp.int32), -1)
        slots_u, g_u = C.dedup_put(slot_signed, gm,
                                   int(queue["slots"].shape[1]))
        return self._hybrid_unique(state, queue, slots_u, g_u)

    def _hybrid_flat_legacy(self, state, queue, flat, g, valid):
        safe = jnp.clip(flat, 0, self.dev_slots - 1)
        logical = jnp.where(valid, state["slot_ids"][safe], -1)
        queue, old_slots, old_ids, old_g = self._queue_push_pop(
            queue, jnp.where(valid, flat.astype(jnp.int32), -1), logical, g)
        # a tau-stale put only lands if its slot still holds the same row
        old_safe = jnp.clip(old_slots, 0, self.dev_slots - 1)
        still = (old_slots >= 0) & (old_ids >= 0) & \
            (state["slot_ids"][old_safe] == old_ids)
        st, m = self._put_flat(state, jnp.where(still, old_slots, -1), old_g)
        return st, queue, m

    def _put_plan(self, state, plan, grads):
        # plan.dev already IS the (-1-signed) cache-slot vector: fuse the
        # segment-sum with the slot-sparse optimizer apply directly
        new, _ = _fused_backward(self.spec, state, plan.inv, grads,
                                 plan.dev.astype(jnp.int32), None,
                                 apply_self=True)
        return new, {}

    def _hybrid_plan(self, state, queue, plan, grads):
        spec = self.spec
        if spec.staleness <= 0 or queue is None:
            st, m = self._put_plan(state, plan, grads)
            return st, queue, m
        # pop the tau-stale (slot, id, grads) first, drop it if its slot
        # was recycled since the push, fuse its apply with this step's
        # segment-sum, then push the fresh payload at the popped position
        cap = int(queue["slots"].shape[1])
        slots_cap = D.pad_axis0(plan.dev.astype(jnp.int32), cap, -1)
        safe = jnp.clip(slots_cap, 0, self.dev_slots - 1)
        logical = jnp.where(slots_cap >= 0, state["slot_ids"][safe], -1)
        ptr = queue["ptr"]
        old_slots = jnp.take(queue["slots"], ptr, axis=0)
        old_ids = jnp.take(queue["ids"], ptr, axis=0)
        old_g = jnp.take(queue["grads"], ptr, axis=0)
        old_safe = jnp.clip(old_slots, 0, self.dev_slots - 1)
        popped = (old_slots >= 0) & (old_ids >= 0)
        still = popped & (state["slot_ids"][old_safe] == old_ids)
        new, g_push = _fused_backward(spec, state, plan.inv, grads,
                                      jnp.where(still, old_slots, -1),
                                      old_g)
        tau = queue["slots"].shape[0]
        new_q = {
            "slots": jax.lax.dynamic_update_index_in_dim(
                queue["slots"], slots_cap, ptr, 0),
            "ids": jax.lax.dynamic_update_index_in_dim(
                queue["ids"], logical.astype(jnp.int32), ptr, 0),
            "grads": jax.lax.dynamic_update_index_in_dim(
                queue["grads"], g_push.astype(queue["grads"].dtype),
                ptr, 0),
            "ptr": (ptr + 1) % tau,
            "filled": jnp.minimum(queue["filled"] + 1, tau),
        }
        # rows of the popped put whose slot was recycled: the lost puts
        return new, new_q, {"lost_rows": jnp.sum(popped & ~still)}

    def _hybrid_unique(self, state, queue, slots_u, g_u):
        spec = self.spec
        if spec.staleness <= 0 or queue is None:
            st, m = self._put_unique(state, slots_u, g_u)
            return st, queue, m
        cap = int(queue["slots"].shape[1])
        slots_cap = D.pad_axis0(slots_u.astype(jnp.int32), cap, -1)
        g_cap = D.pad_axis0(g_u, cap, 0)
        safe = jnp.clip(slots_cap, 0, self.dev_slots - 1)
        logical = jnp.where(slots_cap >= 0, state["slot_ids"][safe], -1)
        queue, old_slots, old_ids, old_g = self._queue_push_pop(
            queue, slots_cap, logical, g_cap)
        old_safe = jnp.clip(old_slots, 0, self.dev_slots - 1)
        still = (old_slots >= 0) & (old_ids >= 0) & \
            (state["slot_ids"][old_safe] == old_ids)
        st, m = self._put_unique(state, jnp.where(still, old_slots, -1),
                                 old_g)
        return st, queue, m

    def _queue_push_pop(self, queue, slots, logical, g):
        """Push (slots, ids, grads); pop the tau-stale entry."""
        ptr = queue["ptr"]
        old_slots = jnp.take(queue["slots"], ptr, axis=0)
        old_ids = jnp.take(queue["ids"], ptr, axis=0)
        old_g = jnp.take(queue["grads"], ptr, axis=0)
        tau = queue["slots"].shape[0]
        new_q = {
            "slots": jax.lax.dynamic_update_index_in_dim(
                queue["slots"], slots, ptr, 0),
            "ids": jax.lax.dynamic_update_index_in_dim(
                queue["ids"], logical.astype(jnp.int32), ptr, 0),
            "grads": jax.lax.dynamic_update_index_in_dim(
                queue["grads"], g.astype(queue["grads"].dtype), ptr, 0),
            "ptr": (ptr + 1) % tau,
            "filled": jnp.minimum(queue["filled"] + 1, tau),
        }
        return new_q, old_slots, old_ids, old_g

    # -- checkpoint ----------------------------------------------------------

    def state_for_checkpoint(self, state):
        """Snapshot ALL tiers: the device cache (so queued slot references
        stay live across restore) and the host store — plain or tiered,
        with its recency order — plus the slot map and (when admission is
        on) the hotness sketch: a restore resumes bit-identically."""
        with self._lock:
            cm = {
                "id_for_slot": self._id_for_slot.copy(),
                "slot_clock": self._slot_clock.copy(),
                "scalars": np.array([self._tick, self.faults,
                                     self.writebacks, self.hits,
                                     self.admits, self.bypasses,
                                     self.promotes],
                                    np.int64),
            }
            if self._sketch is not None:
                cm["hotness"] = self._sketch.serialize()
            return {
                "cache": jax.tree.map(np.asarray, state),
                "store": self.store.serialize(),
                "cache_meta": cm,
            }

    def restore_from_checkpoint(self, blob):
        self.last_restore_resharded = False
        if isinstance(blob, dict) and "shard_meta" in blob:
            # sharded-router checkpoint into a single-shard trainer: gather
            # the logical rows (device caches overlaid on host stores) and
            # rebuild the two tiers (N -> 1 reshard; pending slot-addressed
            # puts are dropped — the paper's tolerated in-flight loss)
            vec, acc = extract_logical_rows(blob, self.spec, "host_lru")
            state = self._init_with_rows(np.arange(self.spec.rows), vec, acc)
            self.last_restore_resharded = True
            return state
        with self._lock:
            return self._restore_locked(blob)

    def _restore_locked(self, blob):
        spec = self.spec
        if not isinstance(blob, dict) or "store" not in blob \
                or "cache" not in blob:
            raise ValueError(
                "checkpoint blob has no host store — it was not written by "
                "the host_lru backend (restoring across backends is not "
                "supported)")
        meta = blob["store"]["meta"]
        cap, dim = int(meta[0]), int(meta[1])
        if cap != spec.rows or dim != spec.dim:
            raise ValueError(
                f"checkpoint host store is ({cap}, {dim}) but this table's "
                f"spec wants ({spec.rows}, {spec.dim}) — collection changed "
                "since the save?")
        cache_tbl = blob["cache"]["table"]
        if cache_tbl.shape[0] != self.dev_slots:
            raise ValueError(
                f"checkpoint device cache has {cache_tbl.shape[0]} slots but "
                f"this table runs {self.dev_slots} "
                f"(cache_rows={self.cache_rows} + "
                f"bypass_rows={self.bypass_rows}) — rebuild the trainer "
                "with the cache geometry the checkpoint was trained under")
        sblob = blob["store"]
        if ("disk" in sblob) == self._disk:
            # matching store format: bit-identical tier restore when the
            # blob's store_dtype matches the spec's; a dtype mismatch
            # re-encodes the blob's fp32 logical rows (both directions)
            if self._disk:
                self.store = TieredHostStore.deserialize(
                    sblob, path=spec.disk_path,
                    store_dtype=spec.store_dtype)
            else:
                self.store = LRUEmbeddingStore.deserialize(
                    sblob, store_dtype=spec.store_dtype)
                self.store.track_recency = False   # backend-owned: see init
        else:
            # cross-format restore (two-tier blob into a +disk backend, or
            # the reverse): rebuild the configured hierarchy from the
            # blob's logical rows — row-exact, tier residency starts fresh
            vec, acc = _store_logical_rows(sblob, spec.rows, spec.dim)
            self.store = self._make_store()
            self.store.preload(np.arange(spec.rows), vec, acc)
        cm = blob["cache_meta"]
        self._pin_count = np.zeros(self.dev_slots, np.int32)
        self._id_for_slot = np.asarray(cm["id_for_slot"], np.int64).copy()
        self._slot_clock = np.asarray(cm["slot_clock"], np.int64).copy()
        scalars = [int(x) for x in cm["scalars"]]
        self._tick, self.faults, self.writebacks = scalars[:3]
        # pre-shard-router checkpoints carry 3 scalars (no hit counter);
        # pre-admission ones carry 4 (no admit/bypass/promote counters)
        self.hits = scalars[3] if len(scalars) > 3 else 0
        self.admits = scalars[4] if len(scalars) > 4 else 0
        self.bypasses = scalars[5] if len(scalars) > 5 else 0
        self.promotes = scalars[6] if len(scalars) > 6 else 0
        self.last_admit = self.last_bypass = self.last_promote = 0
        if self._sketch is not None:
            self._sketch = (HotnessSketch.deserialize(cm["hotness"])
                            if "hotness" in cm else HotnessSketch())
        self._slot_arr = np.full(spec.rows, -1, np.int32)
        live = np.nonzero(self._id_for_slot >= 0)[0]
        self._slot_arr[self._id_for_slot[live]] = live.astype(np.int32)
        return {k: jnp.asarray(v) for k, v in blob["cache"].items()}

    # -- capacity accounting / inspection ------------------------------------

    def slot_map(self) -> dict[int, int]:
        """``{id: cache slot}`` of the resident rows, read off the slot ->
        id array (for inspection; the fault path reads the arrays)."""
        with self._lock:
            live = np.nonzero(self._id_for_slot >= 0)[0]
            return dict(zip(self._id_for_slot[live].tolist(), live.tolist()))

    def host_bytes(self) -> int:
        s = self.store
        if s is None:
            return 0
        if hasattr(s, "host_bytes"):        # tiered: host-tier arrays only
            return s.host_bytes()
        return int(s.payload_bytes() + s.opt_acc.nbytes + s.prev.nbytes
                   + s.next.nbytes + s.keys.nbytes)

    def cache_metrics(self) -> dict:
        """Per-step admission gauges (empty when the sketch is off)."""
        if self._sketch is None:
            return {}
        return {"admit": float(self.last_admit),
                "bypass": float(self.last_bypass),
                "promote": float(self.last_promote)}

    def recency_order(self) -> list[int]:
        """Host-store ids most- to least-recently used (checkpointed)."""
        return self.store.recency_ids()


# ===========================================================================
# ShardedBackend — the sharded embedding parameter-server router (§4.1)
# ===========================================================================

# Knuth's multiplicative-hash constant (2^32 / phi, odd): the routing premix.
# Distinct from the in-shard placement shuffle so shard choice and row
# placement stay decorrelated.
_ROUTE_MULT = 2_654_435_761
_ROUTE_ADD = 97_531


class _ShardRouting:
    """Deterministic affine-hash ``id -> (shard, local id)`` routing.

    Ids are premixed by a bijective affine map over the padded domain
    ``P = round_up(rows, k)`` (the multiplier is adjusted odd-upwards until
    coprime with P, so the map is a bijection); then ``shard = premix % k``
    and ``local = premix // k``. Bijectivity keeps the per-shard local id
    spaces disjoint and exactly invertible, which is what makes checkpoint
    resharding (save with N shards, restore with M) row-exact.
    """

    def __init__(self, rows: int, k: int):
        self.rows, self.k = int(rows), int(k)
        P = round_up(max(self.rows, self.k), self.k)
        mult = _ROUTE_MULT
        while math.gcd(mult, P) != 1:
            mult += 2
        self.P, self.mult, self.add = P, mult, _ROUTE_ADD % P
        self.sub_rows = P // self.k          # per-shard local id space

    def shard_and_local(self, ids):
        ids = np.asarray(ids, np.int64)
        pre = (ids * self.mult + self.add) % self.P
        return pre % self.k, pre // self.k


def _dense_state_from_logical(spec: EmbeddingSpec, n_rows: int, vec, acc):
    """Build a dense PS state of ``n_rows`` storage rows holding logical row
    ``i`` of ``vec`` at its uniform-shuffle position (the inverse of
    reading a dense table back out row-by-row)."""
    pos = np.asarray(PS.shuffle_pos(jnp.arange(vec.shape[0]), n_rows))
    table = np.zeros((n_rows, vec.shape[1]), vec.dtype)
    table[pos] = vec
    state = {"table": jnp.asarray(table)}
    if spec.optimizer == "adagrad":
        a = np.zeros((n_rows,), np.float32)
        if acc is not None:
            a[pos] = np.asarray(acc, np.float32)
        state["acc"] = jnp.asarray(a)
    return state


def _store_logical_rows(sblob, rows: int, dim: int):
    """Host-store checkpoint sub-blob -> dense ``(vec, acc)`` over all
    ``rows`` logical rows (zeros for never-stored ids). Handles both the
    plain LRU blob and the tiered host+disk blob — for the latter the
    disk tier is laid down first, then the host tier overlaid on top (the
    host copy is the freshest: spills only happen on demotion)."""
    vec = np.zeros((rows, dim), np.float32)
    acc = np.zeros((rows,), np.float32)

    def overlay(b):
        meta = np.asarray(b["meta"], np.int64).reshape(-1)
        # plain LRU meta is [capacity, dim, head, tail, size, evictions];
        # the mmap tier's is just [capacity, dim, size]
        size = int(meta[4]) if meta.size > 4 else int(meta[2])
        keys = np.asarray(b["keys"], np.int64)[:size]
        vec[keys] = np.asarray(b["vectors"], np.float32)[:size]
        acc[keys] = np.asarray(b["opt_acc"], np.float32)[:size]

    if "disk" in sblob:
        overlay(sblob["disk"])
        overlay(sblob["host"])
    else:
        overlay(sblob)
    return vec, acc


def extract_logical_rows(blob, spec: EmbeddingSpec, base: str):
    """Checkpoint blob -> ``(vec, acc)`` in *logical row order*: ``vec[i]``
    is the value a lookup of id ``i`` would return (and ``acc[i]`` its
    optimizer accumulator, or None when the blob carries none).

    Handles all three blob geometries — plain dense (rows read back through
    the uniform shuffle), plain host_lru (host store rows overlaid with the
    device cache, whose copies are the freshest), and shard-tagged router
    blobs (each sub-blob extracted recursively and scattered back through
    the source routing). This is the reshard path: N-shard checkpoints
    restore row-exactly into M-shard trainers for any N, M.
    """
    if isinstance(blob, dict) and "shard_meta" in blob:
        meta = np.asarray(blob["shard_meta"], np.int64).reshape(-1)
        src_k, src_rows = int(meta[0]), int(meta[1])
        if src_rows != spec.rows:
            raise ValueError(
                f"sharded checkpoint holds {src_rows} logical rows but this "
                f"table's spec wants {spec.rows} — collection changed since "
                "the save?")
        routing = _ShardRouting(spec.rows, src_k)
        ids = np.arange(spec.rows)
        own, loc = routing.shard_and_local(ids)
        sub_spec = dataclasses.replace(spec, rows=routing.sub_rows,
                                       emb_shards=1)
        vec = acc = None
        for s in range(src_k):
            sub_blob = blob["shards"][f"s{s}"]
            v_s, a_s = extract_logical_rows(sub_blob, sub_spec, base)
            if vec is None:
                vec = np.zeros((spec.rows, spec.dim), v_s.dtype)
                acc = None if a_s is None \
                    else np.zeros((spec.rows,), np.float32)
            sel = own == s
            vec[sel] = v_s[loc[sel]]
            if acc is not None and a_s is not None:
                acc[sel] = a_s[loc[sel]]
        return vec, acc

    if base == "dense":
        table = blob.get("table") if isinstance(blob, dict) else None
        if table is None:
            raise ValueError(
                "checkpoint blob has no 'table' — it was not written by the "
                "dense backend (restoring across backends is not supported)")
        table = np.asarray(table)
        if table.shape[1] != spec.dim or table.shape[0] < spec.rows:
            raise ValueError(
                f"checkpoint table has shape {tuple(table.shape)} but this "
                f"table's spec wants >= ({spec.rows}, {spec.dim}) — "
                "collection changed since the save?")
        pos = np.asarray(PS.shuffle_pos(jnp.arange(spec.rows),
                                        table.shape[0]))
        acc = blob.get("acc")
        return table[pos], (None if acc is None
                            else np.asarray(acc, np.float32)[pos])

    if not isinstance(blob, dict) or "store" not in blob \
            or "cache" not in blob:
        raise ValueError(
            "checkpoint blob has no host store — it was not written by "
            "the host_lru backend (restoring across backends is not "
            "supported)")
    meta = blob["store"]["meta"]
    cap, dim = int(meta[0]), int(meta[1])
    if cap != spec.rows or dim != spec.dim:
        raise ValueError(
            f"checkpoint host store is ({cap}, {dim}) but this table's "
            f"spec wants ({spec.rows}, {spec.dim}) — collection changed "
            "since the save?")
    vec, acc = _store_logical_rows(blob["store"], spec.rows, spec.dim)
    # the device cache holds the freshest copy of every resident row
    # (write-back only happens on eviction): overlay it over the store,
    # exactly as draining the cache would
    id_for_slot = np.asarray(blob["cache_meta"]["id_for_slot"], np.int64)
    live = np.nonzero(id_for_slot >= 0)[0]
    if live.size:
        cached_ids = id_for_slot[live]
        vec[cached_ids] = np.asarray(blob["cache"]["table"],
                                     np.float32)[live]
        if "acc" in blob["cache"]:
            acc[cached_ids] = np.asarray(blob["cache"]["acc"],
                                         np.float32)[live]
    return vec, acc


class ShardedBackend(EmbeddingBackend):
    """Router over ``n_shards`` independent per-shard backends — the
    embedding-PS tier as a *set of shards* (paper §4.1: capacity and host
    bandwidth scale with the number of embedding workers).

    Each shard is a full Dense/HostLRU backend over its own local id space
    (disjoint by the bijective :class:`_ShardRouting`), with its own lock,
    slot map, LRU store and staleness queue. ``prepare`` fans the batch out
    to all shards through a thread pool, so host-side fault-in runs
    **concurrently** per shard — the per-shard locks replace the old single
    global lock, and miss-heavy prepare latency drops near-linearly with
    shards (``benchmarks/shard_scaling.py``).

    Device ids are shard-encoded: ``dev = shard * stride + local_dev`` with
    one uniform ``stride`` (per-shard cache slots for host_lru, per-shard
    rows for dense), so the traceable ops route by integer division with no
    host round-trip. State/queues are dicts keyed ``"s0".."s{k-1}"``.

    Checkpoints are shard-tagged (``shard_meta`` + per-shard two-tier
    blobs); restore into a different shard count reshards row-exactly via
    :func:`extract_logical_rows` (device caches restart cold and pending
    slot-addressed queue puts are dropped — the paper's tolerated in-flight
    loss, same policy as a worker failover).
    """

    requires_prepare = True
    # floor on the shard count: the in-process router insists on >= 2 (a
    # single shard IS the plain backend); subclasses whose shards live in
    # other processes (repro.net) allow 1 — one PS process is still remote
    min_shards = 2

    def __init__(self, spec: EmbeddingSpec, n_shards: int | None = None):
        base, _ = parse_backend_name(spec.backend)
        if base.startswith("host_lru") and spec.cache_rows <= 0:
            raise ValueError(
                "host_lru backend needs EmbeddingSpec.cache_rows > 0 "
                f"(got {spec.cache_rows})")
        self.spec = spec
        self._base = base
        self._lock = threading.Lock()        # traffic counters only
        self._pool: ThreadPoolExecutor | None = None
        self._configure(int(n_shards if n_shards is not None
                            else spec.emb_shards))

    def _make_sub(self, s: int, sub_spec: EmbeddingSpec) -> EmbeddingBackend:
        """Build shard ``s``'s backend — the hook the remote router
        (repro.net.remote.RemoteShardedBackend) overrides to place each
        shard behind an RPC endpoint instead of in-process."""
        return (HostLRUBackend(sub_spec)
                if self._base.startswith("host_lru")
                else DenseBackend(sub_spec))

    def _configure(self, k: int):
        if k < self.min_shards:
            raise ValueError(
                f"{type(self).__name__} needs >= {self.min_shards} shards "
                f"(got {k}); use the plain backend for a single shard")
        spec = self.spec
        self.n_shards = k
        self._routing = _ShardRouting(spec.rows, k)
        sub_rows = self._routing.sub_rows
        kw = {"backend": self._base, "emb_shards": 1, "rows": sub_rows}
        host = self._base.startswith("host_lru")
        if host:
            # cache_rows stays the table's TOTAL device-cache budget,
            # split evenly across shards — as do the bypass region, the
            # +disk host tier and (when set) the mmap directory
            kw["cache_rows"] = -(-spec.cache_rows // k)
            if spec.bypass_rows:
                kw["bypass_rows"] = -(-int(spec.bypass_rows) // k)
            if spec.host_rows:
                kw["host_rows"] = -(-int(spec.host_rows) // k)
        subs = []
        for s in range(k):
            kws = dict(kw)
            if host and spec.disk_path is not None:
                kws["disk_path"] = os.path.join(spec.disk_path, f"s{s}")
            subs.append(self._make_sub(s, dataclasses.replace(spec, **kws)))
        self.shard_backends = subs
        # device ids are shard-encoded dev = shard*stride + local: for
        # host_lru the local space is the shard's FULL slot pool
        # (cache + bypass), not just its main cache
        self.stride = (subs[0].dev_slots if host else sub_rows)
        self.dev_rows = k * self.stride      # encoded device id space
        self._traffic = np.zeros(k, np.int64)
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def _ensure_pool(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(max_workers=self.n_shards,
                                            thread_name_prefix="emb-shard")
        return self._pool

    # -- host-level ----------------------------------------------------------

    def init(self, key, shards: int = 1, scale: float = 0.02):
        # shards=1 means "no override": the configured count stands (so
        # PersiaTrainer.init's default never downgrades a spec-sharded
        # table); any other count reconfigures the router before init
        if shards not in (1, self.n_shards):
            self._configure(int(shards))
        spec = self.spec
        ref_spec = dataclasses.replace(spec, backend="dense", emb_shards=1)
        # the host draw of the plain dense and host_lru backends
        table = np.asarray(PS.ps_init_on_host(key, ref_spec, 1, scale)
                           ["table"])
        if self._base != "dense":
            table = table.astype(np.float32)
        # logical row i = what a single-shard lookup of i would read; this
        # is what makes the k-shard router bit-exact with the plain backend
        pos = np.asarray(PS.shuffle_pos(jnp.arange(spec.rows),
                                        spec.padded_rows(1)))
        self._traffic = np.zeros(self.n_shards, np.int64)
        return self._sub_states_from_logical(table[pos], None)

    def _sub_states_from_logical(self, vec, acc):
        """Distribute logical rows (and optional accumulators) over the
        shards according to the routing — the shared init/reshard path."""
        r = self._routing
        ids = np.arange(self.spec.rows)
        own, loc = r.shard_and_local(ids)
        states = {}
        for s, sub in enumerate(self.shard_backends):
            sel = own == s
            gl, ll = ids[sel], loc[sel]
            if self._base.startswith("host_lru"):
                states[f"s{s}"] = sub._init_with_rows(
                    ll, np.asarray(vec[gl], np.float32),
                    None if acc is None else acc[gl])
            else:
                sub_vec = np.zeros((r.sub_rows, vec.shape[1]), vec.dtype)
                sub_vec[ll] = vec[gl]
                sub_acc = None
                if acc is not None:
                    sub_acc = np.zeros((r.sub_rows,), np.float32)
                    sub_acc[ll] = acc[gl]
                states[f"s{s}"] = _dense_state_from_logical(
                    sub.spec, r.sub_rows, sub_vec, sub_acc)
        return states

    def dedup_rows(self) -> int:
        return min(self.spec.rows, self.dev_rows)

    def prepare(self, state, ids, assume_unique: bool = False, counts=None):
        return self.prepare_submit(state, ids, assume_unique, counts)()

    def prepare_submit(self, state, ids, assume_unique: bool = False,
                       counts=None):
        """Concurrent per-shard fault-in, two-phase: the batch is split by
        the routing and every shard's prepare is *submitted* (remote
        shards buffer one coalesced RPC into their endpoint's frame;
        in-process shards defer the work); the returned thunk runs the
        per-shard collects on the router's thread pool — each under its
        own shard lock, so host fault-in latency scales down with the
        shard count instead of serializing behind one global lock, and
        shard RPCs wait concurrently. Returns shard-encoded device ids.

        On the batch-dedup path ``ids`` is the plan's unique set (routed
        subsets stay unique, so shards skip their own np.unique) and
        ``counts`` carries per-unique occurrence counts — the traffic /
        imbalance gauges keep measuring the raw id stream, not the
        deduped wire, so hot-key skew stays visible."""
        spec = self.spec
        shape = np.shape(ids)
        flat = np.asarray(ids, np.int64).reshape(-1)
        valid = (flat >= 0) & (flat < spec.rows)
        own_raw, loc = self._routing.shard_and_local(np.where(valid, flat, 0))
        own = np.where(valid, own_raw, -1)
        with self._lock:
            if counts is None:
                self._traffic += np.bincount(own[own >= 0],
                                             minlength=self.n_shards)
            else:
                np.add.at(self._traffic, own[valid],
                          np.asarray(counts, np.int64).reshape(-1)[valid])

        # counts stay positionally aligned: ids not owned by shard s are
        # masked to -1, which the shard's own valid-mask filters
        thunks = [
            self.shard_backends[s].prepare_submit(
                state[f"s{s}"], np.where(own == s, loc, -1),
                assume_unique, counts)
            for s in range(self.n_shards)
        ]

        def collect():
            pool = self._ensure_pool()
            futs = [pool.submit(t) for t in thunks]
            new_state = dict(state)
            devs = np.empty((self.n_shards, flat.size), np.int64)
            for s, f in enumerate(futs):
                st_s, dev_s = f.result()
                new_state[f"s{s}"] = st_s
                devs[s] = np.asarray(dev_s, np.int64).reshape(-1)
            pick = np.where(own >= 0, own, 0)
            local_dev = devs[pick, np.arange(flat.size)]
            out = np.where((own >= 0) & (local_dev >= 0),
                           own * self.stride + local_dev, -1)
            return new_state, jnp.asarray(out.reshape(shape), jnp.int32)
        return collect

    def read_rows(self, state, ids):
        """Serve-path read through the routing: every shard reads its own
        subset concurrently on the router's thread pool (each shard
        pins/reads under its own lock), and the disjoint per-shard rows
        are merged back into occurrence order."""
        spec = self.spec
        arr = np.asarray(ids, np.int64)
        flat = arr.reshape(-1)
        valid = (flat >= 0) & (flat < spec.rows)
        own_raw, loc = self._routing.shard_and_local(np.where(valid, flat, 0))
        own = np.where(valid, own_raw, -1)

        def read_one(s):
            return self.shard_backends[s].read_rows(
                state[f"s{s}"], np.where(own == s, loc, -1))

        pool = self._ensure_pool()
        futs = [pool.submit(read_one, s) for s in range(self.n_shards)]
        out = np.zeros((flat.size, spec.dim), np.float32)
        info = {"reads": 0, "hits": 0, "misses": 0}
        for s, f in enumerate(futs):
            rows, inf = f.result()
            sel = own == s
            out[sel] = rows.reshape(-1, spec.dim)[sel]
            for k in info:
                info[k] += int(inf.get(k, 0))
        return out.reshape(arr.shape + (spec.dim,)), info

    # -- slot pinning / shard introspection ----------------------------------

    def _split_dev(self, dev_ids):
        flat = np.asarray(dev_ids, np.int64).reshape(-1)
        flat = flat[(flat >= 0) & (flat < self.dev_rows)]
        return flat // self.stride, flat % self.stride

    def pin_slots(self, dev_ids):
        own, loc = self._split_dev(dev_ids)
        for s, sub in enumerate(self.shard_backends):
            sel = own == s
            if sel.any():
                sub.pin_slots(loc[sel])

    def unpin_slots(self, dev_ids):
        own, loc = self._split_dev(dev_ids)
        for s, sub in enumerate(self.shard_backends):
            sel = own == s
            if sel.any():
                sub.unpin_slots(loc[sel])

    def reset_pins(self):
        for sub in self.shard_backends:
            sub.reset_pins()

    def n_put_shards(self) -> int:
        return self.n_shards

    def put_shards(self, dev_ids) -> tuple[int, ...]:
        own, _ = self._split_dev(dev_ids)
        return tuple(np.unique(own).tolist())

    def queue_init(self, ids_shape):
        if self.spec.staleness <= 0:
            return None
        # one width for every shard's queue: the ROUTER-level cap — the
        # plan's unique put is pushed into each shard masked to that
        # shard's rows, so every sub-queue must hold the full unique width
        return self._queue_init_width(self.queue_width(_prod(ids_shape)))

    def _queue_init_width(self, width: int):
        return {f"s{s}": sub._queue_init_width(width)
                for s, sub in enumerate(self.shard_backends)}

    # -- traceable -----------------------------------------------------------

    def _local_ids(self, flat, s):
        local = flat - s * self.stride
        return jnp.where((local >= 0) & (local < self.stride), local, -1)

    def _lookup_flat(self, state, dev_ids):
        shape = dev_ids.shape
        flat = dev_ids.reshape(-1)
        total = None
        for s, sub in enumerate(self.shard_backends):
            acts, _ = sub._lookup_flat(state[f"s{s}"],
                                       self._local_ids(flat, s))
            total = acts if total is None else total + acts
        return total.reshape(*shape, self.spec.dim), {}

    def _lookup_unique(self, state, dev_u):
        # every unique id is owned by exactly one shard: the per-shard
        # gathers are disjoint (zeros elsewhere), so the sum is exact
        total = None
        for s, sub in enumerate(self.shard_backends):
            acts, _ = sub._lookup_flat(state[f"s{s}"],
                                       self._local_ids(dev_u, s))
            total = acts if total is None else total + acts
        return total, {}

    def _put_flat(self, state, dev_ids, grads):
        flat = dev_ids.reshape(-1)
        g = grads.reshape(-1, self.spec.dim)
        new = dict(state)
        for s, sub in enumerate(self.shard_backends):
            new[f"s{s}"], _ = sub._put_flat(state[f"s{s}"],
                                            self._local_ids(flat, s), g)
        return new, {}

    def _put_unique(self, state, dev_u, g_u):
        new = dict(state)
        for s, sub in enumerate(self.shard_backends):
            new[f"s{s}"], _ = sub._put_unique(state[f"s{s}"],
                                              self._local_ids(dev_u, s), g_u)
        return new, {}

    def _hybrid_flat(self, state, queue, dev_ids, grads):
        flat = dev_ids.reshape(-1)
        g = grads.reshape(-1, self.spec.dim)
        new_state, new_queue = dict(state), dict(queue or {})
        for s, sub in enumerate(self.shard_backends):
            q = None if queue is None else queue.get(f"s{s}")
            st, q, _ = sub._hybrid_flat(state[f"s{s}"], q,
                                        self._local_ids(flat, s), g)
            new_state[f"s{s}"] = st
            new_queue[f"s{s}"] = q
        if queue is None and all(v is None for v in new_queue.values()):
            return new_state, None, {}
        return new_state, new_queue, {}

    def _hybrid_unique(self, state, queue, dev_u, g_u):
        new_state, new_queue = dict(state), dict(queue or {})
        for s, sub in enumerate(self.shard_backends):
            q = None if queue is None else queue.get(f"s{s}")
            st, q, _ = sub._hybrid_unique(state[f"s{s}"], q,
                                          self._local_ids(dev_u, s), g_u)
            new_state[f"s{s}"] = st
            new_queue[f"s{s}"] = q
        if queue is None and all(v is None for v in new_queue.values()):
            return new_state, None, {}
        return new_state, new_queue, {}

    # -- checkpoint ----------------------------------------------------------

    def state_for_checkpoint(self, state):
        return {
            "shard_meta": np.array([self.n_shards, self.spec.rows,
                                    self.spec.dim], np.int64),
            "shards": {f"s{s}": sub.state_for_checkpoint(state[f"s{s}"])
                       for s, sub in enumerate(self.shard_backends)},
        }

    def restore_from_checkpoint(self, blob):
        self.last_restore_resharded = False
        if isinstance(blob, dict) and "shard_meta" in blob:
            meta = np.asarray(blob["shard_meta"], np.int64).reshape(-1)
            if int(meta[0]) == self.n_shards:
                # same geometry: per-shard bit-identical restore
                out = {}
                for s, sub in enumerate(self.shard_backends):
                    try:
                        out[f"s{s}"] = sub.restore_from_checkpoint(
                            blob["shards"][f"s{s}"])
                    except ValueError as e:
                        raise ValueError(f"shard {s}: {e}") from e
                return out
        vec, acc = extract_logical_rows(blob, self.spec, self._base)
        self.last_restore_resharded = True
        return self._sub_states_from_logical(vec, acc)

    # -- metrics / capacity accounting ---------------------------------------

    def shard_metrics(self) -> dict:
        """Per-shard gauges for the step-metrics dict (keys are relative:
        the trainer prefixes ``shard/<table>/``), plus the max/mean
        load-imbalance gauge over cumulative routed-id traffic."""
        out = {}
        for s, sub in enumerate(self.shard_backends):
            faults = getattr(sub, "faults", 0)
            hits = getattr(sub, "hits", 0)
            looked = hits + faults
            out[f"{s}/hit_rate"] = (hits / looked) if looked else 1.0
            out[f"{s}/faults"] = float(faults)
            store = getattr(sub, "store", None)
            if store is not None:
                out[f"{s}/rows"] = float(store.size)
                out[f"{s}/bytes"] = float(sub.host_bytes())
            else:
                itemsize = jnp.dtype(sub.spec.dtype).itemsize
                out[f"{s}/rows"] = float(sub.spec.rows)
                out[f"{s}/bytes"] = float(sub.spec.rows * sub.spec.dim
                                          * itemsize)
        with self._lock:
            traffic = self._traffic.copy()
        mean = float(traffic.mean()) if traffic.size else 0.0
        out["imbalance"] = (float(traffic.max()) / mean) if mean > 0 else 1.0
        return out

    def cache_metrics(self) -> dict:
        out: dict[str, float] = {}
        for sub in self.shard_backends:
            for k, v in sub.cache_metrics().items():
                out[k] = out.get(k, 0.0) + v
        return out

    def device_bytes(self, state) -> int:
        return sum(sub.device_bytes(state[f"s{s}"])
                   for s, sub in enumerate(self.shard_backends))

    def host_bytes(self) -> int:
        return sum(sub.host_bytes() for sub in self.shard_backends)


# ===========================================================================
# CompressedWireBackend — §4.2.3 wire compression as a decorator
# ===========================================================================

class CompressedWireBackend(EmbeddingBackend):
    """Wraps another backend with the paper's communication compression:
    gradient puts are deduplicated to one row per unique id (lossless) and
    both get and put payloads cross the simulated wire as blockscale fp16
    (lossy, AUC-neutral by design). Per-step bytes-moved metrics surface
    through the trainer's metrics dict as ``wire/<table>/...``."""

    def __init__(self, inner: EmbeddingBackend):
        self.inner = inner
        self.spec = inner.spec
        self._block = int(self.spec.wire_block)
        if self.spec.wire_kernel and self._block != 128:
            raise ValueError("the Pallas blockscale kernel is fixed at "
                             f"block=128 (got wire_block={self._block})")

    @property
    def requires_prepare(self) -> bool:
        return self.inner.requires_prepare

    def _roundtrip(self, v):
        if self.spec.wire_kernel:
            from repro.kernels import ops
            return ops.blockscale_roundtrip(v, block=self._block)
        return C.blockscale_roundtrip(v, block=self._block)

    def _dev_rows(self) -> int:
        if isinstance(self.inner, ShardedBackend):
            return self.inner.dev_rows
        if isinstance(self.inner, HostLRUBackend):
            return self.inner.dev_slots
        return self.spec.rows

    # -- host-level: delegate ------------------------------------------------

    def init(self, key, shards: int = 1, scale: float = 0.02):
        return self.inner.init(key, shards, scale)

    def prepare(self, state, ids, assume_unique: bool = False, counts=None):
        return self.inner.prepare(state, ids, assume_unique, counts)

    def read_rows(self, state, ids):
        # serve reads cross the same lossy wire as training lookups
        rows, info = self.inner.read_rows(state, ids)
        flat = jnp.asarray(rows.reshape(-1, self.spec.dim))
        return (np.asarray(self._roundtrip(flat),
                           np.float32).reshape(rows.shape), info)

    def dedup_rows(self) -> int:
        return self.inner.dedup_rows()

    def queue_width(self, n_occ: int) -> int:
        # the wire ALWAYS dedups its puts (even on the legacy path), so its
        # queue is capped regardless of batch_dedup — the pre-dedup width
        # rule, kept so old wire checkpoints restore without migration
        return D.dedup_cap(n_occ, self._dev_rows())

    def pin_slots(self, dev_ids):
        self.inner.pin_slots(dev_ids)

    def unpin_slots(self, dev_ids):
        self.inner.unpin_slots(dev_ids)

    def reset_pins(self):
        self.inner.reset_pins()

    def n_put_shards(self) -> int:
        return self.inner.n_put_shards()

    def put_shards(self, dev_ids) -> tuple[int, ...]:
        return self.inner.put_shards(dev_ids)

    def shard_metrics(self) -> dict:
        return self.inner.shard_metrics()

    def cache_metrics(self) -> dict:
        return self.inner.cache_metrics()

    @property
    def last_restore_resharded(self) -> bool:
        return self.inner.last_restore_resharded

    def queue_init(self, ids_shape):
        # the queue lives PS-side, AFTER the wire: it holds deduped puts
        if self.spec.staleness <= 0:
            return None
        return self.inner._queue_init_width(
            self.queue_width(_prod(ids_shape)))

    def state_for_checkpoint(self, state):
        return self.inner.state_for_checkpoint(state)

    def restore_from_checkpoint(self, blob):
        return self.inner.restore_from_checkpoint(blob)

    # -- traceable -----------------------------------------------------------

    def lookup(self, state, dev_ids):
        if D.is_plan(dev_ids):
            # the wire ships ONE row per unique id; the inverse scatter to
            # occurrence width happens on the NN-worker side, AFTER the
            # (lossy) wire — so both the bytes moved and the quantisation
            # work shrink by the batch's dup factor
            acts_u, m = self.inner._lookup_unique(state, dev_ids.dev)
            n_raw = int(dev_ids.inv.size) * self.spec.dim
            n_wire = int(acts_u.size)
            acts = D.plan_scatter(self._roundtrip(acts_u), dev_ids.inv)
        else:
            acts, m = self.inner.lookup(state, dev_ids)
            n_raw = n_wire = int(acts.size)
            acts = self._roundtrip(acts)
        blocks = -(-n_wire // self._block)
        m = dict(m)
        m["get_bytes_raw"] = jnp.float32(n_raw * 4)
        m["get_bytes_wire"] = jnp.float32(blocks * self._block * 2
                                          + blocks * 4)
        return acts, m

    def _compress_put(self, dev_ids, grads):
        """(dev_ids | plan, occurrence grads) -> (unique ids, compressed
        unique grads, byte metrics). With a plan the lossless dedup IS the
        plan's segment-sum (no on-device sort); the legacy path keeps the
        sort-based dedup_put."""
        spec = self.spec
        if D.is_plan(dev_ids):
            uniq = dev_ids.dev
            g_u = D.plan_segment_sum(dev_ids.inv, grads,
                                     int(uniq.shape[0]))
            n_put = int(dev_ids.inv.size)
        else:
            flat = dev_ids.reshape(-1).astype(jnp.int32)
            g = grads.reshape(-1, spec.dim).astype(jnp.float32)
            n_put = int(flat.shape[0])
            cap = D.dedup_cap(n_put, self._dev_rows())
            uniq, g_u = C.dedup_put(flat, g, cap)
        g_u = self._roundtrip(g_u)
        n_uniq = jnp.sum(uniq >= 0).astype(jnp.float32)
        n_vals = n_uniq * spec.dim
        metrics = {
            # raw wire: one (int32 id, fp32 row) per put entry, pre-dedup
            "put_bytes_raw": jnp.float32(n_put * (4 + spec.dim * 4)),
            # compressed wire: unique ids + fp16 values + per-block scales
            "put_bytes_wire": n_uniq * 4 + n_vals * 2
            + jnp.ceil(n_vals / self._block) * 4,
        }
        return uniq, g_u, metrics

    def apply_put(self, state, dev_ids, grads):
        uniq, g_u, m = self._compress_put(dev_ids, grads)
        st, m2 = self.inner._put_unique(state, uniq, g_u)
        return st, {**m, **m2}

    def hybrid_update(self, state, queue, dev_ids, grads):
        uniq, g_u, m = self._compress_put(dev_ids, grads)
        st, q, m2 = self.inner._hybrid_unique(state, queue, uniq, g_u)
        return st, q, {**m, **m2}

    # -- capacity accounting -------------------------------------------------

    def device_bytes(self, state) -> int:
        return self.inner.device_bytes(state)

    def host_bytes(self) -> int:
        return self.inner.host_bytes()


# ===========================================================================
# Factory + collection-level drivers
# ===========================================================================

def parse_backend_name(name: str | None) -> tuple[str, bool]:
    """``EmbeddingSpec.backend`` string -> (base, compressed?). Accepted
    forms: ``dense``, ``host_lru``, ``host_lru+disk`` (the three-tier
    hierarchy — ``base`` keeps the ``+disk`` marker), plus a
    ``+compressed`` suffix on any of them (``compressed`` alone means
    ``dense+compressed``)."""
    name = (name or "dense").strip().lower()
    parts = name.split("+")
    base, flags = parts[0], parts[1:]
    wrap = "compressed" in flags
    if base in ("", "compressed"):
        base, wrap, flags = "dense", True, [f for f in flags
                                            if f != "compressed"]
    unknown = [f for f in flags if f not in ("compressed", "disk")]
    if unknown:
        raise ValueError(
            f"unknown backend decorator {unknown[0]!r} in {name!r} "
            "(only '+disk' and '+compressed' exist)")
    if base not in ("dense", "host_lru"):
        raise ValueError(
            f"unknown embedding backend {name!r}: expected 'dense', "
            "'host_lru' or 'host_lru+disk', optionally with a "
            "'+compressed' suffix")
    if "disk" in flags:
        if base != "host_lru":
            raise ValueError(
                f"the '+disk' tier only stacks under 'host_lru' "
                f"(got {name!r})")
        base = "host_lru+disk"
    return base, wrap


def create_backend(spec: EmbeddingSpec) -> EmbeddingBackend:
    """``spec.backend`` -> backend instance (see parse_backend_name).
    ``spec.emb_shards > 1`` routes through the :class:`ShardedBackend`
    router; the compressed wire (when requested) wraps OUTSIDE the router,
    so one wire serves the whole table. ``emb_shards == 1`` returns the
    plain backend — bit- and checkpoint-byte-identical to the pre-router
    code."""
    base, wrap = parse_backend_name(spec.backend)
    if spec.backward_kernel and spec.optimizer != "adagrad":
        raise ValueError(
            "backward_kernel=True runs the Pallas fused backward, which "
            f"applies adagrad only; optimizer={spec.optimizer!r} needs "
            "backward_kernel=False")
    if int(spec.emb_shards) > 1:
        backend: EmbeddingBackend = ShardedBackend(spec)
    elif base == "dense":
        backend = DenseBackend(spec)
    else:
        backend = HostLRUBackend(spec)
    return CompressedWireBackend(backend) if wrap else backend


def unwrap(backend: EmbeddingBackend) -> EmbeddingBackend:
    """Strip wire decorators down to the storage backend (plain or router)."""
    while isinstance(backend, CompressedWireBackend):
        backend = backend.inner
    return backend


def ensure_shards(backend: EmbeddingBackend, k: int) -> EmbeddingBackend:
    """Route a backend through a ``k``-shard router (the
    ``PersiaTrainer.init(emb_shards=...)`` path). ``k == 1`` is "no
    override" and returns the backend unchanged — it never downgrades a
    spec-sharded router. Dense backends without ``spec.emb_shards`` are
    laid out by the ambient mesh instead, so only host-backed tables —
    which used to raise — and existing routers are rebuilt here."""
    if int(k) == 1:
        return backend
    inner = unwrap(backend)
    if isinstance(inner, ShardedBackend):
        if inner.n_shards == int(k):
            return backend
    elif not isinstance(inner, HostLRUBackend):
        return backend                      # dense: the mesh lays it out
    new_inner = ShardedBackend(
        dataclasses.replace(inner.spec, emb_shards=int(k)))
    return CompressedWireBackend(new_inner) \
        if isinstance(backend, CompressedWireBackend) else new_inner


def make_backends(collection) -> dict[str, EmbeddingBackend]:
    """One backend instance per table (instances own mutable host state, so
    each trainer must build its own set)."""
    return {n: create_backend(s) for n, s in collection.items()}


def any_requires_prepare(backends) -> bool:
    return any(b.requires_prepare for b in backends.values())


def shard_step_metrics(backends) -> dict:
    """Host-side per-shard gauges for the step-metrics dict:
    ``shard/<table>/<k>/{hit_rate,faults,rows,bytes}`` plus the
    ``shard/<table>/imbalance`` max/mean traffic gauge (hot-key skew made
    visible). Empty — and cheap — when no table is sharded."""
    out = {}
    for n, b in backends.items():
        for k, v in b.shard_metrics().items():
            out[f"shard/{n}/{k}"] = v
    return out


def prepare_all(backends, states, ids):
    """Host-level per-table prepare: batch dedup + fault-in + id
    translation, once per (table, batch).

    For tables with ``spec.batch_dedup`` (the default) this computes the
    :class:`~repro.core.dedup.DedupPlan` — np.unique on the host, the
    backend's ``prepare`` consuming the already-unique set (no second
    np.unique in the fault path) — and returns it as the table's dev-ids
    entry; the traceable ops then run at unique width. Legacy tables
    (``batch_dedup=False``) keep the occurrence-width translation.

    Returns ``(new_states, dev_ids, metrics)`` where metrics carries the
    per-table ``dedup/<table>/{dup_factor,unique_rows,bytes_saved}``
    host gauges.

    Runs in two phases over the tables: every table's prepare is
    *submitted* first (``prepare_submit``), then collected — remote
    backends buffer all the submits into one coalesced frame per endpoint
    and the collects' RPC waits overlap, so a k-table trainer pays one
    round-trip per endpoint instead of k."""
    new_states = dict(states)
    dev_ids = {}
    metrics = {}
    submitted = []
    for n in ids:
        b = backends[n]
        spec = b.spec
        if not spec.batch_dedup:
            submitted.append((n, None,
                              b.prepare_submit(states[n], ids[n])))
            continue
        with span("prepare/plan", b.span_s, table=n):
            cap = D.dedup_cap(max(int(np.size(ids[n])), 1),
                              b.dedup_rows())
            u_pad, inv, counts, info = D.make_plan(ids[n], spec.rows, cap)
        submitted.append((n, (inv, info),
                          b.prepare_submit(states[n], u_pad,
                                           assume_unique=True,
                                           counts=counts)))
    for n, plan, collect in submitted:
        b = backends[n]
        spec = b.spec
        if plan is None:
            new_states[n], dev_ids[n] = collect()
            for k, v in b.cache_metrics().items():
                metrics[f"cache/{n}/{k}"] = v
            continue
        inv, info = plan
        new_states[n], dev_u = collect()
        with span("prepare/plan", b.span_s, table=n):
            dev_ids[n] = DedupPlan(dev=jnp.asarray(dev_u, jnp.int32),
                                   inv=jnp.asarray(inv, jnp.int32))
        itemsize = jnp.dtype(spec.dtype).itemsize
        metrics[f"dedup/{n}/dup_factor"] = info["dup_factor"]
        metrics[f"dedup/{n}/unique_rows"] = float(info["n_unique"])
        metrics[f"dedup/{n}/bytes_saved"] = float(
            (info["n_occ"] - info["n_unique"]) * spec.dim * itemsize)
        for k, v in b.cache_metrics().items():
            metrics[f"cache/{n}/{k}"] = v
    return new_states, dev_ids, metrics


def _tag(metrics, name, table_metrics):
    for k, v in table_metrics.items():
        metrics[f"wire/{name}/{k}"] = v


@jax.named_scope("persia/lookup")
def lookup_all(backends, states, dev_ids):
    """Traceable fan-out of per-table lookups -> (acts, wire metrics)."""
    acts, metrics = {}, {}
    for n in dev_ids:
        if n not in backends:
            raise KeyError(f"ids for unknown table {n!r}; collection has "
                           f"{sorted(backends)}")
        acts[n], m = backends[n].lookup(states[n], dev_ids[n])
        _tag(metrics, n, m)
    return acts, metrics


@jax.named_scope("persia/put")
def put_all(backends, states, queues, dev_ids, grads):
    """Traceable fan-out of per-table hybrid updates (push this step's put,
    apply the tau-stale one) -> (states, queues, metrics): the wire
    metrics, and ``put/lost_rows``, the queued put rows dropped this step
    because their cache slot was recycled (host_lru tables; summed)."""
    queues = queues or {}
    new_states, new_queues, metrics = dict(states), dict(queues), {}
    lost = []
    for n in dev_ids:
        st, q, m = backends[n].hybrid_update(
            states[n], queues.get(n), dev_ids[n], grads[n])
        new_states[n], new_queues[n] = st, q
        m = dict(m)
        if "lost_rows" in m:
            lost.append(m.pop("lost_rows"))
        _tag(metrics, n, m)
    if lost:
        metrics["put/lost_rows"] = sum(lost)
    return new_states, new_queues, metrics


def span_seconds(backends) -> dict[str, float]:
    """Each host span's seconds (``EmbeddingBackend.span_s``) summed over
    the tables, their wire decorators and their shards."""
    out: dict[str, float] = {}
    todo = list(backends.values())
    while todo:
        b = todo.pop()
        for k, v in b.span_s.items():
            out[k] = out.get(k, 0.0) + v
        if isinstance(b, CompressedWireBackend):
            todo.append(b.inner)
        elif isinstance(b, ShardedBackend):
            todo.extend(b.shard_backends)
    return out
