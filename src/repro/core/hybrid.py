"""The Persia hybrid training algorithm (paper Alg. 1 + Alg. 2).

One train step =
  (1) lookup: fetch embedding activations for the batch's ID features from
      the (possibly tau-stale) PS tables                     [Alg.1 forward]
  (2) dense forward/backward on the NN-worker side; gradients of the dense
      parameters are combined synchronously (the AllReduce paradigm — under
      GSPMD this is the automatic psum of replicated-param grads over the
      batch axes)                                            [Alg.2]
  (3) gradients *of the embedding activations* (F^emb') are sent back and
      pushed through each table's bounded-staleness queue; the put that pops
      out (from step t - tau) is applied by the PS-side optimizer
                                                             [Alg.1 backward]

Three modes reproduce the paper's comparison:
  * hybrid — emb staleness tau>0, dense sync              (Persia)
  * sync   — tau=0 everywhere                              (XDL-sync analog)
  * async  — emb stale AND dense grads applied tau_d steps late
             (Hogwild-style; XDL-async / aggressive-PaddlePaddle analog)

The public surface is :class:`PersiaTrainer`, a facade over a multi-table
:class:`~repro.core.collection.EmbeddingCollection`: it owns the pytree
:class:`TrainState`, the fused jitted step, the decomposed (3-dispatch,
donated) pipeline, eval, and full-state checkpoint/restore. The module-level
free functions (``init_train_state`` / ``make_train_step`` / ...) are kept as
thin single-table shims for the pre-collection API.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import backend as BK
from repro.core import embedding_ps as PS
from repro.core.collection import EmbeddingCollection
from repro.core.embedding_ps import EmbeddingSpec
from repro.core.spans import span


@dataclass(frozen=True)
class TrainMode:
    name: str = "hybrid"
    emb_staleness: int = 3
    dense_staleness: int = 0

    @staticmethod
    def hybrid(tau: int = 3) -> "TrainMode":
        return TrainMode("hybrid", tau, 0)

    @staticmethod
    def sync() -> "TrainMode":
        return TrainMode("sync", 0, 0)

    @staticmethod
    def async_(tau: int = 3, tau_dense: int = 3) -> "TrainMode":
        return TrainMode("async", tau, tau_dense)


@dataclass(frozen=True)
class ModelAdapter:
    """Bridges a concrete model family to the hybrid trainer.

    ``emb_ids`` maps a batch to a dict of per-table id arrays keyed by the
    collection's table names; ``loss``/``predict`` receive the matching dict
    of looked-up activations.
    """
    cfg: Any
    collection: EmbeddingCollection
    init_dense: Callable[[jax.Array], Any]
    emb_ids: Callable[[dict], dict[str, jax.Array]]
    loss: Callable[[Any, dict[str, jax.Array], dict], tuple]
    predict: Optional[Callable] = None       # (dense, acts, batch) -> preds

    @property
    def emb_spec(self) -> EmbeddingSpec:
        """Legacy single-table view (pre-collection API)."""
        return _sole_table(self)[1]


# -- the train state ----------------------------------------------------------

@dataclass
class TrainState:
    """Everything one training run owns, as a single registered pytree:
    dense params + optimizer, per-table PS states, per-table staleness
    queues, the async-dense delay queue, and the step counter."""
    dense: Any
    opt: Any
    emb: dict                  # name -> {"table", "acc"?}
    emb_queue: Any             # name -> staleness FIFO | None
    dense_queue: Any           # delay queue for 'async' mode | None
    step: jax.Array

    def replace(self, **kw) -> "TrainState":
        return dataclasses.replace(self, **kw)


jax.tree_util.register_dataclass(
    TrainState,
    data_fields=("dense", "opt", "emb", "emb_queue", "dense_queue", "step"),
    meta_fields=())


# -- dense gradient delay queue (async baseline) ------------------------------

def _dense_queue_init(dense, tau):
    return {
        "grads": jax.tree.map(
            lambda p: jnp.zeros((tau,) + p.shape, jnp.float32), dense),
        "ptr": jnp.zeros((), jnp.int32),
        "filled": jnp.zeros((), jnp.int32),
    }


def _dense_queue_push_pop(queue, grads):
    ptr = queue["ptr"]
    old = jax.tree.map(lambda q: jnp.take(q, ptr, axis=0), queue["grads"])
    new_g = jax.tree.map(
        lambda q, g: jax.lax.dynamic_update_index_in_dim(
            q, g.astype(jnp.float32), ptr, 0),
        queue["grads"], grads)
    n_tau = jax.tree.leaves(queue["grads"])[0].shape[0]
    warm = queue["filled"] < n_tau
    # during warmup apply the fresh grad (queue slot still zero)
    old = jax.tree.map(lambda o, g: jnp.where(warm, g.astype(jnp.float32), o),
                       old, grads)
    return {"grads": new_g, "ptr": (ptr + 1) % n_tau,
            "filled": jnp.minimum(queue["filled"] + 1, n_tau)}, old


def _queue_leaf(q):
    """The (tau, W, ...) 'ids' array of a staleness queue, reaching into
    sharded-router queues ({"s0": {...}, ...}) when needed."""
    if q is None:
        return None
    return q["ids"] if "ids" in q else q["s0"]["ids"]


def _queue_depth(q) -> int:
    ids = _queue_leaf(q)
    return 0 if ids is None else int(ids.shape[0])


def _queue_width(q) -> int:
    ids = _queue_leaf(q)
    if ids is None:
        return 0
    w = 1
    for s in ids.shape[1:]:
        w *= int(s)
    return w


def _migrate_queue_widths(backend, q):
    """Restore-time staleness-queue width migration (worker-side dedup,
    core/dedup.py): the queue width is derived from the blob's own width
    through the backend's capacity rule — idempotent, so blobs already at
    unique width pass through unchanged, while full-width blobs written by
    a pre-dedup (or ``batch_dedup=False``) trainer are re-encoded by
    deduplicating each pending put host-side."""
    import numpy as np
    from repro.core import dedup as DD
    if q is None:
        return None
    if "ids" not in q:                   # sharded router: per-shard queues
        return {k: _migrate_queue_widths(backend, v) for k, v in q.items()}
    saved = int(np.shape(q["ids"])[1])
    new_w = int(backend.queue_width(saved))
    if new_w == saved:
        return q
    return DD.migrate_queue_blob(q, new_w)


def _emb_grad_norm(agrads: dict) -> jax.Array:
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in agrads.values())
    return jnp.sqrt(sq)


# =============================================================================
# PersiaTrainer — the unified facade
# =============================================================================

class PersiaTrainer:
    """One object owning the whole hybrid training loop.

    >>> trainer = PersiaTrainer(adapter, TrainMode.hybrid(3),
    ...                         OptConfig(kind="adam", lr=3e-3))
    >>> state = trainer.init(jax.random.PRNGKey(0), batch)
    >>> state, metrics = trainer.step(state, batch)          # fused, donated
    >>> state, metrics = trainer.decomposed_step(state, batch)  # 3 dispatches
    >>> metrics = trainer.eval(state, batch)
    >>> trainer.save(ckpt_dir, state)                        # full state
    >>> state = trainer.restore(ckpt_dir)                    # bit-identical

    ``opt`` is either an ``OptConfig`` or a pre-built ``(opt_init,
    opt_update)`` pair. By default every table's staleness is overridden by
    ``mode.emb_staleness`` (matching the legacy API); pass
    ``per_table_staleness=True`` to honour each table's own
    ``EmbeddingSpec.staleness`` (heterogeneous update policies).
    """

    def __init__(self, adapter: ModelAdapter, mode: TrainMode | None = None,
                 opt: Any = None, lr_fn=None,
                 per_table_staleness: bool = False,
                 batch_dedup: bool | None = None):
        from repro.optim.optimizers import OptConfig, make_optimizer
        self.adapter = adapter
        self.mode = mode or TrainMode.hybrid()
        if opt is None:
            opt = OptConfig()
        if isinstance(opt, OptConfig):
            self.opt_init, self.opt_update = make_optimizer(opt)
        else:
            self.opt_init, self.opt_update = opt
        self.lr_fn = lr_fn
        if per_table_staleness:
            self.collection = adapter.collection
        else:
            self.collection = adapter.collection.with_staleness(
                self.mode.emb_staleness)
        # batch_dedup=None honours each spec's own flag (default True:
        # the worker-side dedup path, core/dedup.py); an explicit bool
        # overrides every table — False restores the occurrence-width
        # PR-4 data path (benchmarking / old-format checkpoints)
        if batch_dedup is not None:
            self.collection = self.collection.map_specs(
                lambda _, s: dataclasses.replace(s, batch_dedup=batch_dedup))
        # one storage backend per table (core/backend.py): dense PS,
        # host-LRU out-of-core, or either behind the compressed wire
        self.backends = self.collection.make_backends()
        self._needs_prepare = BK.any_requires_prepare(self.backends)
        self._needs_plan = any(s.batch_dedup
                               for _, s in self.collection.items())
        self._fused = None
        self._eval = None
        self._decomposed = None

    # -- init -----------------------------------------------------------------

    def init(self, key, batch_example=None, emb_shards=1) -> TrainState:
        """batch_example: abstract or concrete batch (for queue shapes).
        Required whenever any staleness is in play — without it the queues
        cannot be sized and tau>0 would silently train synchronously.

        ``emb_shards`` (an int or a {table: k} mapping, validated against
        the collection) selects per-table embedding-PS shard counts: dense
        tables ignore it (the ambient mesh fixes their padding and layout)
        while host-backed tables route through the ShardedBackend router
        (k independent shards, concurrent fault-in) — they used to reject
        shards != 1 outright. Tables whose ``EmbeddingSpec.emb_shards`` is
        already > 1 are routers from construction; the default of 1 here
        never downgrades them."""
        # swap in routers BEFORE drawing state: backends are shared by the
        # cached jitted fns via the self.backends dict, mutated in place
        self.collection._check_shard_mapping(emb_shards)
        for n in self.collection.names:
            self.backends[n] = BK.ensure_shards(
                self.backends[n], self.collection._shards_for(n, emb_shards))
        self._needs_prepare = BK.any_requires_prepare(self.backends)
        max_tau = max((s.staleness for _, s in self.collection.items()),
                      default=0)
        if batch_example is None and \
                (max_tau > 0 or self.mode.dense_staleness > 0):
            raise ValueError(
                "init() needs a batch_example to size the staleness queues "
                f"(emb tau up to {max_tau}, dense tau_d="
                f"{self.mode.dense_staleness})")
        kd, ke = jax.random.split(key)
        dense = self.adapter.init_dense(kd)
        # per-table backend init (same key fan-out as collection.init)
        keys = jax.random.split(ke, max(len(self.collection), 1))
        emb = {n: self.backends[n].init(
            keys[i], self.collection._shards_for(n, emb_shards))
            for i, n in enumerate(self.collection.names)}
        emb_queue = {n: None for n in self.collection.names}
        dense_queue = None
        if batch_example is not None:
            ids = self.adapter.emb_ids(batch_example)
            emb_queue = {n: self.backends[n].queue_init(tuple(a.shape))
                         for n, a in ids.items()}
            for n in self.collection.names:
                emb_queue.setdefault(n, None)
            if self.mode.dense_staleness > 0:
                dense_queue = _dense_queue_init(dense,
                                                self.mode.dense_staleness)
        return TrainState(dense=dense, opt=self.opt_init(dense), emb=emb,
                          emb_queue=emb_queue, dense_queue=dense_queue,
                          step=jnp.zeros((), jnp.int32))

    # -- the host-side prepare phase (batch dedup + out-of-core fault-in) -----
    #
    # Two things happen here, once per step, OUTSIDE jit: (1) worker-side
    # batch dedup (core/dedup.py) — each table's ids are deduplicated to a
    # DedupPlan so the whole traceable path runs at unique width; (2) the
    # out-of-core fault-in for host-backed tables — missing rows load
    # host->device (consuming the plan's already-unique set, no second
    # np.unique), evicted rows write back, ids translate to device ids.
    # Only a trainer whose every table opts out (batch_dedup=False) with no
    # host-backed tables skips the phase entirely — that all-dense legacy
    # path is exactly the pre-dedup program.

    def _prepare(self, state: TrainState, batch):
        """Returns (state-with-faulted-caches, dev_ids-or-None, metrics)."""
        if not (self._needs_prepare or self._needs_plan):
            return state, None, {}
        ids = self.adapter.emb_ids(batch)
        emb, dev_ids, m = BK.prepare_all(self.backends, state.emb, ids)
        return state.replace(emb=emb), dev_ids, m

    # -- fused step (one program, one schedule) -------------------------------

    def train_step(self, state: TrainState, batch, dev_ids=None):
        """The fused step as a pure traceable function (jit it yourself, or
        use :meth:`step` for the cached donated jit). ``dev_ids`` carries
        prepared device ids for host-backed tables; all-dense trainers may
        leave it None."""
        adapter, mode = self.adapter, self.mode
        if dev_ids is None:
            if self._needs_prepare:
                raise ValueError(
                    "this trainer has host-backed (out-of-core) tables: "
                    "the fused step needs prepared device ids — call "
                    "step()/decomposed_step(), which run the host fault-in "
                    "phase, instead of jitting train_step directly")
            dev_ids = adapter.emb_ids(batch)
        acts, get_metrics = BK.lookup_all(self.backends, state.emb,
                                          dev_ids)                # Alg.1 fwd

        def loss_fn(dense, acts_):
            with jax.named_scope("persia/tower"):
                return adapter.loss(dense, acts_, batch)

        (loss, metrics), (dgrads, agrads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(state.dense, acts)

        lr = self.lr_fn(state.step) if self.lr_fn is not None else None

        # ---- dense side (Alg.2): synchronous, or delayed for 'async' ----
        dense_queue = state.dense_queue
        if mode.dense_staleness > 0 and dense_queue is not None:
            dense_queue, dgrads_apply = _dense_queue_push_pop(dense_queue,
                                                              dgrads)
        else:
            dgrads_apply = dgrads
        dense, opt = self.opt_update(state.dense, dgrads_apply, state.opt,
                                     lr=lr)

        # ---- embedding side (Alg.1 bwd): async puts through the queues ----
        emb, emb_queue, put_metrics = BK.put_all(
            self.backends, state.emb, state.emb_queue, dev_ids, agrads)

        metrics = dict(metrics)
        metrics["emb_grad_norm"] = _emb_grad_norm(agrads)
        metrics.update(get_metrics)
        metrics.update(put_metrics)
        return state.replace(dense=dense, opt=opt, emb=emb,
                             emb_queue=emb_queue, dense_queue=dense_queue,
                             step=state.step + 1), metrics

    def step(self, state: TrainState, batch):
        """Fused step through a cached jit; donates ``state``. The host
        prepare phase (batch dedup + out-of-core fault-in) runs before the
        jitted program. Host spans: ``persia/step`` over the call,
        ``persia/prepare`` and ``persia/step/dispatch`` inside it."""
        with span("step"):
            with span("prepare"):
                state, dev_ids, prep_m = self._prepare(state, batch)
            if self._fused is None:
                self._fused = jax.jit(self.train_step, donate_argnums=(0,))
            with span("step/dispatch"):
                state, metrics = self._fused(state, batch, dev_ids)
            metrics.update(prep_m)
            metrics.update(BK.shard_step_metrics(self.backends))
        return state, metrics

    # -- decomposed pipeline ---------------------------------------------------
    #
    # The fused step is what the dry-run lowers (one program, one schedule).
    # At runtime Persia's architecture is *decomposed*: the embedding get,
    # the dense step and the embedding put are separate dispatches (separate
    # RPCs in the paper), which lets the runtime overlap them and — crucially
    # — lets XLA alias the donated PS tables in the put (in-place row
    # scatter, O(#puts) instead of an O(rows) defensive copy).

    def decomposed_fns(self):
        """(lookup_fn, dense_step, emb_put) — separate jitted dispatches."""
        if self._decomposed is not None:
            return self._decomposed
        adapter, mode = self.adapter, self.mode
        backends = self.backends
        lr_fn, opt_update = self.lr_fn, self.opt_update

        @jax.jit
        def lookup_fn(emb_states, dev_ids):
            return BK.lookup_all(backends, emb_states, dev_ids)  # Alg.1 fwd

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def dense_step(dense, opt, dense_queue, acts, batch, step_no):
            def loss_fn(dense_, acts_):                        # Alg.2
                with jax.named_scope("persia/tower"):
                    return adapter.loss(dense_, acts_, batch)

            (loss, metrics), (dgrads, agrads) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(dense, acts)
            lr = lr_fn(step_no) if lr_fn is not None else None
            if mode.dense_staleness > 0 and dense_queue is not None:
                dense_queue, dgrads = _dense_queue_push_pop(dense_queue,
                                                            dgrads)
            dense, opt = opt_update(dense, dgrads, opt, lr=lr)
            metrics = dict(metrics)
            metrics["emb_grad_norm"] = _emb_grad_norm(agrads)
            return dense, opt, dense_queue, agrads, metrics

        @partial(jax.jit, donate_argnums=(0, 1))
        def emb_put(emb_states, queues, dev_ids, agrads):      # Alg.1 bwd
            return BK.put_all(backends, emb_states, queues, dev_ids, agrads)

        self._decomposed = (lookup_fn, dense_step, emb_put)
        return self._decomposed

    def decomposed_step(self, state: TrainState, batch):
        """One iteration through the decomposed pipeline (host-driven): the
        out-of-core fault-in (prepare), the embedding get, the dense step
        and the embedding put are separate dispatches."""
        lookup_fn, dense_step, emb_put = self.decomposed_fns()
        state, dev_ids, prep_m = self._prepare(state, batch)
        if dev_ids is None:
            dev_ids = self.adapter.emb_ids(batch)
        acts, get_metrics = lookup_fn(state.emb, dev_ids)
        dense, opt, dense_queue, agrads, metrics = dense_step(
            state.dense, state.opt, state.dense_queue, acts, batch,
            state.step)
        # the put is dispatched without blocking — the async leg of the hybrid
        emb, queues, put_metrics = emb_put(state.emb, state.emb_queue,
                                           dev_ids, agrads)
        metrics = dict(metrics)
        metrics.update(prep_m)
        metrics.update(get_metrics)
        metrics.update(put_metrics)
        # host-side per-shard gauges (hit rates, faults, load imbalance)
        metrics.update(BK.shard_step_metrics(self.backends))
        return state.replace(dense=dense, opt=opt, dense_queue=dense_queue,
                             emb=emb, emb_queue=queues,
                             step=state.step + 1), metrics

    def run(self, state: TrainState, batches, steps: int | None = None,
            delay_fn=None) -> tuple[TrainState, list[dict]]:
        """Serial reference loop: one ``decomposed_step`` per batch
        (optionally capped at ``steps``), returning the final state and the
        per-step metrics. ``delay_fn(stage, step) -> seconds`` injects the
        same per-stage latencies the pipelined engine understands — paid
        serially here, which is what makes ``benchmarks/pipeline.py`` an
        apples-to-apples serial-vs-pipelined comparison."""
        import time
        stages = ("loader", "prepare", "lookup", "dense", "put")
        metrics_list: list[dict] = []
        for idx, batch in enumerate(batches):
            if steps is not None and idx >= steps:
                break
            if delay_fn is not None:
                for stage in stages:
                    d = float(delay_fn(stage, idx))
                    if d > 0:
                        time.sleep(d)
            state, m = self.decomposed_step(state, batch)
            metrics_list.append(m)
        return state, metrics_list

    # -- eval / predict --------------------------------------------------------

    def eval_step(self, state: TrainState, batch, dev_ids=None):
        if dev_ids is None:
            if self._needs_prepare:
                raise ValueError(
                    "this trainer has host-backed (out-of-core) tables: "
                    "eval_step needs prepared device ids — call eval()")
            dev_ids = self.adapter.emb_ids(batch)
        acts, _ = BK.lookup_all(self.backends, state.emb, dev_ids)
        _, metrics = self.adapter.loss(state.dense, acts, batch)
        return metrics

    def serve_lookup(self, state: TrainState, batch):
        """Read-path lookup (``EmbeddingBackend.read_rows``): logical ids
        -> fp32 activations, **without** faulting rows into the device
        cache or touching any backend host state. Host-tier rows are read
        straight from the store; residency is resolved against the passed
        state snapshot, so a serving thread can call this concurrently
        with a trainer stepping on the same backends. Returns ``(acts,
        info)`` with per-table ``{reads, hits, misses}`` read gauges."""
        ids = self.adapter.emb_ids(batch)
        acts, info = {}, {}
        for n, a in ids.items():
            rows, inf = self.backends[n].read_rows(state.emb[n], a)
            acts[n] = jnp.asarray(rows)
            info[n] = inf
        return acts, info

    def eval(self, state: TrainState, batch):
        """Eval on the current tables through the read-only serve path.
        Unlike the pre-serving implementation this never faults rows into
        the device cache — no state mutation, no evictions, no dropped
        queued puts — so eval is perfectly side-effect-free on every
        backend."""
        acts, _ = self.serve_lookup(state, batch)
        if self._eval is None:
            adapter = self.adapter
            self._eval = jax.jit(
                lambda dense, acts_, b: adapter.loss(dense, acts_, b)[1])
        return self._eval(state.dense, acts, batch)

    def lookup(self, state: TrainState, batch):
        acts, _ = self.serve_lookup(state, batch)
        return acts

    def predict(self, state: TrainState, batch):
        if self.adapter.predict is None:
            raise ValueError("adapter has no predict fn")
        acts = self.lookup(state, batch)
        return self.adapter.predict(state.dense, acts, batch)

    # -- checkpoint (full state, paper §4.2.4 policy) --------------------------
    #
    # The dense tree (params + optimizer + delay queue) is saved atomically;
    # the per-table PS states and staleness queues ride in the independent
    # embedding blob. Everything round-trips — including the adagrad
    # accumulators and queue contents — so a restore resumes bit-identically.

    def save(self, directory: str, state: TrainState,
             step: int | None = None) -> str:
        from repro.checkpoint.ckpt import save_checkpoint
        import numpy as np
        step = int(state.step) if step is None else int(step)
        to_np = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        dense_tree = {"dense": to_np(state.dense), "opt": to_np(state.opt)}
        if state.dense_queue is not None:
            dense_tree["dense_queue"] = to_np(state.dense_queue)
        # each backend snapshots its own tiers (dense: the PS shard arrays;
        # host_lru: device cache + host store + slot map, recency included)
        emb_tree = {"emb": {n: self.backends[n].state_for_checkpoint(
                        state.emb[n]) for n in state.emb},
                    "emb_queue": to_np(state.emb_queue)}
        return save_checkpoint(directory, step, dense_tree, emb_tree)

    def restore(self, directory: str, step: int | None = None) -> TrainState:
        from repro.checkpoint.ckpt import load_checkpoint
        step_no, dense_tree, emb_tree = load_checkpoint(directory, step)
        if not emb_tree or "emb" not in emb_tree or "dense" not in dense_tree:
            raise ValueError(
                f"checkpoint at {directory!r} is not a PersiaTrainer "
                "full-state snapshot (no per-table embedding blob) — it was "
                "likely written by the legacy save_checkpoint API")
        want, got = set(self.collection.names), set(emb_tree["emb"])
        if want != got:
            raise ValueError(
                f"checkpoint tables {sorted(got)} do not match this "
                f"trainer's collection {sorted(want)}")
        emb = {}
        for n in self.collection.names:
            try:
                emb[n] = self.backends[n].restore_from_checkpoint(
                    emb_tree["emb"][n])
            except ValueError as e:
                raise ValueError(f"checkpoint table {n!r}: {e}") from e
        queues = emb_tree.get("emb_queue", {})
        emb_queue = {n: queues.get(n) for n in self.collection.names}
        for n in self.collection.names:
            tau, q = self.collection[n].staleness, emb_queue[n]
            saved = _queue_depth(q)
            if (tau > 0) != (q is not None) or (q is not None
                                                and saved != tau):
                raise ValueError(
                    f"checkpoint table {n!r} was saved with staleness "
                    f"tau={saved} but this trainer runs tau={tau} — "
                    "restoring across modes would silently drop or bypass "
                    "the pending-put queue; rebuild the trainer with the "
                    "mode the checkpoint was trained under")
        for n in self.collection.names:
            bk = BK.unwrap(self.backends[n])
            if emb_queue[n] is not None and \
                    getattr(bk, "last_restore_resharded", False):
                # the table was resharded on restore: pending queue puts
                # are addressed in the OLD shard geometry (cache slots /
                # per-shard local ids), so they are dropped — the paper's
                # tolerated in-flight loss — and the FIFO restarts empty
                # in the new geometry, replaying its warmup
                emb_queue[n] = bk.queue_init((_queue_width(emb_queue[n]),))
        for n in self.collection.names:
            # old-format (occurrence-width) queue blobs restore into a
            # batch-dedup trainer by re-encoding each pending put at the
            # unique width this trainer runs (host-side dedup; the pops
            # then apply the exact same fp32 updates). Width-stable blobs
            # pass through untouched — same-geometry restores stay
            # bit-identical.
            emb_queue[n] = _migrate_queue_widths(self.backends[n],
                                                 emb_queue[n])
        dq = dense_tree.get("dense_queue")
        tau_d = self.mode.dense_staleness
        dq_depth = 0 if dq is None else \
            int(jax.tree.leaves(dq["grads"])[0].shape[0])
        if (tau_d > 0) != (dq is not None) or dq_depth not in (0, tau_d):
            raise ValueError(
                f"checkpoint was saved with dense staleness tau_d="
                f"{dq_depth} but this trainer runs tau_d={tau_d} — "
                "rebuild the trainer with the mode the checkpoint was "
                "trained under")
        return TrainState(
            dense=dense_tree["dense"], opt=dense_tree["opt"],
            emb=emb, emb_queue=emb_queue,
            dense_queue=dq,
            step=jnp.asarray(step_no, jnp.int32))


# =============================================================================
# Legacy single-table shims (pre-collection free-function API)
# =============================================================================
#
# These keep the original dict-state surface working for adapters whose
# collection holds exactly one table (the LM family). Multi-table models
# must use PersiaTrainer. The step logic is intentionally duplicated rather
# than delegated: the legacy factories receive opt_init and opt_update at
# different call sites, which doesn't map onto one facade construction, and
# freezing the old behavior here keeps the deprecated surface stable until
# its callers are migrated.

def _sole_table(adapter: ModelAdapter) -> tuple[str, EmbeddingSpec]:
    items = adapter.collection.items()
    if len(items) != 1:
        raise ValueError(
            "the legacy free-function API supports single-table adapters "
            f"only (got {len(items)} tables); use PersiaTrainer instead")
    return items[0]


def init_train_state(adapter: ModelAdapter, mode: TrainMode, opt_init,
                     key, batch_example=None, emb_shards: int = 1):
    """batch_example: abstract or concrete batch (for queue shapes)."""
    name, spec0 = _sole_table(adapter)
    kd, ke = jax.random.split(key)
    dense = adapter.init_dense(kd)
    spec = dataclasses.replace(spec0, staleness=mode.emb_staleness)
    emb = PS.ps_init(ke, spec, emb_shards)
    state = {
        "dense": dense,
        "opt": opt_init(dense),
        "emb": emb,
        "emb_queue": None,
        "dense_queue": None,
        "step": jnp.zeros((), jnp.int32),
    }
    if batch_example is not None:
        ids = adapter.emb_ids(batch_example)[name]
        n_ids = 1
        for s in ids.shape:
            n_ids *= s
        if mode.emb_staleness > 0:
            state["emb_queue"] = PS.queue_init(spec, (n_ids,), spec.dim)
        if mode.dense_staleness > 0:
            state["dense_queue"] = _dense_queue_init(dense,
                                                     mode.dense_staleness)
    return state, spec


def make_train_step(adapter: ModelAdapter, spec: EmbeddingSpec,
                    mode: TrainMode, opt_update, lr_fn=None):
    """Returns train_step(state, batch) -> (state, metrics); jit-able,
    lowerable on any mesh. Single-table legacy surface."""
    name, _ = _sole_table(adapter)

    def train_step(state, batch):
        ids = adapter.emb_ids(batch)[name]
        acts = PS.lookup(state["emb"], spec, ids)                 # Alg.1 fwd

        def loss_fn(dense, acts_):
            return adapter.loss(dense, {name: acts_}, batch)

        (loss, metrics), (dgrads, agrads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(state["dense"], acts)

        lr = lr_fn(state["step"]) if lr_fn is not None else None

        # ---- dense side (Alg.2): synchronous, or delayed for 'async' ----
        dense_queue = state["dense_queue"]
        if mode.dense_staleness > 0 and dense_queue is not None:
            dense_queue, dgrads_apply = _dense_queue_push_pop(dense_queue,
                                                              dgrads)
        else:
            dgrads_apply = dgrads
        dense, opt = opt_update(state["dense"], dgrads_apply, state["opt"],
                                lr=lr)

        # ---- embedding side (Alg.1 bwd): async put through the queue ----
        flat_ids = ids.reshape(-1)
        flat_g = agrads.reshape(-1, spec.dim)
        emb, emb_queue = PS.hybrid_emb_update(
            state["emb"], state["emb_queue"], spec, flat_ids, flat_g)

        new_state = {
            "dense": dense, "opt": opt, "emb": emb,
            "emb_queue": emb_queue, "dense_queue": dense_queue,
            "step": state["step"] + 1,
        }
        metrics = dict(metrics)
        metrics["emb_grad_norm"] = jnp.sqrt(
            jnp.sum(jnp.square(flat_g.astype(jnp.float32))))
        return new_state, metrics

    return train_step


def make_decomposed_fns(adapter: ModelAdapter, spec: EmbeddingSpec,
                        mode: TrainMode, opt_update, lr_fn=None):
    name, _ = _sole_table(adapter)

    @jax.jit
    def lookup_fn(emb_state, ids):
        return PS.lookup(emb_state, spec, ids)                 # Alg.1 fwd

    @partial(jax.jit, donate_argnums=(0, 1))
    def dense_step(dense, opt, acts, batch, step_no):          # Alg.2
        def loss_fn(dense_, acts_):
            return adapter.loss(dense_, {name: acts_}, batch)

        (loss, metrics), (dgrads, agrads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(dense, acts)
        lr = lr_fn(step_no) if lr_fn is not None else None
        dense, opt = opt_update(dense, dgrads, opt, lr=lr)
        return dense, opt, agrads, metrics

    @partial(jax.jit, donate_argnums=(0, 1))
    def emb_put(emb_state, queue, ids, agrads):                # Alg.1 bwd
        flat_ids = ids.reshape(-1)
        flat_g = agrads.reshape(-1, spec.dim)
        return PS.hybrid_emb_update(emb_state, queue, spec, flat_ids, flat_g)

    return lookup_fn, dense_step, emb_put


def decomposed_train_step(fns, state, batch, adapter):
    """One iteration through the decomposed pipeline (host-driven)."""
    name, _ = _sole_table(adapter)
    lookup_fn, dense_step, emb_put = fns
    ids = adapter.emb_ids(batch)[name]
    acts = lookup_fn(state["emb"], ids)
    dense, opt, agrads, metrics = dense_step(state["dense"], state["opt"],
                                             acts, batch, state["step"])
    # the put is dispatched without blocking — the async leg of the hybrid
    emb, queue = emb_put(state["emb"], state["emb_queue"], ids, agrads)
    new_state = dict(state)
    new_state.update(dense=dense, opt=opt, emb=emb, emb_queue=queue,
                     step=state["step"] + 1)
    return new_state, metrics


def make_eval_step(adapter: ModelAdapter, spec: EmbeddingSpec):
    name, _ = _sole_table(adapter)

    def eval_step(state, batch):
        ids = adapter.emb_ids(batch)[name]
        acts = PS.lookup(state["emb"], spec, ids)
        _, metrics = adapter.loss(state["dense"], {name: acts}, batch)
        return metrics
    return eval_step
