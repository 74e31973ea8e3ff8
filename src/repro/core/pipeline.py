"""Async pipelined execution of the hybrid trainer (paper §4, Fig. 4–5).

Persia's system contribution is not only the hybrid algorithm but its
*pipelined* execution: the embedding get, the dense compute and the
embedding put of different microbatches run concurrently across workers, so
the memory-bound embedding path hides behind the compute-bound dense path.
:class:`~repro.core.hybrid.PersiaTrainer` runs ``prepare → lookup → dense →
put`` strictly serially per batch; this module runs the same four dispatches
(plus the data loader and an optional prefetch stage) as a bounded pipeline:

    loader ──q──▶ prefetch ──q──▶ prepare ──q──▶ lookup ──q──▶ dense ──q──▶ put
    (batches)    (look-ahead     (host fault-in  (jitted)     (jitted,   (jitted,
                  fault-in)       or passthrough)              donated)   donated)

With ``prefetch=k > 0`` the host fault-in moves into the prefetch stage,
which may run up to ``k`` batches AHEAD of the inflight window: step
``t+k``'s unique rows fault host→device while step ``t`` is still in its
dense compute, hiding host-store latency (the disk tier's, in particular)
behind training. Prefetched slots are pinned from the prefetch until the
batch's applied put, so the deeper horizon can never recycle an in-flight
row; ``cache_rows`` must cover the combined ``max_inflight + prefetch``
working set. ``prefetch=0`` (the default) keeps the fault-in inside the
prepare stage — the prefetch stage is a passthrough and dispatch order is
unchanged, bit for bit.

The same overlap extends to *remote* tables (``repro.net.remote``): there
``prepare_all`` submits every table's fault-in as one coalesced
``step_ops`` frame per PS endpoint and collects the replies together, so a
prefetching pipeline holds up to ``k`` remote fault-ins in flight per
endpoint — the PS round-trip hides behind the dense compute exactly like
the disk tier's latency does, and the put path's outstanding-ack window
(bounded by tau) keeps the paper's staleness contract while doing it.

Each stage is a thread; bounded queues carry up to ``max_inflight``
microbatches, so the host ``prepare`` phase (the out-of-core fault-in of the
``host_lru`` backend — the memory-bound leg) of step *t+1* overlaps the
jitted dense step of step *t*. Three invariants are enforced:

* **Bounded staleness, by backpressure.** Per table, the number of puts
  outstanding — batches past their lookup whose ``emb_put`` has not been
  applied — never exceeds ``min(max_inflight, tau)`` (and exactly 1 for
  synchronous tables, tau=0, which must never read past an unapplied put).
  A counting semaphore blocks the lookup stage instead of dropping puts.
  The windows are per (table, PS shard): a sharded table
  (``EmbeddingSpec.emb_shards > 1``) gets one window per shard. For
  *synchronous* sharded tables (tau=0) a batch only consumes windows of
  shards it actually routed ids to — a put is a true no-op on untouched
  shards, so batches touching disjoint shards overlap where a table-wide
  window would serialize them (disjoint shards share no rows). For
  *hybrid* sharded tables (tau>0) every batch charges every shard's
  window: the router advances every shard's FIFO on every put (a queued
  shard-s gradient is applied tau puts later regardless of who routed ids
  to s), so only full-window accounting preserves the hard
  ``tau + min(max_inflight, tau)`` staleness bound.
  Note the pipeline window is *additional* read staleness on top of the
  device-side FIFO's algorithmic tau: a lookup can observe parameters up
  to ``tau + min(max_inflight, tau)`` updates old (bounded by ``2*tau``) —
  the same shape of total asynchrony a real PS deployment has, and still a
  hard bound, but wider than the serial trainer's; set ``max_inflight=1``
  where the exact serial staleness matters.
* **Sequenced table state.** The emb pytree and staleness queues are
  versioned through a single table store: every emb-touching dispatch
  (prepare's fault-in scatter, the lookup snapshot, the donated put) happens
  under the store lock, so puts are applied in batch order, no put is
  dropped by the engine, and a donated buffer is never re-dispatched. The
  dense/opt/optimizer-queue state is owned solely by the dense stage.
  Host-backed tables additionally *pin* each in-flight batch's cache slots
  (prepare → applied put), so a deep pipeline's fault-ins can never recycle
  a row a pending lookup or put still targets; if the combined in-flight
  working set cannot fit the cache, the fault-in raises instead of silently
  reading wrong rows.
* **Fail fast.** Any stage exception stops the pipeline and re-raises from
  ``run()`` as :class:`PipelineStageError` naming the stage and step —
  queues and semaphores are polled against a stop event, so a dead
  downstream stage cannot hang its producers.

With ``max_inflight=1`` the permit cycle (prepare acquires, put releases)
reproduces the serial order of ``PersiaTrainer.decomposed_step`` exactly —
same jitted fns, same dispatch order — so the result is bit-exact with the
serial trainer for every mode and backend; that is the determinism contract
``tests/test_pipeline.py`` pins.

Per-stage timing/occupancy flows out of :meth:`PipelinedTrainer.
pipeline_metrics` as ``pipeline/<stage>/busy_s`` / ``.../queue_depth_*``;
``delay_fn(stage, step) -> seconds`` injects per-stage latency (simulated
host RPCs in ``benchmarks/pipeline.py``, seeded jitter in the stress
tests). ``PersiaTrainer.run`` accepts the same ``delay_fn`` and pays the
delays serially, which is what makes the serial-vs-pipelined benchmark an
apples-to-apples comparison.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Optional

from repro.core import backend as BK
from repro.core.dedup import plan_dev
from repro.core.hybrid import PersiaTrainer, TrainState
from repro.core.spans import PREPARE_PHASES, span

STAGES = ("loader", "prefetch", "prepare", "lookup", "dense", "put")

_DONE = object()          # end-of-stream sentinel flowing through the queues
_TICK = 0.02              # poll period for stop-aware queue/semaphore waits


class PipelineStageError(RuntimeError):
    """A pipeline stage raised; carries the stage name, step and cause."""

    def __init__(self, stage: str, step: int, original: BaseException):
        super().__init__(
            f"pipeline stage {stage!r} failed at step {step}: "
            f"{type(original).__name__}: {original}")
        self.stage = stage
        self.step = step
        self.original = original


class _StageStats:
    """Per-stage items + input-queue depth accounting (busy time is the
    ``stage/<stage>`` span, kept in ``PipelinedTrainer._span_s``)."""

    def __init__(self):
        self.items = 0
        self.depth_max = 0
        self.depth_sum = 0
        self.depth_samples = 0

    def sample_depth(self, depth: int):
        self.depth_max = max(self.depth_max, depth)
        self.depth_sum += depth
        self.depth_samples += 1


class PipelinedTrainer:
    """Bounded multi-stage pipeline over ``PersiaTrainer.decomposed_fns()``.

    >>> trainer = PersiaTrainer(adapter, TrainMode.hybrid(3), opt)
    >>> engine = PipelinedTrainer(trainer, max_inflight=4)
    >>> state = engine.init(jax.random.PRNGKey(0), batch)     # delegated
    >>> state, metrics = engine.run(state, batches)           # pipelined
    >>> engine.pipeline_metrics()["pipeline/prepare/busy_s"]
    >>> engine.eval(state, batch); engine.save(d, state)      # delegated

    ``init`` / ``eval`` / ``save`` / ``restore`` (and ``step`` /
    ``decomposed_step`` / ``lookup`` / ``predict``) delegate to the wrapped
    trainer, so the engine is a drop-in for the serial facade wherever the
    stream-level ``run`` replaces the per-batch step. ``run()`` owns the
    train state while it executes: don't eval/save concurrently.
    """

    def __init__(self, trainer: PersiaTrainer, max_inflight: int = 4,
                 delay_fn: Optional[Callable[[str, int], float]] = None,
                 prefetch: int = 0):
        if not isinstance(trainer, PersiaTrainer):
            raise TypeError(
                "PipelinedTrainer wraps a PersiaTrainer (build one first); "
                f"got {type(trainer).__name__}")
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1 "
                             f"(got {max_inflight})")
        if prefetch < 0:
            raise ValueError(f"prefetch must be >= 0 (got {prefetch})")
        self.trainer = trainer
        self.max_inflight = int(max_inflight)
        # prefetch > 0 moves the host fault-in (BK.prepare_all + slot
        # pinning) into a dedicated stage that may run up to ``prefetch``
        # batches AHEAD of the inflight window: step t+prefetch's rows
        # fault host->device while step t is still training. The faulted
        # slots stay pinned from prefetch until the batch's applied put,
        # so a deeper horizon can never recycle an in-flight row —
        # ``cache_rows`` must cover the combined (max_inflight + prefetch)
        # working set or the fault-in raises. prefetch=0 keeps the
        # fault-in inside the prepare stage (the pre-prefetch behaviour,
        # bit for bit).
        self.prefetch = int(prefetch)
        self.delay_fn = delay_fn
        self._stats: dict[str, _StageStats] = {}
        # seconds of the last run's host spans by name: the stages' busy
        # intervals, the store-lock wait, and each backend's prepare phases
        # (core/spans.py)
        self._span_s: dict[str, float] = {}
        self._lost_rows = None    # device scalar: the last run's lost puts
        self._wall_s = 0.0
        self._steps_done = 0
        self.max_outstanding: dict[str, int] = {}
        self.applied_order: list[int] = []
        self._running = False

    # -- delegated PersiaTrainer surface --------------------------------------

    @property
    def adapter(self):
        return self.trainer.adapter

    @property
    def mode(self):
        return self.trainer.mode

    @property
    def collection(self):
        return self.trainer.collection

    @property
    def backends(self):
        return self.trainer.backends

    def init(self, key, batch_example=None, emb_shards=1) -> TrainState:
        return self.trainer.init(key, batch_example, emb_shards)

    def step(self, state, batch):
        return self.trainer.step(state, batch)

    def decomposed_step(self, state, batch):
        return self.trainer.decomposed_step(state, batch)

    def eval(self, state, batch):
        return self.trainer.eval(state, batch)

    def lookup(self, state, batch):
        return self.trainer.lookup(state, batch)

    def predict(self, state, batch):
        return self.trainer.predict(state, batch)

    def save(self, directory: str, state: TrainState,
             step: int | None = None) -> str:
        return self.trainer.save(directory, state, step)

    def restore(self, directory: str, step: int | None = None) -> TrainState:
        return self.trainer.restore(directory, step)

    # -- the staleness window -------------------------------------------------

    def put_window(self, name: str) -> int:
        """Max puts outstanding (post-lookup, pre-apply) for one table: the
        pipeline may run at most ``tau`` lookups ahead of the last applied
        put (1 for synchronous tables — sync means no un-applied put is
        ever read past), and never more than ``max_inflight``."""
        tau = self.trainer.collection[name].staleness
        return 1 if tau <= 0 else min(self.max_inflight, tau)

    # -- the pipelined loop ---------------------------------------------------

    def run(self, state: TrainState, batches: Iterable[Any],
            steps: int | None = None,
            delay_fn: Optional[Callable[[str, int], float]] = None
            ) -> tuple[TrainState, list[dict]]:
        """Drive ``batches`` (an iterable of batch dicts, optionally capped
        at ``steps``) through the five-stage pipeline; returns the final
        state and the per-step metrics in batch order."""
        if self._running:
            raise RuntimeError("run() is not reentrant: this engine is "
                               "already driving a pipeline")
        delay_fn = delay_fn if delay_fn is not None else self.delay_fn
        trainer = self.trainer
        lookup_fn, dense_step, emb_put = trainer.decomposed_fns()
        adapter, backends = trainer.adapter, trainer.backends
        names = trainer.collection.names

        # shared cells: the table store (emb + staleness queues; every
        # touching dispatch is serialized by store_lock) and the dense cell
        # (owned by the dense stage alone, no lock needed)
        store = {"emb": state.emb, "queues": state.emb_queue}
        store_lock = threading.Lock()
        dense_cell = {"dense": state.dense, "opt": state.opt,
                      "queue": state.dense_queue, "step": state.step}

        stop = threading.Event()
        errors: list[PipelineStageError] = []
        inflight = threading.Semaphore(self.max_inflight)
        # put backpressure is per (table, PS shard): a sharded table gets one
        # window per shard, and a batch only consumes the windows of shards
        # it actually routed ids to — batches touching disjoint shards can
        # overlap where a table-wide window would have serialized them.
        # Unsharded tables have exactly one shard (0), reproducing the old
        # per-table semantics bit for bit.
        windows = {(n, s): threading.Semaphore(self.put_window(n))
                   for n in names
                   for s in range(backends[n].n_put_shards())}
        out_lock = threading.Lock()
        outstanding = {n: 0 for n in names}
        self.max_outstanding = {n: 0 for n in names}
        self.applied_order = []
        # the prefetch horizon: how many batches may sit between
        # prefetch-start and put-applied (the global inflight window plus
        # the look-ahead depth). One semaphore bounds it; with prefetch=0
        # the prefetch stage is a passthrough and the permit is unused.
        prefetch_sem = threading.Semaphore(self.max_inflight + self.prefetch)
        self._stats = {s: _StageStats() for s in STAGES}
        spans = self._span_s = {}
        self._lost_rows = None
        phases0 = BK.span_seconds(backends)
        qs = {s: queue.Queue(maxsize=self.max_inflight)
              for s in ("prefetch", "lookup", "dense", "put")}
        # the prepare queue buffers the look-ahead: faulted batches wait
        # here until the inflight window admits them
        qs["prepare"] = queue.Queue(
            maxsize=self.max_inflight + self.prefetch)
        results: list[tuple[int, dict]] = []

        def fail(stage: str, idx: int, exc: BaseException):
            errors.append(PipelineStageError(stage, idx, exc))
            stop.set()

        def sleep_for(stage: str, idx: int):
            if delay_fn is not None:
                d = float(delay_fn(stage, idx))
                if d > 0:
                    time.sleep(d)

        def q_put(stage_to: str, item) -> bool:
            q = qs[stage_to]
            while not stop.is_set():
                try:
                    q.put(item, timeout=_TICK)
                    self._stats[stage_to].sample_depth(q.qsize())
                    return True
                except queue.Full:
                    pass
            return False

        def q_get(stage: str):
            q = qs[stage]
            while not stop.is_set():
                try:
                    return q.get(timeout=_TICK)
                except queue.Empty:
                    pass
            return None

        def acquire(sem: threading.Semaphore) -> bool:
            while not stop.is_set():
                if sem.acquire(timeout=_TICK):
                    return True
            return False

        def loader():
            st = self._stats["loader"]
            idx = 0
            try:
                for batch in batches:
                    if steps is not None and idx >= steps:
                        break
                    if stop.is_set():
                        return
                    with span("stage/loader", spans):
                        sleep_for("loader", idx)
                    st.items += 1
                    if not q_put("prefetch", (idx, batch)):
                        return
                    idx += 1
                q_put("prefetch", _DONE)
            except Exception as e:   # noqa: BLE001
                fail("loader", idx, e)

        def touched_shards(n, dev_ids):
            """(table, shard) windows this batch must charge. Hybrid
            (tau>0) sharded tables charge EVERY shard — their put advances
            every shard's FIFO (see module docstring); sync sharded tables
            charge only the shards the batch routed ids to (no-op puts on
            the rest); unsharded tables are their single shard 0."""
            if n not in dev_ids:
                return (0,)
            bk = backends[n]
            if bk.n_put_shards() > 1 and \
                    trainer.collection[n].staleness > 0:
                return tuple(range(bk.n_put_shards()))
            # a DedupPlan's unique dev ids touch exactly the shards the
            # occurrence stream would (dedup never changes ownership)
            return bk.put_shards(plan_dev(dev_ids[n]))

        def fault_in(batch):
            """The host fault-in: translate ids, fault rows into the
            device caches, pin this batch's cache slots until its put has
            been applied — a later batch's fault-in must not recycle rows
            a pending lookup/put still targets (a plan's unique dev ids
            ARE the batch's slot set: one pin per distinct slot). The
            touched shards are decoded here too, while the dev ids are
            fresh host-built arrays — not between the lookup stage's
            window acquire and its jitted dispatch."""
            ids = adapter.emb_ids(batch)
            with span("prepare/lock_wait", spans):
                store_lock.acquire()
            try:
                emb, dev_ids, prep_m = BK.prepare_all(
                    backends, store["emb"], ids)
                store["emb"] = emb
                for n in dev_ids:
                    with span("prepare/slots", backends[n].span_s, table=n):
                        backends[n].pin_slots(plan_dev(dev_ids[n]))
            finally:
                store_lock.release()
            touched = {n: touched_shards(n, dev_ids) for n in names}
            return dev_ids, touched, prep_m

        def prefetch_stage():
            # prefetch=0: pure passthrough (no permits, no timing) — the
            # fault-in stays in prepare and dispatch order is unchanged.
            # prefetch>0: fault step t+k's rows while step t trains, ahead
            # of the inflight window but bounded by the prefetch horizon.
            st = self._stats["prefetch"]
            while True:
                item = q_get("prefetch")
                if item is None:
                    return
                if item is _DONE:
                    q_put("prepare", _DONE)
                    return
                if self.prefetch <= 0:
                    if not q_put("prepare", item):
                        return
                    st.items += 1
                    continue
                idx, batch = item
                try:
                    if not acquire(prefetch_sem):
                        return
                    with span("stage/prefetch", spans):
                        sleep_for("prefetch", idx)
                        dev_ids, touched, prep_m = fault_in(batch)
                    st.items += 1
                    if not q_put("prepare", (idx, batch, dev_ids, touched,
                                             prep_m)):
                        return
                except Exception as e:   # noqa: BLE001
                    fail("prefetch", idx, e)
                    return

        def prepare():
            st = self._stats["prepare"]
            while True:
                item = q_get("prepare")
                if item is None:
                    return
                if item is _DONE:
                    q_put("lookup", _DONE)
                    return
                idx, batch = item[0], item[1]
                try:
                    # the global permit: at most max_inflight batches
                    # between prepare-start and put-applied. With one
                    # permit this pins the exact serial dispatch order.
                    if not acquire(inflight):
                        return
                    with span("stage/prepare", spans):
                        sleep_for("prepare", idx)
                        if len(item) == 2:
                            dev_ids, touched, prep_m = fault_in(batch)
                        else:      # already faulted by the prefetch stage
                            _, _, dev_ids, touched, prep_m = item
                    st.items += 1
                    if not q_put("lookup", (idx, batch, dev_ids, touched,
                                            prep_m)):
                        return
                except Exception as e:   # noqa: BLE001
                    fail("prepare", idx, e)
                    return

        def lookup_stage():
            st = self._stats["lookup"]
            while True:
                item = q_get("lookup")
                if item is None:
                    return
                if item is _DONE:
                    q_put("dense", _DONE)
                    return
                idx, batch, dev_ids, touched, prep_m = item
                try:
                    with span("stage/lookup", spans):
                        sleep_for("lookup", idx)
                        # staleness backpressure: block (never drop) until
                        # every (table, shard) this batch charges is within
                        # its put window (see touched_shards)
                        for n in names:
                            for s in touched[n]:
                                if not acquire(windows[(n, s)]):
                                    return
                        with out_lock:
                            for n in names:
                                outstanding[n] += 1
                                self.max_outstanding[n] = max(
                                    self.max_outstanding[n], outstanding[n])
                        with store_lock:
                            acts, get_m = lookup_fn(store["emb"], dev_ids)
                    st.items += 1
                    if not q_put("dense", (idx, batch, dev_ids, acts, get_m,
                                           touched, prep_m)):
                        return
                except Exception as e:   # noqa: BLE001
                    fail("lookup", idx, e)
                    return

        def dense_stage():
            st = self._stats["dense"]
            while True:
                item = q_get("dense")
                if item is None:
                    return
                if item is _DONE:
                    q_put("put", _DONE)
                    return
                idx, batch, dev_ids, acts, get_m, touched, prep_m = item
                try:
                    with span("stage/dense", spans):
                        sleep_for("dense", idx)
                        d = dense_cell
                        dense, opt, dq, agrads, metrics = dense_step(
                            d["dense"], d["opt"], d["queue"], acts, batch,
                            d["step"])
                        dense_cell.update(dense=dense, opt=opt, queue=dq,
                                          step=d["step"] + 1)
                    st.items += 1
                    if not q_put("put", (idx, dev_ids, agrads,
                                         metrics, get_m, touched, prep_m)):
                        return
                except Exception as e:   # noqa: BLE001
                    fail("dense", idx, e)
                    return

        def put_stage():
            st = self._stats["put"]
            while True:
                item = q_get("put")
                if item is None or item is _DONE:
                    return
                idx, dev_ids, agrads, metrics, get_m, touched, prep_m = item
                try:
                    with span("stage/put", spans):
                        sleep_for("put", idx)
                        with store_lock:
                            emb, queues, put_m = emb_put(
                                store["emb"], store["queues"], dev_ids,
                                agrads)
                            store["emb"] = emb
                            store["queues"] = queues
                            for n in dev_ids:
                                backends[n].unpin_slots(
                                    plan_dev(dev_ids[n]))
                        self.applied_order.append(idx)
                        with out_lock:
                            for n in names:
                                outstanding[n] -= 1
                        for n in names:
                            for s in touched[n]:
                                windows[(n, s)].release()
                        inflight.release()
                        if self.prefetch > 0:
                            prefetch_sem.release()
                        if "put/lost_rows" in put_m:
                            # one running device scalar: no host read here
                            lost = put_m["put/lost_rows"]
                            self._lost_rows = lost if self._lost_rows is \
                                None else self._lost_rows + lost
                        merged = dict(metrics)
                        merged.update(prep_m)
                        merged.update(get_m)
                        merged.update(put_m)
                        merged.update(BK.shard_step_metrics(backends))
                        results.append((idx, merged))
                    st.items += 1
                except Exception as e:   # noqa: BLE001
                    fail("put", idx, e)
                    return

        threads = [
            threading.Thread(target=fn, name=f"pipeline-{name}", daemon=True)
            for name, fn in (("loader", loader),
                             ("prefetch", prefetch_stage),
                             ("prepare", prepare),
                             ("lookup", lookup_stage), ("dense", dense_stage),
                             ("put", put_stage))]
        self._running = True
        t_wall = time.perf_counter()
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600.0)
            hung = [t.name for t in threads if t.is_alive()]
            if hung and not errors:
                stop.set()
                raise PipelineStageError(
                    hung[0].removeprefix("pipeline-"), -1,
                    TimeoutError("stage did not finish within 600s"))
        finally:
            stop.set()
            # an aborted run may leave batches pinned mid-flight; the
            # backends outlive the run, so drop the pins before handing
            # the trainer back
            for b in backends.values():
                b.reset_pins()
            self._wall_s = time.perf_counter() - t_wall
            for k, v in BK.span_seconds(backends).items():
                spans[k] = spans.get(k, 0.0) + v - phases0.get(k, 0.0)
            self._steps_done = len(results)
            self._running = False
        if errors:
            raise errors[0]

        results.sort(key=lambda r: r[0])
        final = state.replace(
            dense=dense_cell["dense"], opt=dense_cell["opt"],
            dense_queue=dense_cell["queue"], step=dense_cell["step"],
            emb=store["emb"], emb_queue=store["queues"])
        return final, [m for _, m in results]

    # -- per-stage metrics ----------------------------------------------------

    def pipeline_metrics(self) -> dict[str, float]:
        """Timing/occupancy of the last ``run()``: per-stage busy seconds
        (the ``persia/stage/<stage>`` spans), occupancy (busy/wall), items,
        and input-queue depth stats; the host fault-in's seconds per phase
        (``pipeline/prepare/<phase>_s``, the ``persia/prepare/<phase>``
        spans summed over tables; with ``prefetch`` > 0 the fault-in runs
        in the prefetch stage); the queued put rows lost to a recycled
        cache slot (``pipeline/put/lost_rows``); and the run-level wall
        time and steps/s."""
        wall = max(self._wall_s, 1e-9)
        out: dict[str, float] = {
            "pipeline/wall_s": self._wall_s,
            "pipeline/steps": float(self._steps_done),
            "pipeline/steps_per_s": self._steps_done / wall,
            "pipeline/max_inflight": float(self.max_inflight),
            "pipeline/prefetch": float(self.prefetch),
        }
        for stage, st in self._stats.items():
            busy = self._span_s.get(f"stage/{stage}", 0.0)
            out[f"pipeline/{stage}/busy_s"] = busy
            out[f"pipeline/{stage}/occupancy"] = busy / wall
            out[f"pipeline/{stage}/items"] = float(st.items)
            if stage != "loader":        # stages fed by a bounded queue
                avg = (st.depth_sum / st.depth_samples
                       if st.depth_samples else 0.0)
                out[f"pipeline/{stage}/queue_depth"] = avg
                out[f"pipeline/{stage}/queue_depth_max"] = float(st.depth_max)
        for p in PREPARE_PHASES:
            out[f"pipeline/prepare/{p}_s"] = self._span_s.get(
                f"prepare/{p}", 0.0)
        out["pipeline/put/lost_rows"] = (
            0.0 if self._lost_rows is None else float(self._lost_rows))
        for n, v in self.max_outstanding.items():
            out[f"pipeline/outstanding_puts_max/{n}"] = float(v)
        return out
