"""The system under test, driven the way a user drives it.

A configuration names one of two paths:

* ``fused``: ``PersiaTrainer.step`` over a stream of host batches. The host
  runs the step's prepare (dedup plans) and dispatches the jitted step
  without waiting; at most ``inflight`` steps are in flight, as a training
  loop with bounded asynchronous dispatch keeps them.
* ``pipelined``: ``PipelinedTrainer.run`` over the same stream, its stages
  on their own threads.

Both are timed from the host. A watcher thread waits on each step's loss in
order and takes the clock when it is ready, so completions are seen without
the loop forcing a sync per step. One chip: a cell on a mesh needs the
batch laid out over it and the tables sharded, which no cell asks for yet.
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import adapters
from repro.core import backend as BK
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.core.pipeline import PipelinedTrainer
from repro.launch.shards import apply_backend_choice
from repro.optim.optimizers import OptConfig


@dataclasses.dataclass
class Window:
    steps: int
    samples: int
    seconds: float
    gaps_s: list
    losses: list
    consumed: list          # stream positions, in the order they were stepped


class System:
    """One trainer built from a configuration, with its state."""

    def __init__(self, config: dict, batch: int):
        self.config = config
        self.batch = batch
        m = config["model"]
        t = config["tables"]
        rows = int(config["rows_per_field"])
        cfg = ModelConfig(
            name=config["name"], arch_type="recsys",
            n_id_fields=m["n_id_fields"], ids_per_field=m["ids_per_field"],
            emb_dim=m["emb_dim"], emb_rows=rows * m["n_id_fields"],
            n_dense_features=m["n_dense_features"],
            mlp_dims=tuple(m["mlp_dims"]), n_tasks=m["n_tasks"],
            emb_staleness=t["staleness"], emb_optimizer=t["optimizer"])
        field_rows = (rows,) * m["n_id_fields"]
        coll = adapters.ctr_collection(cfg, lr=t["lr"],
                                            field_rows=field_rows)
        if t["backend"] != "dense":
            coll = apply_backend_choice(coll, t["backend"],
                                             int(config["cache_rows"]))
        adapter = adapters.recsys_adapter(cfg, field_rows=field_rows,
                                               collection=coll)
        tw = config["tower"]
        opt = OptConfig(kind=tw["optimizer"], lr=tw["lr"], b1=tw["b1"],
                             b2=tw["b2"], eps=tw["eps"],
                             grad_clip=tw["grad_clip"])
        self.observer = None
        trainer_cls = _observed(PersiaTrainer, self)
        self.trainer = trainer_cls(adapter, TrainMode.hybrid(
            t["staleness"]), opt)
        self.names = list(coll.names)
        self.path = config["trainer"]["path"]
        self.engine = None
        if self.path == "pipelined":
            self.engine = PipelinedTrainer(
                self.trainer, max_inflight=config["trainer"]["max_inflight"])
        self.state = None

    def init(self, seed: int, example: dict):
        """Draw the model from ``seed``. Dense tables are drawn on the
        device in one jitted call (a traced key takes the table draw onto
        the device); host-backed tables are drawn into the host store, as
        their backend does."""
        key = jax.random.PRNGKey(seed)
        if self.config["tables"]["backend"] == "dense":
            self.state = jax.jit(self.trainer.init)(key, example)
        else:
            self.state = self.trainer.init(key, example)
        jax.block_until_ready(self.state)

    # -- reading state (set-up only) ------------------------------------------

    def rows(self, ids: dict) -> dict:
        """Current rows of the given logical ids, per table, as fp32 host
        arrays, read through each backend's read path."""
        out = {}
        for n, x in ids.items():
            r, _ = self.trainer.backends[n].read_rows(self.state.emb[n], x)
            out[n] = np.asarray(r, np.float64)
        return out

    def stored(self, ids: dict) -> dict:
        """Of the given logical ids per table, those a host-backed table no
        longer holds in its device cache, with their rows as its host store
        holds them: {table: (ids, fp32 rows as float64)}. A row the steps
        faulted in and that is gone from the cache was evicted, so what the
        store holds is what the write-back stored. Dense tables: none."""
        out = {}
        for n, x in ids.items():
            store = getattr(BK.unwrap(self.trainer.backends[n]), "store",
                            None)
            if store is None:
                continue
            x = np.asarray(x, np.int64)
            x = x[x >= 0]
            resident = np.asarray(self.state.emb[n]["slot_ids"], np.int64)
            gone = x[~np.isin(x, resident)]
            vecs, _ = store.read_rows(gone)
            out[n] = (gone, np.asarray(vecs, np.float64))
        return out

    def tower(self) -> dict:
        return _leaves(self.state.dense)

    def first_grads(self) -> dict:
        """After one step: the tower gradient as Adam received it (its first
        moment over 1 - b1) and each table's put as it entered the queue."""
        b1 = self.config["tower"]["b1"]
        out = {k: v / (1.0 - b1)
               for k, v in _norms(self.state.opt["m"]).items()}
        for n in self.names:
            q = self.state.emb_queue[n]
            if q is not None:
                out[f"emb/{n}"] = float(jnp.linalg.norm(
                    q["grads"][0].astype(jnp.float32)))
        return out

    # -- stepping -------------------------------------------------------------

    def step_once(self, batch: dict) -> float:
        """One step through the window's own call, waited for."""
        if self.engine is None:
            self.state, m = self.trainer.step(self.state, batch)
            loss = m["loss"]
        else:
            self.state, ms = self.engine.run(self.state, [batch])
            loss = ms[0]["loss"]
        jax.block_until_ready(self.state)
        return float(loss)

    def counters(self) -> dict:
        out = {}
        stores = [BK.unwrap(b) for b in self.trainer.backends.values()]
        if any(hasattr(s, "faults") for s in stores):
            out["faults"] = float(sum(getattr(s, "faults", 0)
                                      for s in stores))
            out["writebacks"] = float(sum(getattr(s, "writebacks", 0)
                                          for s in stores))
        if self.engine is not None:
            out.update(self.engine.pipeline_metrics())
        return out

    def run(self, stream, start: int, seconds: float,
            annotate: bool = False) -> Window:
        """Step through ``stream[start], stream[start + 1], ...`` for
        ``seconds``; the clock stops when the last step's state is ready."""
        watcher = _Watcher()
        consumed = []
        ann = jax.profiler.TraceAnnotation if annotate else \
            (lambda name: contextlib.nullcontext())

        # the pipeline's loader pulls as far ahead as its queues allow; the
        # feed holds it to a few steps past the last completed one, so the
        # window ends with that many steps to drain, not a queue's worth
        ahead = int(self.config["trainer"].get("max_inflight", 0)) + 2

        def batches(deadline):
            i = start
            while time.perf_counter() < deadline:
                if self.engine is not None:
                    watcher.wait_done(i - start - ahead)
                consumed.append(i)
                yield stream[i]
                i += 1

        t0 = time.perf_counter()
        watcher.start()
        deadline = t0 + seconds
        if self.engine is None:
            depth = int(self.config["trainer"].get("inflight", 2))
            n = 0
            for b in batches(deadline):
                with ann("bench:step"):
                    self.state, m = self.trainer.step(self.state, b)
                watcher.put(m["loss"])
                n += 1
                with ann("bench:wait"):
                    watcher.wait_done(n - depth)
        else:
            self.observer = watcher.put
            try:
                with ann("bench:pipeline"):
                    self.state, _ = self.engine.run(self.state,
                                                    batches(deadline))
            finally:
                self.observer = None
        jax.block_until_ready(self.state)
        t1 = time.perf_counter()
        times, losses = watcher.finish()
        steps = len(consumed)
        edges = [t0] + times
        return Window(steps=steps, samples=steps * self.batch,
                      seconds=t1 - t0, gaps_s=list(np.diff(edges)),
                      losses=losses, consumed=consumed)

    def free(self):
        """Drop the program's device state before the reference runs."""
        self.state = None
        self.engine = None
        self.trainer = None
        import gc
        gc.collect()


def _observed(base, system):
    """A trainer whose decomposed dense step reports each step's loss to
    the system's observer (the pipelined window's watcher). The step itself
    is the program's, unchanged."""

    class ObservedTrainer(base):
        def decomposed_fns(self):
            lookup_fn, dense_step, emb_put = super().decomposed_fns()

            def observed(*args):
                out = dense_step(*args)
                if system.observer is not None:
                    system.observer(out[4]["loss"])
                return out
            return lookup_fn, observed, emb_put

    return ObservedTrainer


class _Watcher:
    """Waits on each step's loss in order; records when each was ready."""

    def __init__(self):
        self.q = queue.Queue()
        self.times = []
        self.losses = []
        self.cv = threading.Condition()
        self.thread = None
        self.error = None

    def start(self):
        self.thread = threading.Thread(target=self._loop, name="bench-watch",
                                       daemon=True)
        self.thread.start()

    def put(self, loss):
        self.q.put(loss)

    def _loop(self):
        try:
            while True:
                x = self.q.get()
                if x is None:
                    return
                x.block_until_ready()
                t = time.perf_counter()
                with self.cv:
                    self.times.append(t)
                    self.losses.append(x)
                    self.cv.notify_all()
        except Exception as e:  # noqa: BLE001 - re-raised by finish()
            self.error = e
            with self.cv:
                self.cv.notify_all()

    def wait_done(self, n: int):
        """Block until at least ``n`` steps have completed."""
        with self.cv:
            while len(self.times) < n and self.error is None:
                self.cv.wait(timeout=60.0)

    def finish(self):
        self.q.put(None)
        self.thread.join(timeout=600.0)
        if self.thread.is_alive():
            raise RuntimeError("the step watcher did not finish")
        if self.error is not None:
            raise self.error
        return self.times, [float(x) for x in self.losses]


def _leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"tower" + jax.tree_util.keystr(p): np.asarray(x, np.float64)
            for p, x in flat}


def _norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"tower" + jax.tree_util.keystr(p):
            float(jnp.linalg.norm(x.astype(jnp.float32)))
            for p, x in flat}
