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
the loop forcing a sync per step.

On several chips (``fused`` only) the trainer is built, initialised,
stepped and read under a one-axis mesh ``("data",)`` over them: each batch
is laid out over the chips on its batch axis, and the tables as their
specs' ``mode`` lays them out (``'full'``: rows sharded over every chip).

Each field gets its own table spec: its rows, its backend and its cache,
as ``spec.fields`` reads them from the configuration.
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
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.harness import spec
from repro.configs.base import ModelConfig
from repro.core import adapters
from repro.core import backend as BK
from repro.core.collection import EmbeddingCollection
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
    """One trainer built from a configuration, with its state. ``batch``
    is the global batch; ``devices``, where more than one, are the chips of
    the mesh the trainer runs on."""

    def __init__(self, config: dict, batch: int, devices=()):
        self.config = config
        self.batch = batch
        self.path = config["trainer"]["path"]
        self.mesh = None
        if len(devices) > 1:
            if self.path != "fused":
                raise ValueError(f"{config['name']}: the {self.path!r} path "
                                 "runs on one chip only")
            self.mesh = jax.make_mesh(
                (len(devices),), ("data",), devices=list(devices),
                axis_types=(jax.sharding.AxisType.Auto,))
        t = config["tables"]
        self.fields = spec.fields(config)
        model = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in config["model"].items()}
        cfg = ModelConfig(
            name=config["name"], arch_type="recsys",
            emb_rows=sum(f["rows"] for f in self.fields),
            emb_staleness=t["staleness"], emb_optimizer=t["optimizer"],
            **model)
        field_rows = tuple(f["rows"] for f in self.fields)
        tw = config["tower"]
        opt = OptConfig(kind=tw["optimizer"], lr=tw["lr"], b1=tw["b1"],
                        b2=tw["b2"], eps=tw["eps"],
                        grad_clip=tw["grad_clip"])
        with self.on_mesh():
            coll = adapters.ctr_collection(cfg, lr=t["lr"],
                                           field_rows=field_rows)
            coll = EmbeddingCollection(tuple(
                (n, _placed(n, s, f))
                for (n, s), f in zip(coll.items(), self.fields)))
            adapter = adapters.recsys_adapter(cfg, field_rows=field_rows,
                                              collection=coll)
            self.observer = None
            trainer_cls = _observed(PersiaTrainer, self)
            self.trainer = trainer_cls(adapter, TrainMode.hybrid(
                t["staleness"]), opt)
        self.names = list(coll.names)
        self._lookup = None
        self.engine = None
        if self.path == "pipelined":
            self.engine = PipelinedTrainer(
                self.trainer, max_inflight=config["trainer"]["max_inflight"])
        self.state = None

    def on_mesh(self):
        """The context the trainer runs in: its mesh, where it has one."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.sharding.set_mesh(self.mesh)

    def feed(self, batch: dict) -> dict:
        """A host batch as the step takes it: on a mesh, laid out over the
        chips on its batch axis; on one chip, as it is."""
        if self.mesh is None:
            return batch
        return jax.device_put(batch, NamedSharding(self.mesh, P("data")))

    def init(self, seed: int, example: dict):
        """Draw the model from ``seed``. On one chip where every table is
        dense, the draw is one jitted call on the device (a traced key takes
        the table draw there). Otherwise the program draws as its backends
        do: host-backed tables into their host store, and on a mesh dense
        tables on the host, each chip then receiving its own rows (the
        program lays a table out by ``device_put``, which inside a jitted
        call does not constrain: a jitted draw would leave every table
        whole on every chip)."""
        key = jax.random.PRNGKey(seed)
        with self.on_mesh():
            if self.mesh is None and all(f["backend"] == "dense"
                                         for f in self.fields):
                self.state = jax.jit(self.trainer.init)(key, example)
            else:
                self.state = self.trainer.init(key, example)
            jax.block_until_ready(self.state)

    # -- reading state (set-up only) ------------------------------------------

    def rows(self, ids: dict) -> dict:
        """Current rows of the given logical ids, per table, as fp32 host
        arrays: of a device-resident table through its lookup, all such
        tables in one jitted call (on a mesh an eager lookup of a sharded
        table retraces its exchange, a second a table); of a host-backed
        table through its read path, which reads rows its device cache
        does not hold from its host store."""
        backends = self.trainer.backends
        dev = [n for n in ids if not backends[n].requires_prepare]
        out = {}
        with self.on_mesh():
            if dev:
                if self._lookup is None:
                    self._lookup = jax.jit(lambda states, xs: {
                        n: backends[n].lookup(states[n], x)[0]
                        for n, x in xs.items()})
                got = self._lookup(
                    {n: self.state.emb[n] for n in dev},
                    {n: jnp.asarray(ids[n], jnp.int32) for n in dev})
                out.update({n: np.asarray(got[n], np.float64) for n in dev})
            for n in ids:
                if n not in out:
                    r, _ = backends[n].read_rows(self.state.emb[n], ids[n])
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
            with self.on_mesh():
                self.state, m = self.trainer.step(self.state,
                                                  self.feed(batch))
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
                with ann("bench:step"), self.on_mesh():
                    self.state, m = self.trainer.step(self.state,
                                                      self.feed(b))
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
        self._lookup = None
        import gc
        gc.collect()


def _placed(name: str, table, field: dict):
    """``table``'s spec with the field's backend and device cache, set as
    the program's launchers set a backend choice."""
    one = EmbeddingCollection.single(name, table)
    return apply_backend_choice(one, field["backend"],
                                field["cache_rows"]).items()[0][1]


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
