"""A plain reference of the hybrid CTR training step, in ``jax.numpy``.

It imports nothing of the program and takes nothing the program made. From
the seed it draws the same model the configuration describes (the tower as
its module under ``bench/towers/`` draws it; table rows N(0, scale^2),
logical id ``i`` of a field's table of ``R`` rows holding draw row
``(i * 1000003 + 12345) mod R``, the uniform shuffle of paper section
4.2.3) and follows the first training steps on the same batches:

* lookup: each field's bag of rows, summed over its valid ids;
* tower: the pooled rows and the dense features, concatenated in field
  order, through the tower module's ``forward`` to one logit per task;
  binary cross-entropy with logits, averaged over samples and tasks;
* tower update: gradients clipped to a global norm, then Adam;
* table update (Persia Alg. 1 with staleness tau): each step's per-row sum
  of occurrence gradients joins a FIFO; the put pushed tau steps earlier
  leaves it and is applied by row-wise adagrad (accumulator += mean of the
  squared row gradient; row -= lr * g / sqrt(accumulator + eps)).

Only the rows the compared batches touch are kept, each field's padded to
a width of its own (``padded``), so a table of any size costs one draw on
the device and a gather, and a field of wide bags costs no more than its
own rows. Float32 at the ``highest`` matmul precision; ``dtype=bfloat16``
gives the control (everything held and computed in bf16), ``half_batch``
the planted fault that averages the loss over the first half of the batch
only.
"""
from __future__ import annotations

import collections
import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import spec

SHUFFLE_MULT = 1_000_003
SHUFFLE_ADD = 12_345


def shuffle(ids: np.ndarray, rows: int) -> np.ndarray:
    return (np.asarray(ids, np.int64) * SHUFFLE_MULT + SHUFFLE_ADD) % rows


def table_name(i: int) -> str:
    return f"field_{i:02d}"


def touched(batches: list, n_fields: int) -> list:
    """Sorted distinct valid ids per field over ``batches``."""
    out = []
    for f in range(n_fields):
        x = np.concatenate([np.asarray(spec.field_ids(b["ids"], f))
                            .reshape(-1) for b in batches])
        out.append(np.unique(x[x >= 0]).astype(np.int64))
    return out


def padded(ids_per_field: list) -> list:
    """Each field's touched ids padded with -1 to a power of two of its own,
    at least 1,024, so that a field's reads and draws change shape only
    when its own touched count crosses a power of two, and a field of
    100-id bags does not set the width of the 25 others."""
    out = []
    for x in ids_per_field:
        width = 1 << max(int(x.size - 1).bit_length(), 10)
        out.append(np.concatenate([x, np.full(width - x.size, -1,
                                              np.int64)]))
    return out


class Reference:
    """``tower`` is the module of the configuration's tower (``Cell.tower``):
    its ``init(key, model, d_in)`` and ``forward(params, x, model)``."""

    def __init__(self, config: dict, seed: int, tower,
                 dtype=jnp.float32, half_batch: bool = False):
        self.config = config
        self.seed = seed
        self.rows = [f["rows"] for f in spec.fields(config)]
        self.tower = tower
        self.dtype = dtype
        self.half_batch = half_batch

    # -- the model ------------------------------------------------------------

    def _init(self, ids_per_field: list):
        m, t = self.config["model"], self.config["tables"]
        kd, ke = jax.random.split(jax.random.PRNGKey(self.seed))
        tower = self.tower.init(kd, m, spec.tower_input(self.config))
        keys = jax.random.split(ke, m["n_id_fields"])

        @partial(jax.jit, static_argnums=2)
        def draw(k, idx, rows):
            return (jax.random.normal(k, (rows, m["emb_dim"]), jnp.float32)
                    * t["init_scale"])[idx]

        tables = [draw(keys[f], jnp.asarray(shuffle(ids, self.rows[f]),
                                            jnp.int32), self.rows[f])
                  for f, ids in enumerate(padded(ids_per_field))]
        return tower, tables

    def _loss(self, tower, tables, cids, dense, labels):
        pooled = []
        for f, e in enumerate(tables):
            c = spec.field_ids(cids, f)                      # (B, L_f)
            rows = e[jnp.where(c >= 0, c, 0)]
            pooled.append(jnp.sum(rows * (c >= 0)[..., None].astype(e.dtype),
                                  axis=1))
        x = jnp.concatenate(pooled + [dense.astype(self.dtype)], axis=-1)
        z = self.tower.forward(tower, x, self.config["model"])
        y = labels.astype(self.dtype)
        nll = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        if self.half_batch:
            nll = nll[: nll.shape[0] // 2]
        return jnp.mean(nll)

    def _step_fn(self):
        tw, tb = self.config["tower"], self.config["tables"]
        dt = self.dtype

        def step(tower, opt, tables, cids, dense, labels):
            loss, (g_t, g_e) = jax.value_and_grad(
                self._loss, argnums=(0, 1))(tower, tables, cids, dense,
                                            labels)
            leaves = jax.tree.leaves(g_t)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
            scale = jnp.minimum(jnp.asarray(1.0, dt),
                                tw["grad_clip"] / jnp.maximum(gn, 1e-9))
            g_t = jax.tree.map(lambda g: (g * scale).astype(dt), g_t)
            t = opt["t"] + 1
            bc1 = 1.0 - tw["b1"] ** t.astype(jnp.float32)
            bc2 = 1.0 - tw["b2"] ** t.astype(jnp.float32)
            m = jax.tree.map(lambda m_, g: tw["b1"] * m_ + (1 - tw["b1"]) * g,
                             opt["m"], g_t)
            v = jax.tree.map(lambda v_, g: tw["b2"] * v_
                             + (1 - tw["b2"]) * g * g, opt["v"], g_t)
            tower = jax.tree.map(
                lambda p, m_, v_: (p - tw["lr"] * (m_ / bc1.astype(dt))
                                   * jax.lax.rsqrt(v_ / bc2.astype(dt)
                                                   + tw["eps"] ** 2)
                                   ).astype(dt), tower, m, v)
            return loss, tower, {"m": m, "v": v, "t": t}, g_t, g_e

        return jax.jit(step)

    def _adagrad(self):
        tb = self.config["tables"]

        def apply(e, acc, g):
            acc = acc + jnp.mean(g * g, axis=-1)
            return e - tb["lr"] * g * jax.lax.rsqrt(acc + tb["eps"])[:, None], acc

        return jax.jit(apply)

    # -- the run --------------------------------------------------------------

    def run(self, batches: list, subsets: dict | None = None) -> dict:
        """Follow ``len(batches)`` steps; the readings the check compares.
        ``subsets`` ({leaf: (field, ids)}) adds the change of those ids'
        rows of a field as leaves of their own."""
        m = self.config["model"]
        F = m["n_id_fields"]
        tau = self.config["tables"]["staleness"]
        ids = touched(batches, F)
        ctx = (jax.default_matmul_precision("highest")
               if self.dtype == jnp.float32 else contextlib.nullcontext())
        with ctx:
            tower, tables = self._init(ids)
            tower = jax.tree.map(lambda x: x.astype(self.dtype), tower)
            tables = [e.astype(self.dtype) for e in tables]
            tower0 = jax.tree.map(lambda x: np.asarray(x, np.float64), tower)
            tables0 = [np.asarray(e, np.float64) for e in tables]
            accs = [jnp.zeros((e.shape[0],), self.dtype) for e in tables]
            opt = {"m": jax.tree.map(jnp.zeros_like, tower),
                   "v": jax.tree.map(jnp.zeros_like, tower),
                   "t": jnp.zeros((), jnp.int32)}
            step, adagrad = self._step_fn(), self._adagrad()
            fifo = collections.deque()
            losses, grad1 = [], {}
            for k, b in enumerate(batches):
                cids = _compact_all(b["ids"], ids)
                loss, tower, opt, g_t, g_e = step(
                    tower, opt, tables, cids,
                    jnp.asarray(b["dense"]), jnp.asarray(b["labels"]))
                losses.append(float(loss))
                if k == 0:
                    grad1 = _tower_norms(g_t)
                    for f, g in enumerate(g_e if tau > 0 else ()):
                        grad1[f"emb/{table_name(f)}"] = float(
                            jnp.linalg.norm(g.astype(jnp.float32)))
                fifo.append(g_e)
                if len(fifo) > tau:
                    old = fifo.popleft()
                    for f in range(F):
                        tables[f], accs[f] = adagrad(tables[f], accs[f],
                                                     old[f])
            change = {name: float(np.linalg.norm(
                np.asarray(leaf, np.float64) - tower0_leaf))
                for (name, leaf), tower0_leaf in zip(
                    _tower_leaves(tower).items(),
                    _tower_leaves(tower0).values())}
            for f, e in enumerate(tables):
                n = ids[f].size        # the padding rows are never stepped
                change[f"emb/{table_name(f)}"] = float(np.linalg.norm(
                    np.asarray(e, np.float64)[:n] - tables0[f][:n]))
            for leaf, (f, x) in (subsets or {}).items():
                at = np.searchsorted(ids[f], np.asarray(x, np.int64))
                change[leaf] = float(np.linalg.norm(
                    np.asarray(tables[f], np.float64)[at] - tables0[f][at]))
        return {"losses": losses, "grad1": grad1, "change": change,
                "touched": ids}


def _compact(ids: np.ndarray, uniq: np.ndarray) -> np.ndarray:
    """Logical ids -> positions in the sorted touched set (-1 kept)."""
    pos = np.searchsorted(uniq, np.where(ids >= 0, ids, 0))
    return np.where(ids >= 0, pos, -1).astype(np.int32)


def _compact_all(ids, uniq: list):
    """A batch's ``ids`` compacted field by field, in the batch's own
    layout: one ``(B, F, L)`` array, or a list of ``(B, L_f)`` arrays."""
    per = [_compact(np.asarray(spec.field_ids(ids, f)), u)
           for f, u in enumerate(uniq)]
    if isinstance(ids, (list, tuple)):
        return [jnp.asarray(c) for c in per]
    return jnp.asarray(np.stack(per, axis=1))


def _tower_leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"tower" + jax.tree_util.keystr(p): x for p, x in flat}


def _tower_norms(tree) -> dict:
    return {k: float(jnp.linalg.norm(jnp.asarray(v, jnp.float32)))
            for k, v in _tower_leaves(tree).items()}
