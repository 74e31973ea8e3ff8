#!/usr/bin/env python3
"""Bring-up smoke of the Persia CTR trainer on a TPU v5e chip.

    python3 chip_smoke.py              # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4    # four chips: the sharded-table phase

Runs the system's main path through the entry points a user calls
(``PersiaTrainer``, ``PipelinedTrainer``, ``ServingService``) at the paper's
Table-1 Criteo widths: 26 per-field tables of 20,769 rows x dim 128 (the
``criteo_ad`` stream, rows scaled 1e-3 as in ``data/ctr.py``), the
4096-2048-1024-512-256 tower, batch 4096, ``TrainMode.hybrid(3)``, weights
and data drawn from ``--seed``.

One chip:
  (a) fused ``trainer.step`` over dense device tables; ``field_00`` sits
      behind the compressed wire (jnp codec);
  (b) the same batches over ``host_lru`` tables whose device cache holds
      fewer rows than a field (so rows fault in from the host): serially,
      bit-exact with (a), then through ``PipelinedTrainer(max_inflight=2)``;
  (c) (a) again with the Pallas fused backward and the Pallas blockscale
      wire codec; the compiled step must contain ``tpu_custom_call``, and
      each kernel step must match (a)'s jnp oracle step from the same
      state to the fp32 regroup class;
  (d) ``ServingService`` answers requests over (a)'s live backend while
      (a)'s trainer keeps stepping, then agrees with ``trainer.predict``.
Four chips: ``mode="full"`` dense tables sharded over a 4-device mesh; the
mesh draws the one-device model from the same seed, and each mesh step
matches the one-device step from the same state.

Every phase prints its losses and step times labelled with the device.
Times are bring-up observations, not a benchmark. The last stdout line is
``{"ok": true, "device": {...}}``; without a TPU the script exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.recsys_configs import CRITEO  # noqa: E402
from repro.core import adapters  # noqa: E402
from repro.core import backend as BK  # noqa: E402
from repro.core.hybrid import PersiaTrainer, TrainMode  # noqa: E402
from repro.core.pipeline import PipelinedTrainer  # noqa: E402
from repro.data.ctr import CTR_BENCHMARKS  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.shards import apply_backend_choice  # noqa: E402
from repro.optim.optimizers import OptConfig  # noqa: E402
from repro.serving import ServingConfig, ServingService, StateCell  # noqa

STEPS = 8                # per phase; the first compiles, the rest are timed
# Adam's first step moves every tower weight by ~lr: at the launcher's 3e-3
# and fan-in 3,341 that saturates the logits (losses of 10-18 by step 2);
# 1e-4 keeps the few steps here where a converging run would be.
DENSE_LR, EMB_LR = 1e-4, 5e-2
# One step from the same state. The kernels and the mesh regroup fp32 sums
# (a hot row's payload sums thousands of occurrence grads), ~1e-7 typical.
KERNEL_RTOL = 1e-5
MESH_RTOL = 1e-5
SERVE_ATOL = 1e-5


@dataclasses.dataclass(frozen=True)
class Setup:
    """One deployment shape: model config, id stream, batch, cache."""
    cfg: object
    ds: object
    batch: int
    cache_rows: int
    seed: int = 0


def criteo_setup(seed: int) -> Setup:
    ds = CTR_BENCHMARKS["criteo_ad"]
    assert CRITEO.n_id_fields == ds.n_fields
    # ~1,400 unique ids per field per batch; 8,192 cache slots hold the
    # in-flight working sets but not a field's 20,769 rows
    return Setup(CRITEO, ds, batch=4096, cache_rows=8192, seed=seed)


def device_label(devices=None) -> str:
    d = (devices or jax.devices())[0]
    return f"{d.platform}:{d.device_kind}"


def make_trainer(setup: Setup, backend: str = "dense",
                 kernels: bool = False, wire: bool = True) -> PersiaTrainer:
    """The CTR trainer of ``launch/train.py`` at ``setup``'s widths:
    ``backend`` for every table, the compressed wire on ``field_00`` when
    ``wire``, and the Pallas fused backward and wire codec when
    ``kernels``."""
    cfg, ds = setup.cfg, setup.ds
    coll = adapters.ctr_collection(cfg, lr=EMB_LR, field_rows=ds.field_rows())
    coll = apply_backend_choice(coll, backend, setup.cache_rows)
    if wire:
        coll = coll.map_specs(lambda n, s: dataclasses.replace(
            s, backend=s.backend + "+compressed", wire_kernel=kernels)
            if n == coll.names[0] else s)
    if kernels:
        coll = coll.with_backward_kernel(True)
    adapter = adapters.recsys_adapter(cfg, field_rows=ds.field_rows(),
                                      collection=coll)
    return PersiaTrainer(adapter, TrainMode.hybrid(3),
                         OptConfig(kind="adam", lr=DENSE_LR))


def make_batches(setup: Setup, n: int, seed: int | None = None):
    it = setup.ds.sampler(setup.batch,
                          seed=setup.seed if seed is None else seed)
    return [next(it) for _ in range(n)]


def to_device(batch, sharding=None):
    if sharding is None:
        return {k: jnp.asarray(v) for k, v in batch.items()}
    return {k: jax.device_put(v, sharding) for k, v in batch.items()}


def check_finite(name: str, losses) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss in {losses}")


def report(name: str, losses, times_s, extra: str = "") -> None:
    timed = times_s[1:] or times_s
    print(f"[{device_label()}] phase {name}: {len(losses)} steps, losses "
          f"{[float(x) for x in losses]}")
    print(f"[{device_label()}] phase {name}: first step {times_s[0]:.3f} s "
          f"(compile included), step time median "
          f"{1e3 * float(np.median(timed)):.3f} ms over {len(timed)} steps"
          + (f"; {extra}" if extra else ""))


def fused_run(trainer: PersiaTrainer, state, batches):
    """``trainer.step`` over ``batches``: (state, losses, step seconds)."""
    losses, times = [], []
    for b in batches:
        t0 = time.perf_counter()
        state, m = trainer.step(state, to_device(b))
        jax.block_until_ready((state, m))
        times.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
    return state, losses, times


def rel_diff(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def norm_rel(a, b) -> float:
    """Largest elementwise difference over the largest magnitude of ``b``
    (computed where the arrays live)."""
    return float(jnp.max(jnp.abs(a - b))
                 / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))


def emb_diffs(a, b) -> dict:
    """Per table: the largest ``norm_rel`` over its PS state (rows and
    adagrad accumulator) and its queued put payloads. Queued ids must be
    equal."""
    out = {}
    for n in a.emb:
        pairs = [(a.emb[n][k], b.emb[n][k]) for k in a.emb[n]]
        qa, qb = a.emb_queue[n], b.emb_queue[n]
        if qa is not None:
            if not np.array_equal(np.asarray(qa["ids"]),
                                  np.asarray(qb["ids"])):
                raise AssertionError(f"{n}: queued put ids differ")
            pairs.append((qa["grads"], qb["grads"]))
        out[n] = max(norm_rel(x, y) for x, y in pairs)
    return out


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_dense(setup: Setup, batches):
    trainer = make_trainer(setup, "dense")
    state = trainer.init(jax.random.PRNGKey(setup.seed),
                         to_device(batches[0]))
    state, losses, times = fused_run(trainer, state, batches)
    check_finite("a", losses)
    report("a (dense, fused)", losses, times)
    return trainer, state, losses


def phase_host_lru(setup: Setup, batches, more, dense_losses):
    trainer = make_trainer(setup, "host_lru")
    state = trainer.init(jax.random.PRNGKey(setup.seed),
                         to_device(batches[0]))
    state, losses, times = fused_run(trainer, state, batches)
    check_finite("b", losses)
    diff = float(np.max(np.abs(np.subtract(losses, dense_losses))))
    report("b (host_lru, fused)", losses, times,
           f"max |loss - phase a loss| = {diff!r}")
    if diff != 0.0:      # the same model behind another store: bit-exact
        raise AssertionError(f"host_lru losses differ from dense by {diff}")
    engine = PipelinedTrainer(trainer, max_inflight=2)
    t0 = time.perf_counter()
    state, ms = engine.run(state, (to_device(b) for b in more))
    jax.block_until_ready(state)
    wall = time.perf_counter() - t0
    piped = [float(m["loss"]) for m in ms]
    check_finite("b pipelined", piped)
    if engine.applied_order != list(range(len(more))):
        raise AssertionError(f"pipelined puts applied out of order: "
                             f"{engine.applied_order}")
    stores = [BK.unwrap(b) for b in trainer.backends.values()]
    faults = sum(s.faults for s in stores)
    writebacks = sum(s.writebacks for s in stores)
    if faults == 0:
        raise AssertionError("host_lru phase faulted no rows")
    print(f"[{device_label()}] phase b (host_lru, PipelinedTrainer "
          f"max_inflight=2): {len(piped)} steps, losses {piped}")
    print(f"[{device_label()}] phase b (host_lru, PipelinedTrainer "
          f"max_inflight=2): wall {wall:.3f} s for {len(piped)} steps "
          f"(compile included), {faults} rows faulted in and {writebacks} "
          f"written back over {len(stores)} tables")
    return diff


def compiled_step_text(trainer: PersiaTrainer, state, batch) -> str:
    """HLO of the fused step as compiled for this batch's dedup plans."""
    b = to_device(batch)
    _, dev_ids, _ = BK.prepare_all(trainer.backends, state.emb,
                                   trainer.adapter.emb_ids(b))
    return jax.jit(trainer.train_step).lower(state, b, dev_ids) \
        .compile().as_text()


def phase_kernels(setup: Setup, batches, oracle: PersiaTrainer,
                  dense_losses):
    """(c): the kernel trainer runs the batches of (a). Before each of its
    steps, (a)'s oracle trainer takes the same step from a copy of the same
    state, so every comparison sees one step of kernel error rather than a
    trajectory that Adam's per-coordinate normalisation has amplified."""
    trainer = make_trainer(setup, "dense", kernels=True)
    state = trainer.init(jax.random.PRNGKey(setup.seed),
                         to_device(batches[0]))
    n_custom = compiled_step_text(trainer, state, batches[0]).count(
        "tpu_custom_call")
    if jax.default_backend() == "tpu" and n_custom == 0:
        raise AssertionError("the kernel step compiled without any "
                             "tpu_custom_call")
    wire = trainer.collection.names[0]
    losses, times = [], []
    loss_diff = fb_diff = wire_diff = 0.0
    for b in batches:
        ref, ref_m = oracle.step(jax.tree.map(jnp.copy, state),
                                 to_device(b))
        t0 = time.perf_counter()
        state, m = trainer.step(state, to_device(b))
        jax.block_until_ready((state, m))
        times.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
        loss_diff = max(loss_diff, rel_diff(m["loss"], ref_m["loss"]))
        d = emb_diffs(state, ref)
        wire_diff = max(wire_diff, d.pop(wire))
        fb_diff = max(fb_diff, max(d.values()))
    check_finite("c", losses)
    report("c (Pallas fused backward + blockscale wire)", losses, times,
           f"{n_custom} tpu_custom_call in the compiled step")
    print(f"[{device_label()}] phase c vs the oracle step from the same "
          f"state: max relative loss diff {loss_diff!r}, fused backward "
          f"tables max diff {fb_diff!r}, blockscale wire table ({wire}) "
          f"max diff {wire_diff!r}; free-running losses vs phase a: max "
          f"relative diff {rel_diff(losses, dense_losses)!r}")
    if max(loss_diff, fb_diff, wire_diff) > KERNEL_RTOL:
        raise AssertionError(
            f"kernel step differs from the oracle step: loss {loss_diff}, "
            f"tables {fb_diff}, wire table {wire_diff} > {KERNEL_RTOL}")
    return {"loss": loss_diff, "fused_backward": fb_diff,
            "wire": wire_diff}, n_custom


def phase_serve(setup: Setup, trainer: PersiaTrainer, state, batches,
                n_requests: int = 64):
    req_batch = make_batches(setup, 1, seed=setup.seed + 999)[0]
    reqs = [{"ids": req_batch["ids"][i], "dense": req_batch["dense"][i]}
            for i in range(n_requests)]
    cell = StateCell(state, int(state.step))
    svc = ServingService(trainer, cell,
                         ServingConfig(max_batch=n_requests, max_wait_ms=2.0))
    losses, errors = [], []

    def train():
        s = state
        try:
            for b in batches:
                with cell.lock:
                    s, m = trainer.step(s, to_device(b))
                    cell.publish(s)
                losses.append(float(m["loss"]))
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    svc.start()
    try:
        th = threading.Thread(target=train, name="trainer")
        th.start()
        live = [svc.predict_many(reqs)]
        while th.is_alive():
            live.append(svc.predict_many(reqs))
        th.join()
        if errors:
            raise errors[0]
        final, _ = cell.snapshot()
        served = svc.predict_many(reqs)
    finally:
        svc.stop()
    check_finite("d", losses)
    rows = {k: jnp.asarray(v[:n_requests]) for k, v in req_batch.items()}
    want = np.asarray(trainer.predict(final, rows), np.float32)
    if served.shape != want.shape or not np.all(np.isfinite(served)):
        raise AssertionError(f"served predictions {served.shape} not finite "
                             f"or not shaped like {want.shape}")
    if not all(np.all(np.isfinite(p)) for p in live):
        raise AssertionError("non-finite prediction served while training")
    err = float(np.max(np.abs(served - want)))
    if err > SERVE_ATOL:
        raise AssertionError(f"served predictions differ from "
                             f"trainer.predict by {err}")
    sv = svc.metrics()
    stale = max(v for k, v in sv.items() if k.endswith("/stale_steps"))
    print(f"[{device_label()}] phase d (ServingService while training): "
          f"{len(live)} bursts of {n_requests} served during "
          f"{len(losses)} steps, losses {losses}, p50 "
          f"{sv['serving/p50_ms']:.3f} ms p99 {sv['serving/p99_ms']:.3f} ms, "
          f"max stale_steps {stale:g}, max |served - predict| {err!r}")
    return err


def run_one_chip(setup: Setup) -> dict:
    batches = make_batches(setup, 3 * STEPS)
    first, second, third = (batches[:STEPS], batches[STEPS:2 * STEPS],
                            batches[2 * STEPS:])
    trainer, state, dense_losses = phase_dense(setup, first)
    lru_diff = phase_host_lru(setup, first, second, dense_losses)
    kernel_diff, n_custom = phase_kernels(setup, first, trainer,
                                          dense_losses)
    serve_err = phase_serve(setup, trainer, state, third)
    return {"dense_vs_host_lru_loss_diff": lru_diff,
            "kernel_vs_oracle_rel_diff": kernel_diff,
            "tpu_custom_calls": n_custom, "serve_err": serve_err}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def table_shares(state) -> dict:
    """Per table: each device's share of the table's bytes, read from the
    addressable shards."""
    out = {}
    for name, st in state.emb.items():
        t = st["table"]
        per = {}
        for s in t.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
        total = sum(per.values())
        out[name] = {d: per[d] / total for d in sorted(per)}
    return out


def to_one_device(trainer: PersiaTrainer, state, device):
    """A state laid out for any mesh, as the same model laid out on one
    device for ``trainer`` (built and called outside the mesh): each table
    goes through its backend's checkpoint restore, which moves every
    logical row to where a one-device lookup reads it."""
    emb = {n: trainer.backends[n].restore_from_checkpoint(
        jax.tree.map(np.asarray, st)) for n, st in state.emb.items()}
    host = jax.tree.map(np.asarray, state.replace(emb=emb))
    return jax.device_put(host, device)


def run_mesh(setup: Setup, n: int = 4) -> dict:
    """``mode='full'`` tables sharded over an ``n``-device mesh. Before
    every mesh step the one-device trainer takes the same step from the
    mesh's state moved onto one device; losses and tables are compared
    step by step."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    devices = jax.devices()[:n]
    batches = make_batches(setup, STEPS)
    key = jax.random.PRNGKey(setup.seed)
    ref_tr = make_trainer(setup, wire=False)
    ref_init = ref_tr.init(key, to_device(batches[0]))

    mesh = jax.make_mesh((n,), ("data",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    with jax.sharding.set_mesh(mesh):
        tr = make_trainer(setup, wire=False)
        state = tr.init(key, to_device(batches[0]))
    one = to_one_device(ref_tr, state, devices[0])
    # tables are drawn on the host CPU, so the mesh must draw the same rows
    init_emb = max(emb_diffs(one, ref_init).values())
    init_tower = max(norm_rel(a, b) for a, b in zip(
        jax.tree.leaves(one.dense), jax.tree.leaves(ref_init.dense)))
    if init_emb != 0.0 or init_tower > MESH_RTOL:
        raise AssertionError(f"the mesh drew another model than one device "
                             f"does: tables {init_emb}, tower {init_tower}")
    del ref_init
    losses, ref_losses, times = [], [], []
    loss_diff = emb_diff = 0.0
    for b in batches:
        ref, ref_m = ref_tr.step(one, to_device(b))
        with jax.sharding.set_mesh(mesh):
            t0 = time.perf_counter()
            state, m = tr.step(state, to_device(
                b, NamedSharding(mesh, P("data"))))
            jax.block_until_ready((state, m))
            times.append(time.perf_counter() - t0)
        one = to_one_device(ref_tr, state, devices[0])
        losses.append(float(m["loss"]))
        ref_losses.append(float(ref_m["loss"]))
        loss_diff = max(loss_diff, rel_diff(m["loss"], ref_m["loss"]))
        emb_diff = max(emb_diff, max(emb_diffs(one, ref).values()))
        del ref
    shares = table_shares(state)
    check_finite("mesh", losses)
    report(f"mesh ({n} devices, mode='full' tables)", losses, times)
    print(f"[{device_label()}] mesh vs the one-device step from the same "
          f"state: losses {ref_losses}, max relative loss diff "
          f"{loss_diff!r}, tables max diff {emb_diff!r}; initial model vs "
          f"one device: tables {init_emb!r}, tower {init_tower!r}")
    for name, per in shares.items():
        print(f"[{device_label()}] table {name} bytes by device after "
              f"training: "
              + ", ".join(f"{d}: {f:.4f}" for d, f in per.items()))
    spread = all(len(per) == n and min(per.values()) > 0.2
                 for per in shares.values())
    if not spread:
        raise AssertionError(f"tables not spread over {n} devices: {shares}")
    if max(loss_diff, emb_diff) > MESH_RTOL:
        raise AssertionError(f"mesh step differs from one device: loss "
                             f"{loss_diff}, tables {emb_diff} > {MESH_RTOL}")
    return {"mesh_vs_one_device": {"loss": loss_diff, "tables": emb_diff}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(d); 4: only the sharded-table "
                         "phase and its one-device reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); it does not run elsewhere",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    enable_compile_cache()
    setup = criteo_setup(args.seed)
    t0 = time.perf_counter()
    if args.chips == 1:
        out = run_one_chip(setup)
    else:
        out = run_mesh(setup, args.chips)
    print(f"[{device_label()}] done in {time.perf_counter() - t0:.1f} s: "
          f"{out}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
