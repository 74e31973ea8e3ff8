"""Training driver: runs the Persia hybrid trainer end-to-end (the
production meshes are exercised by dryrun.py).

Usage:
  PYTHONPATH=src python -m repro.launch.train --task ctr --dataset taobao_ad \
      --mode hybrid --steps 300 --batch 512
  PYTHONPATH=src python -m repro.launch.train --task lm --steps 200 --batch 8
  PYTHONPATH=src python -m repro.launch.train --task ctr --pipeline decomposed \
      --ckpt-dir /tmp/ck --resume

Both tasks run through the PersiaTrainer facade: the CTR path trains one
embedding table per ID feature field (the multi-table EmbeddingCollection);
checkpoints carry the FULL train state — dense params, optimizer moments,
every PS table with its adagrad accumulator, and the staleness queues — so
``--resume`` continues bit-identically.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import BlockCfg, ModelConfig
from repro.core import adapters
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.checkpoint import CheckpointManager
from repro.data.ctr import CTR_BENCHMARKS
from repro.data.lm import lm_batches
from repro.optim.optimizers import OptConfig


def scaled_recsys_cfg(dataset: str, scale: float = 1.0) -> ModelConfig:
    ds = CTR_BENCHMARKS[dataset]
    return ModelConfig(
        name=f"{dataset}-dlrm", arch_type="recsys",
        n_id_fields=ds.n_fields, ids_per_field=ds.ids_per_field,
        emb_dim=32, emb_rows=ds.n_rows, n_dense_features=ds.n_dense,
        mlp_dims=(256, 128, 64), n_tasks=ds.n_tasks, emb_staleness=3)


def small_lm_cfg() -> ModelConfig:
    """~100M dense params (the end-to-end example scale)."""
    return ModelConfig(
        name="lm-100m", d_model=512, n_heads=8, n_kv_heads=4, head_dim=64,
        d_ff=2048, vocab_size=8192,
        pattern=(BlockCfg("gqa", "dense"),), pattern_repeats=20,
        emb_staleness=2)


def mode_from_name(name: str, tau: int) -> TrainMode:
    if name == "sync":
        return TrainMode.sync()
    if name == "hybrid":
        return TrainMode.hybrid(tau)
    if name == "async":
        return TrainMode.async_(tau, tau)
    raise ValueError(name)


def _step_fn(trainer: PersiaTrainer, pipeline: str):
    if pipeline == "decomposed":
        return trainer.decomposed_step
    return trainer.step


def _make_engine(trainer: PersiaTrainer, args):
    """--pipeline pipelined: the async five-stage engine (core/pipeline.py)
    carrying up to --max-inflight microbatches."""
    from repro.core.pipeline import PipelinedTrainer
    return PipelinedTrainer(trainer, max_inflight=args.max_inflight)


def _pipelined_span(engine, state, it, n):
    """Run n steps through the engine, pulling batches lazily from ``it``;
    returns (state, last-step metrics)."""
    stream = ({k: jnp.asarray(v) for k, v in next(it).items()}
              for _ in range(n))
    state, ms = engine.run(state, stream)
    return state, (ms[-1] if ms else {})


# the --emb-shards grammar is shared across launchers (train/serve/cluster);
# re-exported here because this was its original home
from repro.launch.shards import (  # noqa: E402,F401
    apply_backend_choice, default_cache_rows, parse_emb_shards)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def _ctr_collection_for(cfg, ds, args):
    """Per-field tables with the CLI-selected storage backend (dense PS,
    host-LRU out-of-core, or either behind the compressed wire) and
    per-table PS shard counts (--emb-shards routes through the sharded
    router of core/backend.py)."""
    coll = adapters.ctr_collection(cfg, lr=args.emb_lr,
                                   field_rows=ds.field_rows())
    coll = apply_backend_choice(
        coll, args.emb_backend,
        default_cache_rows(ds.rows_per_field, args.cache_rows))
    shards = parse_emb_shards(args.emb_shards)
    if shards != 1:
        coll = coll.with_shards(shards)
    return _apply_emb_tuning(coll, args)


def _apply_emb_tuning(coll, args):
    """--store-dtype / --backward-kernel spec overrides (both paper-hot-path
    knobs from kernels/fused_backward.py and the core/lru.py codec)."""
    if args.store_dtype != "fp32":
        coll = coll.with_store_dtype(args.store_dtype)
    if args.backward_kernel:
        coll = coll.with_backward_kernel(True)
    return coll


def train_ctr(args):
    ds = CTR_BENCHMARKS[args.dataset]
    cfg = scaled_recsys_cfg(args.dataset)
    adapter = adapters.recsys_adapter(
        cfg, lr=args.emb_lr, field_rows=ds.field_rows(),
        collection=_ctr_collection_for(cfg, ds, args))
    mode = mode_from_name(args.mode, args.tau)
    trainer = PersiaTrainer(adapter, mode,
                            OptConfig(kind="adam", lr=args.lr),
                            batch_dedup=False if args.no_batch_dedup
                            else None)
    it = ds.sampler(args.batch)
    eval_it = ds.sampler(args.batch, seed=999)
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
    start = 0
    mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every) \
        if args.ckpt_dir else None
    if args.resume and not mgr:
        raise SystemExit("--resume requires --ckpt-dir")
    have_ckpt = mgr and os.path.isdir(args.ckpt_dir) and \
        any(d.startswith("step_") for d in os.listdir(args.ckpt_dir))
    if args.resume and not have_ckpt:
        print(f"--resume: no checkpoints under {args.ckpt_dir!r}, "
              "starting fresh")
    if args.resume and have_ckpt:
        state = trainer.restore(args.ckpt_dir)
        start = int(state.step)
        # fast-forward the deterministic streams to where the run stopped,
        # so resumed training sees the batches an uninterrupted run would
        for _ in range(start):
            next(it)
        for _ in range(start // args.eval_every):
            next(eval_it)
        print(f"resumed full state from step {start}")
    else:
        state = trainer.init(jax.random.PRNGKey(args.seed), batch)
    history = []
    t0 = time.time()
    if args.pipeline == "pipelined":
        # the async engine consumes whole eval_every-sized spans so the
        # five stages overlap across microbatches; eval/ckpt run at the
        # span boundaries on the settled state
        engine = _make_engine(trainer, args)
        step = start
        while step < args.steps:
            # spans stop at every eval AND checkpoint boundary, so
            # --ckpt-every keeps its granularity under the pipeline
            n = min(args.eval_every - step % args.eval_every,
                    args.steps - step)
            if mgr:
                n = min(n, args.ckpt_every - step % args.ckpt_every)
            state, metrics = _pipelined_span(engine, state, it, n)
            step += n
            if step % args.eval_every == 0:
                eb = {k: jnp.asarray(v) for k, v in next(eval_it).items()}
                preds = trainer.predict(state, eb)
                a = adapters.auc(np.asarray(eb["labels"]), np.asarray(preds))
                dt = time.time() - t0
                thr = (step - start) * args.batch / dt
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"AUC {a:.4f} thr {thr:,.0f} samples/s")
                history.append({"step": step, "time_s": dt,
                                "loss": float(metrics["loss"]), "auc": a,
                                "throughput": thr})
            if mgr:
                mgr.maybe_save_state(step, trainer, state)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"mode": args.mode, "dataset": args.dataset,
                           "pipeline": args.pipeline, "history": history,
                           "pipeline_metrics": engine.pipeline_metrics()},
                          f, indent=1)
        return history
    step_fn = _step_fn(trainer, args.pipeline)
    for step in range(start, args.steps):
        b = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, metrics = step_fn(state, b)
        if (step + 1) % args.eval_every == 0:
            eb = {k: jnp.asarray(v) for k, v in next(eval_it).items()}
            preds = trainer.predict(state, eb)
            a = adapters.auc(np.asarray(eb["labels"]), np.asarray(preds))
            dt = time.time() - t0
            thr = (step + 1 - start) * args.batch / dt
            print(f"step {step+1:5d} loss {float(metrics['loss']):.4f} "
                  f"AUC {a:.4f} thr {thr:,.0f} samples/s")
            history.append({"step": step + 1, "time_s": dt,
                            "loss": float(metrics["loss"]), "auc": a,
                            "throughput": thr})
        if mgr:
            mgr.maybe_save_state(step + 1, trainer, state)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"mode": args.mode, "dataset": args.dataset,
                       "pipeline": args.pipeline, "history": history}, f,
                      indent=1)
    return history


def train_lm(args):
    import dataclasses
    cfg = small_lm_cfg()
    adapter = adapters.lm_adapter(cfg, lr=args.emb_lr)
    coll = apply_backend_choice(
        adapter.collection, args.emb_backend,
        default_cache_rows(cfg.vocab_size, args.cache_rows))
    shards = parse_emb_shards(args.emb_shards)
    if shards != 1:
        coll = coll.with_shards(shards)
    coll = _apply_emb_tuning(coll, args)
    if coll is not adapter.collection:
        adapter = dataclasses.replace(adapter, collection=coll)
    mode = mode_from_name(args.mode, args.tau)
    trainer = PersiaTrainer(adapter, mode,
                            OptConfig(kind="adam", lr=args.lr),
                            batch_dedup=False if args.no_batch_dedup
                            else None)
    it = lm_batches(cfg.vocab_size, args.batch, args.seq_len)
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
    state = trainer.init(jax.random.PRNGKey(args.seed), batch)
    n_params = sum(x.size for x in jax.tree.leaves(state.dense))
    vocab_spec = trainer.collection["vocab"]
    print(f"dense params: {n_params/1e6:.1f}M + emb "
          f"{vocab_spec.rows * vocab_spec.dim/1e6:.1f}M")
    if args.pipeline == "pipelined":
        engine = _make_engine(trainer, args)
        history = []
        t0 = time.time()
        step = 0
        while step < args.steps:
            n = min(args.eval_every - step % args.eval_every,
                    args.steps - step)
            state, metrics = _pipelined_span(engine, state, it, n)
            step += n
            if step % args.eval_every == 0:
                dt = time.time() - t0
                tok_s = step * args.batch * args.seq_len / dt
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"{tok_s:,.0f} tok/s")
                history.append({"step": step, "time_s": dt,
                                "loss": float(metrics["loss"])})
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"mode": args.mode, "history": history,
                           "pipeline_metrics": engine.pipeline_metrics()},
                          f, indent=1)
        return history
    step_fn = _step_fn(trainer, args.pipeline)
    history = []
    t0 = time.time()
    for step in range(args.steps):
        b = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, metrics = step_fn(state, b)
        if (step + 1) % args.eval_every == 0:
            dt = time.time() - t0
            tok_s = (step + 1) * args.batch * args.seq_len / dt
            print(f"step {step+1:5d} loss {float(metrics['loss']):.4f} "
                  f"{tok_s:,.0f} tok/s")
            history.append({"step": step + 1, "time_s": dt,
                            "loss": float(metrics["loss"])})
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"mode": args.mode, "history": history}, f, indent=1)
    return history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=["ctr", "lm"], default="ctr")
    ap.add_argument("--dataset", default="taobao_ad")
    ap.add_argument("--mode", choices=["sync", "hybrid", "async"],
                    default="hybrid")
    ap.add_argument("--pipeline",
                    choices=["fused", "decomposed", "pipelined"],
                    default="fused",
                    help="fused = one jitted program; decomposed = serial "
                         "get/dense/put dispatches; pipelined = the async "
                         "five-stage engine (core/pipeline.py)")
    ap.add_argument("--max-inflight", type=int, default=4,
                    help="pipelined engine: max microbatches in flight "
                         "(1 = bit-exact with --pipeline decomposed)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--tau", type=int, default=3)
    ap.add_argument("--emb-backend", default="dense",
                    choices=["dense", "host_lru", "host_lru+disk",
                             "dense+compressed", "host_lru+compressed",
                             "host_lru+disk+compressed"],
                    help="embedding storage backend (core/backend.py): "
                         "host_lru keeps tables host-side behind a device "
                         "hot-cache; +disk stacks the mmap tier under the "
                         "host store; +compressed adds the §4.2.3 wire")
    ap.add_argument("--cache-rows", type=int, default=0,
                    help="host_lru device-cache slots per table "
                         "(0 = rows_per_field/8, at least 1024)")
    ap.add_argument("--store-dtype", default="fp32",
                    choices=["fp32", "blockscale16"],
                    help="host/disk cold-row format (core/lru.py): "
                         "blockscale16 halves host bytes via the §4.2.3 "
                         "blockscale fp16 codec (decompress on fault-in, "
                         "compress on write-back)")
    ap.add_argument("--backward-kernel", action="store_true",
                    help="use the fused Pallas embedding backward "
                         "(kernels/fused_backward.py) instead of the "
                         "jitted jnp oracle — one pass for segment-sum + "
                         "adagrad + queue payload")
    ap.add_argument("--tuned-host", action="store_true",
                    help="apply the tuned host profile (launch/hostenv.py): "
                         "tcmalloc LD_PRELOAD (re-execs once; graceful "
                         "no-op when absent) + XLA/TF host env tuning")
    ap.add_argument("--no-batch-dedup", action="store_true",
                    help="disable worker-side batch dedup (core/dedup.py): "
                         "run the pre-dedup occurrence-width lookup/queue/"
                         "put path. Default is ON — one row per unique id "
                         "per batch, staleness queues sized at the dedup "
                         "cap, dedup/<table>/* step metrics")
    ap.add_argument("--emb-shards", default="1",
                    help="embedding-PS shards per table: an int for every "
                         "table, or 'table=k,table=k' pairs. k > 1 routes "
                         "through the sharded router (core/backend.py): "
                         "hash id->shard routing, per-shard stores/locks, "
                         "concurrent fault-in, reshardable checkpoints")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--emb-lr", type=float, default=5e-2)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.tuned_host:
        from repro.launch.hostenv import apply_tuned_host
        status = apply_tuned_host()      # re-execs once when tcmalloc found
        if status == "no-tcmalloc":
            print("--tuned-host: libtcmalloc not installed; "
                  "applying env-only profile")
    enable_compile_cache()
    if args.task == "ctr":
        train_ctr(args)
    else:
        if args.resume:
            raise SystemExit("--resume is only supported for --task ctr")
        train_lm(args)


if __name__ == "__main__":
    main()
