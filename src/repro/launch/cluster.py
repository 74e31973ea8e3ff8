"""Single-box multi-process PS cluster: spawn one trainer + k embedding-PS
processes, train over the RPC wire, and (optionally) kill a shard mid-run
to exercise the elastic recovery path end to end.

Usage::

    PYTHONPATH=src python -m repro.launch.cluster --steps 20 --ps 2
    PYTHONPATH=src python -m repro.launch.cluster --steps 20 --ps 3 \
        --kill-shard 1 --kill-at 8       # SIGKILL shard 1 before step 8

Each PS process binds port 0 and publishes its actual port through a
``--port-file`` (written atomically by the server once listening), so
parallel launches never race on ports. Every shard spools applied state
next to its port file; when a shard is killed, the trainer reshards its
rows from that spool onto the survivors and keeps stepping — the
membership events and any lost rows land in the end-of-run summary.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

import repro
from repro.configs.base import ModelConfig
from repro.core import adapters
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.data.ctr import CTRDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.shards import apply_backend_choice
from repro.net.elastic import ElasticPSCluster, PSMember
from repro.optim.optimizers import OptConfig


def wait_for_port_file(port_file: str, proc: subprocess.Popen,
                       timeout: float = 30.0) -> int:
    """Poll for the server's atomically-written port file; fails fast if
    the process died before publishing."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"ps_server exited with {proc.returncode} before "
                f"publishing {port_file}")
        try:
            with open(port_file) as f:
                text = f.read().strip()
            if text:
                return int(text)
        except (OSError, ValueError):
            pass
        time.sleep(0.05)
    raise TimeoutError(f"no port published at {port_file} "
                       f"within {timeout:.0f}s")


def spawn_ps(workdir: str, idx: int, host: str = "127.0.0.1",
             spool_every: int = 1, timeout: float = 30.0,
             reply_delay: float = 0.0) -> PSMember:
    """Launch one PS shard process; returns its member record (endpoint +
    spool dir + process handle). ``reply_delay`` injects a per-op reply
    latency server-side (benchmarks: a synthetic network RTT the
    pipelined transport should overlap, the blocking one pays per op)."""
    port_file = os.path.join(workdir, f"ps{idx}.port")
    spool_dir = os.path.join(workdir, f"ps{idx}.spool")
    log_path = os.path.join(workdir, f"ps{idx}.log")
    # the PS tier is host memory (paper §4.2.2): pin the child's JAX to the
    # CPU so it never opens the accelerator the trainer process holds
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # repro may be a namespace package (__file__ is None): locate its
    # parent via __path__ so the child process can import it
    src_root = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "repro.net.ps_server",
           "--host", host, "--port", "0", "--port-file", port_file,
           "--spool-dir", spool_dir, "--spool-every", str(spool_every)]
    if reply_delay > 0:
        cmd += ["--reply-delay", str(reply_delay)]
    log = open(log_path, "w")
    proc = subprocess.Popen(cmd, env=env, stdout=log,
                            stderr=subprocess.STDOUT)
    port = wait_for_port_file(port_file, proc, timeout)
    return PSMember(host, port, spool_dir=spool_dir, proc=proc)


def small_ctr_trainer(mode: str = "hybrid", backend: str = "host_lru",
                      tau: int = 2, fields: int = 2,
                      rows_per_field: int = 64, dim: int = 8,
                      cache_rows: int = 48, seed: int = 0):
    """A small CTR trainer + batch stream (the tests' model, sized so a
    cluster run finishes in seconds on CPU)."""
    cfg = ModelConfig(name="cluster", arch_type="recsys",
                      n_id_fields=fields, ids_per_field=3,
                      emb_dim=dim, emb_rows=fields * rows_per_field,
                      n_dense_features=4, mlp_dims=(16,), n_tasks=1)
    ds = CTRDataset("cluster", n_rows=fields * rows_per_field,
                    n_fields=fields, ids_per_field=3, n_dense=4)
    coll = adapters.ctr_collection(cfg, lr=5e-2, field_rows=ds.field_rows())
    coll = apply_backend_choice(coll, backend, cache_rows)
    ad = adapters.recsys_adapter(cfg, field_rows=ds.field_rows(),
                                 collection=coll)
    tm = {"sync": TrainMode.sync(), "hybrid": TrainMode.hybrid(tau),
          "async": TrainMode.async_(tau, tau)}[mode]
    trainer = PersiaTrainer(ad, tm, OptConfig(kind="adam", lr=5e-3))
    return trainer, ds


def run_cluster(steps: int = 20, n_ps: int = 2, mode: str = "hybrid",
                backend: str = "host_lru", batch: int = 16,
                kill_shard: int | None = None, kill_at: int | None = None,
                lossy: bool | None = None, spool_every: int = 1,
                workdir: str | None = None, seed: int = 0,
                heartbeats: bool = True, pipelined: bool = True,
                put_window: int | None = None,
                reply_delay: float = 0.0) -> dict:
    """Spawn the cluster, train ``steps`` steps, optionally SIGKILL one
    shard mid-run, and return a summary (steps/s, loss, membership
    events, lost rows). ``pipelined=False`` selects the blocking
    per-op-round-trip wire baseline; ``put_window`` overrides the
    outstanding-ack window (default: 1 for sync, min(tau, 8) for
    hybrid); ``reply_delay`` injects per-op reply latency PS-side."""
    workdir = workdir or tempfile.mkdtemp(prefix="ps_cluster_")
    trainer, ds = small_ctr_trainer(mode=mode, backend=backend, seed=seed)
    members, cluster = [], None
    try:
        members = [spawn_ps(workdir, i, spool_every=spool_every,
                            reply_delay=reply_delay)
                   for i in range(n_ps)]
        cluster = ElasticPSCluster(trainer, members)
        cluster.connect(lossy=lossy, pipelined=pipelined,
                        put_window=put_window)
        if heartbeats:
            cluster.start_heartbeats(interval=0.3, miss_threshold=2)
        it = ds.sampler(batch, seed=seed)
        batches = ({k: jnp.asarray(v) for k, v in b.items()}
                   for b in iter(it.__next__, None))
        first = next(batches)
        state = trainer.init(jax.random.PRNGKey(seed), first)
        metrics, t0 = {}, time.monotonic()
        for t in range(steps):
            if kill_shard is not None and t == (kill_at or steps // 2):
                proc = cluster.members[kill_shard].proc
                if proc is not None:
                    proc.kill()
                    proc.wait()
            state, metrics = cluster.step(state, first if t == 0
                                          else next(batches))
        jax.block_until_ready(state.dense)
        dt = time.monotonic() - t0
        return {
            "steps": steps,
            "steps_per_s": steps / max(dt, 1e-9),
            "loss": float(metrics.get("loss", float("nan"))),
            "members": len(cluster.members),
            "events": list(cluster.events)
            + ([] if cluster.monitor is None
               else list(cluster.monitor.events)),
            "lost_rows": {k: v for e in cluster.events
                          if e["kind"] == "reshard"
                          for k, v in e["lost_rows"].items()},
            "workdir": workdir,
        }
    finally:
        if cluster is not None:
            cluster.close()
        for m in members:
            if m.proc is not None and m.proc.poll() is None:
                m.proc.kill()
                m.proc.wait()


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="one-box multi-process embedding-PS training run")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ps", type=int, default=2,
                    help="number of PS shard processes")
    ap.add_argument("--mode", default="hybrid",
                    choices=["sync", "hybrid", "async"])
    ap.add_argument("--backend", default="host_lru",
                    choices=["dense", "host_lru"])
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--kill-shard", type=int, default=None,
                    help="SIGKILL this shard index mid-run (fault drill)")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="step before which the kill fires (default mid)")
    ap.add_argument("--lossy", action="store_true", default=None,
                    help="blockscale-fp16 wire payloads")
    ap.add_argument("--spool-every", type=int, default=1)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--transport", default="pipelined",
                    choices=["pipelined", "blocking"],
                    help="wire path: coalesced async (default) or the "
                         "per-op synchronous-round-trip baseline")
    ap.add_argument("--put-window", type=int, default=None,
                    help="outstanding-ack window per table-shard "
                         "(default: 1 sync, min(tau, 8) hybrid)")
    ap.add_argument("--reply-delay", type=float, default=0.0,
                    help="server-side per-op reply latency in seconds "
                         "(synthetic network RTT)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    res = run_cluster(steps=args.steps, n_ps=args.ps, mode=args.mode,
                      backend=args.backend, batch=args.batch,
                      kill_shard=args.kill_shard, kill_at=args.kill_at,
                      lossy=args.lossy, spool_every=args.spool_every,
                      workdir=args.workdir,
                      pipelined=args.transport == "pipelined",
                      put_window=args.put_window,
                      reply_delay=args.reply_delay)
    print(f"cluster: {res['steps']} steps @ {res['steps_per_s']:.2f} "
          f"steps/s, final loss {res['loss']:.4f}, "
          f"{res['members']} PS members at exit")
    for e in res["events"]:
        print(f"  event: {e}")
    if res["lost_rows"]:
        print(f"  lost rows on reshard: {res['lost_rows']}")
    return res


if __name__ == "__main__":
    main()
