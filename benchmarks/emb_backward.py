"""Fused embedding backward + blockscale cold-row storage (ISSUE 9).

Three measurements, one per tentpole claim:

* ``fused_vs_decomposed`` — the SAME put stream (dedup plans + occurrence
  grads at a dup-heavy CTR shape) is applied through the one-pass fused
  backward (``_hybrid_plan`` / ``_put_plan``, kernels/fused_backward.py:
  segment-sum + adagrad + queue payload in a single dispatch) and through
  the decomposed three-dispatch base path (``plan_segment_sum`` then
  ``_hybrid_unique``). States and queues must stay bit-equal; reported
  speedup plus the STRUCTURAL win: the decomposed path materializes the
  unique-width grad buffer between dispatches (one write + one read of
  cap x dim fp32 crossing the dispatch boundary), the fused pass never
  builds it.
* ``pallas_kernel`` — the Pallas kernel vs the jnp oracle at the same
  shape. The row names the device and whether the kernel ran compiled or
  through the Pallas interpreter (``ops.interpret_mode``); an interpreted
  time is not a kernel time, so the closeness check is the load-bearing
  part.
* ``store_dtype`` — two identical host_lru hybrid training runs at
  ``dim=64``, fp32 vs blockscale16 cold rows (core/lru.py codec): row
  payload bytes must drop >= 1.9x while eval AUC moves <= 2e-3.
* ``tuned_host`` — a malloc-churn microbenchmark (the host put path's
  gather/scatter temporaries) run in two subprocesses: stock env vs the
  ``--tuned-host`` profile (launch/hostenv.py). Quantifies the free
  tcmalloc win; reports ``tcmalloc=absent`` and ratio ~1.0 when the lib
  is not installed (graceful no-op).

    PYTHONPATH=src python benchmarks/emb_backward.py --steps 40 --check

``--check`` enforces the PR bar: fused/decomposed bit-equality AND the
structural intermediate-bytes ratio >= 1.2x; storage payload >= 1.9x at
<= 2e-3 AUC delta. The fused/decomposed A/B runs the jnp oracle on every
platform, so its step times are reported with their device and are not
a bar.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import adapters
from repro.core import backend as BK
from repro.core import dedup as D
from repro.core.dedup import DedupPlan
from repro.core.embedding_ps import EmbeddingSpec
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.data.ctr import CTRDataset
from repro.optim.optimizers import OptConfig

B, L, DIM = 256, 16, 32          # n_occ = 4096 put occurrences per step
ROWS, TAU, DUP = 8192, 3, 8      # ids drawn from a pool of n_occ/DUP keys
STORE_DIM = 64                   # the storage A/B dim (>= 2 codec blocks
STORE_ROWS = 4 * 2048            # never hit at 64 -- one scale per row)


def _plans(steps: int, seed: int = 0):
    """Pre-built (plan, grads) puts so plan construction stays outside
    the clock."""
    rng = np.random.default_rng(seed)
    pool = B * L // DUP
    cap = D.dedup_cap(B * L, ROWS)
    out = []
    for _ in range(steps):
        ids = rng.integers(-1, pool, (B, L))
        u_pad, inv, _, _ = D.make_plan(ids, ROWS, cap, floor=8)
        out.append((DedupPlan(dev=jnp.asarray(u_pad, jnp.int32),
                              inv=jnp.asarray(inv, jnp.int32)),
                    jnp.asarray(rng.standard_normal(
                        (B, L, DIM)).astype(np.float32))))
    return out, cap


def _decomposed_hybrid(b, state, queue, plan, grads):
    """The pre-fusion three-dispatch path: segment-sum to unique width,
    then the queue-push + apply dispatch re-reads that buffer."""
    g_u = D.plan_segment_sum(plan.inv, grads, int(plan.dev.shape[0]))
    return b._hybrid_unique(state, queue, plan.dev, g_u)


def _tree_bitequal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _device() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind}"


def _backward_ab(steps: int):
    """-> (fused_us, decomposed_us, bitequal, cap)."""
    spec = EmbeddingSpec(rows=ROWS, dim=DIM, lr=5e-2, staleness=TAU,
                         backend="dense")
    b = BK.DenseBackend(spec)
    puts, cap = _plans(steps + 2)
    sf = so = b.init(jax.random.PRNGKey(0))
    qf = b.queue_init((B, L))
    qo = jax.tree.map(jnp.copy, qf)
    for plan, grads in puts[:2]:            # compile outside the clock
        sf, qf, _ = b.hybrid_update(sf, qf, plan, grads)
        so, qo, _ = _decomposed_hybrid(b, so, qo, plan, grads)
    bitequal = _tree_bitequal((sf, qf), (so, qo))

    t0 = time.perf_counter()
    for plan, grads in puts[2:]:
        sf, qf, _ = b.hybrid_update(sf, qf, plan, grads)
    jax.block_until_ready(sf)
    fused_us = (time.perf_counter() - t0) / steps * 1e6

    t0 = time.perf_counter()
    for plan, grads in puts[2:]:
        so, qo, _ = _decomposed_hybrid(b, so, qo, plan, grads)
    jax.block_until_ready(so)
    dec_us = (time.perf_counter() - t0) / steps * 1e6
    return fused_us, dec_us, bitequal and _tree_bitequal((sf, qf), (so, qo)), \
        cap


def _pallas_row():
    """Kernel-vs-oracle closeness + indicative timing (cf. the
    dedup/unique_bag row). The push payload is bit-exact; table/acc sit in
    the documented ~1e-7 reduction-order class, hence allclose."""
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    R, Dm, U, n_occ = 512, DIM, 64, 256
    table = jnp.asarray(rng.standard_normal((R, Dm)).astype(np.float32))
    acc = jnp.asarray(rng.random(R).astype(np.float32))
    inv = jnp.asarray(rng.integers(-1, U, n_occ), jnp.int32)
    grads = jnp.asarray(rng.standard_normal((n_occ, Dm)).astype(np.float32))
    apply_idx = jnp.asarray(
        np.concatenate([rng.permutation(R)[:U // 2], [-1] * (U - U // 2)]),
        jnp.int32)
    apply_g = jnp.asarray(rng.standard_normal((U, Dm)).astype(np.float32))
    want = ref.fused_backward_ref(table, acc, inv, grads, apply_idx,
                                  apply_g, cap=U, lr=5e-2, eps=1e-8)
    got = ops.fused_backward(table, acc, inv, grads, apply_idx, apply_g,
                             lr=5e-2, eps=1e-8)
    for w, g in zip(want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-6, atol=2e-6)
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(
            ops.fused_backward(table, acc, inv, grads, apply_idx, apply_g,
                               lr=5e-2, eps=1e-8))
    us = (time.perf_counter() - t0) / 3 * 1e6
    return ("emb_backward/pallas_kernel", us,
            f"kernel~=oracle(2e-6) R={R} D={Dm} U={U} n_occ={n_occ} "
            f"device={_device()} interpret={ops.interpret_mode()}")


def _store_run(store_dtype: str, steps: int):
    """-> (per-step losses, eval AUC, payload bytes, steps/s) for a
    host_lru hybrid run whose cold rows live in ``store_dtype``."""
    ds = CTRDataset("embbw", n_rows=STORE_ROWS, n_fields=4, ids_per_field=2,
                    n_dense=13)
    cfg = ModelConfig(name="embbw", arch_type="recsys", n_id_fields=4,
                      ids_per_field=2, emb_dim=STORE_DIM, emb_rows=STORE_ROWS,
                      n_dense_features=13, mlp_dims=(64, 32), n_tasks=1)
    coll = adapters.ctr_collection(cfg, lr=5e-2, field_rows=ds.field_rows())
    coll = coll.with_backend("host_lru", 256).with_store_dtype(store_dtype)
    adapter = adapters.recsys_adapter(cfg, field_rows=ds.field_rows(),
                                      collection=coll)
    tr = PersiaTrainer(adapter, TrainMode.hybrid(2),
                       OptConfig(kind="adam", lr=1e-3))
    it = ds.sampler(64)
    bs = [{k: jnp.asarray(v) for k, v in next(it).items()}
          for _ in range(steps)]
    st = tr.init(jax.random.PRNGKey(0), bs[0])
    t0 = time.perf_counter()
    losses = []
    for bt in bs:
        st, m = tr.decomposed_step(st, bt)
        losses.append(np.float32(m["loss"]))
    jax.block_until_ready(st.emb)
    sps = steps / (time.perf_counter() - t0)
    ev = {k: jnp.asarray(v) for k, v in next(ds.sampler(2048, seed=7)).items()}
    a = adapters.auc(np.asarray(ev["labels"]),
                     np.asarray(tr.predict(st, ev)))
    payload = sum(bk.store.payload_bytes() for bk in tr.backends.values())
    return losses, a, payload, sps


_CHURN = r"""
import numpy as np, time
rng = np.random.default_rng(0)
pool = rng.standard_normal((1 << 15, 64)).astype(np.float32)
idx = rng.integers(0, 1 << 15, (160, 4096))
t0 = time.perf_counter()
for i in range(160):
    rows = pool[idx[i]]              # fancy gather -> fresh 1MB buffer
    upd = rows * 0.5 + 1.0           # two more full-width temporaries
    pool[idx[i]] = upd
print(time.perf_counter() - t0)
"""


def _tuned_host_row():
    """Stock vs tuned-host env on the malloc-churn shape of the host put
    path, each in its own subprocess (LD_PRELOAD only binds at start)."""
    from repro.launch.hostenv import find_tcmalloc, tuned_env
    lib = find_tcmalloc()
    base = {k: v for k, v in os.environ.items() if k != "LD_PRELOAD"}
    tuned = dict(base, **tuned_env())
    if lib:
        tuned["LD_PRELOAD"] = lib

    def once(env):
        out = subprocess.run([sys.executable, "-c", _CHURN], env=env,
                             capture_output=True, text=True, check=True)
        return float(out.stdout.strip().splitlines()[-1])

    once(base), once(tuned)           # warm the page cache both ways
    t_base = min(once(base) for _ in range(3))
    t_tuned = min(once(tuned) for _ in range(3))
    ratio = t_base / t_tuned
    return ("emb_backward/tuned_host", t_tuned * 1e6,
            f"stock={t_base*1e3:.1f}ms tuned={t_tuned*1e3:.1f}ms "
            f"speedup={ratio:.2f}x tcmalloc="
            f"{'present' if lib else 'absent'}")


def run(steps: int = 40, results: dict | None = None):
    """benchmarks/run.py entry — CSV rows (name, us, derived). Pass a dict
    as ``results`` to also receive the --check inputs."""
    fused_us, dec_us, bitequal, cap = _backward_ab(steps)
    # the decomposed path writes then re-reads the unique-width grad
    # buffer across its dispatch boundary; the fused pass never builds it
    inter = 2 * cap * DIM * 4
    rows = [(
        "emb_backward/fused_vs_decomposed", fused_us,
        f"fused={fused_us:.0f}us decomposed={dec_us:.0f}us "
        f"speedup={dec_us / fused_us:.2f}x bitequal={bitequal} "
        f"intermediate_bytes={inter} vs 0 cap={cap} device={_device()}")]
    rows.append(_pallas_row())

    l16, auc16, pay16, sps16 = _store_run("blockscale16", steps)
    l32, auc32, pay32, _ = _store_run("fp32", steps)
    pay_ratio = pay32 / pay16
    auc_delta = abs(auc32 - auc16)
    rows.append((
        "emb_backward/store_dtype", 1e6 / sps16,
        f"payload={pay16} vs fp32 {pay32} ({pay_ratio:.2f}x) "
        f"auc={auc16:.4f} vs {auc32:.4f} (delta={auc_delta:.4f}) "
        f"loss_delta={max(abs(a - b) for a, b in zip(l16, l32)):.2e} "
        f"dim={STORE_DIM}"))
    rows.append(_tuned_host_row())

    if results is not None:
        results.update(speedup=dec_us / fused_us, bitequal=bitequal,
                       inter_ratio=inter / 1.0, pay_ratio=pay_ratio,
                       auc_delta=auc_delta)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless fused==decomposed bit-exact, "
                         "structural intermediate-bytes >= 1.2x, storage "
                         "payload >= 1.9x at <= 2e-3 AUC delta")
    args = ap.parse_args()
    results: dict = {}
    rows = run(args.steps, results)
    print("name,us_per_call,derived")
    for n, us, derived in rows:
        print(f"{n},{us:.1f},{derived}")
    # repo root on the path so this also works as `python benchmarks/...`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.report import save_bench
    save_bench("emb_backward", rows, results)
    if args.check:
        ok = True
        if not results["bitequal"]:
            print("FAIL: fused backward diverges from the decomposed path",
                  file=sys.stderr)
            ok = False
        if results["inter_ratio"] < 1.2:
            print(f"FAIL: intermediate-bytes ratio "
                  f"{results['inter_ratio']:.2f}x < 1.2x", file=sys.stderr)
            ok = False
        if results["pay_ratio"] < 1.9:
            print(f"FAIL: blockscale16 payload ratio "
                  f"{results['pay_ratio']:.2f}x < 1.9x at dim {STORE_DIM}",
                  file=sys.stderr)
            ok = False
        if results["auc_delta"] > 2e-3:
            print(f"FAIL: blockscale16 AUC delta {results['auc_delta']:.4f} "
                  "> 2e-3", file=sys.stderr)
            ok = False
        if not ok:
            raise SystemExit(1)
        print(f"OK: bit-equal; speedup {results['speedup']:.2f}x "
              f"({_device()}); payload "
              f"{results['pay_ratio']:.2f}x; AUC delta "
              f"{results['auc_delta']:.4f}")


if __name__ == "__main__":
    main()
