#!/usr/bin/env python3
"""Readings of the check's control and planted faults, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13
    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        --fault drop_writeback --seconds 2

Without ``--fault``, for each seed this draws the cell's compared batches
and model as a run does, runs the float32 reference over them, and puts in
the program's place, one at a time:

* ``control``: the same reference computed in bfloat16 (the precision below
  the configuration's float32);
* ``half_batch``: the reference with the loss averaged over the first half
  of each batch only.

These readings run the reference alone, at the cell's global batch, on one
chip.

With ``--fault <name>``, a fault from ``FAULTS`` is planted in the program
and a whole run (a window of ``--seconds``) is made per seed. Each prints
one JSON line per seed and case with the three compared numbers, the
readings the limits in ``bench/configs/<config>.json`` are set between. A
step that leaves the state unchanged reads ``change_gap`` = 1 by
construction and needs no run. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)


# -- planted faults: each takes ``patch(owner, name, value)`` -----------------

def unchanged_state(patch):
    """Every optimizer leaves its parameters as they were."""
    from repro.core import backend as BK
    from repro.optim import optimizers as O

    def adam_update(params, grads, state, **kw):
        return params, state

    def put_all(backends, states, queues, dev_ids, grads):
        return dict(states), dict(queues or {}), {}

    patch(O, "adam_update", adam_update)
    patch(BK, "put_all", put_all)


def half_batch(patch):
    """The loss is averaged over the first half of the batch only."""
    from repro.models import recsys
    real = recsys._bce_loss

    def half(logits, batch):
        n = logits.shape[0] // 2
        return real(logits[:n], {**batch, "labels": batch["labels"][:n]})

    patch(recsys, "_bce_loss", half)


def drop_writeback(patch):
    """The host store keeps its old row where the device cache writes an
    evicted row back."""
    from repro.core import lru

    def write_rows(self, ids, vectors, opt_acc=None):
        return None

    patch(lru.LRUEmbeddingStore, "write_rows", write_rows)


def no_exchange(patch):
    """The sharded tables' lookup leaves out the exchange between chips:
    each chip keeps the partial rows of the ids it owns, and the rows the
    other chips own read as zeros."""
    import jax

    def psum(x, axis_name, **kw):
        return x

    patch(jax.lax, "psum", psum)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "drop_writeback": drop_writeback, "no_exchange": no_exchange}


def readings(cell, seed: int) -> list:
    import jax.numpy as jnp
    from bench.harness import compare, reference
    cfg = cell.config
    batches = cell.batches(seed, 0, int(cfg["check"]["steps"]))
    ref = reference.Reference(cfg, seed, cell.tower).run(batches)
    out = []
    for case, kw in (("control", {"dtype": jnp.bfloat16}),
                     ("half_batch", {"half_batch": True})):
        got = reference.Reference(cfg, seed, cell.tower, **kw).run(batches)
        g = compare.gaps(got, ref)
        out.append({"seed": seed, "case": case,
                    **{n: g[n] for n in compare.NAMES},
                    "grad1_gap_at": g["grad1_gap_at"],
                    "change_gap_at": g["change_gap_at"]})
    return out


def fault_readings(cell, seed: int, fault: str, seconds: float,
                   devices) -> dict:
    from bench import run
    from bench.harness import device
    out = run.execute(cell, seed, seconds, False, devices,
                      device.peaks(devices[0].device_kind))
    return {"seed": seed, "case": fault, "correct": out["correct"],
            **{n: c["value"] for n, c in out["checks"].items()},
            "change_gap_at": out["compared"]["change_gap_at"],
            "stored_rows": out["compared"]["stored_rows"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from bench.harness import device, spec
    cell = spec.resolve(args.workload)
    devices = device.require_tpu(cell.chips if args.fault else 1)
    if args.fault:
        from bench import run       # puts the program on the path
        run.enable_compile_cache()
        FAULTS[args.fault](setattr)
    for s in args.seeds.split(","):
        rs = [fault_readings(cell, int(s), args.fault, args.seconds,
                             devices)] if args.fault \
            else readings(cell, int(s))
        for r in rs:
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
