"""The work a training step must do, counted from shapes.

These are the algorithm's operations and bytes, not what a compiled program
happens to execute, so a later change to the program cannot move them. The
tower's operations are counted by its own module (``bench/towers/``).
"""
from __future__ import annotations

import numpy as np

from bench.harness import spec

FP32 = 4
ID = 4


def unique_counts(ids, n_fields: int) -> np.ndarray:
    """Distinct valid ids per field of one batch's ``ids`` (-1 pads), in
    either layout ``spec.field_ids`` reads."""
    out = np.zeros(n_fields, np.int64)
    for f in range(n_fields):
        x = np.asarray(spec.field_ids(ids, f)).reshape(-1)
        out[f] = np.unique(x[x >= 0]).size
    return out


def emb_bytes(unique_now: np.ndarray, unique_popped: np.ndarray,
              dim: int) -> float:
    """HBM bytes the embedding tier must move in one step, summed over the
    tables: the lookup reads each unique row once; this step's put is
    written to the staleness queue (rows and ids); the put popped from tau
    steps ago is read back and applied by adagrad, which reads and writes
    each of its rows and accumulators."""
    row = dim * FP32
    now = float(np.sum(unique_now))
    old = float(np.sum(unique_popped))
    lookup = now * row
    push = now * (row + ID)
    pop = old * (row + ID)
    apply = old * 2 * (row + FP32)
    return lookup + push + pop + apply
