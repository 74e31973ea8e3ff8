"""The work a training step must do, counted from shapes.

These are the algorithm's operations and bytes, not what a compiled program
happens to execute, so a later change to the program cannot move them.
"""
from __future__ import annotations

import numpy as np

FP32 = 4
ID = 4


def tower_dims(model: dict) -> list[int]:
    """Widths of the tower from its input to its logits."""
    d_in = model["n_id_fields"] * model["emb_dim"] + model["n_dense_features"]
    return [d_in, *model["mlp_dims"], model["n_tasks"]]


def tower_flops_per_sample(model: dict) -> float:
    """Forward and backward FLOPs of the tower for one sample.

    Each layer is one (in x out) matmul: 2*in*out FLOPs forward, and twice
    that backward (the gradient of the weights and of the layer's input; the
    first layer's input gradient is the embedding gradient, so it is needed
    too). Bias, activation and loss are left out."""
    dims = tower_dims(model)
    macs = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return 6.0 * macs


def unique_counts(ids: np.ndarray) -> np.ndarray:
    """Distinct valid ids per field of one batch ``(B, F, L)`` (-1 pads)."""
    out = np.zeros(ids.shape[1], np.int64)
    for f in range(ids.shape[1]):
        x = ids[:, f].reshape(-1)
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
