"""The concat-then-MLP tower: its plain reference and its work count.

The pooled fields and the dense features, concatenated in field order
(``d_in`` wide), pass through ReLU layers of ``model["mlp_dims"]`` to one
logit per task (``model["n_tasks"]``). Weights are N(0, 2/fan_in), biases
zero. A configuration names this tower by ``"reference_tower": "mlp"``, or
by naming none.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(model: dict, d_in: int) -> list[int]:
    """Widths of the tower from its input to its logits."""
    return [d_in, *model["mlp_dims"], model["n_tasks"]]


def init(key, model: dict, d_in: int) -> dict:
    """The tower's parameters drawn from ``key``, float32: layer ``i``'s
    weight from the ``i``-th of ``len(dims)`` keys split from ``key``."""
    d = dims(model, d_in)
    ks = jax.random.split(key, len(d))
    return {"mlp": [
        {"b": jnp.zeros((d[i + 1],), jnp.float32),
         "w": jax.random.normal(ks[i], (d[i], d[i + 1]), jnp.float32)
         * math.sqrt(2.0 / d[i])}
        for i in range(len(d) - 1)]}


def forward(params: dict, x, model: dict):
    """Logits ``(B, n_tasks)`` of the tower's input ``x`` ``(B, d_in)``, in
    the dtype of ``x`` and ``params``."""
    n = len(params["mlp"])
    for i, lyr in enumerate(params["mlp"]):
        x = x @ lyr["w"] + lyr["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def flops_per_sample(model: dict, d_in: int) -> float:
    """Forward and backward FLOPs of the tower for one sample.

    Each layer is one (in x out) matmul: 2*in*out FLOPs forward, and twice
    that backward (the gradient of the weights and of the layer's input; the
    first layer's input gradient is the embedding gradient, so it is needed
    too). Bias, activation and loss are left out."""
    d = dims(model, d_in)
    macs = sum(a * b for a, b in zip(d[:-1], d[1:]))
    return 6.0 * macs
