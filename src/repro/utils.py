"""Small shared utilities: pytree helpers, sharding helpers, dtype policy."""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any


def tree_size(tree: PyTree) -> int:
    """Total number of scalar parameters in a pytree."""
    return sum(x.size for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_paths(tree: PyTree) -> list[str]:
    """Flat list of '/'-joined key paths for a pytree of dicts/lists."""
    paths, _ = zip(*jax.tree_util.tree_flatten_with_path(tree)[0]) if jax.tree.leaves(tree) else ((), ())
    return [jax.tree_util.keystr(p) for p in paths]


def map_with_path(fn: Callable[[str, Any], Any], tree: PyTree) -> PyTree:
    """Map fn(path_str, leaf) over a pytree."""
    def _fn(path, leaf):
        return fn(jax.tree_util.keystr(path), leaf)
    return jax.tree_util.tree_map_with_path(_fn, tree)


# ---------------------------------------------------------------------------
# Sharding helper: apply a constraint only when the abstract mesh in scope
# actually carries the axis names (so model code runs unchanged on a bare CPU).
# ---------------------------------------------------------------------------

def _mesh_axis_names() -> tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return ()
    return tuple(mesh.axis_names)


def shard(x: jax.Array, *spec: Any) -> jax.Array:
    """with_sharding_constraint(x, P(*spec)) if the axes exist in scope.

    Axis entries may be None, a name, or a tuple of names. Entries whose
    name(s) are not present in the current mesh are dropped to None, so the
    same model code lowers under (data, model), (pod, data, model), or no
    mesh at all. Entries that do not evenly divide the corresponding dim are
    dropped too (e.g. 8 kv heads over a 16-way model axis) — a conflicting
    constraint there would force SPMD full-rematerialisation copies.
    """
    names = _mesh_axis_names()
    if not names:
        return x
    mesh = jax.sharding.get_abstract_mesh()

    def _nshards(entry) -> int:
        if isinstance(entry, (tuple, list)):
            n = 1
            for e in entry:
                n *= mesh.shape[e]
            return n
        return mesh.shape[entry]

    def _filter(entry, dim):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(e for e in entry if e in names)
            entry = kept if kept else None
        else:
            entry = entry if entry in names else None
        if entry is not None and dim % _nshards(entry) != 0:
            return None
        return entry

    cleaned = tuple(_filter(e, x.shape[i]) for i, e in enumerate(spec))
    if all(c is None for c in cleaned):
        return x
    return jax.lax.with_sharding_constraint(x, P(*cleaned))


def batch_axes() -> tuple[str, ...]:
    """Mesh axes over which the batch is sharded ('pod' first when present)."""
    names = _mesh_axis_names()
    return tuple(n for n in ("pod", "data") if n in names)


def n_batch_shards() -> int:
    axes = batch_axes()
    if not axes:
        return 1
    mesh = jax.sharding.get_abstract_mesh()
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def bspec_axes(dim_size: int):
    """Batch axes tuple if dim_size divides over them, else None (replicate).
    Handles B=1 decode shapes on many-shard meshes."""
    axes = batch_axes()
    if not axes or dim_size % n_batch_shards() != 0:
        return None
    return axes


# ---------------------------------------------------------------------------
# Dtype policy
# ---------------------------------------------------------------------------

class Policy:
    """Mixed-precision policy: param storage / compute / accumulation dtypes."""

    def __init__(self, param_dtype=jnp.float32, compute_dtype=jnp.float32,
                 accum_dtype=jnp.float32):
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.accum_dtype = jnp.dtype(accum_dtype)

    def cast_compute(self, tree: PyTree) -> PyTree:
        return jax.tree.map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    @staticmethod
    def from_name(name: str) -> "Policy":
        if name == "f32":
            return Policy()
        if name == "bf16":
            return Policy(jnp.bfloat16, jnp.bfloat16, jnp.float32)
        if name == "bf16_f32params":
            return Policy(jnp.float32, jnp.bfloat16, jnp.float32)
        raise ValueError(f"unknown policy {name!r}")


def default_field_rows(total_rows: int, n_fields: int) -> int:
    """Rows of each field's id space when one flat row budget is split
    evenly over fields — the single source of the formula shared by
    CTRDataset (id generation) and ctr_collection (table sizing)."""
    return max(total_rows // max(n_fields, 1), 4)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
