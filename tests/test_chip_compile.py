"""Ahead-of-time compiles of the CTR path's Pallas kernels for a TPU v5e
chip that is described, not attached: the Mosaic compiler refuses block
shapes, layouts and VMEM budgets that interpret mode accepts.

Shapes are the paper's Table-1 Criteo widths (dim 128, 26 fields of
20,769 rows, two ids per field) at batch 4096: 8,192 id occurrences and a
dedup cap of 8,192 per table. Nothing here runs a kernel; the topology is
described inside a fixture so that no test worker loads the TPU library
while it collects tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import blockscale as bs
from repro.kernels import embedding_bag as bag
from repro.kernels import embedding_sgd as sgd
from repro.kernels import fused_backward as fb
from repro.kernels import unique_bag as ub

ROWS, DIM, BATCH, IDS_PER_FIELD = 20_769, 128, 4096, 2
N_OCC = BATCH * IDS_PER_FIELD        # occurrences per table per step
CAP = 8192                           # core.dedup.dedup_cap(N_OCC, ROWS)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("apply_self", [False, True])
def test_fused_backward_compiles(one_chip, apply_self):
    s = functools.partial(_sds, one_chip)
    compiled = _compile(
        functools.partial(fb.fused_backward, lr=5e-2, eps=1e-8,
                          apply_self=apply_self),
        s((ROWS, DIM), jnp.float32), s((ROWS,), jnp.float32),
        s((BATCH, IDS_PER_FIELD), jnp.int32),
        s((BATCH, IDS_PER_FIELD, DIM), jnp.float32),
        s((CAP,), jnp.int32), s((CAP, DIM), jnp.float32))
    assert compiled.memory_analysis() is not None


def test_unique_bag_compiles(one_chip):
    s = functools.partial(_sds, one_chip)
    _compile(ub.unique_bag, s((ROWS, DIM), jnp.float32),
             s((CAP,), jnp.int32), s((BATCH, IDS_PER_FIELD), jnp.int32))


def test_embedding_bag_compiles(one_chip):
    s = functools.partial(_sds, one_chip)
    _compile(bag.embedding_bag, s((ROWS, DIM), jnp.float32),
             s((BATCH, IDS_PER_FIELD), jnp.int32))


def test_embedding_sgd_compiles(one_chip):
    s = functools.partial(_sds, one_chip)
    _compile(functools.partial(sgd.embedding_sgd, lr=5e-2),
             s((ROWS, DIM), jnp.float32), s((CAP,), jnp.int32),
             s((CAP, DIM), jnp.float32))


def test_blockscale_compiles(one_chip):
    s = functools.partial(_sds, one_chip)
    # the wire payload of one table's unique lookup rows: CAP x DIM fp32
    n = CAP * DIM // bs.BLOCK
    comp, scales = jax.eval_shape(bs.compress,
                                  jax.ShapeDtypeStruct((n, bs.BLOCK),
                                                       jnp.float32))
    _compile(bs.compress, s((n, bs.BLOCK), jnp.float32))
    _compile(bs.decompress, s(comp.shape, comp.dtype),
             s(scales.shape, scales.dtype))
