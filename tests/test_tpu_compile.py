"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for one chip of a
described (not attached) ``v5e:2x2`` topology, which finds the refusals
that interpret mode cannot — block shapes off the (8, 128) tiling, more
VMEM than a kernel may use — and shows the kernel is in the program
(``tpu_custom_call``). The topology is described inside a fixture, so a
machine without the TPU compiler skips these tests and every test
worker collects the same ones.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_topk import block_topk_payload, diff_topk_payload
from repro.kernels.scatter_accum import (
    block_scatter_accumulate,
    scatter_accumulate,
)
from repro.kernels.scatter_accum.ops import streamed_slab_update


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("d, n, k", [
    (300, 142, 3000),     # w8a TopK: the single-block accumulator
    (2048, 8, 4096),      # over the single-block budget: output-tiled
])
def test_scatter_accumulate_compiles(one_chip, d, n, k):
    for symmetric in (False, True):
        _compile(lambda v, i: scatter_accumulate(
            v, i, (d, d), use_pallas=True, interpret=False,
            symmetric=symmetric), one_chip, ((n, k), F32), ((n, k), I32))


def test_streamed_init_variant_compiles(one_chip):
    """One silo slab continuing the running server sum (w8a width)."""
    _compile(lambda acc, v, i: streamed_slab_update(
        acc, v, i, (300, 300), interpret=False, symmetric=True),
        one_chip, ((304, 384), F32), ((16, 3000), F32), ((16, 3000), I32))


@pytest.mark.parametrize("n, grid, k", [
    (142, (3, 3), 1024),    # w8a BlockTopK server
    (4, (7, 38), 2048),     # a qwen2 MLP tensor, one silo per chip
])
def test_block_scatter_accumulate_compiles(one_chip, n, grid, k):
    nblk = grid[0] * grid[1]
    _compile(lambda v, i: block_scatter_accumulate(
        v, i, grid, 128, use_pallas=True, interpret=False),
        one_chip, ((n, nblk, k), F32), ((n, nblk, k), I32))


def test_block_topk_payload_compiles(one_chip):
    _compile(lambda x: block_topk_payload(x, 1024, use_pallas=True,
                                          interpret=False),
             one_chip, ((300, 300), F32))


@pytest.mark.parametrize("shape, k", [
    ((300, 300), 1024),     # w8a Hessian diff
    ((896, 4864), 2048),    # qwen2-0.5b MLP tensor at the training k
])
def test_diff_topk_payload_compiles(one_chip, shape, k):
    _compile(lambda a, b: diff_topk_payload(a, b, k, use_pallas=True,
                                            interpret=False),
             one_chip, (shape, F32), (shape, F32))
