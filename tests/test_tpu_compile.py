"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for one chip of a
described (not attached) ``v5e:2x2`` topology, which finds the refusals
that interpret mode cannot — block shapes off the (8, 128) tiling, more
VMEM than a kernel may use — and shows the kernel is in the program
(``tpu_custom_call``). The topology is described inside a fixture, so a
machine without the TPU compiler skips these tests and every test
worker collects the same ones.
"""

import base64
import os
import re

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


def _mosaic_modules(compiled):
    """The Mosaic module of each ``tpu_custom_call`` in a compiled
    program, decoded from its ``custom_call_config.body`` (base64 MLIR
    bytecode)."""
    from jaxlib.mlir import ir
    from jaxlib.mlir.passmanager import PassManager
    from jaxlib.mosaic.python import tpu

    modules = []
    for body in re.findall(r'"body":"([^"]+)"', compiled.as_text()):
        ctx = ir.Context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True  # the serialized dialect
        with ctx, ir.Location.unknown():
            module = ir.Module.parse(base64.b64decode(body))
            PassManager.parse(
                "builtin.module(mosaic-serde{serialize=false})").run(
                    module.operation)
            modules.append(module)
    return modules


def _nested_ops(op):
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                yield inner.operation
                yield from _nested_ops(inner.operation)


@pytest.mark.parametrize("op", ["block", "diff"])
@pytest.mark.parametrize("shape, k", [
    ((300, 300), 1024),     # w8a Hessian diff
    ((896, 4864), 2048),    # qwen2-0.5b MLP tensor at the training k
])
def test_payload_kernel_contractions_single_pass(one_chip, op, shape, k):
    """For f32 tiles every contraction of the payload kernel is one bf16
    MXU pass (no ``contract_precision<fp32>``), also inside a program
    that asks for the highest matmul precision (as the FedNL cells do),
    and the compaction loop holds exactly one ``tpu.matmul``; the
    bisection loop holds none."""
    with jax.default_matmul_precision("highest"):
        if op == "block":
            compiled = _compile(lambda x: block_topk_payload(
                x, k, use_pallas=True, interpret=False),
                one_chip, (shape, F32))
        else:
            compiled = _compile(lambda a, b: diff_topk_payload(
                a, b, k, use_pallas=True, interpret=False),
                one_chip, (shape, F32), (shape, F32))
    (module,) = _mosaic_modules(compiled)
    ops = list(_nested_ops(module.operation))
    matmuls = [o for o in ops if o.name == "tpu.matmul"]
    assert matmuls
    assert not any("contract_precision<fp32>" in str(o) for o in matmuls)
    per_loop = sorted(sum(o.name == "tpu.matmul" for o in _nested_ops(loop))
                      for loop in ops if loop.name == "scf.for")
    assert per_loop == [0, 1]
