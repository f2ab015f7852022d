"""The trace reduction finds every Pallas kernel of a cell's timed
program by its jitted wrapper: the program compiled for a described TPU
v5e (no chip), a federated round at the cell's own size and a training
step at toy size on the cell's number of chips, and each
``tpu_custom_call`` of its HLO attributed to one of the driver's kernels.
A rename or an inlining that drops a wrapper's name fails here instead
of silencing a roofline on the chip."""

import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import run, trace
from chipbench.tests.toy import benchmark_cells

CUSTOM = 'custom_call_target="tpu_custom_call"'
EXPECTED = {"topk": {"scatter_accum"},
            "blocktopk": {"diff_topk_payload", "block_scatter"}}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _attributed(drv, hlo):
    calls = [line for line in hlo.splitlines() if CUSTOM in line]
    drv.hlo = [hlo]
    tags = drv.kernel_tags()
    found = trace.kernel_instructions(tags["hlo"], tags["scopes"])
    assert calls and len(found) == len(calls), calls
    return set(found.values())


@pytest.mark.parametrize("workload", benchmark_cells("fednl_rounds"))
def test_round_kernels_attributed(workload, one_chip, monkeypatch):
    from repro.engine.method import scan_rounds

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = run.find_cell(workload)
    drv = run.make_driver(cell, 1)
    cfg = cell["config_data"]
    n, m, d = cfg["silos"], cfg["rows_per_silo"], cfg["features"]
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    a, b = spec((n, m, d), jnp.float32), spec((n, m), jnp.float32)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        state = jax.eval_shape(lambda a, b: drv._method(a, b).init(
            jnp.zeros(d, jnp.float32), n, seed=0), a, b)
        state = jax.tree.map(lambda s: spec(s.shape, s.dtype), state)
        hlo = jax.jit(lambda s, a, b: scan_rounds(
            drv._method(a, b), s, drv.units_per_call)[0]
        ).lower(state, a, b).compile().as_text()
    assert _attributed(drv, hlo) == EXPECTED[
        cell["traffic_data"]["compressor"]]


@pytest.mark.parametrize("workload", benchmark_cells("train_steps")
                         + benchmark_cells("train_steps", chips=4))
def test_step_kernels_attributed(workload, topo, toy_cell, monkeypatch):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import (
        make_activation_sharder,
        make_layer_param_constrainer,
        opt_state_shardings,
        tree_param_specs,
    )
    from repro.launch.steps import make_optimizer, make_train_step
    from repro.models import build_model
    from repro.models.common import set_activation_sharder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = toy_cell(workload)
    drv = run.make_driver(cell, 1)
    mix, pcfg = cell["traffic_data"], drv.program_config()
    chips = int(cell["chips"])
    mesh = make_mesh((chips, 1), ("data", "model"),
                     devices=list(topo.devices[:chips]))
    set_activation_sharder(make_activation_sharder(mesh),
                           make_layer_param_constrainer(mesh, pcfg))
    try:
        model = build_model(pcfg, use_remat=True)
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, tree_param_specs(shapes, mesh, pcfg))
        opt = make_optimizer(mix["optimizer"], float(mix["lr"]),
                             k_per_block=int(mix["curvature_k"]), mesh=mesh,
                             curvature=mix["curvature"])
        st = jax.eval_shape(opt.init, shapes)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            st, opt_state_shardings(st, shapes, mesh, pcfg))
        rows = jax.ShapeDtypeStruct((int(mix["batch"]), int(mix["seq"])),
                                    jnp.int32,
                                    sharding=NamedSharding(mesh, P("data")))
        step = jax.jit(make_train_step(
            model, opt, refresh_every=int(mix["refresh_every"]),
            n_silos=chips))
        hlo = step.lower(params, state, {"tokens": rows, "targets": rows}).compile().as_text()
    finally:
        set_activation_sharder(None, None)
    assert _attributed(drv, hlo) == {"diff_topk_payload", "block_scatter"}
