"""The reduction from a trace to the device time of the program's phase
scopes (``chipbench/scopes.py``): on a synthetic program and trace
checked by hand, through ``run.read_per_layer`` as a run calls the
readers, on a program without scopes (the readers report nothing), on
every cell's program compiled for a described TPU v5e, and on a small
trace recorded on a TPU v5e (``fednl-w8a.topk3000``, four rounds a call)."""

import collections
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from chipbench import run, scopes, trace
from chipbench.tests.toy import benchmark_cells

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
DATA = os.path.join(os.path.dirname(__file__), "data")
PHASE_METRICS = [m for m in BENCH["per_layer"]
                 if "_ms." in m["name"] or m["name"].startswith("scoped_share.")]

HLO = """\
%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %scatter.1 = f32[8]{0} scatter(%p, %p, %p), metadata={op_name="jit(run)/while/body/fednl.local_update/vmap()/scatter"}
}

%fused_computation.2 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %add.9 = f32[8]{0} add(%p.1, %p.1)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %sort.1 = f32[8]{0} sort(%a), dimensions={0}, metadata={op_name="jit(run)/while/body/vmap(fednl.uplink)/top_k"}
  %fusion.1 = f32[8]{0} fusion(%sort.1), kind=kLoop, calls=%fused_computation.1
  %copy.1 = f32[8]{0} copy(%fusion.1)
  %multiply.1 = f32[8]{0} multiply(%copy.1, %a), metadata={op_name="jit(run)/fednl.solve/fednl.server/mul"}
  %fusion.2 = f32[8]{0} fusion(%multiply.1), kind=kLoop, calls=%fused_computation.2
  ROOT %add.2 = f32[8]{0} add(%fusion.2, %a), metadata={op_name="jit(run)/while/body/add"}
}
"""
# name -> (start_ns, duration_ns); the window is [0, 10000]
EVENTS = {"sort.1": (0, 2000), "fusion.1": (2000, 1000), "copy.1": (3000, 500),
          "multiply.1": (3500, 1500), "fusion.2": (5000, 1000),
          "add.2": (6000, 1000), "while.3": (0, 7000)}


def _trace():
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for i, n in enumerate(EVENTS, 1))
    ev = " ".join(f"events {{ metadata_id: {i} offset_ps: {s * 1000} "
                  f"duration_ps: {d * 1000} }}"
                  for i, (s, d) in enumerate(EVENTS.values(), 1))
    return ProfileData.from_text_proto(f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ev} }} {meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }} }}
""")


def test_scope_of_op_names():
    assert scopes.scope_of("jit(f)/while/body/fednl.uplink/vmap()/top_k") \
        == "fednl.uplink"
    assert scopes.scope_of("jit(f)/cond/branch_1_fun/vmap(fednl.uplink)/"
                           "jit(_diff_topk_payload_impl)/pallas_call") \
        == "fednl.uplink"
    assert scopes.scope_of("jit(f)/train.forward_backward/"
                           "transpose(jvp(train.observe))/dot") == "train.observe"
    assert scopes.scope_of("jit(f)/fednl.solve/fednl.server/mul") \
        == "fednl.server"
    for name in ("jit(f)/while/body/add", "jit(f)/fednl/add",
                 "jit(f)/fednl.Uplink/add", "jit(f)/bench.window/add"):
        assert scopes.scope_of(name) is None


def test_instructions_by_hand():
    assert scopes.scope_instructions([HLO]) == {
        "scatter.1": "fednl.local_update",
        "p": "fednl.local_update",          # its one user is the scatter
        "sort.1": "fednl.uplink",           # under vmap(...)
        "fusion.1": "fednl.local_update",   # the root it calls
        "copy.1": "fednl.server",           # its one user
        "multiply.1": "fednl.server",       # the innermost of two
    }


def test_seconds_by_hand():
    r = trace.reduce_profile(_trace(), {"scopes": {}, "hlo": [HLO]}, chips=1)
    seconds, total = scopes.scope_seconds(r, [HLO])
    assert seconds == {"fednl.uplink": pytest.approx(2e-6),
                       "fednl.local_update": pytest.approx(1e-6),
                       "fednl.server": pytest.approx(2e-6)}
    assert total == pytest.approx(7e-6)  # the while is a container
    ctx = dict(trace=r, hlo=[HLO], units=4, calls=2)
    assert scopes.scope_ms(ctx, "fednl.uplink", per="round") == \
        pytest.approx(2e-3 / 4)
    assert scopes.scope_ms(ctx, "fednl.server", per="step") == \
        pytest.approx(2e-3 / 2)
    assert scopes.scope_ms(ctx, "fednl.oracle", per="round") is None
    assert scopes.scoped_share(ctx) == pytest.approx(100 * 5 / 7)


class _Driver:
    units_per_call = 2
    flops_per_unit = 1.0

    def __init__(self, hlo):
        self.hlo = [hlo]

    def kernel_tags(self):
        return {"scopes": {}, "hlo": self.hlo}

    def layer_counts(self, calls):
        return {}


def _read(hlo):
    r = trace.reduce_profile(_trace(), {"scopes": {}, "hlo": [hlo]}, chips=1)
    cell = {"per_layer": [m for m in PHASE_METRICS if m["name"].endswith(".round")]}
    return run.read_per_layer(cell, _Driver(hlo), r, calls=2, window_s=1e-5,
                              devs=[None], peaks={})


def test_readers_take_the_drivers_hlo():
    """``run.py`` hands the readers no HLO: they take the driver's."""
    out = {k: v["value"] for k, v in _read(HLO).items()}
    assert out == {"uplink_ms.round": pytest.approx(2e-3 / 4),
                   "local_update_ms.round": pytest.approx(1e-3 / 4),
                   "server_ms.round": pytest.approx(2e-3 / 4),
                   "scoped_share.round": pytest.approx(100 * 5 / 7)}


def test_program_without_scopes_reads_nothing():
    """A program without phase scopes (the parent of the change that
    added them) reports none of these metrics, and raises nothing."""
    assert _read(re.sub(r"(fednl|train)\.", "", HLO)) == {}
    ctx = dict(trace=trace.reduce_profile(_trace(), {}, chips=1), units=4,
               calls=2)
    for m in PHASE_METRICS:
        mod = run.load_module(run.BENCH / "metrics" / f"{m['name']}.py", "metric")
        assert mod.read(ctx) is None


# -- every cell's program, compiled for a described TPU v5e ------------------

KERNEL_PHASE = {"scatter_accum": "fednl.server", "block_scatter": "fednl.server",
                "diff_topk_payload": "fednl.uplink"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _round_program(cell, device):
    from jax.sharding import SingleDeviceSharding

    from repro.engine.method import scan_rounds

    drv = run.make_driver(cell, 1)
    cfg = cell["config_data"]
    n, m, d = cfg["silos"], cfg["rows_per_silo"], cfg["features"]
    one = SingleDeviceSharding(device)
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    a, b = spec((n, m, d), jnp.float32), spec((n, m), jnp.float32)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        state = jax.eval_shape(lambda a, b: drv._method(a, b).init(
            jnp.zeros(d, jnp.float32), n, seed=0), a, b)
        state = jax.tree.map(lambda s: spec(s.shape, s.dtype), state)
        hlo = jax.jit(lambda s, a, b: scan_rounds(
            drv._method(a, b), s, drv.units_per_call)[0]
        ).lower(state, a, b).compile().as_text()
    return drv, hlo


def _step_program(cell, device):
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

    drv = run.make_driver(cell, 1)
    mix, pcfg = cell["traffic_data"], drv.program_config()
    mesh = make_mesh((1, 1), ("data", "model"), devices=[device])
    set_activation_sharder(make_activation_sharder(mesh),
                           make_layer_param_constrainer(mesh, pcfg))
    try:
        model = build_model(pcfg, use_remat=True)
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        placed = lambda tree, shardings: jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings)
        opt = make_optimizer(mix["optimizer"], float(mix["lr"]),
                             k_per_block=int(mix["curvature_k"]), mesh=mesh,
                             curvature=mix["curvature"])
        st = jax.eval_shape(opt.init, shapes)
        rows = jax.ShapeDtypeStruct((int(mix["batch"]), int(mix["seq"])),
                                    jnp.int32,
                                    sharding=NamedSharding(mesh, P("data")))
        step = jax.jit(make_train_step(
            model, opt, refresh_every=int(mix["refresh_every"]), n_silos=1))
        hlo = step.lower(placed(shapes, tree_param_specs(shapes, mesh, pcfg)),
                         placed(st, opt_state_shardings(st, shapes, mesh, pcfg)),
                         {"tokens": rows, "targets": rows}).compile().as_text()
    finally:
        set_activation_sharder(None, None)
    return drv, hlo


@pytest.mark.parametrize("workload", benchmark_cells("fednl_rounds")
                         + benchmark_cells("train_steps"))
def test_every_phase_reaches_the_cells_program(workload, topo, toy_cell,
                                               monkeypatch):
    """Each phase metric of the cell reads something from the program the
    cell runs (the federated round at the cell's own size, the train step
    at toy size), and each kernel lies in the phase that owns it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if run.find_cell(workload)["traffic_data"]["driver"] == "fednl_rounds":
        drv, hlo = _round_program(run.find_cell(workload), topo.devices[0])
    else:
        drv, hlo = _step_program(toy_cell(workload), topo.devices[0])
    phases = scopes.scope_instructions([hlo])
    # one second for every instruction of the program
    ops = collections.Counter({f"{name} op": 1.0 for name in phases})
    ops["unscoped.1 op"] = 1.0
    reduced = trace.Reduced(window=(0, 10**9), busy={}, kernels={}, ops=ops,
                            collective_exposed={})
    ctx = dict(trace=reduced, hlo=[hlo], units=100, calls=1)
    for m in PHASE_METRICS:
        if workload in m["workloads"]:
            mod = run.load_module(run.BENCH / "metrics" / f"{m['name']}.py",
                                  "metric")
            value = mod.read(ctx)
            assert value is not None and value > 0, m["name"]
    drv.hlo = [hlo]
    kernels = trace.kernel_instructions([hlo], drv.kernel_tags()["scopes"])
    assert kernels
    for name, tag in kernels.items():
        assert phases.get(name) == KERNEL_PHASE[tag], (name, tag)


RECORDED = os.path.join(DATA, "topk3000.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    with open(os.path.join(DATA, "topk3000.hlo_scopes.txt")) as f:
        hlo = f.read()
    r = trace.reduce_profile(ProfileData.from_file(RECORDED),
                             {"scopes": {}, "hlo": [hlo]}, chips=1)
    seconds, total = scopes.scope_seconds(r, [hlo])
    assert set(seconds) == {"fednl.oracle", "fednl.uplink",
                            "fednl.local_update", "fednl.server",
                            "fednl.solve"}
    assert all(s > 0 for s in seconds.values())
    assert 0 < total <= r.busy_s
    assert scopes.scoped_share(dict(trace=r, hlo=[hlo])) >= 95.0
