"""The reduction from a profiler trace to per-layer numbers: on a
synthetic trace whose numbers are checked by hand, and on a small trace
recorded on a TPU v5e with the harness's own window (the BlockTopK
federated cell, four rounds a call)."""

import os

import pytest
from jax.profiler import ProfileData

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")

HLO = ('  %block_scatter_accumulate.9 = f32[384,384]{1,0} custom-call(%a, %b), '
       'custom_call_target="tpu_custom_call", '
       'metadata={op_name="jit(f)/while/body/jit(block_scatter_accumulate)'
       '/pallas_call" stack_frame_id=8}\n'
       '  %fusion.1 = f32[8]{0} fusion(%c), kind=kLoop\n')
TAGS = {"scopes": {"block_scatter": "block_scatter_accumulate",
                   "diff_topk_payload": "_diff_topk_payload_impl"},
        "hlo": [HLO]}


def _event(mid, start_ns, dur_ns):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _synthetic():
    return ProfileData.from_text_proto(_synthetic_proto())


def _synthetic_proto() -> str:
    ops = {1: "fusion.1", 2: "all-reduce.3", 3: "block_scatter_accumulate.9"}
    spans = {1: "bench.window", 2: "bench.dispatch", 3: "bench.final_sync"}
    meta = lambda d: " ".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
        for k, v in d.items())
    return (f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_event(1, 1000, 2000)} {_event(2, 2000, 3000)} {_event(3, 6000, 1000)}
    {_event(1, 12000, 500)} }}
  {meta(ops)} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_event(1, 0, 10000)} {_event(2, 0, 1500)} {_event(3, 6500, 3500)} }}
  {meta(spans)} }}
""")


def test_kernel_instructions_from_hlo():
    assert trace.kernel_instructions([HLO], TAGS["scopes"]) == {
        "block_scatter_accumulate.9": "block_scatter"}


def test_synthetic_trace_by_hand():
    r = trace.reduce_profile(_synthetic(), TAGS, chips=1)
    assert r.window_s == pytest.approx(10e-6)
    # busy: [1000, 5000] and [6000, 7000]; the op at 12000 is after the window
    assert r.busy_s == pytest.approx(5e-6)
    assert r.kernels == {"block_scatter": {"seconds": pytest.approx(1e-6),
                                           "events": 1}}
    # the all-reduce runs alone on [3000, 5000]
    assert r.collective_exposed[0] == pytest.approx(2e-6)
    assert r.idle_gaps() == [("bench.final_sync", pytest.approx(3e-6)),
                             ("bench.dispatch", pytest.approx(1e-6)),
                             ("host: none", pytest.approx(1e-6))]
    b = r.breakdown()
    assert b["device_ops"][0] == ["all-reduce.3", pytest.approx(3e-6)]
    assert len(b["idle_gaps"]) == 3


@pytest.mark.parametrize("event, want", [
    # a TPU v5e names an op event by the instruction's whole text
    ("%block_scatter_accumulate.9 = f32[384,384]{1,0:T(8,128)S(1)} "
     "custom-call(f32[9,142,1024]{2,1,0} %bitcast.224), "
     'custom_call_target="tpu_custom_call"',
     ("block_scatter_accumulate.9", "custom-call")),
    ("%sort.11 = (s32[426000]{0:T(1024)S(1)}, f32[426000]{0:T(1024)S(1)}) "
     "sort(s32[426000]{0:T(1024)S(1)} %custom-call.97), dimensions={0}",
     ("sort.11", "sort")),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.2), kind=kLoop",
     ("fusion.3", "fusion")),
    ("fusion.1", ("fusion.1", "")),
])
def test_instruction_from_event_name(event, want):
    assert trace.instruction(event) == want


def test_fused_kernel_attributed():
    hlo = ('%fused_computation.4 (param_0: f32[8]) -> f32[384,384] {\n'
           '  %param_0 = f32[8]{0} parameter(0)\n'
           '  ROOT %block_scatter_accumulate.2 = f32[384,384]{1,0} '
           'custom-call(%param_0), custom_call_target="tpu_custom_call", '
           'metadata={op_name="jit(f)/jit(block_scatter_accumulate)/pallas_call"}\n'
           '}\n\n'
           'ENTRY %main (p: f32[8]) -> f32[384,384] {\n'
           '  %p = f32[8]{0} parameter(0)\n'
           '  ROOT %fusion.7 = f32[384,384]{1,0} fusion(%p), kind=kCustom, '
           'calls=%fused_computation.4\n'
           '}\n')
    assert trace.kernel_instructions([hlo], TAGS["scopes"]) == {
        "block_scatter_accumulate.2": "block_scatter",
        "fusion.7": "block_scatter"}


def test_whole_text_event_names():
    """Events named by whole instruction text: the kernel is found by its
    name, and an op that merely reads a collective's result is compute."""
    whole = {1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), "
                "kind=kLoop",
             2: "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %p), "
                "to_apply=%add",
             3: "%block_scatter_accumulate.9 = f32[384,384]{1,0} "
                "custom-call(f32[8]{0} %a), kind=kCustom"}
    text = _synthetic_proto().replace('name: "fusion.1"',
                                      f'name: "{whole[1]}"')
    text = text.replace('name: "all-reduce.3"', f'name: "{whole[2]}"')
    text = text.replace('name: "block_scatter_accumulate.9"',
                        f'name: "{whole[3]}"')
    r = trace.reduce_profile(ProfileData.from_text_proto(text), TAGS, chips=1)
    assert r.kernels == {"block_scatter": {"seconds": pytest.approx(1e-6),
                                           "events": 1}}
    assert r.collective_exposed[0] == pytest.approx(2e-6)
    assert r.breakdown()["device_ops"][0] == [
        "all-reduce.3 all-reduce", pytest.approx(3e-6)]


def test_trace_without_window_is_refused():
    pd = ProfileData.from_text_proto(
        'planes { id: 1 name: "/device:TPU:0" lines { id: 1 name: "XLA Ops" '
        'events { metadata_id: 1 offset_ps: 0 duration_ps: 5 } } '
        'event_metadata { key: 1 value { id: 1 name: "fusion.1" } } }')
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_profile(pd, TAGS, chips=1)


RECORDED = os.path.join(DATA, "blocktopk1024.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    with open(os.path.join(DATA, "blocktopk1024.hlo_kernels.txt")) as f:
        hlo = f.read()
    tags = dict(TAGS, hlo=[hlo])
    r = trace.reduce_profile(ProfileData.from_file(RECORDED), tags, chips=1)
    assert 0 < r.busy_s <= r.window_s
    # one payload kernel and one block scatter per round
    ev = {t: k["events"] for t, k in r.kernels.items()}
    assert ev["diff_topk_payload"] == ev["block_scatter"] > 0
    assert ev["diff_topk_payload"] % 4 == 0
    assert all(k["seconds"] > 0 for k in r.kernels.values())
    kernel_s = sum(k["seconds"] for k in r.kernels.values())
    assert kernel_s <= r.busy_s
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
