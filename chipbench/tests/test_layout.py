"""Every configuration, traffic mix, limits file and per-layer metric of
``BENCHMARK.json`` loads by name from its directory, and a metric whose
kernel is absent from the trace reports nothing, not 0."""

import collections
import json
import os

import pytest

from chipbench import run
from chipbench.trace import Reduced

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(name):
    cell = run.find_cell(name)
    assert cell["traffic_data"]["driver"]
    assert (run.BENCH / "drivers" / f"{cell['traffic_data']['driver']}.py"
            ).exists()
    assert cell["limits"] and all(v > 0 for v in cell["limits"].values())
    assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    data = run.load_json(run.ROOT / cfg["file"])
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    assert (run.ROOT / data["reference"]).exists()


def _empty_ctx():
    tr = Reduced(window=(0, 10**9), busy={0: [[0, 5 * 10**8]]}, kernels={},
                 ops=collections.Counter(), collective_exposed={0: 0.0})
    return dict(trace=tr, counts={}, calls=3, window_s=1.0, units=300,
                flops_per_unit=1e9, chips=1,
                peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader(m):
    mod = run.load_module(run.BENCH / "metrics" / f"{m['name']}.py", "metric")
    value = mod.read(_empty_ctx())
    if "roofline" in m["name"]:
        assert value is None  # nothing of its kind in the trace
    else:
        assert value is not None and 0 < value < 100


def test_roofline_reads_a_present_kernel():
    ctx = _empty_ctx()
    ctx["trace"].kernels["block_scatter"] = {"seconds": 1e-3, "events": 10}
    ctx["counts"]["block_scatter"] = {"flops": 0.0, "bytes": 819e9 * 1e-4}
    mod = run.load_module(run.BENCH / "metrics" /
                          "block_scatter_roofline.round.py", "metric")
    assert mod.read(ctx) == pytest.approx(10.0)
