"""Each cell's run end to end at toy size on the CPU, the Pallas kernels
taken on their TPU path under the TPU interpreter."""

import json
import os

import pytest

from chipbench import run

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload, toy_cell, tpu_dispatch):
    cell = toy_cell(workload)
    r = run.run_cell(cell, 2**31 + 5, 0.5, False, require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    names = {m["name"] for m in cell["end_to_end"]}
    assert set(r["metrics"]) == names
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == 1


@pytest.mark.parametrize("workload", CELLS)
def test_same_seed_same_inputs(workload, toy_cell):
    """The first steps' readings depend on the seed alone."""
    cell = toy_cell(workload)
    a = run.make_driver(cell, 12345)
    b = run.make_driver(cell, 12345)
    a.setup()
    b.setup()
    if hasattr(a, "first"):
        assert a.first == b.first
    else:
        import numpy as np

        np.testing.assert_array_equal(np.asarray(a.a), np.asarray(b.a))
