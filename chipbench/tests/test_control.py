"""The control of each cell comes out not correct: the reference in the
program's place one precision step below the configured one (toy size on
the CPU; ``control.py`` takes the same readings on the chip at the
cell's own size)."""

import pytest

from chipbench import control, run
from chipbench.tests.toy import benchmark_cells


@pytest.mark.parametrize("workload", benchmark_cells("fednl_rounds"))
def test_fednl_control_is_not_correct(workload, toy_cell):
    cell = toy_cell(workload)
    r = run.run_cell(cell, 77, 0.5, False, require_tpu=False,
                     driver_kw={"control": True})
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("workload", benchmark_cells("train_steps"))
def test_train_control_is_not_correct(workload, toy_cell):
    cell = toy_cell(workload)
    readings = list(control.training(cell, [], [78], require_tpu=False))
    kinds = {r["kind"]: r["numbers"] for r in readings}
    for kind in ("control", "fault:half_batch"):
        assert any(v > cell["limits"][k] for k, v in kinds[kind].items()), \
            (kind, kinds[kind])
