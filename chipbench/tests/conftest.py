"""The benchmark's tests run on the CPU: ``python -m pytest chipbench/tests``
from the root of the checkout."""

import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import pytest  # noqa: E402

@pytest.fixture(autouse=True, scope="session")
def _no_persistent_cache():
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture
def toy_cell(monkeypatch):
    """``toy_cell(workload)``: the cell cut to a toy size (``toy.py``)."""
    from chipbench.tests import toy

    return lambda workload: toy.toy_cell(workload, monkeypatch.setattr)


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """The kernels' TPU path on the CPU: the ops dispatch as on a TPU and
    the TPU interpreter runs each pallas_call."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield
