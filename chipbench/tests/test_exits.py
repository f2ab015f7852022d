"""The harness reports nothing without a TPU, on an unknown device kind,
or where the program is absent."""

import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run

CELL = "fednl-w8a.topk3000"


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out):
    return not any(line.startswith("{") for line in out.stdout.splitlines())


def test_exits_without_tpu():
    out = _run(run.ROOT)
    assert out.returncode != 0 and _no_result(out)
    assert "no TPU" in out.stderr


def test_exits_with_only_the_benchmark(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0 and _no_result(out)


class _Dev:
    platform = "tpu"
    device_kind = "TPU v9 imaginary"


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(run.Refused, match="not in chipbench/peaks.json"):
        run.devices_for(1)


def test_too_few_chips_is_refused(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(run.Refused, match="needs 4 chips"):
        run.devices_for(4)


def test_unknown_workload_is_refused():
    with pytest.raises(run.Refused, match="unknown workload"):
        run.find_cell("no-such.cell")
