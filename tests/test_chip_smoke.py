"""Rehearsal of ``chip_smoke.py`` on the CPU: its phase functions at toy
sizes, with the TPU dispatch taken (``jax.default_backend`` reads "tpu")
and every Pallas kernel run by the TPU interpreter; and its refusal to
report a result without a TPU or without the rest of the repo."""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """The kernels' TPU path on the CPU: the ops dispatch as on a TPU and
    the TPU interpreter runs each pallas_call."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


def _no_result(stdout: str) -> bool:
    return not any(line.startswith("{") for line in stdout.splitlines())


def test_phase_fednl_rehearsal(tpu_dispatch):
    smoke = _load()
    out = smoke.phase_fednl(problem="a1a",
                            cells=(("topk", 2000), ("blocktopk", 4096)),
                            rounds=20, kernels=False)
    assert set(out) == {"fednl:topk2000", "fednl:blocktopk4096"}
    assert all(rel <= smoke.FEDNL_BAND for rel in out.values()), out


def test_phase_train_rehearsal(tpu_dispatch):
    smoke = _load()
    run = smoke.phase_train("qwen2-0.5b", smoke=True, steps=3, batch=4,
                            seq=32, refresh_every=2, curvature_k=256,
                            kernels=False)
    assert run.refreshes == 2 and len(run.losses) == 3


def test_phase_fednl_fails_outside_band(tpu_dispatch):
    smoke = _load()
    with pytest.raises(smoke.PhaseFailed):
        smoke.phase_fednl(problem="a1a", cells=(("topk", 2000),),
                          rounds=2, kernels=False)


def test_phase_c_rehearsal_on_four_host_devices():
    """Phase C on 4 forced host devices (subprocess, so the device count
    does not leak into this session). C1 takes the TPU dispatch; C2 runs
    the CPU kernels, since the TPU interpreter's host callbacks cannot be
    partitioned across devices."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import importlib.util
        import jax
        from jax.experimental.pallas import tpu as pltpu
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        backend = jax.default_backend
        jax.default_backend = lambda: "tpu"
        with pltpu.force_tpu_interpret_mode():
            c1 = smoke.phase_sharded_fednl(problem="a1a", level=2000,
                                           rounds=20, kernels=False)
        jax.default_backend = backend
        c2 = smoke.phase_silo_mesh_train(smoke=True, steps=3, batch=4,
                                         seq=32, curvature_k=256,
                                         kernels=False)
        print("PHASE_C_OK", c1["pair"], c2)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "PHASE_C_OK" in out.stdout, out.stdout[-3000:] + out.stderr[-3000:]


def test_main_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert _no_result(out.stdout), out.stdout
    assert "no TPU" in out.stderr


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert _no_result(out.stdout), out.stdout

