"""End-to-end system tests: the train driver learns, the serve driver
generates, and the dry-run path lowers+compiles on a host-scale mesh."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.mark.slow
def test_train_driver_learns():
    from repro.launch.train import train

    hist = train("qwen2-0.5b", smoke=True, steps=30, batch=4, seq=64,
                 lr=1e-3, optimizer="adamw", log_every=100).losses
    assert hist[-1] < hist[0] - 0.5, hist[:3] + hist[-3:]


@pytest.mark.slow
def test_train_driver_fednl_optimizer_learns():
    from repro.launch.train import train

    hist = train("qwen2-0.5b", smoke=True, steps=30, batch=4, seq=64,
                 lr=2e-3, optimizer="fednl", log_every=100).losses
    assert hist[-1] < hist[0] - 0.5, hist[:3] + hist[-3:]


def test_train_microbatching_equivalence():
    """k-microbatch accumulation == full-batch step (same grads)."""
    from repro.configs import get_config
    from repro.launch.steps import make_optimizer, make_train_step
    from repro.models import build_model

    cfg = get_config("qwen2-0.5b", smoke=True)
    model = build_model(cfg, use_remat=True)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = make_optimizer("sgd", 1e-2)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}

    p1, _, m1 = jax.jit(make_train_step(model, opt, 1))(
        params, opt.init(params), batch)
    p2, _, m2 = jax.jit(make_train_step(model, opt, 2))(
        params, opt.init(params), batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-5)


def test_serve_driver_generates():
    from repro.launch.serve import generate

    seqs = generate("xlstm-350m", smoke=True, batch=2, prompt_len=8, gen=6)
    assert seqs.shape == (2, 14)
    assert not bool(jnp.any(seqs < 0))


def test_dryrun_smoke_mesh_subprocess():
    """The dry-run path (shardings, lower, compile, cost/memory analysis)
    on an 8-device host mesh with the reduced config."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.dryrun import dryrun_pair
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        row = dryrun_pair("qwen2-0.5b", "train_4k", mesh=mesh, smoke=True,
                          verbose=False, with_probes=False)
        assert row["status"] == "ok", row
        assert row["flops"] > 0 and row["peak_bytes_per_device"] > 0
        row2 = dryrun_pair("granite-moe-1b-a400m", "decode_32k", mesh=mesh,
                           smoke=True, verbose=False, with_probes=False)
        assert row2["status"] == "ok", row2
        print("DRYRUN_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert "DRYRUN_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]


def test_collective_bytes_parser():
    from repro.launch.roofline import collective_bytes

    hlo = """
      %all-gather.1 = f32[16,64]{1,0} all-gather(%x), dimensions={0}
      %ar = (bf16[8,8]{1,0}, bf16[4]{0}) all-reduce(%a, %b)
      %rs.2 = f32[4,4]{1,0} reduce-scatter(%y), dimensions={0}
      %aa = bf16[2,2]{1,0} all-to-all(%z)
      %cp-start = f32[10]{0} collective-permute-start(%w)
      %cp-done = f32[10]{0} collective-permute-done(%cp-start)
      %notacoll = f32[100]{0} add(%p, %q)
    """
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 64 * 4
    assert out["all-reduce"] == 8 * 8 * 2 + 4 * 2
    assert out["reduce-scatter"] == 4 * 4 * 4
    assert out["all-to-all"] == 2 * 2 * 2
    assert out["collective-permute"] == 10 * 4  # start counted, done not


def test_skip_reasons_match_design():
    from repro.configs import get_config
    from repro.launch.shapes import SHAPES, skip_reason

    runs_500k = {a for a in
                 ["jamba-1.5-large-398b", "xlstm-350m", "starcoder2-15b",
                  "starcoder2-3b"]}
    from repro.configs import ARCHS

    for arch in ARCHS:
        cfg = get_config(arch)
        r = skip_reason(cfg, SHAPES["long_500k"])
        assert (r is None) == (arch in runs_500k), (arch, r)
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert skip_reason(cfg, SHAPES[s]) is None
