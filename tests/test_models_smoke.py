"""Per-architecture smoke tests (required): REDUCED variant of each family
(2 layers, d_model <= 512, <= 4 experts) — one forward + one train step on
CPU asserting output shapes and no NaNs; plus decode-vs-forward parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.launch.steps import make_optimizer, make_serve_step, make_train_step
from repro.models import build_model

# ~1 min of compile-heavy smoke across 10 architectures: slow lane only
pytestmark = pytest.mark.slow


def _batch(cfg, b=2, t=32, seed=0):
    key = jax.random.PRNGKey(seed)
    toks = jax.random.randint(key, (b, t), 0, cfg.vocab)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (b, cfg.vision_tokens, cfg.d_model)) * 0.02
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (b, cfg.enc_seq, cfg.d_model)) * 0.02
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.num_experts <= 4
    model = build_model(cfg, use_remat=True)
    params = model.init_params(jax.random.PRNGKey(0))
    b, t = 2, 32
    batch = _batch(cfg, b, t)

    logits, aux = model.forward(params, batch)
    t_total = t + (cfg.vision_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (b, t_total, cfg.vocab)
    assert not bool(jnp.isnan(logits).any())

    opt = make_optimizer("adamw", 1e-3)
    state = opt.init(params)
    step = jax.jit(make_train_step(model, opt))
    p2, s2, metrics = step(params, state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # one more step must not NaN and should usually reduce loss
    p3, s3, m3 = step(p2, s2, batch)
    assert np.isfinite(float(m3["loss"]))
    assert float(m3["loss"]) < float(metrics["loss"]) + 0.5


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg, use_remat=False)
    params = model.init_params(jax.random.PRNGKey(0))
    b, cache_len = 2, 16
    cache = model.init_cache(b, cache_len)
    if cfg.family == "encdec":
        cache["enc"] = jnp.zeros((b, cfg.enc_seq, cfg.d_model))
    serve = jax.jit(make_serve_step(model))
    tok = jnp.ones((b, 1), jnp.int32)
    logits, cache2 = serve(params, cache, tok, jnp.asarray(3, jnp.int32))
    assert logits.shape == (b, 1, cfg.vocab)
    assert not bool(jnp.isnan(logits).any())
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


NON_MOE = [a for a in ARCHS if get_config(a, smoke=True).moe is None
           and get_config(a).family != "encdec"]


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_fednl_five_steps_decreasing(arch):
    """5 real fednl steps through the LAUNCH DRIVER (sharded params +
    opt state, curvature refresh every 2 steps, preconditioned updates)
    on every arch in the zoo: finite, decreasing loss. Each step draws a
    fresh batch, so the decrease is read on one batch: the first
    batch's loss after the 5 steps is below its loss before them."""
    from repro.data.tokens import TokenPipeline
    from repro.launch.train import add_modality_inputs, train

    run = train(arch, smoke=True, steps=5, batch=4, seq=32, lr=1e-3,
                optimizer="fednl", log_every=10, refresh_every=2,
                curvature_k=256)
    hist = run.losses
    assert len(hist) == 5 and all(np.isfinite(h) for h in hist), hist
    cfg = get_config(arch, smoke=True)
    first = add_modality_inputs(TokenPipeline(
        vocab_size=cfg.vocab, seq_len=32, global_batch=4, seed=0).batch(0),
        cfg, 0)
    after = float(jax.jit(build_model(cfg, use_remat=True).loss_fn)(
        run.params, first))
    assert after < hist[0], (after, hist)


@pytest.mark.parametrize("arch", NON_MOE)
def test_decode_matches_forward(arch):
    """Teacher-forced forward logits == step-by-step decode logits.
    (MoE archs excluded: capacity-based dropping differs between the
    prefill group size and the single-token decode group — documented.)"""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg, use_remat=False)
    params = model.init_params(jax.random.PRNGKey(0))
    b, t = 2, 20
    toks = jax.random.randint(jax.random.PRNGKey(7), (b, t), 0, cfg.vocab)
    batch = {"tokens": toks, "targets": toks}
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros((b, cfg.vision_tokens, cfg.d_model))
        pytest.skip("vlm decode starts after the patch prefix; covered by "
                    "smoke decode")
    logits_fwd, _ = model.forward(params, batch)
    cache = model.init_cache(b, t)
    serve = jax.jit(make_serve_step(model))
    for pos in range(t):
        lg, cache = serve(params, cache, toks[:, pos:pos + 1],
                          jnp.asarray(pos, jnp.int32))
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(logits_fwd[:, pos]),
                                   atol=2e-3, rtol=2e-3)


def test_sliding_window_masks_old_tokens():
    cfg = get_config("starcoder2-3b", smoke=True)  # window 16 in smoke
    assert cfg.sliding_window == 16
    model = build_model(cfg, use_remat=False)
    params = model.init_params(jax.random.PRNGKey(0))
    t = 40
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, t), 0, cfg.vocab)
    logits, _ = model.forward(params, {"tokens": toks, "targets": toks})
    # changing a token > window positions in the past must not affect logits
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % cfg.vocab)
    logits2, _ = model.forward(params, {"tokens": toks2, "targets": toks2})
    np.testing.assert_allclose(np.asarray(logits[0, -1]),
                               np.asarray(logits2[0, -1]), atol=1e-4)


def test_moe_router_balance_loss_positive():
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    model = build_model(cfg, use_remat=False)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    _, aux = model.forward(params, batch)
    assert float(aux) >= 0.9  # >= 1 at perfect balance, ~E at collapse


def test_param_counts_match_analytic():
    """Analytic count (roofline MODEL_FLOPS) ~ actual init within 2%."""
    from repro.launch.roofline import count_params

    for arch in ["qwen2-0.5b", "granite-moe-1b-a400m", "xlstm-350m"]:
        cfg = get_config(arch, smoke=True)
        model = build_model(cfg)
        params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        est = count_params(cfg)
        assert abs(actual - est) / actual < 0.02, (arch, actual, est)
