"""Data pipeline, checkpointing, optimizers, FedNL preconditioner, and the
shard_map federated runtime."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore, save
from repro.core import FedNL, RankR
from repro.core.federated import run_fednl_sharded
from repro.core.objectives import batch_grad, batch_hess
from repro.data.libsvm import parse_libsvm, partition_across_silos
from repro.data.synthetic import make_iid, make_libsvm_like, make_synthetic
from repro.data.tokens import TokenPipeline
from repro.second_order import adamw, fednl_precond, sgd
from repro.second_order.fednl_precond import FedNLPrecondOptimizer, FedNLPrecondState
from repro.second_order.optim import apply_updates


# -- data ---------------------------------------------------------------------


def test_synthetic_shapes_and_labels():
    data = make_synthetic(jax.random.PRNGKey(0), 1.0, 1.0, n=5, m=11, d=7)
    assert data.a.shape == (5, 11, 7) and data.b.shape == (5, 11)
    assert set(np.unique(np.asarray(data.b))) <= {-1.0, 1.0}


def test_heterogeneity_increases_spread():
    """Synthetic(alpha, beta) with larger alpha/beta => more diverse silo
    optima (the knob Fig. 14 turns)."""

    def spread(alpha, beta):
        data = make_synthetic(jax.random.PRNGKey(1), alpha, beta, n=6, m=40,
                              d=10)
        hess = batch_hess(jnp.zeros(10), data)
        hbar = jnp.mean(hess, axis=0)
        return float(jnp.mean(jnp.sum((hess - hbar) ** 2, (-2, -1))))

    assert spread(10.0, 10.0) > spread(0.0, 0.0)


def test_libsvm_parser_roundtrip():
    text = "+1 1:0.5 3:1.0\n-1 2:2.0\n+1 1:1.0 2:1.0 3:1.0\n-1 3:0.25\n"
    a, b = parse_libsvm(text, d=3)
    np.testing.assert_allclose(a[0], [0.5, 0.0, 1.0])
    np.testing.assert_allclose(b, [1, -1, 1, -1])
    data = partition_across_silos(a, b, n=2)
    assert data.a.shape == (2, 2, 3)


def test_libsvm_like_shapes_match_table3():
    data = make_libsvm_like(jax.random.PRNGKey(0), "a1a")
    assert data.a.shape == (16, 100, 123)


def test_token_pipeline_deterministic_and_sharded_shape():
    pipe = TokenPipeline(vocab_size=100, seq_len=32, global_batch=8, seed=1)
    b1, b2 = pipe.batch(3), pipe.batch(3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert b1["tokens"].shape == (8, 32)
    assert int(b1["tokens"].max()) < 100
    # targets are next-token shifted
    np.testing.assert_array_equal(np.asarray(b1["targets"][:, :-1]),
                                  np.asarray(b1["tokens"][:, 1:]))


# -- checkpoint ----------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
            "b": [jnp.ones(4), {"c": jnp.zeros((2, 2))}]}
    save(str(tmp_path / "ck"), tree, step=7)
    restored, step = restore(str(tmp_path / "ck"), tree)
    assert step == 7
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32))
        assert x.dtype == y.dtype


# -- optimizers -----------------------------------------------------------------


def _quad_loss(params):
    return sum(jnp.sum((p - 3.0) ** 2) for p in jax.tree.leaves(params))


@pytest.mark.parametrize("make_opt", [
    lambda: sgd(0.1, momentum=0.9),
    lambda: adamw(0.05, weight_decay=0.0),
    lambda: fednl_precond(0.5, k_per_block=16, block=8),
])
def test_optimizers_minimize_quadratic(make_opt):
    opt = make_opt()
    params = {"w": jnp.zeros((4, 4)), "b": jnp.zeros(3)}
    state = opt.init(params)
    for _ in range(120):
        grads = jax.grad(_quad_loss)(params)
        upd, state = opt.update(grads, state, params)
        params = apply_updates(params, upd)
    assert _quad_loss(params) < 1e-2 * _quad_loss({"w": jnp.zeros((4, 4)),
                                                   "b": jnp.zeros(3)})


def test_fednl_precond_learns_curvature():
    """On a fixed quadratic the learned diagonal H tracks the (constant)
    Fisher-style observation via the compressed rule."""
    opt = FedNLPrecondOptimizer(lr=0.1, alpha=1.0, k_per_block=64, block=8)
    params = {"w": jnp.ones((8, 8))}
    state = opt.init(params)
    grads = {"w": jnp.full((8, 8), 2.0)}
    for _ in range(5):
        _, state = opt.update(grads, state, params)
    # observation D = g^2 = 4; k_per_block=64 = whole block => exact learn
    np.testing.assert_allclose(np.asarray(state.h["w"]), 4.0, atol=1e-5)


def test_fednl_precond_hutchinson_without_probe_raises():
    """Regression: curvature='hutchinson' with no hvp probe used to
    silently fall back to the Fisher diagonal — it must refuse, naming
    the missing probe."""
    opt = FedNLPrecondOptimizer(curvature="hutchinson")
    grads = {"w": jnp.ones((4, 4))}
    with pytest.raises(ValueError, match="hvp"):
        opt.observe(grads)
    with pytest.raises(ValueError, match="hutchinson"):
        opt.update(grads, opt.init(grads), grads)  # observe() inside
    # with the probe supplied, D = z * (H z)
    z = {"w": jnp.full((4, 4), 2.0)}
    hz = {"w": jnp.full((4, 4), 3.0)}
    obs = opt.observe(grads, hvp=(z, hz))
    np.testing.assert_allclose(np.asarray(obs["w"]), 6.0)


def test_fednl_precond_update_rule_matches_docstring():
    """Numeric pin of the documented Option-2 step
        l = ||D - H||_F / sqrt(numel)
        u = -lr * g / (sqrt(max(H, 0)) + sqrt(l) + eps)
    — the sqrt (Adam-consistent) denominator, including the max(H, 0)
    clamp on a negative curvature entry. momentum=0 and alpha=0 isolate
    the raw preconditioned step."""
    lr, eps = 0.2, 1e-8
    opt = FedNLPrecondOptimizer(lr=lr, alpha=0.0, momentum=0.0,
                                k_per_block=64, block=8, eps=eps)
    h0 = jnp.array([[4.0, 9.0], [-2.0, 0.0]])
    g = jnp.array([[1.0, -2.0], [3.0, 4.0]])
    params = {"w": jnp.zeros((2, 2))}
    state = FedNLPrecondState(jnp.zeros((), jnp.int32), {"w": h0},
                              {"w": jnp.zeros((2, 2))})
    obs = {"w": jnp.full((2, 2), 5.0)}
    upd, _ = opt.update({"w": g}, state, params, observations=obs)
    l = np.linalg.norm(np.asarray(obs["w"] - h0)) / 2.0  # /sqrt(numel=4)
    want = -lr * np.asarray(g) / (np.sqrt(np.maximum(np.asarray(h0), 0.0))
                                  + np.sqrt(l) + eps)
    np.testing.assert_allclose(np.asarray(upd["w"]), want, rtol=1e-5)


def test_fednl_precond_refresh_precondition_consistent_with_update():
    """The amortized protocol pin: ``refresh`` learns exactly the H (and
    ridge l) that ``update(..., observations=...)`` stores, while
    touching nothing else — step and mu come back bit-identical — and
    ``precondition`` on quiet steps reproduces ``update``'s no-obs step
    from that stored state. This is the contract ``make_train_step``'s
    lax.cond refresh gate relies on."""
    opt = FedNLPrecondOptimizer(lr=0.1, alpha=0.5, momentum=0.9,
                                k_per_block=16, block=8)
    params = {"w": jnp.zeros((8, 8)), "b": jnp.zeros(5)}
    grads = {"w": jnp.ones((8, 8)), "b": jnp.full(5, 2.0)}
    obs = opt.observe(grads)

    # the monolithic path: one update that both learns and steps
    s0 = opt.init(params)
    _, s_upd = opt.update(grads, s0, params, observations=obs)

    # the amortized path: refresh (learn only), then precondition (step)
    s_ref = opt.refresh(s0, obs)
    for leaf_u, leaf_r in zip(jax.tree.leaves(s_upd.h),
                              jax.tree.leaves(s_ref.h)):
        np.testing.assert_allclose(np.asarray(leaf_u), np.asarray(leaf_r))
    for leaf_u, leaf_r in zip(jax.tree.leaves(s_upd.l),
                              jax.tree.leaves(s_ref.l)):
        np.testing.assert_allclose(np.asarray(leaf_u), np.asarray(leaf_r))
    # refresh is learning-only: step and momentum are untouched
    assert int(s_ref.step) == int(s0.step)
    for leaf_0, leaf_r in zip(jax.tree.leaves(s0.mu),
                              jax.tree.leaves(s_ref.mu)):
        np.testing.assert_array_equal(np.asarray(leaf_0), np.asarray(leaf_r))

    # update's own step is precondition on the PRE-learning h with the
    # CURRENT observation's l (the documented legacy blend)
    upd_a, _ = opt.update(grads, s0, params, observations=obs)
    upd_b, s_b = opt.precondition(grads, s0._replace(l=s_upd.l), params)
    for leaf_a, leaf_b in zip(jax.tree.leaves(upd_a),
                              jax.tree.leaves(upd_b)):
        np.testing.assert_allclose(np.asarray(leaf_a), np.asarray(leaf_b))
    assert int(s_upd.step) == int(s_b.step) == 1


def test_fednl_precond_pallas_path_builds_no_dense_selection_mask():
    """Acceptance: with the Pallas payload ops forced (the TPU path,
    trace-only so it runs anywhere), the jaxpr of ``update`` contains
    no intermediate with a block^2 = 16384 trailing dim outside
    pallas_call bodies — neither the dense selection mask nor the dense
    per-tile scatter round-trip exists in the training step. The jaxpr
    walk lives in ``repro.analysis`` (the ``no-dense-roundtrip`` rule —
    the registry sweep applies it to every precond/kernel target); this
    test keeps the original call sites pinned plus the codec-compress
    positive control proving the detector sees such masks."""
    from repro import analysis

    d, block = 256, 128
    opt = FedNLPrecondOptimizer(lr=0.1, k_per_block=32, block=block,
                                use_pallas=True)
    params = {"w": jnp.zeros((d, d))}
    state = opt.init(params)
    grads = {"w": jnp.ones((d, d))}

    analysis.check(lambda g, s: opt.update(g, s, params), grads, state,
                   rules=["no-dense-roundtrip"], context={"block": block})

    obs = {"w": jnp.ones((3, d, d))}
    analysis.check(lambda g, s, o: opt.update(g, s, params, observations=o),
                   grads, state, obs,
                   rules=["no-dense-roundtrip"], context={"block": block})

    # positive control: the jnp codec DOES build (nblocks, block^2)
    comp = opt.compressor
    violations = analysis.check(
        lambda m: comp.decompress(comp.compress(m), m.shape), grads["w"],
        rules=["no-dense-roundtrip"], context={"block": block},
        raise_on_violation=False)
    assert violations
    assert {v.rule for v in violations} == {"no-dense-roundtrip"}


# -- shard_map federated runtime -------------------------------------------------


def test_fednl_sharded_matches_vmap_single_device():
    data = make_iid(jax.random.PRNGKey(0), n=4, m=30, d=10)
    grad_fn = lambda x: batch_grad(x, data)
    hess_fn = lambda x: batch_hess(x, data)
    x0 = jnp.ones(10) * 0.3

    alg_plain = FedNL(grad_fn, hess_fn, RankR(1), option=2)
    _, xs_plain = alg_plain.run(x0, 4, 6)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    _, xs_sh = run_fednl_sharded(data, RankR(1), mesh, x0, 6, option=2)
    np.testing.assert_allclose(np.asarray(xs_plain), np.asarray(xs_sh),
                               atol=2e-4)  # reduction-order noise in f32


def test_fednl_sharded_multidevice_subprocess():
    """Real 4-way sharding equivalence, in a subprocess so the forced
    device count doesn't leak into this test session."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import FedNL, RankR
        from repro.core.federated import run_fednl_sharded
        from repro.core.objectives import batch_grad, batch_hess
        from repro.data.synthetic import make_synthetic

        data = make_synthetic(jax.random.PRNGKey(0), 0.5, 0.5, n=8, m=30, d=10)
        grad_fn = lambda x: batch_grad(x, data)
        hess_fn = lambda x: batch_hess(x, data)
        x0 = jnp.ones(10) * 0.3
        alg = FedNL(grad_fn, hess_fn, RankR(1), option=2)
        _, xs_plain = alg.run(x0, 8, 6)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        _, xs_sh = run_fednl_sharded(data, RankR(1), mesh, x0, 6, option=2)
        np.testing.assert_allclose(np.asarray(xs_plain), np.asarray(xs_sh),
                                   atol=1e-4)
        print("SHARDED_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in out.stdout, out.stdout + out.stderr


def test_mesh_paths_refuse_what_cannot_shard_subprocess():
    """On 4 forced host devices, a sweep cell whose silo count does not
    divide the mesh, a cell that has no sharded path, and a training
    batch that does not divide the data axis all raise instead of
    running on one device."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from repro.core.objectives import batch_grad, batch_hess, global_value
        from repro.data.synthetic import make_synthetic
        from repro.engine import ExperimentSpec, Sweep
        from repro.launch.mesh import make_mesh
        from repro.launch.train import train

        data = make_synthetic(jax.random.PRNGKey(0), 0.5, 0.5, n=6, m=20,
                              d=8)
        prob = dict(grad=lambda x: batch_grad(x, data),
                    hess=lambda x: batch_hess(x, data),
                    val=lambda x: global_value(x, data), n=6, d=8,
                    data=data)
        mesh = make_mesh((4,), ("data",))
        for spec, why in [
                (ExperimentSpec("fednl", "topk", 8, num_rounds=2),
                 "do not divide"),
                (ExperimentSpec("fednl-pp", "topk", 8, num_rounds=2,
                                params=dict(tau=2)), "only 'fednl'")]:
            try:
                Sweep([spec], mesh=mesh).run(prob, x0=jnp.zeros(8))
            except ValueError as e:
                assert why in str(e), e
            else:
                raise AssertionError(f"{spec.label} ran on the mesh")
        try:
            train("qwen2-0.5b", smoke=True, steps=1, batch=6, seq=16,
                  optimizer="fednl", curvature_k=64)
        except ValueError as e:
            assert "does not divide" in str(e), e
        else:
            raise AssertionError("batch 6 trained on a 4-way data axis")
        print("REFUSED_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "REFUSED_OK" in out.stdout, out.stdout + out.stderr
