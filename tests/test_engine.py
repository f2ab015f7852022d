"""Engine contract tests: registry round-trip, vmapped sweeps vs serial
runs, and bits accounting pinned to the seed-era (pre-refactor) values."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedNL, FedNLBC, FedNLCR, FedNLLS, FedNLPP, RankR, TopK
from repro.core.objectives import batch_grad, batch_hess, global_value
from repro.data.synthetic import make_synthetic
from repro.engine import (
    ExperimentSpec,
    Oracles,
    Sweep,
    available_methods,
    build_compressor,
    make_method,
)

D, N = 12, 8


@pytest.fixture(scope="module")
def problem():
    with jax.enable_x64(True):
        data = make_synthetic(jax.random.PRNGKey(0), alpha=0.5, beta=0.5,
                              n=N, m=40, d=D, lam=1e-3)
        data = data._replace(a=data.a.astype(jnp.float64),
                             b=data.b.astype(jnp.float64))
        grad_fn = lambda x: batch_grad(x, data)
        hess_fn = lambda x: batch_hess(x, data)
        val_fn = lambda x: global_value(x, data)
        yield dict(data=data, grad=grad_fn, hess=hess_fn, val=val_fn,
                   n=N, d=D, fstar=0.0)


def _oracles(problem):
    return Oracles(value=problem["val"], grad=problem["grad"],
                   hess=problem["hess"])


# Per-method construction params for the registry round-trip. Every key
# of available_methods() must appear here — a new method without a
# working factory fails this test.
def _roundtrip_params(d):
    from repro.core import CohortSpec

    topk = ("topk", d)
    return {
        "fednl": dict(option=1, mu=1e-3),
        "fednl-pp": dict(tau=2),
        "fednl-cohort": dict(cohort=CohortSpec(cohort=3)),
        "fednl-cr": dict(l_star=1.0),
        "fednl-ls": dict(mu=1e-3),
        "fednl-bc": dict(model_compressor=topk, p=0.9, option=1, mu=1e-3),
        "fednl-ppbc": dict(model_compressor=topk, tau=2),
        "fednl-stoch": dict(alpha=0.5),
        "newton": dict(),
        "ns": dict(h_fixed=jnp.eye(d)),
        "n0": dict(mu=1e-3),
        "n0-ls": dict(mu=1e-3),
    }


def test_registry_round_trip(problem):
    """Every registered method is constructible by name and survives a
    2-round run through the shared driver."""
    with jax.enable_x64(True):
        params = _roundtrip_params(D)
        x0 = jnp.zeros(D, jnp.float64)
        comp = build_compressor("rankr", 1)
        missing = [m for m in available_methods() if m not in params]
        assert not missing, f"no round-trip params for {missing}"
        for name in available_methods():
            method = make_method(name, _oracles(problem), comp, **params[name])
            final, xs = method.run(x0, N, 2)
            assert xs.shape == (3, D), (name, xs.shape)
            assert bool(jnp.all(jnp.isfinite(xs))), name
            assert np.asarray(xs[0] == x0).all(), name  # x0 prepended
            # the full Method protocol, not just run(): a registered
            # method without bits accounting would crash every Sweep
            b = method.bits_per_round(D)
            assert (sum(b) if isinstance(b, tuple) else b) >= 0, name


def test_make_method_unknown_name(problem):
    with pytest.raises(KeyError, match="unknown method"):
        make_method("not-a-method", _oracles(problem))


def test_vmapped_sweep_matches_serial_runs(problem):
    """Acceptance: a 3-seed x 4-level fig3-style sweep runs as one
    vmapped jitted program per cell and matches per-seed serial results
    to float64 tolerance.

    Not bitwise: batched eigh/svd take different LAPACK paths than the
    unbatched calls (O(eps) output differences), and a far-from-x*
    transient can amplify those through compressor tie-breaks. In the
    fig3 regime (start in the local basin) the measured worst case is
    ~3e-14; 1e-10 leaves margin while staying firmly float64."""
    with jax.enable_x64(True):
        x0 = jnp.zeros(D, jnp.float64)
        seeds, rounds = (0, 1, 2), 8
        specs = [ExperimentSpec("fednl", "rankr", lvl,
                                params=dict(option=1, mu=1e-3),
                                seeds=seeds, num_rounds=rounds)
                 for lvl in (1, 2, 3, 4)]
        res = Sweep(specs).run(problem, x0=x0)
        assert len(res.cells) == 4
        for cell in res.cells:
            assert cell.xs.shape == (len(seeds), rounds + 1, D)
            alg = FedNL(problem["grad"], problem["hess"],
                        RankR(int(cell.spec.level)), option=1, mu=1e-3)
            for si, seed in enumerate(seeds):
                _, xs_serial = alg.run(x0, N, rounds, seed=seed)
                np.testing.assert_allclose(cell.xs[si],
                                           np.asarray(xs_serial),
                                           rtol=0, atol=1e-10)


def test_sweep_distinct_seeds_distinct_trajectories(problem):
    """Randomized compressors must actually fold the seed in — identical
    trajectories across seeds would mean the vmap axis is dead."""
    with jax.enable_x64(True):
        x0 = jnp.full((D,), 0.5, jnp.float64)
        spec = ExperimentSpec("fednl", "randk", 40,
                              params=dict(option=2, alpha=0.5),
                              seeds=(0, 1), num_rounds=4)
        cell = Sweep([spec]).run(problem, x0=x0).cells[0]
        assert np.abs(cell.xs[0, 1:] - cell.xs[1, 1:]).max() > 0


def test_sweep_records_and_summary(problem):
    with jax.enable_x64(True):
        spec = ExperimentSpec("fednl", "rankr", 1,
                              params=dict(option=1, mu=1e-3),
                              seeds=(0, 1), num_rounds=3, name="cellA")
        res = Sweep([spec]).run(problem, x0=jnp.zeros(D, jnp.float64))
        rows = res.records()
        assert len(rows) == 2 * 4  # seeds x (rounds+1)
        assert {r["name"] for r in rows} == {"cellA"}
        assert rows[0]["round"] == 0 and rows[3]["round"] == 3
        summ = res.summary(target=1e30)  # everything hits a huge target
        assert summ[0]["rounds_to_target"] == 0
        assert summ[0]["us_per_round"] > 0


def test_engine_bc_records_learned_model(problem):
    """FedNL-BC's monitored trajectory is z (the learned model devices
    actually hold), not the server's uncompressed x."""
    with jax.enable_x64(True):
        spec = ExperimentSpec("fednl-bc", "topk", D * D,
                              params=dict(model_compressor=("topk", D),
                                          p=1.0, option=1, mu=1e-3),
                              seeds=(0,), num_rounds=3)
        cell = Sweep([spec]).run(problem, x0=jnp.zeros(D, jnp.float64)).cells[0]
        assert cell.xs.shape == (1, 4, D)
        assert np.all(np.isfinite(cell.xs))


def test_sharded_sweep_matches_plain_single_device(problem):
    """The mesh path (core/federated.py shard_map) agrees with the vmap
    path on a trivial 1-device mesh."""
    with jax.enable_x64(True):
        x0 = jnp.full((D,), 0.3, jnp.float64)
        spec = ExperimentSpec("fednl", "rankr", 1, params=dict(option=2),
                              seeds=(0,), num_rounds=4)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("data",))
        plain = Sweep([spec]).run(problem, x0=x0).cells[0]
        sharded = Sweep([spec], mesh=mesh).run(problem, x0=x0).cells[0]
        np.testing.assert_allclose(sharded.xs, plain.xs, rtol=0, atol=1e-10)


# -- bits accounting pinned to the seed-era formulas --------------------------
# These integers were computed from the pre-refactor implementations
# (FLOAT_BITS=64, INDEX_BITS=32, d=16, RankR(1) / TopK(16)). The engine
# refactor must not move the paper's x-axis.


def test_bits_accounting_identical_pre_post_refactor(problem):
    d = 16
    g, h, v = problem["grad"], problem["hess"], problem["val"]
    rank1 = RankR(1)
    # grad (d floats) + S_i (rank-1: 64*(1+d+d)) + l_i (1 float)
    assert FedNL(g, h, rank1).bits_per_round(d) == 3200
    assert FedNL(g, h, rank1).init_bits(d) == 8704  # d(d+1)/2 floats
    # S_i + l diff (1 float) + g diff (d floats)
    assert FedNLPP(g, h, rank1, tau=2).bits_per_round(d) == 3200
    # grad + S_i + l_i
    assert FedNLCR(g, h, rank1, l_star=1.0).bits_per_round(d) == 3200
    # f_i + grad + S_i
    assert FedNLLS(v, g, h, rank1).bits_per_round(d) == 3200
    # up: p*d floats + TopK(16) (96 bits/entry) + l_i; down: TopK(16) + xi
    up, down = FedNLBC(g, h, TopK(k=16), TopK(k=16),
                       p=0.5).bits_per_round(d)
    assert up == 0.5 * 16 * 64 + 16 * 96 + 64 == 2112.0
    assert down == 16 * 96 + 1 == 1537


def test_engine_bits_curve_matches_method_accounting(problem):
    with jax.enable_x64(True):
        spec = ExperimentSpec("fednl", "rankr", 1,
                              params=dict(option=1, mu=1e-3),
                              seeds=(0,), num_rounds=3)
        cell = Sweep([spec]).run(problem, x0=jnp.zeros(D, jnp.float64)).cells[0]
        alg = FedNL(problem["grad"], problem["hess"], RankR(1))
        expect = alg.init_bits(D) + alg.bits_per_round(D) * np.arange(4)
        np.testing.assert_array_equal(cell.bits, expect)


def test_engine_measured_bits_match_analytic_under_x64(problem):
    """Acceptance: a Sweep cell reports measured per-round bits (derived
    from the payload structure) that match the analytic bits_per_round
    under x64, for the four acceptance compressor families."""
    with jax.enable_x64(True):
        x0 = jnp.zeros(D, jnp.float64)
        specs = [
            ExperimentSpec("fednl", "rankr", 2,
                           params=dict(option=1, mu=1e-3), num_rounds=2),
            ExperimentSpec("fednl", "topk", D, params=dict(option=1, mu=1e-3),
                           num_rounds=2),
            ExperimentSpec("fednl", "blocktopk", 4,
                           params=dict(option=1, mu=1e-3), num_rounds=2),
            ExperimentSpec("fednl", "randk", D,
                           params=dict(option=2, alpha=0.5), num_rounds=2),
        ]
        res = Sweep(specs).run(problem, x0=x0)
        for cell in res.cells:
            np.testing.assert_array_equal(cell.bits_measured, cell.bits)
        rows = res.records()
        assert all(r["bits_measured"] == r["bits"] for r in rows)
        summ = res.summary()
        assert all(s["bits_per_round_measured"] == s["bits_per_round"] > 0
                   for s in summ)


def test_engine_measured_bits_bc_uplink_downlink(problem):
    """FedNL-BC's measured accounting covers both directions: the uplink
    Hessian payload and the downlink model payload."""
    with jax.enable_x64(True):
        from repro.core import TopK
        from repro.engine import measured_bits_per_round

        alg = FedNLBC(problem["grad"], problem["hess"], TopK(k=16),
                      TopK(k=8), p=0.5)
        up, down = alg.measured_bits_per_round(16)
        up_a, down_a = alg.bits_per_round(16)
        assert (up, down) == (up_a, down_a)
        assert measured_bits_per_round(alg, 16) == up_a + down_a
