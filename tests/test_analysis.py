"""The static-analysis framework analyzes programs; these tests analyze
the analyzer: every rule must flag its deliberately-broken fixture (and
ONLY that rule must fire), every documented-legitimate pattern must
pass, and the full registry sweep must be violation-free — the pin that
turns the ISSUE's acceptance criterion into a tier-1 test."""

import jax
import jax.numpy as jnp
import pytest

from repro import analysis
from repro.analysis import Target, get_rule
from repro.core.compressors import Compressor, TopK

_ALL_JAXPR_RULES = ["no-dense-silo-stack", "no-dense-roundtrip",
                    "dtype-discipline", "no-host-sync",
                    "padding-sentinel", "vmem-budget"]


def _only(violations, rule):
    """The fixture is flagged by exactly the intended rule."""
    assert violations, f"expected {rule} to fire"
    assert {v.rule for v in violations} == {rule}


# -- framework ----------------------------------------------------------------


def test_check_raises_analysis_error_with_violations():
    def bad(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    with pytest.raises(analysis.AnalysisError) as ei:
        analysis.check(bad, jnp.ones(4), rules=["no-host-sync"])
    assert ei.value.violations
    assert "no-host-sync" in str(ei.value)


def test_unknown_rule_is_a_loud_error():
    with pytest.raises(KeyError, match="unknown rule"):
        analysis.check(lambda x: x, jnp.ones(3), rules=["no-such-rule"])


def test_rules_registered():
    for name in _ALL_JAXPR_RULES + ["no-deprecated-accessor"]:
        assert name in analysis.available_rules()
        assert get_rule(name).description


# -- no-dense-silo-stack ------------------------------------------------------


def _stacked_payload_struct(comp, n, shape):
    m = jax.ShapeDtypeStruct((n,) + shape, jnp.result_type(float))
    keys = jax.ShapeDtypeStruct((n, 2), jnp.uint32)
    return jax.eval_shape(jax.vmap(comp.compress), m, keys)


def test_dense_decompress_then_mean_aggregate_is_flagged():
    """The generic ``Compressor.aggregate`` fallback decompresses each
    silo and means the (n, d, d) stack — exactly what the rule exists
    to keep out of registered fast paths."""
    comp = TopK(k=5)
    n, shape = 3, (16, 16)
    pay = _stacked_payload_struct(comp, n, shape)
    violations = analysis.check(
        lambda p: Compressor.aggregate(comp, p, shape), pay,
        rules=_ALL_JAXPR_RULES, kind="aggregate",
        context={"silo_axis": n, "dense_shape": shape},
        raise_on_violation=False)
    _only(violations, "no-dense-silo-stack")


def test_payload_space_aggregate_passes():
    comp = TopK(k=5)
    n, shape = 3, (16, 16)
    pay = _stacked_payload_struct(comp, n, shape)
    analysis.check(lambda p: comp.aggregate(p, shape), pay,
                   rules=_ALL_JAXPR_RULES, kind="aggregate",
                   context={"silo_axis": n, "dense_shape": shape})


def test_silo_stack_reduction_in_step_is_flagged():
    """Outside aggregate targets the rule flags (n, d, d) -> (d, d)
    *reductions* (decompress-then-mean server math), while device-side
    (n, d, d) arrays themselves stay legal."""
    n, d = 3, 16

    def bad_step(h_stack):
        return jnp.mean(h_stack, axis=0)  # the server's dense mean

    violations = analysis.check(
        bad_step, jnp.ones((n, d, d)), rules=["no-dense-silo-stack"],
        kind="method-step", context={"silo_axis": n, "dense_shape": (d, d)},
        raise_on_violation=False)
    _only(violations, "no-dense-silo-stack")

    def ok_step(h_stack):
        return h_stack * 2.0 + 1.0  # per-silo state update: legal

    analysis.check(ok_step, jnp.ones((n, d, d)),
                   rules=["no-dense-silo-stack"], kind="method-step",
                   context={"silo_axis": n, "dense_shape": (d, d)})


# -- no-dense-roundtrip -------------------------------------------------------


def test_blocksq_intermediate_is_flagged():
    block = 8

    def bad(tiles):  # dense (nblocks, block^2) selection mask
        return jnp.abs(tiles.reshape(4, block * block))

    violations = analysis.check(bad, jnp.ones((16, block * block // 4)),
                                rules=_ALL_JAXPR_RULES,
                                context={"block": block},
                                raise_on_violation=False)
    _only(violations, "no-dense-roundtrip")


# -- dtype-discipline ---------------------------------------------------------


def test_f64_laundered_through_f32_is_flagged():
    with jax.enable_x64(True):
        def bad(x):
            y = x.astype(jnp.float32)  # silent precision loss
            return (y * 2.0).astype(jnp.float64)  # laundered back

        violations = analysis.check(bad, jnp.ones(8, jnp.float64),
                                    rules=_ALL_JAXPR_RULES,
                                    raise_on_violation=False)
        _only(violations, "dtype-discipline")


def test_selection_only_downcast_passes():
    """BlockTopKThreshold's documented pattern: f32 is fine for
    *selecting* indices (the taint dies at the bool/int boundary) as
    long as the selected values come from the f64 original."""
    with jax.enable_x64(True):
        def ok(x):
            score = jnp.abs(x).astype(jnp.float32)
            _, idx = jax.lax.top_k(score, 3)
            return x[idx]  # values stay f64 end to end

        analysis.check(ok, jnp.ones(8, jnp.float64), rules=_ALL_JAXPR_RULES)


# -- no-host-sync -------------------------------------------------------------


def test_host_callback_is_flagged():
    def bad(x):
        jax.debug.print("step {x}", x=x[0])
        return x + 1

    violations = analysis.check(bad, jnp.ones(4), rules=_ALL_JAXPR_RULES,
                                raise_on_violation=False)
    _only(violations, "no-host-sync")


# -- padding-sentinel ---------------------------------------------------------


def test_unremapped_negative_index_scatter_is_flagged():
    """A payload index stream fed straight into a drop-mode scatter:
    jax wraps -1 to n-1 BEFORE the bounds check, so the padding
    silently overwrites the last slot — the rule must catch it."""
    n = 16

    def bad(vals, idx):
        return jnp.zeros((n,), vals.dtype).at[idx].add(vals, mode="drop")

    violations = analysis.check(
        bad, jnp.ones(4), jnp.zeros(4, jnp.int32),
        rules=_ALL_JAXPR_RULES, raise_on_violation=False)
    _only(violations, "padding-sentinel")


def test_remapped_scatter_passes():
    n = 16

    def ok(vals, idx):
        idx = jnp.where(idx < 0, n, idx)  # sentinel out of range FIRST
        return jnp.zeros((n,), vals.dtype).at[idx].add(vals, mode="drop")

    analysis.check(ok, jnp.ones(4), jnp.zeros(4, jnp.int32),
                   rules=_ALL_JAXPR_RULES)


def test_in_trace_topk_indices_pass():
    """Indices born from top_k inside the trace cannot be -1: no remap
    required (compress->decompress fused in one step must stay legal)."""
    def ok(x):
        v, idx = jax.lax.top_k(x, 3)
        return jnp.zeros_like(x).at[idx].add(v, mode="drop")

    analysis.check(ok, jnp.ones(8), rules=_ALL_JAXPR_RULES)


# -- vmem-budget --------------------------------------------------------------


def _copy_kernel_call(dim):
    import jax.experimental.pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((dim, dim), jnp.float32),
        in_specs=[pl.BlockSpec((dim, dim), lambda: (0, 0))],
        out_specs=pl.BlockSpec((dim, dim), lambda: (0, 0)),
        interpret=True)


def test_over_budget_blockspec_is_flagged():
    """A (2048, 2048) f32 block is 16 MiB; in + out blocks put 32 MiB
    in VMEM against the 8 MiB dispatch budget — caught at trace time."""
    violations = analysis.check(
        _copy_kernel_call(2048), jnp.ones((2048, 2048), jnp.float32),
        rules=_ALL_JAXPR_RULES, raise_on_violation=False)
    _only(violations, "vmem-budget")


def test_within_budget_blockspec_passes():
    analysis.check(_copy_kernel_call(512),
                   jnp.ones((512, 512), jnp.float32),
                   rules=_ALL_JAXPR_RULES)


# -- no-deprecated-accessor (source rule) -------------------------------------


def _run_source_rule(tmp_path, text):
    p = tmp_path / "fixture.py"
    p.write_text(text)
    t = Target(name="fixture", kind="source", trace=lambda: p,
               rules=("no-deprecated-accessor",))
    return get_rule("no-deprecated-accessor").check(p, t)


def test_deprecated_accessors_are_flagged(tmp_path):
    violations = _run_source_rule(tmp_path, (
        "def f(comp, payload):\n"
        "    a = comp.bits((4, 4))\n"
        "    b = comp.spec((4, 4)).bits\n"
        "    c = payload_bits(comp, (4, 4))\n"
        "    d = payload.bits(index_coding='entropy')\n"
        "    return a + b + c + d\n"))
    assert len(violations) == 4
    assert {v.rule for v in violations} == {"no-deprecated-accessor"}


def test_live_bits_fields_and_reexports_pass(tmp_path):
    """``cell.bits`` (a live record field) and ``payload_bits``
    re-export imports must NOT trip the rule — only the quartet's
    usage patterns do."""
    violations = _run_source_rule(tmp_path, (
        "from repro.core.compressors import payload_bits\n"
        "__all__ = ['payload_bits']\n"
        "def f(cell):\n"
        "    return cell.bits[0] + float(cell.bits[-1])\n"))
    assert violations == []


# -- the registry sweep pin ---------------------------------------------------


def test_full_registry_sweep_has_zero_violations():
    """The ISSUE acceptance criterion as a test: every registered
    method x compressor step, every aggregate path, all five kernel
    packages, the precond TPU path, and the source sweep — zero
    violations. A target whose trace breaks surfaces here as an
    ``analysis-error`` violation, so registry rot fails loudly too."""
    results = analysis.analyze()
    assert len(results) > 100  # the sweep actually enumerated the world
    failures = [(t.name, [str(v) for v in vs]) for t, vs in results if vs]
    assert failures == []


def test_train_step_targets_registered():
    """The full fednl train step (fisher AND hvp curvature) is a sweep
    target, carrying every jaxpr data-path rule — so a regression in
    ``make_train_step``'s observation phase fails the registry sweep,
    not just the unit tests."""
    targets = analysis.iter_targets(["train-step"])
    names = {t.name for t in targets}
    assert names == {"train-step:fednl[fisher]", "train-step:fednl[hvp]"}
    for t in targets:
        assert t.kind == "train-step"
        for rule in ("no-dense-silo-stack", "no-dense-roundtrip",
                     "dtype-discipline", "vmem-budget"):
            assert rule in t.rules, (t.name, rule)
        assert t.context["block"] == 128
