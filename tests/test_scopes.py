"""Every phase scope of the two hot paths reaches the compiled program.

The FedNL round (TopK and BlockTopK, through the engine's round loop) and
the ``fednl`` train step name their phases with ``jax.named_scope``; a
profile attributes device time to a phase through the ``op_name``
metadata of the compiled HLO. One case per path and scope: the scope
appears there, no op of it sits under a different scope, and the path
holds no scope outside its table. A rename or a moved call site fails
here instead of silencing a phase in a profile."""

import re

import jax
import jax.numpy as jnp
import pytest

ROUND_SCOPES = ("fednl.oracle", "fednl.uplink", "fednl.local_update",
                "fednl.server", "fednl.solve")
STEP_SCOPES = ("train.forward_backward", "train.observe", "fednl.uplink",
               "fednl.server", "train.update")
PATHS = {"round-topk": ROUND_SCOPES, "round-blocktopk": ROUND_SCOPES,
         "step-fednl": STEP_SCOPES}

# a scope is a whole op-name component, or the inside of a transform
# that wraps it directly: ``vmap(fednl.uplink)``
_SCOPE = re.compile(r"(?:^|[/(])((?:fednl|train)\.[a-z_]+)(?=$|[/)])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _round_hlo(compressor) -> str:
    from repro.core.objectives import LogRegData, batch_grad, batch_hess
    from repro.engine.method import Oracles, make_method, scan_rounds

    n, m, d = 4, 12, 20

    def run(x0, a, b):
        data = LogRegData(a, b, 1e-3)
        method = make_method(
            "fednl", Oracles(None, lambda x: batch_grad(x, data),
                             lambda x: batch_hess(x, data)),
            compressor, option=2)
        return scan_rounds(method, method.init(x0, n), 3)[0]

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    return jax.jit(run).lower(f32(d), f32(n, m, d), f32(n, m)).compile() \
        .as_text()


def _step_hlo() -> str:
    from repro.configs import get_config
    from repro.launch.steps import make_optimizer, make_train_step
    from repro.models import build_model

    cfg = get_config("qwen2-0.5b", smoke=True)
    model = build_model(cfg, use_remat=True)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    opt = make_optimizer("fednl", 1e-3, k_per_block=64)
    state = jax.eval_shape(opt.init, params)
    rows = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    # refresh every other step: the refresh stays a branch of its own
    step = jax.jit(make_train_step(model, opt, refresh_every=2, n_silos=2))
    return step.lower(params, state, {"tokens": rows, "targets": rows}
                      ).compile().as_text()


@pytest.fixture(scope="module")
def scoped_op_names():
    """path -> [(op name, its scopes)] of the compiled program, built once."""
    from repro.core.compressors import BlockTopK, TopK

    build = {"round-topk": lambda: _round_hlo(TopK(k=40)),
             "round-blocktopk": lambda: _round_hlo(
                 BlockTopK(k_per_block=64, block=128)),
             "step-fednl": _step_hlo}
    cache = {}

    def get(path):
        if path not in cache:
            names = set(_OP_NAME.findall(build[path]()))
            cache[path] = [(n, set(_SCOPE.findall(n))) for n in names]
        return cache[path]

    return get


@pytest.mark.parametrize("path, scope", [(p, s) for p, scopes in PATHS.items()
                                         for s in scopes])
def test_scope_reaches_compiled_hlo(path, scope, scoped_op_names):
    ops = scoped_op_names(path)
    assert any(scope in found for _, found in ops), f"no op under {scope}"
    nested = [name for name, found in ops if scope in found and len(found) > 1]
    assert not nested, nested[:5]
    outside = set().union(*(found for _, found in ops)) - set(PATHS[path])
    assert not outside
