"""Driver of the federated cells: FedNL rounds through the engine.

The timed path is the engine's round program: ``scan_rounds`` over the
method that ``ExperimentSpec(method, compressor, level, params).build``
makes from the silos' oracles (``repro.core.objectives``), jitted with
the data as an argument and compiled once ahead of time. Each call runs
``rounds_per_call`` rounds and the state is carried from call to call:
set-up makes the data from the seed, the initial state (every silo's
exact Hessian at x0 = 0) and one warm-up call; the window continues from
there.

What the window produced is checked against FedNL's fixed point, which
the float64 reference computes (``chipbench/reference/logreg.py``): the
iterate x and every silo's Hessian estimate, after the window's first
call and after its last, each under its limit where the cell's limits
file names it. The server aggregate is read by the server's Hessian
estimate against the mean of the silos' own (``h_global_drift``): a
running sum of the silos' mean increments, it drifts from their mean by
float32 rounding a little every round, so after thousands of rounds it
reads the round count as much as the program, and it is compared after
the window's first call only. Set-up and the window's first call run
twice the rounds in which FedNL reaches its fixed point at these sizes
(the configuration's ``rounds_to_settle``), so every check compares a
settled state.

``control`` puts the control in the program's place: the silos' oracles
and the server's payload values one precision step below the configured
one (three bfloat16 passes). ``fault`` plants a fault in the server
aggregate alone: ``aggregate_bf16`` (the payload values in one bfloat16
pass) or ``aggregate_drop`` (one pair in 16 lost).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import inputs
from chipbench.counts import fednl as fednl_counts
from chipbench.counts import kernels as kernel_counts
from chipbench.reference import logreg as ref

# kernel of the program -> the jitted wrapper its calls are traced under
KERNEL_SCOPES = {
    "scatter_accum": "_scatter_accumulate_pallas",
    "block_scatter": "block_scatter_accumulate",
    "diff_topk_payload": "_diff_topk_payload_impl",
}


def _drop_pairs(values):
    import jax.numpy as jnp

    keep = jnp.arange(values.shape[-1]) % 16 != 0
    return jnp.where(keep, values, jnp.zeros_like(values))


class Driver:
    def __init__(self, cfg: dict, mix: dict, limits: dict, seed: int,
                 devices=None, control: bool = False, fault: str | None = None):
        self.cfg, self.mix, self.limits = cfg, mix, limits
        self.seed = int(seed)
        self.control, self.fault = control, fault
        self.device = devices[0] if devices else None
        self.units_per_call = int(mix["rounds_per_call"])
        if self.units_per_call < int(cfg["rounds_to_settle"]):
            raise ValueError(f"{self.units_per_call} rounds a call do not "
                             f"reach the {cfg['rounds_to_settle']} in which "
                             f"FedNL settles; the check needs a settled state")
        n, m, d = cfg["silos"], cfg["rows_per_silo"], cfg["features"]
        self.flops_per_unit = fednl_counts.round_flops(n, m, d)
        self.calls = 0
        self.first = None  # the state after the window's first call

    # -- set-up --------------------------------------------------------------

    def _method(self, a, b):
        from repro.core.objectives import LogRegData, batch_grad, batch_hess
        from repro.engine import ExperimentSpec
        from repro.engine.method import Oracles

        mix = self.mix
        spec = ExperimentSpec(mix["method"], mix["compressor"], mix["level"],
                              params=dict(mix["params"]))
        if self.control:
            grad, hess = ref.control_oracles(a, b, self.cfg["lam"])
        else:
            data = LogRegData(a, b, self.cfg["lam"])
            grad = lambda x: batch_grad(x, data)
            hess = lambda x: batch_hess(x, data)
        method = spec.build(Oracles(value=None, grad=grad, hess=hess))
        lower = {"aggregate_bf16": lambda v: ref.lower_values(v, 1),
                 "aggregate_drop": _drop_pairs}.get(self.fault)
        if self.control:
            lower = lambda v: ref.lower_values(v, 3)
        if lower is not None:
            aggregate = method._server_aggregate
            object.__setattr__(
                method, "_server_aggregate",
                lambda p, shape, weights=None: aggregate(
                    dataclasses.replace(p, values=lower(p.values)), shape,
                    weights))
        return method

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.engine.method import scan_rounds

        cfg, rounds = self.cfg, self.units_per_call
        n, m, d = cfg["silos"], cfg["rows_per_silo"], cfg["features"]

        def make_data(key):
            return inputs.libsvm_like(key, n, m, d, cfg["density"])

        def init(a, b):
            method = self._method(a, b)
            return method.init(jnp.zeros(d, jnp.float32), n, seed=0)

        def run(state, a, b):
            return scan_rounds(self._method(a, b), state, rounds)[0]

        with jax.default_matmul_precision(cfg["matmul_precision"]):
            key = inputs.seed_key(self.seed)
            self.a, self.b = jax.jit(make_data)(key)
            state = jax.jit(init)(self.a, self.b)
            self.program = jax.jit(run).lower(state, self.a, self.b).compile()
        self.hlo = [self.program.as_text()]
        self.state = state
        jax.block_until_ready(self.call())

    def call(self):
        self.state = self.program(self.state, self.a, self.b)
        self.calls += 1
        if self.calls == 2:
            self.first = self.state
        return self.state.x

    # -- what the window measured --------------------------------------------

    def end_to_end(self, name: str, units: int, window_s: float) -> float:
        if name == "round_ms":
            return window_s * 1e3 / units
        raise KeyError(name)

    def kernel_tags(self) -> dict:
        return {"scopes": KERNEL_SCOPES, "hlo": self.hlo}

    def layer_counts(self, calls: int) -> dict:
        """The work each kernel of this cell needs in ``calls`` calls."""
        cfg, mix = self.cfg, self.mix
        n, d = cfg["silos"], cfg["features"]
        k, rounds = int(mix["level"]), calls * self.units_per_call
        per_round = {}
        if mix["compressor"] == "topk":
            per_round["scatter_accum"] = kernel_counts.scatter_accum(n, k, d, d)
        elif mix["compressor"] == "blocktopk":
            per_round["diff_topk_payload"] = \
                kernel_counts.diff_topk_payload(n, k, d, d)
            per_round["block_scatter"] = kernel_counts.block_scatter(n, k, d, d)
        return {tag: {key: v * rounds for key, v in c.items()}
                for tag, c in per_round.items()}

    # -- the check -----------------------------------------------------------

    def check(self) -> dict:
        """FedNL's carried state against the float64 fixed point."""
        import jax

        host = lambda st: {f: np.asarray(jax.device_get(getattr(st, f)))
                           for f in ("x", "h_global", "h_local")}
        first, last = host(self.first), host(self.state)
        a, b = np.asarray(self.a), np.asarray(self.b)
        self.release()
        want = ref.solution(a, b, self.cfg["lam"])
        e1, e2 = ref.rel_errors(first, want), ref.rel_errors(last, want)
        self.numbers = {
            "x_rel_err": max(e1["x_rel_err"], e2["x_rel_err"]),
            "h_local_rel_err": max(e1["h_local_rel_err"],
                                   e2["h_local_rel_err"]),
            "h_global_drift": e1["h_global_drift"],
            "h_global_rel_err": e1["h_global_rel_err"],
            "h_global_drift_last": e2["h_global_drift"]}
        return {name: {"value": v, "limit": self.limits[name],
                       "ok": bool(np.isfinite(v) and v <= self.limits[name])}
                for name, v in self.numbers.items() if name in self.limits}

    def release(self) -> None:
        self.state = self.first = self.a = self.b = self.program = None
