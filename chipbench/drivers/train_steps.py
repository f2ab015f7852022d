"""Driver of the training cells: language-model train steps with the
``fednl`` optimizer (FedNL-learned diagonal curvature).

The timed path is the program's jitted train step,
``repro.launch.steps.make_train_step`` over ``repro.models`` and the
``fednl`` optimizer, built and placed as ``repro.launch.train.train``
builds it (mesh, activation and parameter shardings, sharded optimizer
state) and compiled once ahead of time. The weights are the benchmark's
own, made on the device from the seed in bfloat16 in the program's
parameter layout; the batches come from the benchmark's generator, a new
one for every step.

Set-up drives the compiled step through the first three steps, keeping
each step's loss, the per-leaf norms of the first gradient as the
optimizer received it (worked out from its state after one step) and the
per-leaf norms of the parameters' change over the three; the window then
continues with the same step, parameters and state. After the window the
float32 reference (``chipbench/reference/qwen2.py``) follows the same
three steps from the same weights and batches.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import inputs
from chipbench.counts import kernels as kernel_counts
from chipbench.counts import lm as lm_counts
from chipbench.reference import qwen2 as ref

FIRST_STEPS = 3
KERNEL_SCOPES = {
    "block_scatter": "block_scatter_accumulate",
    "diff_topk_payload": "_diff_topk_payload_impl",
}
# configuration-file key -> the program's ModelConfig field
PROGRAM_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "kv_heads",
                "intermediate_size": "d_ff", "vocab_size": "vocab",
                "rope_theta": "rope_theta",
                "tie_word_embeddings": "tie_embeddings"}


def init_weights(key, shapes):
    """Weights in the program's layout (``shapes``: its parameter
    pytree of ShapeDtypeStructs), by the parameter's role: norm scales 1,
    biases 0, the embedding N(0, 0.02), every other matrix N(0, 1/d_in),
    output projections further scaled by 1/sqrt(2 layers)."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(flat))
    layers = max(s.shape[0] for _, s in flat if len(s.shape) == 3)
    out = []
    for (path, s), k in zip(flat, keys):
        names = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        leaf = names[-1]
        if leaf == "w" and len(s.shape) <= 2:
            x = jnp.ones(s.shape, jnp.float32)
        elif leaf in ("bq", "bk", "bv"):
            x = jnp.zeros(s.shape, jnp.float32)
        elif leaf == "embed":
            x = jax.random.normal(k, s.shape) * 0.02
        else:
            scale = 1.0 / math.sqrt(s.shape[-2])
            if leaf == "wo":
                scale /= math.sqrt(2 * layers)
            x = jax.random.normal(k, s.shape) * scale
        out.append(x.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _shape2d(shape):
    if len(shape) == 1:
        return 1, shape[0]
    return int(np.prod(shape[:-1])), shape[-1]


class Driver:
    def __init__(self, cfg: dict, mix: dict, limits: dict, seed: int,
                 devices=None):
        self.cfg, self.mix, self.limits = cfg, mix, limits
        self.seed = int(seed)
        self.devices = list(devices) if devices else None
        self.batch, self.seq = int(mix["batch"]), int(mix["seq"])
        self.units_per_call = self.batch * self.seq
        self.flops_per_unit = lm_counts.train_flops_per_token(cfg, self.seq)

    def program_config(self):
        """The program's configuration, checked against the file."""
        from repro.configs import get_config

        pcfg = get_config(self.cfg["program_arch"])
        for key, field in PROGRAM_KEYS.items():
            if getattr(pcfg, field) != self.cfg[key]:
                raise ValueError(f"{self.cfg['program_arch']}: {field} is "
                                 f"{getattr(pcfg, field)!r}, the configuration "
                                 f"file says {key} {self.cfg[key]!r}")
        if pcfg.dtype != self.cfg["torch_dtype"]:
            raise ValueError(f"{self.cfg['program_arch']}: dtype is "
                             f"{pcfg.dtype!r}, the configuration file says "
                             f"{self.cfg['torch_dtype']!r}")
        return pcfg

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.launch.mesh import make_mesh
        from repro.launch.sharding import (
            make_activation_sharder,
            make_layer_param_constrainer,
            opt_state_shardings,
            tree_param_specs,
        )
        from repro.launch.steps import make_optimizer, make_train_step
        from repro.models import build_model
        from repro.models.common import set_activation_sharder
        from jax.sharding import NamedSharding, PartitionSpec as P

        mix = self.mix
        pcfg = self.program_config()
        devs = self.devices or jax.devices()[:1]
        mesh = make_mesh((len(devs), 1), ("data", "model"), devices=devs)
        set_activation_sharder(make_activation_sharder(mesh),
                               make_layer_param_constrainer(mesh, pcfg))
        model = build_model(pcfg, use_remat=True)
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        self.shapes = shapes
        key = inputs.seed_key(self.seed)
        wkey, self.tkey = jax.random.split(key)
        self.make_weights = jax.jit(
            lambda k: init_weights(k, shapes),
            out_shardings=tree_param_specs(shapes, mesh, pcfg))
        params = self.make_weights(wkey)
        self.wkey = wkey

        opt = make_optimizer(mix["optimizer"], float(mix["lr"]),
                             k_per_block=int(mix["curvature_k"]), mesh=mesh,
                             curvature=mix["curvature"])
        state_shape = jax.eval_shape(opt.init, params)
        state = jax.jit(opt.init, out_shardings=opt_state_shardings(
            state_shape, params, mesh, pcfg))(params)
        self.silos = len(devs)  # one FedNL silo per chip, as train() has it
        step = jax.jit(make_train_step(
            model, opt, refresh_every=int(mix["refresh_every"]),
            n_silos=self.silos))
        vocab, b, t = self.cfg["vocab_size"], self.batch, self.seq
        self.make_batch = jax.jit(
            lambda k, i: inputs.token_batch(k, i, vocab, b, t),
            out_shardings=NamedSharding(mesh, P("data"))
        ).lower(self.tkey, jnp.int32(0)).compile()
        batch0 = self.make_batch(self.tkey, jnp.int32(0))
        self.program = step.lower(params, state, batch0).compile()
        self.hlo = [self.program.as_text()]

        # the first steps, through the window's own call and feed; the
        # reference follows them after the window
        self.params, self.state, self.step_index = params, state, 0
        losses = []
        for i in range(FIRST_STEPS):
            losses.append(self.call())
            if i == 0:
                grad_norms = jax.jit(_grad_norms)(self.state)
        change = jax.jit(_change_norms)(self.params, params)
        del params, state
        self.first = {"losses": [float(x) for x in losses],
                      "grad_norms": [float(x) for x in
                                     jax.tree.leaves(grad_norms)],
                      "change_norms": [float(x) for x in
                                       jax.tree.leaves(change)]}

    def call(self):
        import jax.numpy as jnp

        batch = self.make_batch(self.tkey, jnp.int32(self.step_index))
        self.step_index += 1
        self.params, self.state, metrics = self.program(self.params,
                                                        self.state, batch)
        return metrics["loss"]

    # -- what the window measured --------------------------------------------

    def end_to_end(self, name: str, units: int, window_s: float) -> float:
        if name == "tokens_per_s":
            return units / window_s
        raise KeyError(name)

    def kernel_tags(self) -> dict:
        return {"scopes": KERNEL_SCOPES, "hlo": self.hlo}

    def layer_counts(self, calls: int) -> dict:
        """The work of the curvature refresh's kernels on each chip in
        ``calls`` steps, every ``refresh_every`` steps: per parameter
        tensor, one diff->TopK payload of the chip's own silo, and one
        block scatter of every silo's payload (each chip holds the server
        mean)."""
        import jax

        k = int(self.mix["curvature_k"])
        refreshes = -(-calls // int(self.mix["refresh_every"]))
        total = {"diff_topk_payload": {"flops": 0.0, "bytes": 0.0},
                 "block_scatter": {"flops": 0.0, "bytes": 0.0}}
        for s in jax.tree.leaves(self.shapes):
            rows, cols = _shape2d(s.shape)
            for tag, fn, n in (
                    ("diff_topk_payload", kernel_counts.diff_topk_payload, 1),
                    ("block_scatter", kernel_counts.block_scatter, self.silos)):
                c = fn(n, k, rows, cols)
                for key in c:
                    total[tag][key] += c[key] * refreshes
        return total

    # -- the check -----------------------------------------------------------

    def check(self) -> dict:
        import jax
        import jax.numpy as jnp

        self.release()
        params = self.make_weights(self.wkey)
        batches = [self.make_batch(self.tkey, jnp.int32(i))
                   for i in range(FIRST_STEPS)]
        want = ref.train(params, batches, self.cfg, self.mix,
                         silos=self.silos)
        del params, batches
        jax.clear_caches()
        got = self.first
        numbers = compare(got, want)
        return {name: {"value": v, "limit": self.limits[name],
                       "ok": bool(np.isfinite(v) and v <= self.limits[name])}
                for name, v in numbers.items()}

    def release(self) -> None:
        self.params = self.state = self.program = None


def _grad_norms(state):
    """Per-leaf norms of the gradient the optimizer received in its first
    step: its momentum then holds g / (sqrt(max(h, 0)) + sqrt(l) + eps)."""
    import jax
    import jax.numpy as jnp

    def one(mu, h, l):
        g = mu * (jnp.sqrt(jnp.maximum(h, 0.0)) + jnp.sqrt(l) + 1e-8)
        return jnp.linalg.norm(g.ravel())

    return jax.tree.map(one, state.mu, state.h, state.l)


def _change_norms(p, p0):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a, b: jnp.linalg.norm(
        (a.astype(jnp.float32) - b.astype(jnp.float32)).ravel()), p, p0)


def leaf_gap(got, want, skip=()):
    """Worst leaf's gap between two lists of per-leaf norms, each against
    the larger of its reference norm and the median leaf's."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    floor = np.median(want)
    gaps = [abs(g - w) / max(w, floor)
            for i, (g, w) in enumerate(zip(got, want)) if i not in skip]
    return float(max(gaps))


def compare(got: dict, want: dict) -> dict:
    """The numbers that decide ``correct``. Leaves whose reference
    gradient is under a thousandth of the median leaf's (a key bias under
    the softmax) move by round-off alone: their change is not compared."""
    g_ref = np.asarray(want["grad_norms"])
    tiny = {i for i, g in enumerate(g_ref) if g < 1e-3 * np.median(g_ref)}
    return {
        "loss_gap": float(max(abs(a - b) for a, b in
                              zip(got["losses"], want["losses"]))),
        "grad_norm_gap": leaf_gap(got["grad_norms"], want["grad_norms"]),
        "change_norm_gap": leaf_gap(got["change_norms"],
                                    want["change_norms"], skip=tiny),
    }
