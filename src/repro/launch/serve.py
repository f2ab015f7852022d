"""Serving driver: batched prefill + decode loop with a KV/state cache.

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-350m --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import make_activation_sharder, make_layer_param_constrainer
from repro.launch.steps import make_serve_step
from repro.models import build_model
from repro.models.common import set_activation_sharder


def generate(arch: str, smoke: bool = True, batch: int = 4,
             prompt_len: int = 16, gen: int = 16, seed: int = 0,
             temperature: float = 1.0, greedy: bool = False):
    cfg = get_config(arch, smoke=smoke)
    mesh = make_host_mesh()
    set_activation_sharder(make_activation_sharder(mesh),
                           make_layer_param_constrainer(mesh, cfg))
    model = build_model(cfg, use_remat=False)
    params = model.init_params(jax.random.PRNGKey(seed))
    serve = jax.jit(make_serve_step(model))

    key = jax.random.PRNGKey(seed + 1)
    prompt = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)
    max_len = prompt_len + gen
    cache = model.init_cache(batch, max_len)
    if cfg.family == "encdec":
        cache["enc"] = jax.random.normal(
            key, (batch, cfg.enc_seq, cfg.d_model), cfg.jdtype) * 0.02

    # prefill token-by-token through the serve path (exercises the cache
    # exactly as production decode does; a fused prefill is the fast path)
    toks = prompt
    logits = None
    for pos in range(prompt_len):
        logits, cache = serve(params, cache, toks[:, pos:pos + 1],
                              jnp.asarray(pos, jnp.int32))

    out = [toks]
    t0 = time.time()
    for i in range(gen):
        key, sub = jax.random.split(key)
        if greedy:
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        else:
            nxt = jax.random.categorical(
                sub, logits[:, -1] / temperature, axis=-1)[:, None]
        out.append(nxt)
        logits, cache = serve(params, cache, nxt,
                              jnp.asarray(prompt_len + i, jnp.int32))
    dt = time.time() - t0
    seqs = jnp.concatenate(out, axis=1)
    print(f"generated {gen} tokens x {batch} seqs in {dt:.2f}s "
          f"({batch * gen / dt:.1f} tok/s)")
    return seqs


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    seqs = generate(args.arch, smoke=args.smoke, batch=args.batch,
                    prompt_len=args.prompt_len, gen=args.gen)
    print("sample token ids:", seqs[0, : args.prompt_len + 8].tolist())


if __name__ == "__main__":
    main()
