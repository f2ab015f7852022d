"""End-to-end LM training driver: a qwen2-family model trained for a few
hundred steps with either AdamW or the paper-derived FedNL structured-
curvature preconditioner (--optimizer fednl).

Defaults are sized for the CPU container (a ~15M-param reduced config,
200 steps, ~minutes). `--full` selects the real qwen2-0.5b config — the
same script, pointed at a TPU slice, is the production path the dry-run
proves out.

    PYTHONPATH=src python examples/train_lm.py --steps 200 --optimizer fednl

Second-order walkthrough (--optimizer fednl): the train step splits the
global batch over the mesh data axis — each shard plays one FedNL silo.
Every --refresh-every steps (a jittable lax.cond, so intermediate steps
pay nothing) each silo takes a local curvature observation — the
empirical-Fisher g^2 diagonal, or a Hutchinson z*(Hz) probe with --hvp —
compresses the diff against the shared estimate H through the fused
Block-TopK payload kernel (--curvature-k values per 128x128 block, the
paper's C(D - H) uplink), and H learns from the payload-space server
mean: H <- H + alpha*C(D - H), with the Option-2 ridge l = ||D - H||_F
making sqrt(H) + sqrt(l) a safe diagonal preconditioner. All other steps
just apply that stored preconditioner — per-step cost is elementwise, and
the driver logs the uplink cost as curv_bits next to loss/gnorm.
"""

import argparse
import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgd", "fednl"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--refresh-every", type=int, default=4)
    ap.add_argument("--curvature-k", type=int, default=2048)
    ap.add_argument("--hvp", action="store_true")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    hist = train(args.arch, smoke=not args.full, steps=args.steps,
                 batch=args.batch, seq=args.seq, lr=args.lr,
                 optimizer=args.optimizer, ckpt=args.ckpt,
                 refresh_every=args.refresh_every,
                 curvature_k=args.curvature_k, hvp=args.hvp).losses
    print(f"\nloss: {hist[0]:.3f} -> {hist[-1]:.3f} over {args.steps} steps "
          f"({args.optimizer})")


if __name__ == "__main__":
    main()
