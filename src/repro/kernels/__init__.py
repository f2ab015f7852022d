"""Pallas TPU kernels for FedNL's compute hot spots.

  block_topk      — block-local Top-K contractive compressor (Def 3.3 with
                    delta = k/b^2); the TPU-native replacement for global
                    Top-K (A.3.3).
  scatter_accum   — payload-space server aggregation: sum n silos' sparse
                    payloads into ONE dense accumulator (one-hot-matmul
                    scatter; backs ``Compressor.aggregate`` fast paths).
  hess_update     — fused H += alpha*S with the ||D - H||_F compression-
                    error reduction (l_i^k) in the same HBM pass.
  tiled_matmul    — MXU-tiled matmul used by the PowerSGD/Rank-R power
                    iteration (A.3.2's TPU form).
  flash_attention — causal online-softmax attention (serving fast path).

Every kernel ships an ops.py (jit'd wrapper with interpret fallback on
CPU) and a ref.py (pure-jnp oracle used by the allclose test sweeps).
"""

# The per-program VMEM budget for BlockSpec blocks: 8 MiB of the 16 MiB
# scoped VMEM limit the v5e compiler gives a kernel by default, leaving
# the rest for in-body temporaries and the pipeline's second buffers.
# The ``vmem-budget`` static-analysis rule enforces it on every traced
# ``pallas_call``'s BlockSpec footprint; dispatchers whose kernels keep
# large temporaries count them against it (scatter_accum's single-block
# accumulator gets a quarter — see its ops.py).
VMEM_BUDGET_BYTES = 8 * 1024 * 1024
