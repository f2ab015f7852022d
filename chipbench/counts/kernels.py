"""Operations and bytes that each shared FedNL kernel needs for one call,
from the problem's shapes: inputs read once, outputs written once, in the
algorithm's own formats (a payload slot is a value and a 32-bit index).
Padding that a kernel adds for its tiling, and the one-hot matmuls with
which the scatter kernels turn a scatter into MXU work, are not counted:
they are how this implementation does the work, not the work."""

from __future__ import annotations

F32 = 4
I32 = 4
PAIR = F32 + I32  # one payload slot: value and index


def _blocks(rows: int, cols: int, block: int) -> int:
    return -(-rows // block) * -(-cols // block)


def scatter_accum(n: int, k: int, rows: int, cols: int) -> dict:
    """Dense (rows, cols) sum of n silos' k (value, flat index) pairs:
    one add per pair; the pairs read once and the sum written once."""
    return {"flops": float(n * k),
            "bytes": float(n * k * PAIR + rows * cols * F32)}


def block_scatter(n: int, k: int, rows: int, cols: int,
                  block: int = 128) -> dict:
    """Dense (rows, cols) sum of n silos' block-sparse payloads, k pairs
    in each (block x block) tile."""
    pairs = n * _blocks(rows, cols, block) * k
    return {"flops": float(pairs),
            "bytes": float(pairs * PAIR + rows * cols * F32)}


def diff_topk_payload(n: int, k: int, rows: int, cols: int,
                      block: int = 128) -> dict:
    """For each of n silos, D = a - b of two (rows, cols) f32 matrices,
    its squared Frobenius norm, and its Block-TopK payload: k pairs per
    tile and one partial norm per tile. Three operations per entry
    (difference, square, sum); both operands read once."""
    tiles = _blocks(rows, cols, block)
    return {"flops": float(n * 3 * rows * cols),
            "bytes": float(n * (2 * rows * cols * F32 + tiles * k * PAIR
                                + tiles * F32))}


def least_seconds(count: dict, peak_flops: float, peak_bytes_per_s: float):
    """The least time the chip could take for ``count``, and which bound
    sets it ("flops" or "bytes")."""
    t_flops = count["flops"] / peak_flops
    t_bytes = count["bytes"] / peak_bytes_per_s
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
