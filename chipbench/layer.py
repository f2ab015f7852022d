"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader gets a context from ``run.py``: the reduced trace
(``trace.Reduced``), the work each kernel needed in the window
(``counts``), the window's program calls, units (rounds or tokens) and
seconds, the model FLOPs per unit, the number of chips and the chip's
peaks. A reader that finds nothing to read returns None."""

from __future__ import annotations

from chipbench.counts.kernels import least_seconds


def idle_share(ctx) -> float | None:
    tr = ctx["trace"]
    if tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def mfu(ctx) -> float | None:
    """Model FLOPs of the window's units over the window, as a share of
    the chips' bf16 peak."""
    if not ctx["units"] or ctx["window_s"] <= 0:
        return None
    rate = ctx["units"] * ctx["flops_per_unit"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])


def roofline(ctx, tag: str) -> float | None:
    """The least time the kernel's work could take on the chip, over the
    time its events took on the device, in the traced window."""
    count = ctx["counts"].get(tag)
    seen = ctx["trace"].kernels.get(tag)
    if not count or not seen or not seen["events"] or seen["seconds"] <= 0:
        return None
    t_min, _ = least_seconds(count, ctx["peaks"]["bf16_flops"],
                             ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * t_min / seen["seconds"]
