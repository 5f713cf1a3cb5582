"""Arithmetic the per-layer metric readers share.  A reader that finds
nothing to read returns None, and the metric is left out of the line;
a share of a peak is never reported as 0 for want of data."""

from __future__ import annotations

from typing import Any, Optional


def idle_pct(ctx: Any) -> Optional[float]:
    tr = getattr(ctx, "trace", None)
    if tr is None or not tr.devices:
        return None
    return 100.0 * tr.idle_share


def mfu_pct(ctx: Any) -> Optional[float]:
    """Dense-equivalent model operations in the window over the window
    and the chip's peak for the cell's precision (``ctx.peak_key``: the
    int8 peak for low-bit products, the bf16 peak for bf16 ones)."""
    if ctx is None or ctx.model_ops <= 0:
        return None
    return 100.0 * ctx.model_ops / ctx.window_s / ctx.peaks[ctx.peak_key]


def module_ms(ctx: Any, fragment: str) -> Optional[float]:
    tr = getattr(ctx, "trace", None)
    if tr is None:
        return None
    runs = tr.module_seconds(fragment)
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
