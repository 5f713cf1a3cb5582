"""Jitted steps: dense-equivalent model operations of every token the
window put through the model (projections, attention at its context,
the LM head for each output token) over the window and the chip's peak
for the configuration's precision."""

from benchkit.readers import mfu_pct


def read(ctx):
    return mfu_pct(ctx)
