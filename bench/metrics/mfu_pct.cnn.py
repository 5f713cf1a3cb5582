"""Jitted steps: dense-equivalent conv operations of every image the
window completed over the window and the int8 peak."""

from benchkit.readers import mfu_pct


def read(ctx):
    return mfu_pct(ctx)
