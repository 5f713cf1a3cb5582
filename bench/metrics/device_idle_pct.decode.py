"""Device: share of the traced window in which no op ran on the chip
(one minus the union of op intervals over the window)."""

from benchkit.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
