"""Jitted steps: device time of each execution of the decode step
(the program's ``serve_step`` module), mean over the traced window."""

from benchkit.readers import module_ms


def read(ctx):
    return module_ms(ctx, "serve_step")
