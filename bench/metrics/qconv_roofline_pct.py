"""Kernels: the least time the window's low-bit convs could take on the
chip (each conv of each batch: its operations at the int8 peak or its
bytes at the HBM bandwidth, whichever is longer) over the device time
of the ops under the program's ``qconv[<mode>]`` scopes."""

from benchkit import programs, scopes


def read(ctx):
    tr = getattr(ctx, "trace", None)
    if tr is None or not tr.ops or not ctx.batches:
        return None
    b = programs.cnn_batch(ctx)
    ops = scopes.ScopedOps(tr, lambda: [programs.cnn_forward_texts(ctx.cfg, b)])
    busy = ops.seconds("forward", lambda s: s.startswith("qconv["))
    if busy <= 0:
        return None
    least = sum(c["least_s"] for c in programs.qconv_least(ctx))
    return 100.0 * ctx.batches * least / busy
