"""Kernels: device time of the ops under the program's ``kv_page_view``
scope (the paged cache's unpack of every cached position) in each
execution of the decode step (``serve_step``), mean over the traced
window's executions."""

from benchkit import programs, scopes


def read(ctx):
    tr = getattr(ctx, "trace", None)
    if tr is None or not tr.ops:
        return None
    ops = scopes.ScopedOps(tr, lambda: [programs.lm_serve_step_texts(ctx.cfg)])
    n = ops.executions("serve_step")
    if not n or not ops.any_scope(lambda s: s == "kv_page_view"):
        return None
    return 1e3 * ops.seconds("serve_step", lambda s: s == "kv_page_view") / n
