"""Scheduler: the device-to-host copy of a prefill chunk's logits when
the chunk completes a prompt (``sched/logits_pull``), mean duration over
the traced window's pulls."""

from benchkit import spans


def read(ctx):
    tr = getattr(ctx, "trace", None)
    return None if tr is None else spans.mean_ms(tr, "sched/logits_pull")
