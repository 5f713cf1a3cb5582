"""Scheduler: host time of a tick in which the tick has nothing of its
own running on the chip: each ``engine/tick`` span less what its
``sched/prefill_wait`` and ``sched/token_wait`` spans cover, mean over
the traced window's ticks."""

from benchkit import spans


def read(ctx):
    tr = getattr(ctx, "trace", None)
    return None if tr is None else spans.tick_host_ms(tr)
