"""Scheduler: mean over the window's ticks of live slots / slots, as the
engine's slot state stands after each tick (``Engine.slot_uid``)."""


def read(ctx):
    ticks = getattr(ctx, "ticks", None)
    if not ticks:
        return None
    slots = ctx.cfg["serve"]["num_slots"]
    return 100.0 * sum(k["live"] for k in ticks) / (len(ticks) * slots)
