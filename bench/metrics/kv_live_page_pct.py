"""Kernels: the share of the paged cache's pages that the decode ticks'
attention reads.  Each decode tick's ``sched/inputs`` span carries the
pages the packed entries' attention kernel reads for the step's live
lengths (``kv_pages_live``) and the pages a dense view of every page
table entry would decode (``kv_pages_view``); 100 * sum of the first
over sum of the second over the traced window's decode ticks.  The
stats are counted on the host from the lengths the kernel is given, so
the share describes the traffic (how much of the cache the live lengths
cover), not what the kernel copied.  A program whose spans carry no
such stats reads nothing."""

from benchkit import spans


def read(ctx):
    tr = getattr(ctx, "trace", None)
    if tr is None:
        return None
    found = [e["stats"] for e in spans.in_window(tr, ("sched/inputs",))
             if e["stats"].get("step") == "decode"
             and "kv_pages_view" in e["stats"]]
    view = sum(float(s["kv_pages_view"]) for s in found)
    if not view:
        return None
    return 100.0 * sum(float(s["kv_pages_live"]) for s in found) / view
