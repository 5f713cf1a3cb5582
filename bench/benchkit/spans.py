"""The program's own host spans in a traced window.

With obs on, the engine wraps each scheduler tick in ``engine/tick`` and
each host step of it in a ``sched/*`` span (``serving/scheduler.py``).
Two of them wait for the chip: ``sched/prefill_wait`` (the chunk's
logits) and ``sched/token_wait`` (the decode step's tokens).  The rest
of a tick is host time in which the tick has nothing of its own running
on the chip.  A trace of a program without these spans reads nothing.
"""

from __future__ import annotations

from typing import Iterable, List, Optional

from . import trace

TICK = "engine/tick"
WAITS = ("sched/prefill_wait", "sched/token_wait")
SCHED = ("sched/expire", "sched/admit", "sched/release", "sched/page_sync",
         "sched/inputs", "sched/prefill_wait", "sched/logits_pull",
         "sched/numeric_guard", "sched/token_wait", "sched/emit")
# what idle gaps of an LM cell can be labelled with: the harness's spans
# and every span the program opens in a tick
PROGRAM = (TICK, "decode_step", "prefill_chunk") + SCHED


def in_window(summary, names: Iterable[str]) -> List[dict]:
    """Host spans named ``names`` that start inside the window."""
    names = set(names)
    return [e for e in summary.events
            if e["name"] in names and not trace.is_device_plane(e["plane"])
            and summary.t0 <= e["start_ns"] < summary.t1]


def tick_host_ms(summary) -> Optional[float]:
    """Mean over the window's ticks of the tick's duration less the part
    of it its wait spans cover, in ms; None without tick spans."""
    ticks = in_window(summary, (TICK,))
    if not ticks:
        return None
    waits = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                   for e in in_window(summary, WAITS))
    total = 0.0
    for t in ticks:
        a0, b0 = t["start_ns"], t["start_ns"] + t["dur_ns"]
        inside = [(max(a, a0), min(b, b0)) for a, b in waits
                  if b > a0 and a < b0]
        covered = sum(b - a for a, b in trace._union(inside))
        total += t["dur_ns"] - covered
    return total / len(ticks) / 1e6


def mean_ms(summary, name: str) -> Optional[float]:
    """Mean duration of the window's spans named ``name``, in ms."""
    found = in_window(summary, (name,))
    if not found:
        return None
    return sum(e["dur_ns"] for e in found) / len(found) / 1e6


def idle_by_program(summary, harness_spans: Iterable[str] = ()) -> list:
    """Idle seconds of the window by what the host was doing, labelled
    with the harness's spans and the program's own."""
    labelled = trace.TraceSummary(summary.events,
                                  tuple(harness_spans) + PROGRAM)
    return labelled.idle_by_host(len(PROGRAM) + 8)
