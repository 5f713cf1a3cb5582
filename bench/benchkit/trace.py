"""Profiler capture and the reduction from a trace to numbers.

A trace is reduced from a flat list of events, each a dict with
``plane``, ``line``, ``name``, ``start_ns``, ``dur_ns`` and ``stats``
(string keys; values as recorded), so the same code reads a live
``.xplane.pb`` and a small recorded fixture.

* Device ops: events on a device plane (``/device:...``) on its ops line
  (``XLA Ops``); on a CPU-only trace, host events that carry an
  ``hlo_op`` stat.
* Window: the host span named ``bench_window`` that the harness wraps
  around the measured window; device time outside it is clipped away.
* Busy: the union of device-op intervals in the window, per device,
  averaged over the devices; idle share is ``1 - busy / window``.
* Idle gaps: the complement of that union, each labelled with the host
  spans (harness and program annotations) open at the gap's middle.
"""

from __future__ import annotations

import collections
import glob
import os
import warnings
from typing import Any, Dict, Iterable, List, Tuple

WINDOW_SPAN = "bench_window"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)

Event = Dict[str, Any]


def _plain(v):
    if isinstance(v, (int, float, str, bool)) or v is None:
        return v
    return str(v)


def load_xplane(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out: List[Event] = []
    with warnings.catch_warnings():
        # jaxlib's event_stats type warns on every iteration
        warnings.simplefilter("ignore", DeprecationWarning)
        for pl in pd.planes:
            for ln in pl.lines:
                for e in ln.events:
                    out.append({"plane": pl.name, "line": ln.name,
                                "name": e.name, "start_ns": float(e.start_ns),
                                "dur_ns": float(e.duration_ns),
                                "stats": {k: _plain(v) for k, v in e.stats}})
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:")


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float) -> Tuple[float, float]:
    return max(a, lo), min(b, hi)


class TraceSummary:
    """Numbers read from one traced window."""

    def __init__(self, events: List[Event], span_names: Iterable[str] = ()):
        self.events = events
        win = [e for e in events if e["name"] == WINDOW_SPAN
               and not is_device_plane(e["plane"])]
        if not win:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
        w = max(win, key=lambda e: e["dur_ns"])
        self.t0, self.t1 = w["start_ns"], w["start_ns"] + w["dur_ns"]
        self.window_s = (self.t1 - self.t0) / 1e9

        dev_planes = sorted({e["plane"] for e in events
                             if is_device_plane(e["plane"])
                             and e["line"] in OPS_LINES})
        if dev_planes:
            self.ops = [e for e in events if e["plane"] in dev_planes
                        and e["line"] in OPS_LINES]
            self.devices = dev_planes
            key = "plane"
        else:       # CPU backend: ops run on host threads
            self.ops = [e for e in events if "hlo_op" in e["stats"]]
            self.devices = ["host"] if self.ops else []
            key = None
        self.ops = [e for e in self.ops
                    if e["start_ns"] + e["dur_ns"] > self.t0
                    and e["start_ns"] < self.t1]
        self.modules = [e for e in events if is_device_plane(e["plane"])
                        and e["line"] in MODULE_LINES
                        and self.t0 <= e["start_ns"] < self.t1]

        self.busy_by_device: Dict[str, List[Tuple[float, float]]] = {}
        for dev in self.devices:
            iv = [_clip(e["start_ns"], e["start_ns"] + e["dur_ns"],
                        self.t0, self.t1)
                  for e in self.ops if key is None or e[key] == dev]
            self.busy_by_device[dev] = _union((a, b) for a, b in iv if b > a)
        n = max(len(self.devices), 1)
        self.busy_s = sum(sum(b - a for a, b in iv)
                          for iv in self.busy_by_device.values()) / n / 1e9

        names = set(span_names) | {WINDOW_SPAN}
        self.spans = [e for e in events if not is_device_plane(e["plane"])
                      and e["name"] in names]

    # ------------------------------------------------------------ reads

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, fragment: str) -> List[float]:
        """Device time of each execution of the jitted programs whose
        module name contains ``fragment``."""
        return [e["dur_ns"] / 1e9 for e in self.modules
                if fragment in e["name"]]

    def top_ops(self, n: int = 10) -> List[List[Any]]:
        """Device time by op, leaves only: a control-flow op (a loop,
        a conditional, a call) spans the ops of its body."""
        tot: Dict[str, float] = collections.Counter()
        for e in self.ops:
            if op_label(e).split(" ")[1:2] in (["while"], ["conditional"],
                                                ["call"]):
                continue
            a, b = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"],
                         self.t0, self.t1)
            tot[op_label(e)] += max(b - a, 0.0) / 1e9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the first device inside the window."""
        if not self.devices:
            return [(self.t0, self.t1)]
        busy = self.busy_by_device[self.devices[0]]
        gaps, cur = [], self.t0
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        return gaps

    def host_labels(self, times: List[float]) -> List[str]:
        """What the host was doing at each time: the names of the spans
        open then, outermost first (one sweep over sorted times)."""
        marks = []
        for i, e in enumerate(self.spans):
            if e["name"] == WINDOW_SPAN:
                continue
            marks.append((e["start_ns"], 0, i))
            marks.append((e["start_ns"] + e["dur_ns"], 1, i))
        marks.sort()
        order = sorted(range(len(times)), key=lambda j: times[j])
        out = [""] * len(times)
        open_: Dict[int, Event] = {}
        m = 0
        for j in order:
            t = times[j]
            while m < len(marks) and marks[m][0] <= t:
                _, kind, i = marks[m]
                if kind == 0:
                    open_[i] = self.spans[i]
                else:
                    open_.pop(i, None)
                m += 1
            spans = sorted(open_.values(),
                           key=lambda e: (e["start_ns"], -e["dur_ns"]))
            out[j] = "/".join(e["name"] for e in spans) or "other host work"
        return out

    def idle_by_host(self, n: int = 10) -> List[List[Any]]:
        """Idle seconds summed by what the host was doing."""
        gaps = self.idle_gaps()
        labels = self.host_labels([(a + b) / 2 for a, b in gaps])
        tot: Dict[str, float] = collections.Counter()
        for (a, b), lab in zip(gaps, labels):
            tot[lab] += (b - a) / 1e9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> Dict[str, List[List[Any]]]:
        return {"device_ops": self.top_ops(10),
                "idle_gaps": self.idle_by_host(10)}


def op_label(e: Event) -> str:
    """A short name for a device op.  TPU traces name each op by its HLO
    text (``%fusion.3 = f32[8,128]{...} fusion(...), kind=kLoop``):
    keep the instruction name, its opcode and the start of its result
    type."""
    name = e["name"]
    lhs, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    depth, i = 0, 0
    for i, ch in enumerate(rest):          # skip the (possibly tuple) type
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].split("(", 1)[0]
    return f"{lhs} {opcode} {rest[:i][:60]}"


class Capture:
    """``jax.profiler`` trace of one window into ``trace_dir``."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # annotations only, no call stacks
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> List[Event]:
        import jax
        jax.profiler.stop_trace()
        return load_xplane(find_xplane(self.dir))
