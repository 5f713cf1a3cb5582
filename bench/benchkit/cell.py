"""What every cell runner shares: the run's parameters, its result, the
clock from process start, and compile counting inside the window."""

from __future__ import annotations

import copy
import dataclasses
import os
import time
from typing import Any, Dict, List, Optional

_T_IMPORT = time.perf_counter()


def process_age_s() -> float:
    """Seconds since this process started (``/proc``; falls back to
    the time since this module was imported)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


@dataclasses.dataclass
class Run:
    """One run of one cell."""
    workload: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    chips: int = 1
    trace_dir: Optional[str] = None
    control: bool = False

    @property
    def program_config(self) -> Dict[str, Any]:
        """The configuration the program runs: the cell's own, or with
        ``control`` the configuration's ``control`` overrides applied
        (the program's path one precision step below the stated one).
        The reference always follows the cell's own configuration."""
        if not self.control:
            return self.config
        return with_overrides(self.config, self.config["control"])


def with_overrides(cfg: Dict[str, Any], overrides: Dict[str, Any]):
    """``cfg`` with dotted-path overrides (``"convs.0.mode": "int8"``)."""
    out = copy.deepcopy(cfg)
    for path, value in overrides.items():
        node, keys = out, path.split(".")
        for k in keys[:-1]:
            node = node[int(k)] if isinstance(node, list) else node[k]
        last = keys[-1]
        if isinstance(node, list):
            node[int(last)] = value
        else:
            node[last] = value
    return out


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    higher_fails: bool = True       # value above the limit fails

    @property
    def ok(self) -> bool:
        if self.value != self.value:    # NaN never passes
            return False
        return self.value <= self.limit if self.higher_fails \
            else self.value >= self.limit


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    e2e: Dict[str, float]
    checks: List[Check]
    memory_peak_bytes: int
    layer: Any = None               # context handed to per-layer readers
    trace: Any = None               # TraceSummary of the traced window
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


class CompileCounter:
    """Counts compilations and persistent-cache loads while armed."""

    def __init__(self) -> None:
        import jax

        self.armed = False
        self.count = 0

        def on_event(event, **_):
            if self.armed and event == "/jax/compilation_cache/cache_hits":
                self.count += 1

        def on_duration(event, duration, **_):
            if self.armed and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
