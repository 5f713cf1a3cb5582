"""Find cells, configurations, traffic mixes and metric readers by name.

``BENCHMARK.json`` names every cell; each name resolves to a file:

* configuration ``<name>``  -> ``bench/configs/<name>.json``
* traffic mix ``<name>``    -> ``bench/traffic/<name>.json``
* per-layer metric ``<name>`` -> ``bench/metrics/<name>.py`` (a module
  with ``read(ctx) -> float | None``)

A later change adds a configuration, a mix or a metric by adding a file
and an entry; nothing here needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class Spec:
    """``BENCHMARK.json`` plus the directories its names resolve in."""

    def __init__(self, data: Dict[str, Any], bench_dir: Path = BENCH_DIR):
        self.data = data
        self.bench_dir = Path(bench_dir)

    @classmethod
    def load(cls, path: Optional[Path] = None,
             bench_dir: Path = BENCH_DIR) -> "Spec":
        path = Path(path) if path else Path(bench_dir).parent / "BENCHMARK.json"
        return cls(json.loads(path.read_text()), bench_dir)

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config_entry(self, name: str) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict[str, Any]:
        """The configuration file as it is run."""
        entry = self.config_entry(name)
        return json.loads((self.bench_dir.parent / entry["file"]).read_text())

    def traffic(self, name: str) -> Dict[str, Any]:
        path = self.bench_dir / "traffic" / f"{name}.json"
        if not path.is_file():
            raise KeyError(f"no traffic mix {name!r} ({path} missing)")
        return json.loads(path.read_text())

    def end_to_end(self, workload: str) -> List[Dict[str, Any]]:
        """End-to-end metrics this cell reports."""
        return [m for m in self.data["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[Dict[str, Any]]:
        """Per-layer metrics read in this cell's traced run: those that
        list it, and those without a list whose ``moves`` the cell
        reports."""
        reported = {m["name"] for m in self.end_to_end(workload)}
        out = []
        for m in self.data["per_layer"]:
            if "workloads" in m:
                if workload in m["workloads"]:
                    out.append(m)
            elif m["moves"] in reported:
                out.append(m)
        return out

    def reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        path = self.bench_dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise KeyError(f"no reader for metric {metric!r} ({path} missing)")
        mod_name = "bench_metric_" + metric.replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
