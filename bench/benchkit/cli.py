"""``python bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: run one cell once and print one JSON line.

The cell's configuration and traffic mix are found by name; the
configuration's ``kind`` picks the runner (``lm_serve`` or ``cnn``).
With ``--trace 0`` the line holds the cell's end-to-end metrics; with
``--trace 1`` the profiler records the window and the line holds the
per-layer metrics, ``busy_s``/``window_s`` and a ``breakdown``.  Every
number the output check compares is printed beside its limit, last on
standard error and last in the line.
"""

from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import tempfile
from typing import Any, Dict, List, Optional

from . import device
from .cell import Result, Run
from .spec import ROOT, Spec

RUNNERS = {"lm_serve": "benchkit.lm", "cnn": "benchkit.cnn"}


def parse(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def make_run(spec: Spec, args: argparse.Namespace, trace_dir=None) -> Run:
    w = spec.workload(args.workload)
    return Run(workload=w["name"], config=spec.config(w["config"]),
               traffic=spec.traffic(w["traffic"]), seed=args.seed,
               seconds=args.seconds, trace=bool(args.trace),
               chips=int(w["chips"]), trace_dir=trace_dir)


def execute(run: Run) -> Result:
    kind = run.config["kind"]
    if kind not in RUNNERS:
        raise KeyError(f"no runner for configuration kind {kind!r}")
    return importlib.import_module(RUNNERS[kind]).run(run)


def result_line(spec: Spec, run: Run, res: Result) -> Dict[str, Any]:
    """The JSON object the run prints last."""
    metrics: Dict[str, Dict[str, Any]] = {}
    if run.trace:
        for m in spec.per_layer(run.workload):
            val = spec.reader(m["name"])(res.layer)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        for m in spec.end_to_end(run.workload):
            if m["name"] not in res.e2e:
                raise KeyError(f"{run.workload}: runner measured no "
                               f"{m['name']!r}")
            metrics[m["name"]] = {"value": float(res.e2e[m["name"]]),
                                  "unit": m["unit"]}
    dev = device.info(run.chips)
    dev["memory_peak_bytes"] = res.memory_peak_bytes
    line: Dict[str, Any] = {
        "correct": all(c.ok for c in res.checks) and res.failed == 0,
        "attempted": res.attempted, "failed": res.failed,
        "metrics": metrics, "device": dev}
    if run.trace and res.trace is not None:
        dev["busy_s"] = res.trace.busy_s
        dev["window_s"] = res.trace.window_s
        line["breakdown"] = res.trace.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                               "ok": c.ok} for c in res.checks}
    return line


def main(argv: Optional[List[str]] = None) -> int:
    args = parse(argv)
    spec = Spec.load()
    w = spec.workload(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    try:
        device.require_accelerator(int(w["chips"]))
    except device.NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    cache = device.use_compile_cache()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        run = make_run(spec, args, trace_dir)
        res = execute(run)
        line = result_line(spec, run, res)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    d = line["device"]
    print(f"bench: {run.workload} seed {run.seed} on {d['platform']} "
          f"{d['kind']} x{d['count']}; compile cache {cache}; "
          f"{json.dumps(res.notes, default=str)}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
