"""Readings that set a cell's output limit: sound runs of the program
on many seeds and control runs (the program's path one precision step
below the configuration's), all in one process on the chip.

    python bench/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 --seconds 30

Prints one JSON line a run: the seed, whether it was a control, every
number the check compares and the run's end-to-end numbers.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from benchkit import cli, device  # noqa: E402
from benchkit.cell import Run  # noqa: E402
from benchkit.spec import Spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    spec = Spec.load()
    w = spec.workload(args.workload)
    device.require_accelerator(int(w["chips"]))
    device.use_compile_cache()
    plan = [(int(s), False) for s in args.seeds.split(",") if s] + \
        [(int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in plan:
        t = time.perf_counter()
        run = Run(workload=w["name"], config=spec.config(w["config"]),
                  traffic=spec.traffic(w["traffic"]), seed=seed,
                  seconds=args.seconds, trace=False, chips=int(w["chips"]),
                  control=control)
        res = cli.execute(run)
        print(json.dumps({
            "cell": w["name"], "seed": seed, "control": control,
            "checks": {c.name: c.value for c in res.checks},
            "e2e": res.e2e, "failed": res.failed,
            "memory_peak_bytes": res.memory_peak_bytes,
            "notes": {k: res.notes[k] for k in res.notes
                      if k not in ("sampled",)},
            "wall_s": time.perf_counter() - t}, default=str), flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
