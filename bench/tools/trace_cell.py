"""One traced run of a cell, read further than the benchmark's line:
idle gaps by the program's own spans, device time by named scope, and a
recorded slice of the trace for the benchmark's CPU tests.

    python bench/tools/trace_cell.py --workload <cell> --seed <n> \\
        --seconds <s> --out <dir> [--slice <file.json>]

Keeps the profiler's ``.xplane.pb`` under ``--out`` and prints JSON
lines: the run's result line (as ``bench/run.py --trace 1`` prints it),
then ``idle_by_program``, ``spans`` (count and mean ms of each program
span), ``scopes`` (device seconds by scope in the cell's main module)
and ``tf_op_check`` (the scope join against the trace's own ``tf_op``
op metadata, where the installation can read it).
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from benchkit import cli, cnn, device, lm, programs  # noqa: E402
from benchkit import scopes, spans, trace  # noqa: E402
from benchkit.spec import Spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--slice", default="")
    args = ap.parse_args()
    spec = Spec.load()
    w = spec.workload(args.workload)
    device.require_accelerator(int(w["chips"]))
    device.use_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    args.trace = 1
    run = cli.make_run(spec, args, trace_dir=args.out)
    res = cli.execute(run)
    t = time.perf_counter()
    emit("result", cli.result_line(spec, run, res))
    emit("readers_s", time.perf_counter() - t)
    emit("notes", res.notes)
    tr, ctx = res.trace, res.layer
    is_lm = run.config["kind"] == "lm_serve"
    harness = lm.SPANS if is_lm else cnn.SPANS
    emit("idle_by_program", spans.idle_by_program(tr, harness))
    emit("spans", {n: [len(spans.in_window(tr, (n,))), spans.mean_ms(tr, n)]
                   for n in spans.PROGRAM})
    if is_lm:
        module = "serve_step"
        texts = [programs.lm_serve_step_texts(run.config)]
    else:
        module = "forward"
        texts = [programs.cnn_forward_texts(run.config,
                                           programs.cnn_batch(ctx))]
    ops = scopes.ScopedOps(tr, texts)
    emit("scopes", {"module": module, "executions": ops.executions(module),
                    "mismatched": dict(ops.mismatched),
                    "seconds": {str(k): v for k, v in
                                ops.seconds_by_scope(module).items()},
                    "bounds": None if is_lm else programs.qconv_least(ctx)})
    emit("tf_op_check", tf_op_check(args.out, texts))
    if args.slice:
        write_slice(args.slice, run, tr, ops, harness)
    return 0


def emit(kind, value) -> None:
    print(json.dumps({kind: value}, default=str), flush=True)


def tf_op_check(trace_dir, texts):
    """The TPU trace also keeps each op's ``op_name`` as a ``tf_op`` stat
    of the op's metadata, which ``jax.profiler.ProfileData`` does not
    expose: compare the scopes the compiled-text join gives with it."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:          # not every installation has it
        return {"skipped": repr(e)}
    path = trace.find_xplane(trace_dir)
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    tables = dict(scopes.module_scopes(*t) for t in texts)
    tally = collections.Counter()
    examples = {}
    for pl in xs.planes:
        if not trace.is_device_plane(pl.name):
            continue
        names = {k: v.name for k, v in pl.stat_metadata.items()}
        progs = {}
        for em in pl.event_metadata.values():
            st = {names.get(s.metadata_id): s for s in em.stats}
            if "tf_op" not in st or "program_id" not in st:
                continue
            op_name = st["tf_op"].str_value or pl.stat_metadata[
                st["tf_op"].ref_value].name
            progs.setdefault(st["program_id"].uint64_value, []).append(
                (em.display_name or em.name.split(" = ")[0].lstrip("%"),
                 op_name))
        for em in pl.event_metadata.values():
            mod = scopes.module_base(em.name)
            if mod not in tables or "(" not in em.name:
                continue
            pid = int(em.name.rsplit("(", 1)[1].rstrip(")"))
            for iname, op_name in progs.get(pid, []):
                ins = tables[mod].get(iname)
                if ins is None:
                    kind = "not_in_text"
                elif ins.scope == scopes.scope_in(op_name):
                    kind = "agree"
                else:
                    kind = f"{scopes.scope_in(op_name)}->{ins.scope}"
                tally[kind] += 1
                if kind != "agree" and len(examples.setdefault(kind, [])) < 4:
                    examples[kind].append([em.name, iname, op_name[:160]])
    return {"tally": dict(tally), "examples": examples}


def write_slice(path, run, tr, ops, harness) -> None:
    """The window's first tick that pulls a chunk's logits, as a small
    recorded trace: the program's spans, the device ops with their scope
    as a ``scope`` stat, and the module executions."""
    pull = min(spans.in_window(tr, ("sched/logits_pull",)),
               key=lambda e: e["start_ns"])
    tick = next(t for t in spans.in_window(tr, (spans.TICK,))
                if t["start_ns"] <= pull["start_ns"]
                < t["start_ns"] + t["dur_ns"])
    t0 = tick["start_ns"] - 1e6
    t1 = tick["start_ns"] + tick["dur_ns"] + 1e6
    names = set(harness) | set(spans.PROGRAM)
    table = {id(r.event): r.scope for r in ops.rows}
    out = [{"plane": "/host:CPU", "line": "python3", "name": trace.WINDOW_SPAN,
            "start_ns": t0, "dur_ns": t1 - t0, "stats": {}}]
    for e in tr.events:
        if not (t0 <= e["start_ns"] < t1):
            continue
        dev = trace.is_device_plane(e["plane"])
        if not dev and e["name"] in names:
            out.append(dict(e))
        elif dev and e["line"] in trace.MODULE_LINES:
            out.append(dict(e))
        elif dev and e["line"] in trace.OPS_LINES:
            out.append(dict(e, name=e["name"][:96],
                            stats={"scope": table.get(id(e)) or ""}))
    rec = {"source": f"{run.workload} on a TPU v5e, seed {run.seed}: the "
                     f"window's first tick that pulls a chunk's logits",
           "spans": list(harness) + list(spans.PROGRAM),
           "expect": {"modules": {"serve_step": sum(
               1 for e in out if e["line"] in trace.MODULE_LINES
               and "serve_step" in e["name"])}},
           "events": out}
    ctx = SimpleNamespace(trace=trace.TraceSummary(out, rec["spans"]),
                          cfg=run.config)
    spec = Spec.load()
    rec["expect"]["readings"] = {
        m: spec.reader(m)(ctx)
        for m in ("sched_host_ms", "logits_pull_ms", "kv_page_view_ms")}
    with open(path, "w") as f:
        json.dump(rec, f, separators=(",", ":"))


if __name__ == "__main__":
    sys.exit(main())
