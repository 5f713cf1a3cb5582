"""Device time by the program's named scopes and the program's tick
spans: the join of device ops to compiled text, and the four readers
that use them, on hand-made events with known answers, a trace of a toy
program on the CPU, and a slice of a traced TPU v5e run."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import benchpath  # noqa: F401
from benchkit import counts, peaks, scopes, spans, trace as T
from benchkit.spec import Spec

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = Path(__file__).resolve().parent / "data"
SLICE = DATA / "trace_v5e_lm_spans.json"

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %cosine.1 = f32[8]{0} cosine(%param_0), metadata={op_name="jit(step)/kv_page_view/cos"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  ROOT %t = (s32[], f32[8]{0}) tuple(%p), metadata={op_name="jit(step)/qconv[tnn]/while/body/add"}
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.3 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation
  %copy.4 = f32[8]{1,0:T(8)} copy(%fusion.3)
  %dot.5 = f32[8]{0} dot(%copy.4, %x.1), metadata={op_name="jit(step)/qmm[bnn]/jit(_qmm_jit)/dot_general"}
  %while.6 = (s32[], f32[8]{0}) while(%dot.5), body=%body, metadata={op_name="jit(step)/qconv[tnn]/while"}
  ROOT %add.7 = f32[8]{0} add(%dot.5, %x.1), metadata={op_name="jit(step)/add"}
}
"""


def ev(plane, line, name, start, dur, **stats):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "stats": stats}


def test_scope_is_the_innermost_named_scope():
    assert scopes.scope_in("jit(f)/qconv[tnn]/jit(_qconv_jit)/and") == \
        "qconv[tnn]"
    assert scopes.scope_in("jit(f)/qmm[bnn]/kv_page_view/x") == "kv_page_view"
    assert scopes.scope_in("jit(f)/while/body/add") is None
    assert scopes.scope_in("jit(f)/qconv_helper/add") is None


def test_compiled_text_gives_each_instruction_its_scope():
    name, table = scopes.module_scopes(HLO)
    assert name == "jit_step"
    got = {k: (v.opcode, v.scope) for k, v in table.items()}
    # a fusion without op_name takes its called root's scope, a copy
    # without one its operand's; an op_name without a scope is no scope
    assert got["fusion.3"] == ("fusion", "kv_page_view")
    assert got["copy.4"] == ("copy", "kv_page_view")
    assert got["dot.5"] == ("dot", "qmm[bnn]")
    assert got["while.6"] == ("while", "qconv[tnn]")
    assert got["add.7"] == ("add", None)
    assert table["copy.4"].type == "f32[8]{1,0:T(8)}"


def test_a_program_with_other_names_takes_the_scopes_of_this_code():
    # what the device ran (from the compile cache, another version of the
    # code) names its instructions and op_names otherwise
    ran = (HLO.replace("%copy.4", "%copy.40").replace("kv_page_view/", "")
           .replace("qmm[bnn]/", ""))
    name, table = scopes.module_scopes(ran, own=HLO)
    assert table["copy.40"].scope == "kv_page_view"
    assert table["dot.5"].scope == "qmm[bnn]"
    assert "copy.4" not in table
    other = ran.replace("dot(%copy.40", "multiply(%copy.40")
    assert scopes.module_scopes(other, own=HLO) == (name, {})


def tpu_trace(copy_type="f32[8]{1,0:T(8)}"):
    """Two executions of ``jit_step`` on a TPU-like trace, its ops named
    by their HLO text."""
    def op(name, start, dur):
        return ev(DEV, "XLA Ops", name, start, dur)
    out = [ev(HOST, "python", "bench_window", 1000, 10000)]
    for s in (1000, 6000):
        out += [ev(DEV, "XLA Modules", "jit_step(77)", s, 3000),
                op("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %x.1), kind=kLoop",
                   s, 500),
                op(f"%copy.4 = {copy_type} copy(f32[8]{{0}} %fusion.3)",
                   s + 500, 250),
                op("%dot.5 = f32[8]{0} dot(f32[8]{0} %copy.4, f32[8]{0} %x.1)",
                   s + 750, 1000),
                op("%while.6 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) "
                   "%dot.5), body=%body", s + 1750, 1000),
                op("%add.7 = f32[8]{0} add(f32[8]{0} %dot.5, f32[8]{0} %x.1)",
                   s + 2750, 250)]
    return out


def test_ops_of_a_tpu_trace_are_joined_to_the_compiled_text():
    s = T.TraceSummary(tpu_trace())
    ops = scopes.ScopedOps(s, lambda: [HLO])
    assert ops.executions("step") == 2 and not ops.mismatched
    # the loop spans its body and is left out
    assert ops.seconds_by_scope("step") == pytest.approx(
        {"kv_page_view": 1500e-9, "qmm[bnn]": 2000e-9, None: 500e-9})


@pytest.mark.parametrize("change", ["type", "name"])
def test_an_op_of_another_program_drops_its_module(change):
    evs = tpu_trace(copy_type="f32[8]{0}") if change == "type" else [
        dict(e, name=e["name"].replace("%copy.4 ", "%copy.9 "))
        for e in tpu_trace()]
    ops = scopes.ScopedOps(T.TraceSummary(evs), [HLO])
    assert ops.mismatched == {"jit_step": 2}
    assert ops.executions("step") == 0 and not ops.any_scope(bool)


def test_a_cpu_trace_is_joined_through_its_hlo_op_stats(tmp_path):
    def f(x):
        with jax.named_scope("kv_page_view"):
            y = jnp.cos(x) * 2
        with jax.named_scope("qconv[tnn]"):
            z = (y @ y.T).sum(0)
        return z + 1

    step = jax.jit(f)
    x = jnp.ones((128, 128))
    jax.block_until_ready(step(x))
    cap = T.Capture(str(tmp_path))
    cap.start()
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for _ in range(3):
            jax.block_until_ready(step(x))
    s = T.TraceSummary(cap.stop())
    text = step.lower(x).compile().as_text()
    ops = scopes.ScopedOps(s, [text])
    assert ops.executions("jit_f") == 3 and not ops.mismatched
    table = scopes.module_scopes(text)[1]
    for r in ops.rows:
        assert r.scope == table[r.event["stats"]["hlo_op"]].scope
    by = ops.seconds_by_scope("jit_f")
    assert by["kv_page_view"] > 0 and by["qconv[tnn]"] > 0


# ----------------------------------------------------------- readers

def reader(name):
    return Spec.load().reader(name)


def span_trace():
    """Two ticks: one with a chunk that completes a prompt, one decode."""
    return [
        ev(HOST, "python", "bench_window", 0, 100_000_000),
        ev(HOST, "python", "engine/tick", 1_000_000, 40_000_000, tick=1),
        ev(HOST, "python", "sched/page_sync", 2_000_000, 1_000_000),
        ev(HOST, "python", "sched/prefill_wait", 4_000_000, 10_000_000),
        ev(HOST, "python", "sched/logits_pull", 14_000_000, 6_000_000,
           bytes=268435456, uids="[7]"),
        ev(HOST, "python", "sched/token_wait", 21_000_000, 15_000_000),
        ev(HOST, "python", "sched/emit", 36_000_000, 2_000_000),
        ev(HOST, "python", "engine/tick", 50_000_000, 30_000_000, tick=2),
        ev(HOST, "python", "sched/token_wait", 55_000_000, 20_000_000),
        ev(HOST, "python", "sched/release", 76_000_000, 1_000_000, uid=3),
        # outside the window: not read
        ev(HOST, "python", "engine/tick", 120_000_000, 9_000_000, tick=3),
        ev(HOST, "python", "sched/logits_pull", 121_000_000, 1_000_000),
        ev(DEV, "XLA Ops", "fusion.1", 4_000_000, 30_000_000),
    ]


def test_sched_host_ms_subtracts_the_waits_and_only_them():
    ctx = SimpleNamespace(trace=T.TraceSummary(span_trace()))
    # tick 1: 40 - 10 - 15 = 15 ms; tick 2: 30 - 20 = 10 ms
    assert reader("sched_host_ms")(ctx) == pytest.approx(12.5)
    assert reader("logits_pull_ms")(ctx) == pytest.approx(6.0)


def test_span_readers_read_nothing_without_the_program_spans():
    evs = [e for e in span_trace() if not e["name"].startswith(("engine/",
                                                                "sched/"))]
    ctx = SimpleNamespace(trace=T.TraceSummary(evs))
    assert reader("sched_host_ms")(ctx) is None
    assert reader("logits_pull_ms")(ctx) is None
    assert reader("sched_host_ms")(SimpleNamespace(trace=None)) is None


def scoped_ops(module, spec):
    """A module execution per (start, [(scope, dur)]) in ``spec``, each op
    carrying its scope as a recorded trace does."""
    out = [ev(HOST, "python", "bench_window", 0, 10_000_000)]
    for start, ops in spec:
        out.append(ev(DEV, "XLA Modules", f"{module}(5)", start, 2_000_000))
        t = start
        for scope, dur in ops:
            out.append(ev(DEV, "XLA Ops", f"%op.{t} = f32[8]{{0}} add()",
                          t, dur, scope=scope))
            t += dur
    return out


def test_kv_page_view_ms_is_per_decode_step():
    evs = scoped_ops("jit_serve_step", [
        (1_000_000, [("kv_page_view", 600_000), ("", 400_000)]),
        (4_000_000, [("kv_page_view", 800_000), ("qmm[tnn]", 200_000)])])
    evs += scoped_ops("jit_chunk_step", [
        (7_000_000, [("kv_page_view", 900_000)])])[1:]
    ctx = SimpleNamespace(trace=T.TraceSummary(evs), cfg={})
    assert reader("kv_page_view_ms")(ctx) == pytest.approx(0.7)


def test_kv_page_view_ms_reads_nothing_without_the_scope():
    evs = scoped_ops("jit_serve_step", [(1_000_000, [("", 600_000)])])
    ctx = SimpleNamespace(trace=T.TraceSummary(evs), cfg={})
    assert reader("kv_page_view_ms")(ctx) is None


def test_qconv_roofline_pct_against_the_least_time_of_the_convs():
    cfg = {"img_size": 4, "c_in": 8, "convs": [{"c_out": 8}, {"c_out": 16}],
           "fcs": [{"d_out": 10}], "modes": ["bf16", "tnn", "bnn"]}
    b, batches = 2, 3
    evs = scoped_ops("jit_forward", [
        (1_000_000 * i, [("", 100_000), ("qconv[tnn]", 300_000),
                         ("qmm[bnn]", 50_000)]) for i in range(1, 4)])
    pk = peaks.PEAKS["TPU v5e"]
    ctx = SimpleNamespace(
        trace=T.TraceSummary(evs), cfg=cfg, batches=batches, peaks=pk,
        model_ops=float(batches * b * counts.cnn_image_ops(cfg)))
    conv = counts.cnn_layers(cfg)[1]
    least = counts.least_time_s(
        counts.qconv_ops(b, 4, 4, 3, 3, 8, 16),
        counts.qconv_bytes(b, 4, 4, 4, 4, 3, 3, 8, 16, "tnn"), pk)
    assert conv["mode"] == "tnn"
    assert reader("qconv_roofline_pct")(ctx) == pytest.approx(
        100 * batches * least / 900e-6)


# ------------------------------------------------- recorded TPU slice

@pytest.fixture(scope="module")
def recorded():
    rec = json.loads(SLICE.read_text())
    return rec, T.TraceSummary(rec["events"], rec["spans"])


def test_recorded_slice_reads_the_scope_and_span_metrics(recorded):
    rec, s = recorded
    ctx = SimpleNamespace(trace=s, cfg={})
    want = rec["expect"]["readings"]
    page_view = reader("kv_page_view_ms")(ctx)
    assert page_view == pytest.approx(want["kv_page_view_ms"])
    assert 0 < page_view < 1e3 * min(s.module_seconds("serve_step"))
    host = reader("sched_host_ms")(ctx)
    assert host == pytest.approx(want["sched_host_ms"])
    ticks = spans.in_window(s, (spans.TICK,))
    assert 0 < host < min(t["dur_ns"] for t in ticks) / 1e6
    assert reader("logits_pull_ms")(ctx) == pytest.approx(
        want["logits_pull_ms"])


def test_recorded_slice_labels_idle_gaps_with_the_program_spans(recorded):
    _, s = recorded
    idle = dict(s.idle_by_host(40))
    under = sum(v for k, v in idle.items()
                if any(f"/{n}" in k for n in ("sched/", "decode_step",
                                              "prefill_chunk")))
    assert any("sched/token_wait" in k for k in idle)
    assert under >= 0.8 * sum(v for k, v in idle.items()
                              if k.startswith("engine_step"))
