"""The reduction from a profiler trace to busy time, idle gaps, module
and kernel time, on small hand-made and recorded traces."""

import json
from pathlib import Path

import pytest

import benchpath  # noqa: F401
from benchkit import trace as T

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = Path(__file__).resolve().parent / "data"


def ev(plane, line, name, start, dur, **stats):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "stats": stats}


def hand_trace():
    return [
        ev(HOST, "python", "bench_window", 1000, 10000),
        ev(HOST, "python", "engine_step", 1000, 3000),
        ev(HOST, "python", "decode_step", 1500, 500),
        ev(HOST, "python", "engine_step", 5000, 4000),
        ev(HOST, "python", "unrelated", 5000, 100),
        ev(DEV, "XLA Ops", "fusion.1", 500, 1000),          # clipped to 500
        ev(DEV, "XLA Ops", "%fusion.2 = f32[32,8192]{1,0} fusion(%p0), "
           "kind=kLoop", 2000, 1000),
        ev(DEV, "XLA Ops", "fusion.3", 2500, 1000),          # overlaps .2
        ev(DEV, "XLA Ops", "%while.4 = (s32[], f32[8]) while((s32[], f32[8]) "
           "%t), body=%b", 6000, 1000),
        ev(DEV, "XLA Ops", "fusion.5", 10500, 1500),         # clipped to 500
        ev(DEV, "XLA Modules", "jit_serve_step(7)", 2000, 1500),
        ev(DEV, "XLA Modules", "jit_chunk_step(8)", 6000, 1000),
        ev(DEV, "XLA Modules", "jit_serve_step(7)", 20000, 1000),  # outside
    ]


def test_busy_is_the_union_of_op_intervals_in_the_window():
    s = T.TraceSummary(hand_trace(), ("engine_step", "decode_step"))
    assert s.window_s == pytest.approx(10000e-9)
    assert s.devices == [DEV]
    assert s.busy_s == pytest.approx(3500e-9)
    assert s.idle_share == pytest.approx(0.65)


def test_idle_gaps_are_labelled_with_the_open_host_spans():
    s = T.TraceSummary(hand_trace(), ("engine_step", "decode_step"))
    assert s.idle_gaps() == [(1500, 2000), (3500, 6000), (7000, 10500)]
    idle = dict(s.idle_by_host())
    assert idle == pytest.approx({"engine_step/decode_step": 500e-9,
                                  "other host work": 2500e-9,
                                  "engine_step": 3500e-9})
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_module_time_counts_executions_in_the_window():
    s = T.TraceSummary(hand_trace())
    assert s.module_seconds("serve_step") == [pytest.approx(1500e-9)]
    assert s.module_seconds("chunk_step") == [pytest.approx(1000e-9)]
    assert s.module_seconds("forward") == []


def test_breakdown_lists_top_ops_and_idle_by_host():
    s = T.TraceSummary(hand_trace(), ("engine_step", "decode_step"))
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "%fusion.2 fusion f32[32,8192]{1,0}"
    assert not any(" while " in n for n in names)     # leaves only
    assert b["idle_gaps"][0][0] == "engine_step"


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench_window"):
        T.TraceSummary([e for e in hand_trace()
                        if e["name"] != "bench_window"])


def test_cpu_traces_take_ops_from_host_threads():
    evs = [ev(HOST, "python", "bench_window", 0, 1000),
           ev(HOST, "tf_XLAPjRtCpuClient/1", "dot.1", 100, 200,
              hlo_op="dot.1", hlo_module="jit_f"),
           ev(HOST, "tf_XLAPjRtCpuClient/1", "ThreadpoolListener", 300, 0)]
    s = T.TraceSummary(evs)
    assert s.devices == ["host"] and s.busy_s == pytest.approx(200e-9)


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_recorded_chip_traces(name):
    """Slices of traces recorded on a TPU v5e: the reduction finds the
    device, the window and the program's modules there."""
    rec = json.loads((DATA / name).read_text())
    s = T.TraceSummary(rec["events"], rec["spans"])
    assert s.devices and s.devices[0].startswith("/device:TPU")
    assert 0 < s.busy_s <= s.window_s
    assert sum(v for _, v in s.idle_by_host()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    for frag, n in rec["expect"]["modules"].items():
        assert len(s.module_seconds(frag)) == n
    assert s.breakdown()["device_ops"]
