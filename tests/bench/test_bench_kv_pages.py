"""The kernels layer's page counter: ``kv_live_page_pct`` reads the
``kv_pages_live``/``kv_pages_view`` stats of the decode ticks'
``sched/inputs`` spans, on hand-made events with known answers and on a
CPU trace of a packed (tnn2) engine."""

from types import SimpleNamespace

import jax
import pytest

import benchpath  # noqa: F401
from benchkit import trace as T
from benchkit.spec import Spec

HOST = "/host:CPU"


def ev(name, start, dur, **stats):
    return {"plane": HOST, "line": "python", "name": name, "start_ns": start,
            "dur_ns": dur, "stats": stats}


def read(events):
    ctx = SimpleNamespace(trace=T.TraceSummary(events))
    return Spec.load().reader("kv_live_page_pct")(ctx)


def test_share_of_live_pages_over_the_decode_ticks():
    evs = [
        ev("bench_window", 0, 100),
        ev("sched/inputs", 10, 1, step="decode", kv_pages_live=30,
           kv_pages_view=128),
        ev("sched/inputs", 20, 1, step="decode", kv_pages_live=34,
           kv_pages_view=128),
        # a chunk tick's pages and a tick outside the window: not read
        ev("sched/inputs", 30, 1, step="chunk", kv_pages_live=4,
           kv_pages_view=128),
        ev("sched/inputs", 200, 1, step="decode", kv_pages_live=128,
           kv_pages_view=128),
    ]
    assert read(evs) == pytest.approx(100 * 64 / 256)


def test_reads_nothing_without_the_stats():
    # a program whose spans carry no page stats
    evs = [ev("bench_window", 0, 100), ev("sched/inputs", 10, 1)]
    assert read(evs) is None
    ctx = SimpleNamespace(trace=None)
    assert Spec.load().reader("kv_live_page_pct")(ctx) is None


def test_a_packed_engine_trace_carries_the_page_counts(tmp_path,
                                                       monkeypatch):
    from repro import obs
    from repro.configs import get_smoke
    from repro.models import model as model_mod
    from repro.models.common import ShardLayout
    from repro.serving import Engine, Request, SamplerConfig, ServeConfig
    from repro.serving.scheduler import ChunkedScheduler

    cfg = get_smoke("tinyllama-1.1b").with_(kv_cache_dtype="tnn2")
    layout = ShardLayout(tp=1)
    params = model_mod.init_lm(jax.random.PRNGKey(0), cfg, layout)
    eng = Engine(params, cfg, layout, ServeConfig(
        num_slots=2, max_len=64, page_size=8, prefill_chunk=8,
        sampler=SamplerConfig(temperature=0.0)), seed=0)
    counted = []
    kv_pages = ChunkedScheduler._kv_pages

    def record(self, lengths):
        out = kv_pages(self, lengths)
        counted.append((lengths.copy(), out))
        return out

    monkeypatch.setattr(ChunkedScheduler, "_kv_pages", record)
    prev = obs.obs_enabled()
    obs.set_enabled(True)
    try:
        for uid, n in enumerate((5, 19)):
            eng.submit(Request(uid=uid, prompt=list(range(1, n + 1)),
                               max_new_tokens=4))
        cap = T.Capture(str(tmp_path))
        cap.start()
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            eng.run()
        events = cap.stop()
    finally:
        obs.set_enabled(prev)
    inputs = [e["stats"] for e in T.TraceSummary(events).events
              if e["name"] == "sched/inputs"]
    assert [s["step"] for s in inputs].count("chunk") == 3
    assert len(inputs) == len(counted)
    # a page per 8 live positions of each row, ring-capped at npp = 8
    for (lengths, out), stats in zip(counted, inputs):
        assert out["kv_pages_view"] == 2 * 8
        assert out["kv_pages_live"] == sum(-(-n // 8) for n in lengths)
        assert int(stats["kv_pages_live"]) == out["kv_pages_live"]
    decode = [out for (_, out), s in zip(counted, inputs)
              if s["step"] == "decode"]
    assert read(events) == pytest.approx(
        100 * sum(o["kv_pages_live"] for o in decode)
        / sum(o["kv_pages_view"] for o in decode))
