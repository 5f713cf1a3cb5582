"""Both cell runners end to end on the CPU at a tiny size: the harness's
look for a chip is skipped and the rest of a run is driven.  A sound
program comes out correct; the control (the program's own path one
precision step below the configuration's) and each fault a cell can
have, planted in the timed path, come out not correct."""

import json
from pathlib import Path

import jax.numpy as jnp
import pytest

import benchpath  # noqa: F401
from benchkit import cli, peaks
from benchkit.cell import Run
from benchkit.spec import Spec

CONFIGS = Path(benchpath.BENCH) / "configs"

LM_MIX = {"kind": "lm", "loop": "closed", "requests": 256,
          "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
          "output_len": {"dist": "uniform", "min": 40, "max": 120},
          "stagger": True}
CNN_MIX = {"kind": "cnn", "loop": "closed", "batch": 32,
           "distinct_batches": 2}


LONG_SERVE = {"num_slots": 4, "max_len": 768, "page_size": 16,
              "prefill_chunk": 32}


def tiny_lm(**kw):
    cfg = json.loads((CONFIGS / "chameleon-34b-4l-bf16.json").read_text())
    cfg.update(num_layers=2, d_model=256, num_heads=4, num_kv_heads=2,
               d_ff=512, vocab_size=512)
    cfg["serve"] = {"num_slots": 4, "max_len": 192, "page_size": 16,
                    "prefill_chunk": 32}
    # limit at this size, between sound runs (widest gap 0.04-0.10 over
    # four seeds) and the int8 control (0.63-0.93)
    cfg["correct"] = dict(cfg["correct"], logit_gap_max=0.3,
                          sample_requests=3, min_compared_tokens=100)
    cfg.update(kw)
    return cfg


def tiny_cnn():
    cfg = json.loads((CONFIGS / "binarynet-cifar10.json").read_text())
    cfg.update(img_size=8, convs=[
        {"c_out": 8}, {"c_out": 32, "pool": True}, {"c_out": 32},
        {"c_out": 32, "pool": True}],
        fcs=[{"d_out": 64}, {"d_out": 10}],
        modes=["f32", "tnn", "tbn", "bnn", "bnn", "tnn"])
    # limit at this size, between sound runs (worst image 1.4e-7 to
    # 1.5e-7 over six seeds) and the int8 first-conv control (0.0114 to
    # 0.0133)
    cfg["correct"] = {"rel_err_max": 1e-4}
    return cfg


def run_cell(workload, cfg, mix, seconds=1.5, trace=False, tmp=None,
             control=False):
    run = Run(workload=workload, config=cfg, traffic=mix, seed=2**31 + 77,
              seconds=seconds, trace=trace, control=control,
              trace_dir=str(tmp) if tmp else None)
    res = cli.execute(run)
    return cli.result_line(Spec.load(), run, res), res


def untraced(workload):
    """A run without the profiler: its line holds end-to-end metrics."""
    return Run(workload=workload, config={}, traffic={}, seed=0, seconds=0,
               trace=False)


LM_CELL = "chameleon-34b-4l-bf16.decode-long"
CNN_CELL = "binarynet-cifar10.batch1024"


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5e"])


def test_lm_cell_sound_run_is_correct(cpu_peaks, tmp_path):
    line, res = run_cell(LM_CELL, tiny_lm(), LM_MIX, trace=True, tmp=tmp_path)
    end = cli.result_line(Spec.load(), untraced(LM_CELL), res)
    assert set(end["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= {"slot_occupancy_pct", "mfu_pct.decode",
                                    "device_idle_pct.decode"}
    assert 0 < line["metrics"]["slot_occupancy_pct"]["value"] <= 100
    assert line["device"]["window_s"] > 0 and line["device"]["busy_s"] > 0
    assert list(line)[-1] == "checks"
    assert res.notes["compiles_in_window"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_lm_control_is_not_correct():
    # the program's int8 path: one precision step below bf16
    line, _ = run_cell(LM_CELL, tiny_lm(), LM_MIX, control=True)
    assert not line["checks"]["logit_gap_max"]["ok"], line["checks"]
    assert not line["correct"]


def _wrap_serve_step(monkeypatch, change):
    from repro.serving import engine

    orig = engine.make_serve_step

    def make(cfg, layout, scfg=None):
        step = orig(cfg, layout, scfg)

        def broken(params, caches, tokens, step_, key):
            nxt, logits, new = step(params, caches, tokens, step_, key)
            return change(nxt, logits, new, caches, cfg)
        return broken

    monkeypatch.setattr(engine, "make_serve_step", make)


def test_lm_token_altered_where_produced_is_not_correct(monkeypatch):
    _wrap_serve_step(monkeypatch, lambda nxt, lg, new, old, cfg: (
        jnp.where(jnp.arange(nxt.shape[0]) % 2 == 0,
                  (nxt + 1) % cfg.vocab_size, nxt), lg, new))
    line, _ = run_cell(LM_CELL, tiny_lm(), LM_MIX)
    assert not line["checks"]["logit_gap_max"]["ok"], line["checks"]


def test_lm_step_that_keeps_its_state_is_not_correct(monkeypatch):
    _wrap_serve_step(monkeypatch, lambda nxt, lg, new, old, cfg: (
        nxt, lg, old))
    line, _ = run_cell(LM_CELL, tiny_lm(), LM_MIX)
    assert not line["correct"], line["checks"]


# outputs longer than the window: no request admitted in the window
# finishes in it, so only the requests live at its close show what
# admission, prefill and decode did in a recycled slot
LONG_MIX = dict(LM_MIX, requests=64,
                output_len={"dist": "uniform", "min": 400, "max": 600})


def _recycled_fault(monkeypatch, kind):
    """Break the timed path only for requests the window admits into a
    slot another request has left: their prompt is altered before its
    chunked prefill, or each token they decode is altered where the
    scheduler takes it."""
    from repro.serving import scheduler as sch

    cls = sch.ChunkedScheduler
    release, prefill, decode = cls.release, cls._prefill_round, cls.decode_once
    reused = set()

    def released(self, b):
        reused.add(b)
        release(self, b)

    def prefill_round(self):
        for b in reused:
            if kind == "prompt" and self.slot_phase[b] == "prefill" \
                    and self.slot_done[b] == 0:
                p = self.slot_prompt[b].copy()
                p[0] = (p[0] + 1) % self.eng.cfg.vocab_size
                self.slot_prompt[b] = p
        prefill(self)

    def decode_once(self):
        decode(self)
        for b in reused:
            toks = self.slot_tokens[b]
            if kind == "token" and self.slot_uid[b] != -1 \
                    and self.slot_phase[b] == "decode" and len(toks) > 1:
                toks[-1] = (toks[-1] + 1) % self.eng.cfg.vocab_size
                self.last_token[b] = toks[-1]

    monkeypatch.setattr(cls, "release", released)
    monkeypatch.setattr(cls, "_prefill_round", prefill_round)
    monkeypatch.setattr(cls, "decode_once", decode_once)


def test_lm_long_outputs_sound_run_scores_admitted_requests():
    line, res = run_cell(LM_CELL, tiny_lm(serve=LONG_SERVE), LONG_MIX)
    assert line["correct"], line["checks"]
    assert res.notes["admitted_in_window"] > 0
    assert line["checks"]["compared_admitted"]["value"] >= 1


@pytest.mark.parametrize("kind", ["prompt", "token"])
def test_lm_fault_only_in_recycled_slots_is_not_correct(monkeypatch, kind):
    _recycled_fault(monkeypatch, kind)
    line, res = run_cell(LM_CELL, tiny_lm(serve=LONG_SERVE), LONG_MIX)
    # every request the window finished came from the first wave
    assert all(u < 4 for u in res.notes["sampled"])
    assert res.notes["sampled_admitted"]
    assert not line["checks"]["logit_gap_max"]["ok"], line["checks"]


def test_cnn_cell_sound_run_is_correct(cpu_peaks, tmp_path):
    line, res = run_cell(CNN_CELL, tiny_cnn(), CNN_MIX, trace=True,
                         tmp=tmp_path)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) >= {"mfu_pct.cnn", "device_idle_pct.cnn"}
    assert line["attempted"] == res.notes["batches"] > 0
    end = cli.result_line(Spec.load(), untraced(CNN_CELL), res)
    assert set(end["metrics"]) == {"images_per_s", "setup_s"}


def test_cnn_control_is_not_correct():
    # the first conv on the program's int8 path instead of its float one
    line, _ = run_cell(CNN_CELL, tiny_cnn(), CNN_MIX, control=True)
    assert not line["checks"]["rel_err_max"]["ok"], line["checks"]


def _wrap_conv(monkeypatch, change):
    from repro.core import conv

    orig = conv.conv2d_packed

    def broken(x, packed, **kw):
        return change(x, packed, orig, kw)

    monkeypatch.setattr(conv, "conv2d_packed", broken)


def test_cnn_answer_altered_where_produced_is_not_correct(monkeypatch):
    _wrap_conv(monkeypatch, lambda x, p, f, kw: f(x, p, **kw).at[3].multiply(
        1.5))
    line, _ = run_cell(CNN_CELL, tiny_cnn(), CNN_MIX)
    assert not line["checks"]["rel_err_max"]["ok"], line["checks"]


def test_cnn_half_the_batch_left_out_is_not_correct(monkeypatch):
    def half(x, p, f, kw):
        y = f(x[: x.shape[0] // 2], p, **kw)
        return jnp.concatenate([y, jnp.zeros_like(y)])
    _wrap_conv(monkeypatch, half)
    line, _ = run_cell(CNN_CELL, tiny_cnn(), CNN_MIX)
    assert not line["checks"]["rel_err_max"]["ok"], line["checks"]
