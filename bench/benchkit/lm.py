"""LM serving cells: the program's engine, driven tick by tick.

Set-up makes the weights from the seed on the device and packs them in
one jitted call (the program's ``pack_lm_params``), builds the engine,
warms every shape the traffic will use, queues the whole backlog and
runs the ticks that bring the first wave of requests through prefill.
The window then drives ``Engine.run(max_steps=1)`` (one tick, with the
engine's own error containment) until ``seconds`` have passed, stamping
every output token with the time its tick returned.

After the window the engine is freed and the plain reference scores two
samples drawn from the seed: requests the window finished, with the
longest among them, and requests admitted into a recycled slot during
the window, scored on the tokens they had served when it closed (with
the one that had served most).  For every served token: how far the
reference's logit of it lies below the reference's best.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import time
from typing import Any, Dict, List

import numpy as np

from . import counts, device, stats, trace as trace_mod, traffic, weights
from .cell import Check, CompileCounter, Result, Run, process_age_s

# host spans the trace reduction labels idle gaps with: the harness's
# own and the program's (serving/engine.py)
SPANS = ("engine_step", "submit", "decode_step", "prefill_chunk")


@dataclasses.dataclass
class LMLayerContext:
    """What per-layer readers of an LM cell read."""
    cfg: Dict[str, Any]
    window_s: float
    peaks: Dict[str, float]
    ticks: List[Dict[str, float]]
    model_ops: float
    peak_key: str                   # the peak that model_ops count against
    trace: Any = None


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file."""
    import jax.numpy as jnp
    from repro.models.common import ModelConfig

    names = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in names}
    kw["layer_pattern"] = tuple(tuple(p) for p in kw["layer_pattern"])
    kw["dtype"] = getattr(jnp, cfg.get("dtype", "bfloat16"))
    return ModelConfig(**kw)


def _make_params(cfg, layout, key):
    """Weights from the seed, packed as served, in one jitted call."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_mod
    from repro.models.packing import pack_lm_params

    shapes = jax.eval_shape(functools.partial(
        model_mod.init_lm, cfg=cfg, layout=layout, dtype=jnp.bfloat16), key)
    make = jax.jit(lambda k: pack_lm_params(weights.make_tree(k, shapes), cfg))
    return jax.block_until_ready(make(key))


def _warm_page_resets(eng, max_tokens: int) -> None:
    """Releasing a slot poisons its pages with one eager scatter whose
    index length is the slot's page count: compile every count the
    traffic can reach before the window opens."""
    import jax
    from repro.models import paged_kvcache as paged

    page = eng.scfg.page_size
    for entry in eng.caches:
        if not paged.is_paged(entry):
            continue
        _, _, npp = paged.entry_geometry(entry)
        for n in range(1, min(-(-max_tokens // page), npp) + 1):
            jax.block_until_ready(paged.reset_pages(entry, range(1, n + 1))["pos"])


def _reference(cfg: Dict[str, Any]):
    return importlib.import_module(f"reference.{cfg['reference']}")


def run(r: Run) -> Result:
    import jax
    from repro import obs
    from repro.models.common import ShardLayout
    from repro.serving import Engine, Request, SamplerConfig, ServeConfig

    obs.set_enabled(True)           # the program's counters and spans
    cfg = r.config
    serve = cfg["serve"]
    mcfg = model_config(r.program_config)
    layout = ShardLayout(tp=1)
    key = weights.base_key(r.seed)
    from repro.kernels import ops  # noqa: F401  (registers the counter)
    fallbacks = obs.get_registry().get("repro_kernel_fallback_total")
    fb0 = fallbacks.total()

    parts = {"start": process_age_s()}
    params = _make_params(mcfg, layout, key)
    parts["weights"] = process_age_s()
    eng = Engine(params, mcfg, layout, ServeConfig(
        num_slots=serve["num_slots"], max_len=serve["max_len"],
        page_size=serve["page_size"], prefill_chunk=serve["prefill_chunk"],
        eos_id=-1, pack_params=True, autotune="off",
        sampler=SamplerConfig(temperature=0.0)), seed=0)
    del params
    slots = serve["num_slots"]
    reqs = traffic.lm_requests(r.traffic, r.seed, cfg["vocab_size"],
                               slots=slots, max_len=serve["max_len"])
    by_uid = {q.uid: q for q in reqs}
    parts["engine"] = process_age_s()
    _warm_page_resets(eng, traffic.max_request_tokens(reqs))
    parts["page_resets"] = process_age_s()

    ledger = stats.TokenLedger()
    results = eng.results
    seen_results = [0]

    def observe(t: float, in_window: bool) -> int:
        new = 0
        for b, uid in enumerate(eng.slot_uid):
            if uid != -1:
                new += ledger.observe(uid, len(eng.slot_tokens[b]), t, in_window)
        keys = list(results)
        for uid in keys[seen_results[0]:]:
            new += ledger.observe(uid, len(results[uid].tokens), t, in_window)
        seen_results[0] = len(keys)
        return new

    for q in reqs:
        eng.submit(Request(uid=q.uid, prompt=q.prompt,
                           max_new_tokens=q.max_new_tokens))
    first = [q.uid for q in reqs[:slots]]
    while not all(ledger.count.get(u, 0) > 0 for u in first):
        eng.run(max_steps=1)
        # a prefill tick that completes no prompt returns without a
        # sync: wait for each, so set-up does not queue their logits
        jax.block_until_ready(eng.caches)
        observe(time.perf_counter(), False)

    parts["first_wave"] = process_age_s()
    capture = None
    if r.trace:
        capture = trace_mod.Capture(r.trace_dir)
        capture.start()
    compiles = CompileCounter()
    pre = eng.obs.prefill_tokens, eng.obs.decode_tokens
    ticks: List[Dict[str, float]] = []
    live_at_open = {u for u in eng.slot_uid if u != -1}
    in_window: set = set()
    setup_s = process_age_s()
    compiles.armed = True
    span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
    span.__enter__()
    t0 = time.perf_counter()
    t = t0
    while t - t0 < r.seconds:
        p0, d0 = pre[0].total(), pre[1].total()
        with obs.annotate("engine_step"):
            eng.run(max_steps=1)
        t = time.perf_counter()
        in_window.update(u for u in eng.slot_uid if u != -1)
        emitted = observe(t, True)
        live = [b for b, u in enumerate(eng.slot_uid) if u != -1]
        pos = np.asarray(eng.slot_pos)
        ticks.append({"t": t, "live": len(live),
                      "prefill": pre[0].total() - p0,
                      "decode": pre[1].total() - d0, "emitted": emitted,
                      "ctx": float(np.mean(pos[live])) if live else 0.0})
    span.__exit__(None, None, None)
    compiles.armed = False
    t_end = t
    window_s = t_end - t0
    summary = None
    if capture is not None:
        summary = trace_mod.TraceSummary(capture.stop(), SPANS)

    mem = device.memory_peak_bytes(r.chips)
    queued = len(eng.queue)
    done = {u: results[u] for u in list(results)[:seen_results[0]]
            if u in ledger.first_t and ledger.last.get(u, 0) >= t0}
    in_window.update(done)
    bad = [u for u, res in done.items()
           if res.status != "ok"
           or len(res.tokens) != by_uid[u].max_new_tokens + 1]
    step_errors = int(eng.obs.step_errors.total())
    n_fallbacks = int(fallbacks.total() - fb0)

    e2e = {"output_tokens_per_s": stats.rate(ledger.tokens, window_s)}
    if ledger.gaps:
        e2e["itl_p95_ms"] = stats.percentile(ledger.gaps, 95) * 1e3
    e2e["setup_s"] = setup_s

    # what the reference scores, drawn from the seed: finished requests
    # with the longest among them, and requests admitted during the
    # window into a recycled slot, on the tokens served so far
    ok = {u: list(map(int, done[u].tokens)) for u in done if u not in bad}
    live_served = {u: list(map(int, eng.slot_tokens[b]))
            for b, u in enumerate(eng.slot_uid)
            if u != -1 and u not in live_at_open and eng.slot_tokens[b]}
    admitted = {u: v for u, v in {**ok, **live_served}.items()
                if u not in live_at_open}
    rng = np.random.default_rng([int(r.seed) & 0xFFFFFFFFFFFF, 7])
    finished = _draw(ok, int(cfg["correct"]["sample_requests"]), rng)
    recycled = _draw({u: v for u, v in admitted.items() if u not in finished},
                     int(cfg["correct"]["sample_admitted"]), rng)
    served = {**ok, **live_served}
    seqs = [(by_uid[u].prompt, served[u]) for u in finished + recycled]
    n_admitted = sum(1 for u in finished + recycled if u in admitted)

    layer_ctx = None
    if r.trace:
        layer_ctx = _layer_context(cfg, window_s, ticks, summary)
    eng.close()
    del eng, results, done, observe     # the closure holds the engine
    gc.collect()

    t_ref = time.perf_counter()
    if seqs:
        ref = _reference(cfg)
        g = ref.served_gaps(key, cfg, seqs, pad_to=serve["max_len"])
        gap = float(max(float(np.max(x)) for x in g))
        n_cmp = int(sum(len(x) for x in g))
    else:
        gap, n_cmp = float("nan"), 0
    ref_s = time.perf_counter() - t_ref

    failed = len(bad) + step_errors + n_fallbacks
    checks = [Check("logit_gap_max", gap, float(cfg["correct"]["logit_gap_max"])),
              Check("failed", float(failed), 0.0),
              Check("compared_tokens", float(n_cmp),
                    float(cfg["correct"]["min_compared_tokens"]),
                    higher_fails=False),
              # at least one request the window admitted into a
              # recycled slot is scored
              Check("compared_admitted", float(n_admitted), 1.0,
                    higher_fails=False),
              # a closed backlog that runs dry is no longer the cell's load
              Check("queued_at_close", float(queued), 1.0,
                    higher_fails=False)]
    notes = {"window_s": window_s, "ticks": len(ticks),
             "chunk_ticks": sum(1 for k in ticks if k["prefill"] > 0),
             "tick_ms": _tick_ms(t0, ticks),
             "tokens": ledger.tokens, "gaps": len(ledger.gaps),
             "finished_ok": len(ok), "admitted_in_window": len(admitted),
             "sampled": finished, "sampled_admitted": recycled,
             "queued_at_close": queued,
             "compiles_in_window": compiles.count,
             "step_errors": step_errors, "fallbacks": n_fallbacks,
             "reference_s": ref_s,
             "setup_at_s": {k: round(v, 2) for k, v in parts.items()}}
    return Result(attempted=len(in_window), failed=failed, e2e=e2e,
                  checks=checks, memory_peak_bytes=mem, layer=layer_ctx,
                  trace=summary, notes=notes)


def _tick_ms(t0: float, ticks: List[Dict[str, float]]) -> Dict[str, Any]:
    """Host time of the window's ticks, decode-only and with a prefill
    chunk apart: count, median, 99th percentile and longest, in ms."""
    out: Dict[str, Any] = {}
    prev = t0
    dur: Dict[str, List[float]] = {"decode": [], "chunk": []}
    for k in ticks:
        dur["chunk" if k["prefill"] > 0 else "decode"].append(k["t"] - prev)
        prev = k["t"]
    for kind, d in dur.items():
        if d:
            out[kind] = [len(d), 1e3 * stats.percentile(d, 50),
                         1e3 * stats.percentile(d, 99), 1e3 * max(d)]
    return out


def _draw(pool: Dict[int, List[int]], n: int, rng) -> List[int]:
    """The request that served most tokens, and ``n - 1`` more from
    ``rng``."""
    if n <= 0 or not pool:
        return []
    by_len = sorted(pool, key=lambda u: (-len(pool[u]), u))
    rest = rng.permutation(by_len[1:])[:n - 1].tolist()
    return by_len[:1] + sorted(int(u) for u in rest)


def _layer_context(cfg, window_s, ticks, summary) -> LMLayerContext:
    ops = 0.0
    for k in ticks:
        ops += (k["prefill"] + k["decode"]) * counts.lm_token_ops(
            cfg, k["ctx"], head=False)
        ops += k["emitted"] * 2 * cfg["d_model"] * cfg["vocab_size"]
    lowbit = cfg["quant_policy"] in counts.PLANES
    return LMLayerContext(cfg=cfg, window_s=window_s, peaks=_peaks(),
                          ticks=ticks, model_ops=ops,
                          peak_key="int8_ops" if lowbit else "bf16_flops",
                          trace=summary)


def _peaks():
    import jax
    from .peaks import peaks_for
    return peaks_for(jax.devices()[0].device_kind)
