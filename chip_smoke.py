"""Chip smoke test: the packed low-bit serving path on a TPU, end to end.

    python3 chip_smoke.py              # one chip: device, kernels, paged, engine, cnn
    python3 chip_smoke.py --only paged # one chip: device and the named phases
    python3 chip_smoke.py --chips 4    # four chips: mesh engine vs one device

Phases (one process, seeded, no downloads; each prints its own lines):

* device  — exits non-zero unless JAX's first device is a TPU;
* kernels — every fused GEMM registry cell (tnn/tbn/bnn x xla/pallas/
  dense/indexed, int8/int4 x xla/pallas), compiled, at m in {8, 256},
  k=2048, n=5632; each output must be ``array_equal`` with the
  materializing oracle (the int8/int4 reference is their xla cell);
* paged   — the paged attention kernel over the 2-bit pages at the LM
  cell's widths (32 slots of 2048 positions, 8 kv heads of 128) and at
  tinyllama's (4 kv heads of 64) against ``page_view`` and f32 einsums,
  S=1 and S=32 (max abs error <= 1e-4);
* engine  — tinyllama-1.1b at full published width, packed ternary
  weights and the 2-bit paged KV cache, served through ``Engine.submit``
  / ``run`` by a ``tnn`` (XLA popcount) and a ``tnn_dense`` (Pallas MXU)
  engine: 8 greedy requests, prompts of 32-256 tokens, 32 new tokens.
  Every request must end "ok", no step error and no kernel fallback may
  be counted, and the two engines' tokens must be identical;
* cnn     — PAPER_CNN at batch 256 through ``conv2d_packed`` against the
  QAT forward (max abs error <= 1e-4).

``--chips 4`` runs only the tinyllama ``tnn`` engine on a
``make_serve_mesh(model=4)`` mesh and the same engine on one device; the
two token streams must be identical.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  Times on earlier lines are wall
clock on the named device, compilation included where marked.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
KERNEL_MS = (8, 256)
KERNEL_K, KERNEL_N = 2048, 5632
ARCH = "tinyllama-1.1b"
NUM_SLOTS, MAX_LEN = 8, 512
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, (32, 256), 32
CNN_BATCH, CNN_TOL = 256, 1e-4
PAGED_SLOTS, PAGED_MAX_LEN, PAGED_TOL = 32, 2048, 1e-4


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------- kernels

def kernel_cells():
    """(mode, backend) of every fused GEMM registry cell."""
    from repro.kernels import registry

    return [(s.mode, s.backend)
            for s in registry.available(fused=True,
                                        layout=registry.LAYOUT_GEMM)]


def phase_kernels(ms=KERNEL_MS, k=KERNEL_K, n=KERNEL_N, device=""):
    import jax
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.qtensor import QTensor

    kx, kw_ = jax.random.split(jax.random.PRNGKey(SEED))
    packed, refs = {}, {}
    bad = []
    for mode, backend in kernel_cells():
        if mode not in packed:
            w = jax.random.normal(kw_, (k, n)) * k ** -0.5
            packed[mode] = QTensor.from_dense(w, mode)
        qt = packed[mode]
        for m in ms:
            x = jax.random.normal(jax.random.fold_in(kx, m), (m, k))
            if (mode, m) not in refs:
                refs[(mode, m)] = np.asarray(
                    ops.qmm(x, qt, backend="xla") if not mode.is_lowbit
                    else ops._qmm_oracle_jit(x, qt, interpret=None))
            t0 = time.perf_counter()
            y = np.asarray(jax.block_until_ready(
                ops.qmm(x, qt, backend=backend)))
            dt = time.perf_counter() - t0
            ok = np.array_equal(y, refs[(mode, m)])
            log("kernels", f"{mode.value}/{backend} m={m} k={k} n={n}: "
                f"{'ok' if ok else 'MISMATCH'} ({dt:.3f} s incl. compile, "
                f"{device})")
            if not ok:
                bad.append(f"{mode.value}/{backend} m={m}")
    assert not bad, f"not bit-exact with the oracle: {bad}"


# ----------------------------------------------------------------- engine

def make_requests(vocab: int, n=N_REQUESTS, lens=PROMPT_LEN):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, int(rng.integers(lens[0], lens[1] + 1)))
            .astype(np.int32) for _ in range(n)]


def serve(params, cfg, prompts, *, new_tokens=NEW_TOKENS, num_slots=NUM_SLOTS,
          max_len=MAX_LEN, mesh=None, label="", device=""):
    """Serve ``prompts`` twice through one engine (the first pass
    compiles); returns the first pass's tokens after checking both."""
    from repro import obs
    from repro.models.common import ShardLayout
    from repro.serving import (Engine, Request, SamplerConfig,
                               ServeConfig)

    fb = obs.get_registry().get("repro_kernel_fallback_total")
    fb0 = fb.total()
    scfg = ServeConfig(num_slots=num_slots, max_len=max_len,
                       pack_params=True, mesh=mesh,
                       sampler=SamplerConfig(temperature=0.0))
    t0 = time.perf_counter()
    eng = Engine(params, cfg, ShardLayout(tp=1), scfg, seed=SEED)
    log("engine", f"{label}: built in {time.perf_counter() - t0:.1f} s "
        f"(packing, {device})")
    streams = []
    for rep in range(2):
        uid0 = rep * len(prompts)
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=uid0 + i, prompt=p,
                               max_new_tokens=new_tokens))
        res = eng.run()
        dt = time.perf_counter() - t0
        got = [res[uid0 + i] for i in range(len(prompts))]
        # a request's tokens: the one prefill samples, then one per
        # decode step up to max_new_tokens
        bad = [(r.uid, r.status, len(r.tokens)) for r in got
               if r.status != "ok" or len(r.tokens) != new_tokens + 1]
        assert not bad, f"{label}: requests not ok: {bad}"
        n_tok = sum(len(r.tokens) for r in got)
        log("engine", f"{label} pass {rep}: {len(got)} requests ok, "
            f"{n_tok} tokens in {dt:.2f} s "
            f"({'incl. compile' if rep == 0 else 'warm'}, {device})")
        streams.append([list(map(int, r.tokens)) for r in got])
    errors = eng.obs.step_errors.total()
    eng.close()
    assert errors == 0, f"{label}: {errors} engine step errors"
    assert fb.total() == fb0, f"{label}: kernel fallbacks counted"
    assert streams[0] == streams[1], f"{label}: second pass diverged"
    return streams[0]


def lm_setup(policy: str, **cut):
    import jax

    from repro.configs import get_config
    from repro.models import model as model_mod
    from repro.models.common import ShardLayout

    cfg = get_config(ARCH, quant_policy=policy, kv_cache_dtype="tnn2", **cut)
    params = model_mod.init_lm(jax.random.PRNGKey(SEED), cfg,
                               ShardLayout(tp=1))
    return cfg, params


def phase_paged(slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN, device=""):
    """The paged attention kernel against ``page_view`` and f32 einsums
    at the highest matmul precision, at S=1 and S=32, with dead rows and
    slots of 1 to ``max_len`` positions, for two geometries of 16-token
    pages: the LM cell's widths (8 kv heads of 128, 8 query heads each:
    one page a 128-lane row) and tinyllama's (4 kv heads of 64, 8 query
    heads each: two pages a row, rolled by a shift from the page table);
    times both."""
    for name, kvp, dh in (("lm", 8, 128), ("tinyllama", 4, 64)):
        _paged_case(name, kvp, dh, slots, max_len, device)


def _paged_case(name, kvp, dh, slots, max_len, device):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_attention
    from repro.models import paged_kvcache as paged
    from repro.models.attention import attend_page_view
    from repro.models.common import ModelConfig, ShardLayout

    g = 8
    cfg = ModelConfig(name="paged", family="dense", num_layers=1,
                      d_model=kvp * g * dh, num_heads=kvp * g,
                      num_kv_heads=kvp, d_ff=1, vocab_size=1,
                      layer_pattern=(("A", "D"),))
    page = 16
    rng = np.random.default_rng(SEED)
    lens = rng.integers(1, max_len + 1, slots)
    lens[:3] = (0, 1, max_len)
    entry = {k: v[0] for k, v in paged.init_paged_caches(
        cfg, ShardLayout(tp=1), slots, max_len, page_size=page)[0].items()}
    pager = paged.EntryPager.from_entry(entry, slots)
    for b, n in enumerate(lens):
        pager.ensure(b, int(n))
    entry["page_table"] = pager.device_table(1)[0]
    append = jax.jit(paged.append_tokens)
    key = jax.random.PRNGKey(SEED)
    for start in range(0, max_len, 256):
        pos = jnp.broadcast_to(start + jnp.arange(256, dtype=jnp.int32),
                               (slots, 256))
        kk, kv = jax.random.split(jax.random.fold_in(key, start))
        shape = (slots, 256, kvp, dh)
        entry = append(entry, jax.random.normal(kk, shape),
                       jax.random.normal(kv, shape), pos,
                       pos < jnp.asarray(lens)[:, None])

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(5):
            out = jax.block_until_ready(fn(*args))
        return np.asarray(out), (time.perf_counter() - t0) / 5 * 1e3

    def reference(q, e, positions):
        with jax.default_matmul_precision("highest"):
            return attend_page_view(q, e, positions)

    kernel, ref = jax.jit(paged_attention), jax.jit(reference)
    bad = []
    for s in (1, 32):
        q = jax.random.normal(jax.random.fold_in(key, s),
                              (slots, s, kvp, g, dh)).astype(jnp.bfloat16)
        p0 = np.maximum(lens - s, 0).astype(np.int32)
        nv = np.minimum(lens, s).astype(np.int32)
        positions = jnp.asarray(p0)[:, None] + jnp.arange(s)[None]
        got, t_kernel = timed(kernel, q, entry, jnp.asarray(p0),
                              jnp.asarray(nv))
        want, t_view = timed(ref, q.astype(jnp.float32), entry, positions)
        live = nv > 0
        err = float(np.abs(got[live] - want[live]).max())
        dead = bool((got[~live] == 0).all())
        log("paged", f"{name} S={s} slots={slots} live pages "
            f"{int(np.sum(-(-lens // page)))} of {slots * pager.npp}: "
            f"|kernel - f32 view| max {err:.2e} (limit {PAGED_TOL:g}), dead "
            f"rows 0: {dead}; kernel {t_kernel:.3f} ms, view + einsums "
            f"{t_view:.3f} ms ({device})")
        if not (err <= PAGED_TOL and dead):
            bad.append(f"{name} S={s}")
    assert not bad, f"paged attention kernel off the reference: {bad}"


def phase_engine(device="", cut=None, **serve_kw):
    cfg, params = lm_setup("tnn", **(cut or {}))
    prompts = make_requests(cfg.vocab_size,
                            **serve_kw.pop("requests", {}))
    log("engine", f"{cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; prompt lengths "
        f"{[len(p) for p in prompts]}")
    out = {}
    for policy in ("tnn", "tnn_dense"):
        out[policy] = serve(params, cfg.with_(quant_policy=policy), prompts,
                            label=policy, device=device, **serve_kw)
    assert out["tnn"] == out["tnn_dense"], "tnn and tnn_dense tokens differ"
    log("engine", "tnn and tnn_dense token streams identical")


def phase_mesh(device="", cut=None, **serve_kw):
    from repro.launch.mesh import make_serve_mesh

    cfg, params = lm_setup("tnn", **(cut or {}))
    prompts = make_requests(cfg.vocab_size,
                            **serve_kw.pop("requests", {}))
    mesh = make_serve_mesh(model=4)
    single = serve(params, cfg, prompts, label="tnn one device",
                   device=device, **serve_kw)
    meshed = serve(params, cfg, prompts, mesh=mesh,
                   label="tnn mesh (data=1, model=4)", device=device,
                   **serve_kw)
    first = [next((j for j, (a, b) in enumerate(zip(x, y)) if a != b), None)
             for x, y in zip(meshed, single)]
    assert meshed == single, ("mesh engine tokens differ from one device; "
                              f"first differing token per request: {first}")
    log("mesh", "mesh and one-device token streams identical")


# -------------------------------------------------------------------- cnn

def phase_cnn(batch=CNN_BATCH, cfg=None, device=""):
    import jax
    import numpy as np

    from repro.configs.paper_cnn import PAPER_CNN
    from repro.core.conv import (conv2d_packed, conv2d_quantized,
                                 pack_conv_filters)
    from repro.kernels.modes import QuantMode

    cfg = cfg or PAPER_CNN
    key = jax.random.PRNGKey(SEED)
    x = jax.random.normal(key, (batch, cfg.img_size, cfg.img_size, cfg.c_in))
    layers, c_in = [], cfg.c_in
    for spec in cfg.convs:
        key, wk = jax.random.split(key)
        w = jax.random.normal(wk, (spec.kernel, spec.kernel, c_in,
                                   spec.c_out))
        w = w * (spec.kernel * spec.kernel * c_in) ** -0.5
        mode = QuantMode(spec.mode)
        layers.append((spec, w, mode,
                       pack_conv_filters(w, mode) if mode.is_lowbit
                       else None))
        c_in = spec.c_out

    def pool(t):
        b, hh, ww, c = t.shape
        return t.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))

    t0 = time.perf_counter()
    h = h_qat = x
    for spec, w, mode, packed in layers:
        if packed is not None:
            h = conv2d_packed(h, packed, stride=spec.stride)
        else:
            h = conv2d_quantized(h, w, mode=mode, stride=spec.stride)
        h_qat = conv2d_quantized(h_qat, w, mode=mode, stride=spec.stride)
        h, h_qat = jax.nn.relu(h), jax.nn.relu(h_qat)
        if spec.pool:
            h, h_qat = pool(h), pool(h_qat)
    h, h_qat = np.asarray(h), np.asarray(h_qat)
    dt = time.perf_counter() - t0
    err = float(np.max(np.abs(h - h_qat)))
    finite = bool(np.isfinite(h).all())
    log("cnn", f"{cfg.name} batch {batch}: out {h.shape}, |packed - QAT| "
        f"max {err:.3e} (limit {CNN_TOL:g}), finite {finite}, {dt:.2f} s "
        f"incl. compile ({device})")
    assert finite and err <= CNN_TOL, f"cnn: max error {err} > {CNN_TOL}"


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh engine against one device")
    ap.add_argument("--only", action="append", default=None,
                    choices=("kernels", "paged", "engine", "cnn"),
                    help="run only these one-chip phases (repeatable)")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 2

    from repro import obs
    from repro.launch.cache import use_compile_cache

    cache_dir = use_compile_cache()
    hits = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            hits["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    obs.set_enabled(True)     # the engine checks read obs counters
    device = f"{dev.platform} {dev.device_kind} x{len(devs)}"
    log("device", f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__} cache={cache_dir}")

    phases = ([("mesh", phase_mesh)] if args.chips == 4 else
              [(name, fn) for name, fn in (
                  ("kernels", phase_kernels), ("paged", phase_paged),
                  ("engine", phase_engine), ("cnn", phase_cnn))
               if args.only is None or name in args.only])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(device=device)
            log(name, f"passed in {time.perf_counter() - t0:.1f} s")
        except Exception:
            traceback.print_exc()
            log(name, f"FAILED after {time.perf_counter() - t0:.1f} s")
            failed.append(name)
    log("cache", f"persistent compilation cache {cache_dir}: "
        f"{hits['hits']} hits, {hits['misses']} misses")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
