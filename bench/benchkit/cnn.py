"""CNN cells: a low-bit CNN on batches of images.

Set-up makes the weights from the seed and packs the low-bit layers
(the program's ``pack_conv_filters`` for a conv, ``QTensor.from_dense``
for a fully connected layer) in one jitted call, makes a few batches of
images on the device, and compiles the forward pass: the harness jits
its layer loop once, so only the program's layers vary
(``conv2d_packed``, ``conv2d_quantized`` for a float conv, ``ops.qmm``
for a fully connected layer).  The window keeps one batch in flight,
cycling the batches, and counts the images of every batch that
completed.  Afterwards the plain reference recomputes every layer of
every batch from the input the program's layer was given, and each
image's output of each layer is compared.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import time
from typing import Any, Dict

import numpy as np

from . import counts, device, trace as trace_mod, traffic, weights
from .cell import Check, CompileCounter, Result, Run, process_age_s

SPANS = ("batch_dispatch", "block_until_ready")


@dataclasses.dataclass
class CNNLayerContext:
    cfg: Dict[str, Any]
    window_s: float
    peaks: Dict[str, float]
    batches: int
    model_ops: float
    peak_key: str
    trace: Any = None


def _pool(t):
    b, h, w, c = t.shape
    return t.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def make_forward(cfg: Dict[str, Any]):
    """The timed forward: every layer's output, in order."""
    import jax
    import jax.numpy as jnp
    from repro.core import conv as conv_mod
    from repro.kernels import ops
    from repro.kernels.modes import QuantMode

    layers = counts.cnn_layers(cfg)

    def forward(x, params):
        outs, h = [], x
        for L, p in zip(layers, params):
            mode = QuantMode(L["mode"])
            if L["kind"] == "fc":
                h = h.reshape(h.shape[0], -1)
                h = (ops.qmm(h, p) if mode.is_lowbit
                     else jnp.dot(h, p))
            elif mode.is_lowbit:
                h = conv_mod.conv2d_packed(h, p)
            else:
                h = conv_mod.conv2d_quantized(h, p, mode=mode)
            if L.get("pool"):
                h = _pool(h)
            outs.append(h)
        return tuple(outs)

    return jax.jit(forward)


def param_maker(cfg: Dict[str, Any]):
    """key -> per-layer weights: packed QTensors for the low-bit layers,
    float32 weights for a float layer."""
    from repro.core import conv as conv_mod
    from repro.kernels.modes import QuantMode
    from repro.kernels.qtensor import QTensor

    def make(k):
        out = []
        for i, L in enumerate(counts.cnn_layers(cfg)):
            w = weights.cnn_weights(k, cfg, i)
            mode = QuantMode(L["mode"])
            if not mode.is_lowbit:
                out.append(w)
            elif L["kind"] == "fc":
                out.append(QTensor.from_dense(w, mode))
            else:
                out.append(conv_mod.pack_conv_filters(w, mode))
        return out

    return make


def run(r: Run) -> Result:
    import jax
    from repro import obs

    obs.set_enabled(True)
    cfg = r.config
    tb = traffic.cnn_batches(r.traffic)
    b, nb = tb["batch"], tb["distinct_batches"]
    key = weights.base_key(r.seed)
    from repro.kernels import ops  # noqa: F401  (registers the counter)
    fallbacks = obs.get_registry().get("repro_kernel_fallback_total")
    fb0 = fallbacks.total()

    parts = {"start": process_age_s()}
    prog = r.program_config
    params = jax.block_until_ready(jax.jit(param_maker(prog))(key))
    images = jax.block_until_ready(jax.jit(lambda k: [
        weights.cnn_images(k, cfg, b, j) for j in range(nb)])(key))
    parts["weights_images"] = process_age_s()
    fwd = make_forward(prog)
    jax.block_until_ready(fwd(images[0], params))
    parts["forward_warm"] = process_age_s()

    capture = None
    if r.trace:
        capture = trace_mod.Capture(r.trace_dir)
        capture.start()
    compiles = CompileCounter()
    last: Dict[int, Any] = {}
    n = 0
    setup_s = process_age_s()
    compiles.armed = True
    span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
    span.__enter__()
    t0 = time.perf_counter()
    t = t0
    while t - t0 < r.seconds:
        j = n % nb
        with obs.annotate("batch_dispatch"):
            out = fwd(images[j], params)
        with obs.annotate("block_until_ready"):
            jax.block_until_ready(out)
        t = time.perf_counter()
        last[j] = out
        n += 1
    span.__exit__(None, None, None)
    compiles.armed = False
    window_s = t - t0
    summary = None
    if capture is not None:
        summary = trace_mod.TraceSummary(capture.stop(), SPANS)
    mem = device.memory_peak_bytes(r.chips)
    n_fallbacks = int(fallbacks.total() - fb0)

    layer_ctx = None
    if r.trace:
        from .lm import _peaks
        layer_ctx = CNNLayerContext(
            cfg=cfg, window_s=window_s, peaks=_peaks(), batches=n,
            model_ops=float(n * b * counts.cnn_image_ops(cfg)),
            peak_key="int8_ops", trace=summary)
    del fwd, params
    gc.collect()

    ref = importlib.import_module(f"reference.{cfg['reference']}")
    t_ref = time.perf_counter()
    nonfinite = 0
    n_layers = len(counts.cnn_layers(cfg))
    per_layer = np.zeros(n_layers)
    for j, out in sorted(last.items()):
        for i in range(n_layers):
            # each layer from the input the program's layer was given
            want = ref.layer(key, cfg, i, images[j] if i == 0 else out[i - 1])
            g = np.asarray(out[i], np.float32).reshape(b, -1)
            w = np.asarray(want, np.float32).reshape(b, -1)
            nonfinite += int(not np.isfinite(g).all())
            # every image is an answer: its own relative error
            err = np.linalg.norm(g - w, axis=1) / np.maximum(
                np.linalg.norm(w, axis=1), 1e-30)
            per_layer[i] = max(per_layer[i], float(err.max()))
            del want
    worst = float(per_layer.max()) if last else float("nan")
    ref_s = time.perf_counter() - t_ref

    failed = nonfinite + n_fallbacks
    checks = [Check("rel_err_max", worst, float(cfg["correct"]["rel_err_max"])),
              Check("failed", float(failed), 0.0),
              Check("compared_batches", float(len(last)), float(nb),
                    higher_fails=False)]
    notes = {"window_s": window_s, "batches": n,
             "rel_err_by_layer": per_layer.tolist(),
             "compiles_in_window": compiles.count, "fallbacks": n_fallbacks,
             "reference_s": ref_s,
             "setup_at_s": {k: round(v, 2) for k, v in parts.items()}}
    return Result(attempted=n, failed=failed,
                  e2e={"images_per_s": n * b / window_s, "setup_s": setup_s},
                  checks=checks, memory_peak_bytes=mem, layer=layer_ctx,
                  trace=summary, notes=notes)
