"""GeMM-based convolution benchmark (the paper's application layer).

Times representative small-CNN conv layers at each quantization mode —
the QAT forward (on-the-fly quantization) AND the deployment path — and
checks the eq. (5) channel guard.  The deployment path is measured both
ways per low-bit mode:

* materializing — ``conv2d_packed(fused=False)``: im2col writes the
  full float32 patch matrix to HBM, then one fused ``ops.qmm``;
* fused-im2col — ``conv2d_packed(fused=True)`` -> ``ops.qconv``: patch
  extraction folds into the kernel's A-operand load path and the patch
  matrix never exists (registry layout ``im2col_fused``).

The ``--json`` artifact carries, per layer x mode, both timings, the
``fused_speedup`` ratio (what the CI perf gate tracks — ratios are
machine-portable, absolute times are not) and the im2col A-operand HBM
bytes of each path (``hbm_bytes``): the materialized f32 patch matrix vs
the int8 activation codes the fused xla kernel stages — the
memory-traffic win, quantified.

Low-bit modes are enumerated from the kernel registry.

    PYTHONPATH=src python -m benchmarks.bench_conv [--quick] \
        [--json bench_conv.json] [--backend xla]
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict

import jax
import numpy as np

from repro.core.conv import conv2d_packed, conv2d_quantized, pack_conv_filters
from repro.kernels import registry
from repro.kernels.conv_fused import im2col_hbm_bytes
from repro.kernels.modes import DEFAULT_BACKEND
from repro.kernels.ops import QuantMode

LAYERS = [   # (img, c_in, c_out, kernel)
    (32, 32, 64, 3),
    (16, 64, 128, 3),
    (8, 128, 256, 3),
]
MODES = ["bf16", "int8"] + [m.value for m in registry.modes()
                            if m.is_lowbit]


def _time(call, reps=5):
    call().block_until_ready()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call().block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run(quick=False, backend: str = DEFAULT_BACKEND,
        qat: bool = True) -> Dict[str, Dict]:
    key = jax.random.PRNGKey(0)
    # --quick only trims the informational QAT columns: the packed
    # materializing-vs-fused columns always run all three paper layers
    # at >= 11 reps because their fused_speedup ratios feed the CI perf
    # gate, which must not flake on timing noise (each layer is
    # ms-scale, so the gated section stays cheap either way).
    # ``qat=False`` skips the informational QAT columns entirely — used
    # when this runs a second time for another backend's gated columns.
    layers = LAYERS
    reps = 3 if quick else 7
    results: Dict[str, Dict] = {}
    print("\nGeMM-based conv (im2col + low-bit GeMM), batch 4 — QAT "
          "forward and packed deployment, materializing vs fused-im2col "
          f"({backend} backend):")
    print(f"{'layer':>20s}" + "".join(f"{m:>9s}" for m in MODES)
          + f"{'pk-mat(best)':>14s}{'pk-fused(best)':>15s}")
    for img, ci, co, k in layers:
        k1, k2 = jax.random.split(jax.random.fold_in(key, img))
        x = jax.random.normal(k1, (4, img, img, ci))
        w = jax.random.normal(k2, (k, k, ci, co)) * (k * k * ci) ** -0.5
        name = f"{img}x{img}x{ci}->{co}"
        row, layer_res = [], {}
        for m in MODES:
            mode = QuantMode(m)
            if not qat:
                layer_res[m] = {}
                continue
            f = jax.jit(lambda x, w, mode=mode: conv2d_quantized(
                x, w, mode=mode))
            t = _time(lambda: f(x, w), reps=reps)
            row.append(t)
            layer_res[m] = {"qat_s": t}
        # deployment path: pack once, then one dispatch per call — timed
        # with and without the fused-im2col kernel
        best_mat = best_fused = None
        for m in MODES:
            mode = QuantMode(m)
            if not mode.is_lowbit:
                continue
            packed = pack_conv_filters(w, mode)
            # jit the whole deployment call so the comparison with the
            # jitted QAT column is apples-to-apples
            fm = jax.jit(lambda x, p=packed: conv2d_packed(
                x, p, fused=False, backend=backend))
            ff = jax.jit(lambda x, p=packed: conv2d_packed(
                x, p, fused=True, backend=backend))
            # the fused_speedup ratio feeds the CI perf gate: median of
            # more reps than the (informational) QAT columns, because a
            # noisy ratio would flake the gate
            tm = _time(lambda: fm(x), reps=max(reps, 11))
            tf = _time(lambda: ff(x), reps=max(reps, 11))
            hbm = im2col_hbm_bytes(x.shape, packed.geometry, 1, "SAME")
            layer_res[m].update({
                "packed_s": tm,            # legacy key: materializing path
                "packed_materializing_s": tm,
                "packed_fused_s": tf,
                "fused_speedup": tm / tf,
                "hbm_bytes": {**hbm,
                              "saved": hbm["materialized"] - hbm["fused"]},
            })
            best_mat = tm if best_mat is None else min(best_mat, tm)
            best_fused = tf if best_fused is None else min(best_fused, tf)
        results[name] = layer_res
        if row:
            base = row[0]
            print(f"{name:>20s}"
                  + "".join(f"{base/t:8.2f}x" for t in row)
                  + f"{base/best_mat:12.2f}x{base/best_fused:13.2f}x")
        else:   # qat=False: no bf16 reference column — absolute times
            print(f"{name:>20s}  pk-mat {best_mat*1e6:10.0f}us  "
                  f"pk-fused {best_fused*1e6:10.0f}us")
    print("(numbers are speedups vs bf16 on this container CPU; "
          "'pk-mat'/'pk-fused' are the fastest low-bit conv2d_packed "
          "with the materializing / fused-im2col path)")
    for name, layer_res in results.items():
        for m, r in layer_res.items():
            if "fused_speedup" in r:
                hb = r["hbm_bytes"]
                print(f"  {name} {m}: fused-im2col {r['fused_speedup']:.2f}x "
                      f"over materializing; im2col A bytes "
                      f"{hb['materialized']/1e6:.2f}MB -> {hb['fused']/1e6:.2f}MB "
                      f"({hb['saved']/1e6:.2f}MB saved)")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer reps for the QAT columns (the CI-gated "
                         "packed columns always use stable rep counts)")
    ap.add_argument("--json", type=str, default=None,
                    help="write per-layer timings to this JSON file")
    ap.add_argument("--backend", type=str, default=DEFAULT_BACKEND,
                    help="kernel backend for the packed-deployment columns")
    args = ap.parse_args()
    results = run(quick=args.quick, backend=args.backend)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
