"""Plain reference of a low-bit CNN (``bench/configs``), one layer at a
time: ``layer(key, cfg, i, x)`` is layer ``i``'s output for its input
batch ``x``.

* conv: ``kernel`` x ``kernel``, stride 1, SAME padding with zeros, then
  a 2x2 max-pool where the layer has one;
* fc: the input flattened row-major (over H, W, C), times the weights;
* nothing else between layers: the next layer's activation quantizer is
  the nonlinearity.

Precision, per layer from ``modes``:

* ``bf16``: inputs and weights rounded to bfloat16, float32 sums;
* ``f32``: float32;
* ``tnn`` / ``tbn`` / ``bnn``: ternary / ternary / binary activations
  against ternary / binary / binary weights, ``(t_x @ t_w) * a_x * a_w``.

Activations quantize per tensor over the layer's whole GEMM input for
the batch.  For a conv that is the im2col patch matrix, padding
included; a patch matrix only repeats entries of the zero-padded input,
so the reference quantizes the padded input elementwise and convolves
the codes, and its statistics weight each entry by the number of
patches that hold it.  Ternary: threshold ``0.7 * mean|a|``, scale the
mean of the kept magnitudes.  Binary: ``sign(a)`` with 0 as +1, scale
``mean|a|``.  Weights quantize per output channel: ternary as TWN,
binary as ``sign(w)`` (0 as +1) with scale ``mean|w|``.  Products of
codes are exact in float32 at the highest precision.

Weights are drawn again from the seed by the benchmark's own generator
(``benchkit.weights``); the reference takes nothing the program made
but the layer input it is handed.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from benchkit import counts, weights as W

BLOCK = 128         # images a block: the reference's memory stays small


def ternary_columns(w):
    a = jnp.abs(w)
    thr = 0.7 * jnp.mean(a, axis=0, keepdims=True)
    mask = a > thr
    s = jnp.sum(a * mask, axis=0) / jnp.maximum(jnp.sum(mask, axis=0), 1)
    return jnp.sign(w) * mask, s


def binary_columns(w):
    return jnp.where(w < 0, -1.0, 1.0), jnp.mean(jnp.abs(w), axis=0)


WGT = {"tnn": ternary_columns, "tbn": binary_columns, "bnn": binary_columns}
TERNARY_ACT = ("tnn", "tbn")


def act_scalars(absx, weight, mode: str):
    """Per-tensor threshold and scale of the activations whose
    magnitudes are ``absx``, each entry counted ``weight`` times."""
    total = jnp.sum(weight) * (absx.size / weight.size)
    mean = jnp.sum(absx * weight) / total
    if mode not in TERNARY_ACT:
        return None, mean
    thr = 0.7 * mean
    kept = (absx > thr) * weight
    return thr, jnp.sum(absx * kept) / jnp.maximum(jnp.sum(kept), 1)


def codes(x, thr, mode: str):
    if mode in TERNARY_ACT:
        return jnp.sign(x) * (jnp.abs(x) > thr)
    return jnp.where(x < 0, -1.0, 1.0)


def _pool(t):
    b, h, w, c = t.shape
    return t.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def _conv_valid(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _multiplicity(h: int, w: int, k: int):
    """How many k x k patches of an h x w SAME conv hold each entry of
    the padded (h + k - 1, w + k - 1) input."""
    m = jnp.zeros((h + k - 1, w + k - 1), jnp.float32)
    for i in range(k):
        for j in range(k):
            m = m.at[i:i + h, j:j + w].add(1.0)
    return m[None, :, :, None]


@functools.partial(jax.jit, static_argnames=("mode",))
def _conv_stats(xp, mult, mode: str):
    return act_scalars(jnp.abs(xp), mult, mode)


@functools.partial(jax.jit, static_argnames=("mode", "pool"))
def _conv_block(xp, w, thr, s_x, mode: str, pool: bool):
    if mode in ("bf16", "f32"):
        if mode == "bf16":
            xp = xp.astype(jnp.bfloat16).astype(jnp.float32)
            w = w.astype(jnp.bfloat16).astype(jnp.float32)
        y = _conv_valid(xp, w)
    else:
        t_w, s_w = WGT[mode](w.reshape(-1, w.shape[-1]))
        y = _conv_valid(codes(xp, thr, mode), t_w.reshape(w.shape)) \
            * s_x * s_w
    return _pool(y) if pool else y


@functools.partial(jax.jit, static_argnames=("mode",))
def _fc(x, w, mode: str):
    x = x.reshape(x.shape[0], -1)
    hi = jax.lax.Precision.HIGHEST
    if mode in ("bf16", "f32"):
        if mode == "bf16":
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
            w = w.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.dot(x, w, precision=hi)
    thr, s_x = act_scalars(jnp.abs(x), jnp.ones((1, 1)), mode)
    t_w, s_w = WGT[mode](w)
    return jnp.dot(codes(x, thr, mode), t_w, precision=hi) * s_x * s_w


def layer(key, cfg: Dict, i: int, x):
    """Layer ``i``'s output for the input batch ``x``."""
    L = counts.cnn_layers(cfg)[i]
    w = W.cnn_weights(key, cfg, i)
    if L["kind"] == "fc":
        return _fc(x, w, mode=L["mode"])
    k, p = L["k"], L["k"] // 2
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, 0), (p, k - 1 - p), (p, k - 1 - p), (0, 0)))
    thr = s_x = None
    if L["mode"] in WGT:
        thr, s_x = _conv_stats(xp, _multiplicity(L["h"], L["w"], k),
                               mode=L["mode"])
    return jnp.concatenate([
        _conv_block(xp[b:b + BLOCK], w, thr, s_x, mode=L["mode"],
                    pool=L["pool"])
        for b in range(0, xp.shape[0], BLOCK)])
