"""Fused-im2col low-bit conv kernels (registry layout ``im2col_fused``).

``conv2d_packed`` historically materialized the full ``(B*OH*OW,
kh*kw*Cin)`` im2col patch matrix in HBM before the fused GeMM — for a
3x3 conv that is a ~9x blow-up of the activation traffic which the
kernel then re-reads.  The kernels here read the raw ``(B, H, W, Cin)``
activations and quantize them in their own load path, so the float
patch matrix never exists.

The key observation making this bit-exact against the materializing
oracle is that the activation quantizers are **per-tensor**: ``thr`` and
``alpha`` are scalars over the whole im2col matrix, so elementwise
quantization commutes with patch gathering.  :func:`conv_act_stats`
computes those scalars from the padded input in one O(|x|) pass (each
input element weighted by the number of patches containing it — the
exact multiset the im2col matrix holds), and BOTH paths — these fused
kernels and the materializing ``im2col + ops.qmm(act_stats=...)``
oracle — consume the same jitted stats computation, so their quantize /
pack semantics are identical bit for bit.

Operand layout: the popcount kernels pack activations along the
*channel* axis, one word vector per pixel; weights arrive in the
matching per-patch-position layout (``conv_weight_planes``: a no-op
re-view when ``Cin % 32 == 0``, the pack-time positional payload of
``POS_PAYLOAD_KEYS`` otherwise, with an exact in-trace repack as the
legacy-container fallback).
Word-aligned pads are zero on both sides — (0,0) ternary codes and
``+1`` binary codes on both operands — so the popcount sum over the
per-position layout equals the contiguous-k sum exactly and eq. (6)
stays valid with the true ``k_valid``.

Three backends, mirroring the GeMM kernels:

* ``pallas`` — grid ``(m-blocks, n-blocks)``; each cell computes its
  patch coordinates from ``program_id``, gathers the raw activation
  tile, quantizes + packs it in VMEM, runs the chunked popcount
  reduction against the B tile and applies the eq. (2) epilogue
  in-kernel (float32 out, no HBM round-trip of the accumulator);
* ``xla``   — quantize the padded activations once to int8 ±1/0 codes,
  decode the weight planes to int8 codes, and let XLA's integer
  convolution sum them in int32 (the int8 MXU on a TPU), the epilogue
  after it — the cell that compiles for a TPU;
* ``dense`` — lives in ``kernels/dense_fused.py``: same program_id patch
  gather, but the weight bit planes unpack to ±1/0 bf16 tiles in VMEM
  and the reduction rides ``jnp.dot`` / the MXU (integer-exact f32
  accumulation), epilogue in-kernel.

All entries register under ``(mode, backend, fused=True,
layout="im2col_fused")``; ``ops.qconv`` / ``conv2d_packed`` dispatch
here with no API change (the QTensor already carries the conv geometry
as static aux).  The Pallas entries declare a ``TuningSpace`` so the
autotuner covers them (``repro.tune`` — conv plans key on an extra
``geom`` tag); XLA tiles the ``xla`` cell's convolution itself.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import registry
from repro.kernels._matmul_common import (ceil_to, pad2d, resolve_interpret,
                                          scale_epilogue)
from repro.kernels.modes import QuantMode
from repro.tune import cache as tune_cache
from repro.tune.space import CONV_PALLAS_SPACE

# NOTE: repro.core (encoding/quantize) and repro.kernels.ops are imported
# lazily inside functions — ops imports this module to trigger
# registration, and repro.core's __init__ re-enters ops; module-scope
# imports here would close that cycle during interpreter start-up.

__all__ = ["conv_out_hw", "conv_spatial_pad", "conv_act_stats",
           "conv_problem_dims", "geom_tag", "im2col_hbm_bytes",
           "conv_weight_planes", "gather_patch_tile",
           "quantize_patch_values"]


# ---------------------------------------------------------------------------
# Geometry helpers — the single source of truth for output extents and
# spatial padding (core.conv.im2col delegates here, so the materializing
# oracle and the fused kernels can never disagree about the patch grid).
# ---------------------------------------------------------------------------

def conv_out_hw(h: int, w: int, kh: int, kw: int, stride: int,
                padding: str) -> Tuple[int, int, int, int]:
    """(OH, OW, pad_h_total, pad_w_total) for one conv geometry."""
    if padding == "SAME":
        oh, ow = -(-h // stride), -(-w // stride)
        ph = max((oh - 1) * stride + kh - h, 0)
        pw = max((ow - 1) * stride + kw - w, 0)
    elif padding == "VALID":
        oh = (h - kh) // stride + 1
        ow = (w - kw) // stride + 1
        ph = pw = 0
    else:
        raise ValueError(padding)
    return oh, ow, ph, pw


def conv_spatial_pad(x: jnp.ndarray, kh: int, kw: int, stride: int,
                     padding: str):
    """Apply the conv's spatial zero padding: (B, H, W, C) ->
    ((B, Hp, Wp, C), (OH, OW))."""
    _, h, w, _ = x.shape
    oh, ow, ph, pw = conv_out_hw(h, w, kh, kw, stride, padding)
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)))
    return x, (oh, ow)


def geom_tag(kh: int, kw: int, stride: int, padding: str) -> str:
    """Compact conv-geometry tag used in autotuning plan keys."""
    return f"{kh}x{kw}s{stride}{padding.lower()}"


def conv_problem_dims(x_shape, geometry, stride: int, padding: str):
    """(m, n, k, geom_tag) of the implicit im2col GeMM for one call."""
    b, h, w, _ = x_shape
    kh, kw, cin, cout = geometry
    oh, ow, _, _ = conv_out_hw(h, w, kh, kw, stride, padding)
    return b * oh * ow, cout, kh * kw * cin, geom_tag(kh, kw, stride, padding)


def im2col_hbm_bytes(x_shape, geometry, stride: int,
                     padding: str) -> Dict[str, int]:
    """HBM bytes of the im2col A operand, materializing vs fused — the
    memory-traffic win the fused kernels buy (benchmarks report this).

    * materialized: the float32 patch matrix (m, k) the oracle writes
      then re-reads;
    * fused: the int8 codes of the padded input the xla kernel stages
      (one byte per element; the pallas kernel reads the raw
      activations directly and stages nothing at all).
    """
    b, h, w, _ = x_shape
    kh, kw, cin, cout = geometry
    oh, ow, ph, pw = conv_out_hw(h, w, kh, kw, stride, padding)
    m, k = b * oh * ow, kh * kw * cin
    return {
        "materialized": m * k * 4,
        "fused": b * (h + ph) * (w + pw) * cin,
    }


def _patch_multiplicity(hp: int, wp: int, kh: int, kw: int, stride: int,
                        oh: int, ow: int) -> np.ndarray:
    """How many patches contain each padded-input pixel (static)."""
    mult = np.zeros((hp, wp), np.float32)
    for dy in range(kh):
        for dx in range(kw):
            mult[dy:dy + (oh - 1) * stride + 1:stride,
                 dx:dx + (ow - 1) * stride + 1:stride] += 1
    return mult


# ---------------------------------------------------------------------------
# Shared activation-quantization statistics
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("mode", "kh", "kw", "stride", "padding"))
def conv_act_stats(x: jnp.ndarray, mode: QuantMode, kh: int, kw: int,
                   stride: int = 1, padding: str = "SAME"
                   ) -> Dict[str, jnp.ndarray]:
    """Scalar quantization statistics of the *implicit* im2col matrix.

    Computes exactly the per-tensor quantities ``quantize_activations``
    would derive from the materialized patch matrix — mean |A| (and for
    ternary modes the TWN threshold + masked mean) — in one O(|x|) pass:
    every padded-input element enters the sums weighted by the number of
    patches that contain it, which is precisely its multiplicity in the
    im2col matrix.  Both the fused conv kernels and the materializing
    oracle (``ops.qmm(..., act_stats=...)``) consume THIS function's
    output, which is what makes the two paths bit-identical.
    """
    xp, (oh, ow) = conv_spatial_pad(x.astype(jnp.float32), kh, kw,
                                    stride, padding)
    b, hp, wp, c = xp.shape
    mult = jnp.asarray(_patch_multiplicity(hp, wp, kh, kw, stride, oh, ow))
    w4 = mult[None, :, :, None]
    absx = jnp.abs(xp)
    count = b * oh * ow * kh * kw * c            # == m * k, static
    mean_abs = jnp.sum(absx * w4) / count
    if mode == QuantMode.BNN:
        return {"scale": mean_abs}
    thr = 0.7 * mean_abs                         # TWN heuristic, eq. of §II-B
    mask = (absx > thr).astype(jnp.float32)
    nnz = jnp.sum(mask * w4)
    alpha = jnp.sum(absx * mask * w4) / jnp.maximum(nnz, 1.0)
    return {"thr": thr, "scale": alpha}


# ---------------------------------------------------------------------------
# Operand packing in the kernels' per-patch-position layout
# ---------------------------------------------------------------------------

def _conv_weight_planes(b_planes, mode: QuantMode, geometry):
    """LEGACY fallback: re-derive the per-patch-position weight planes
    from the contiguous-k payload inside the trace (O(n*k) per trace —
    pad codes are zero on both operands so the popcount total is
    unchanged).  New packs store this layout at pack time
    (``POS_PAYLOAD_KEYS``); only containers migrated from legacy dicts /
    old checkpoints still route through here.  Bit-identical to the
    stored planes by construction (same quantized values, same
    word-aligned pack)."""
    from repro.core import encoding

    kh, kw, cin, cout = geometry
    if cin % 32 == 0:
        return tuple(b_planes)
    k = kh * kw * cin
    if mode == QuantMode.TNN:                          # ternary weights
        vals = encoding.unpack_ternary(b_planes[0], b_planes[1], k)
    else:                                              # binary weights
        vals = encoding.unpack_binary(b_planes[0], k)
    v3 = vals.reshape(cout, kh * kw, cin)
    if mode == QuantMode.TNN:
        return (encoding.pack_bits(v3 > 0).reshape(cout, -1),
                encoding.pack_bits(v3 < 0).reshape(cout, -1))
    return (encoding.pack_bits(v3 < 0).reshape(cout, -1),)


def conv_weight_planes(qt) -> Tuple[jnp.ndarray, ...]:
    """Weight planes in the per-patch-position layout the fused conv
    kernels stream, resolved from a conv-packed :class:`QTensor`:

    * ``Cin % 32 == 0`` — the stored contiguous-k payload already IS the
      positional layout (word boundaries coincide): zero-copy;
    * positional planes stored at pack time (``POS_PAYLOAD_KEYS``, the
      ``Cin % 32 != 0`` case) — zero-copy;
    * legacy containers without them — exact in-trace repack via
      :func:`_conv_weight_planes` (the pre-positional behaviour).
    """
    from repro.kernels.qtensor import PAYLOAD_KEYS, POS_PAYLOAD_KEYS

    kh, kw, cin, cout = qt.geometry
    planes = tuple(qt.payload[k] for k in PAYLOAD_KEYS[qt.mode])
    if cin % 32 == 0:
        return planes
    pos_keys = POS_PAYLOAD_KEYS[qt.mode]
    if all(k in qt.payload for k in pos_keys):
        return tuple(qt.payload[k] for k in pos_keys)
    return _conv_weight_planes(planes, qt.mode, qt.geometry)


# ---------------------------------------------------------------------------
# Shared A-operand load path of the Pallas conv kernels
# ---------------------------------------------------------------------------

def interpreted_only(interpret: bool | None, backend: str) -> bool:
    """Interpret flag for the fused-im2col Pallas kernels, which run only
    in the Pallas interpreter.  They take the whole activation tensor as
    one block and gather patches with a 4-D gather that Mosaic does not
    lower ("Only 2D gather is supported"), so a compiled launch raises
    here — never a silent fallback — until they get a band-blocked A
    operand (ROADMAP S8).  The ``xla`` conv cell (an XLA integer
    convolution) compiles everywhere."""
    if resolve_interpret(interpret):
        return True
    raise NotImplementedError(
        f"the fused-im2col conv kernel of backend {backend!r} runs only in "
        f"the Pallas interpreter (CPU); on {jax.default_backend()!r} use "
        f"backend='xla' for qconv/conv2d_packed")


def gather_patch_tile(xv: jnp.ndarray, pid_m, *, block_m: int, m: int,
                      oh: int, ow: int, stride: int, kh: int,
                      kw: int) -> jnp.ndarray:
    """Raw (block_m, kh*kw, Cin) float patch tile for one m block: patch
    coordinates derived from ``program_id`` — the A-operand load path
    shared by the popcount (vpu) and dense (mxu) fused conv kernels.
    Pad rows past ``m`` re-gather row m-1 (their output is sliced off)."""
    mi = pid_m * block_m + jax.lax.broadcasted_iota(jnp.int32, (block_m,), 0)
    mi = jnp.minimum(mi, m - 1)
    bi = mi // (oh * ow)
    rem = mi % (oh * ow)
    hi = (rem // ow) * stride
    wi = (rem % ow) * stride
    dy = jax.lax.broadcasted_iota(jnp.int32, (kh, kw), 0)
    dx = jax.lax.broadcasted_iota(jnp.int32, (kh, kw), 1)
    patch = xv[bi[:, None, None], hi[:, None, None] + dy[None],
               wi[:, None, None] + dx[None]]          # (bm, kh, kw, C)
    return patch.reshape(block_m, kh * kw, xv.shape[-1])


def quantize_patch_values(patch: jnp.ndarray, mode: QuantMode,
                          thr) -> jnp.ndarray:
    """Elementwise per-tensor quantization of a gathered patch tile (or
    the whole padded input) to its ±1/0 *values* (per-tensor stats
    commute with gathering) — what the dense kernels and the ``xla``
    cell feed the MXU; the popcount kernels bit-plane pack the same
    comparisons.  ``thr`` is ignored for BNN."""
    if mode == QuantMode.BNN:
        return jnp.where(patch < 0, -1.0, 1.0)
    return jnp.sign(patch) * (jnp.abs(patch) > thr)


# ---------------------------------------------------------------------------
# XLA backend: ±1/0 int8 codes through XLA's integer convolution (MXU)
# ---------------------------------------------------------------------------

def _filter_codes(b_planes, mode: QuantMode, geometry) -> jnp.ndarray:
    """Decode the per-patch-position weight planes to ``(kh, kw, cin,
    cout)`` int8 codes in {-1, 0, +1}.  Each position's run of
    ceil(Cin/32) words unpacks to whole words; its pad channels are
    sliced off, so the (dy, dx, c) order is the im2col column order."""
    from repro.core import encoding

    kh, kw, cin, cout = geometry
    bits = b_planes[0].shape[-1] * encoding.WORD_BITS
    if mode == QuantMode.TNN:
        vals = encoding.unpack_ternary(b_planes[0], b_planes[1], bits,
                                       dtype=jnp.int8)
    else:
        vals = encoding.unpack_binary(b_planes[0], bits, dtype=jnp.int8)
    vals = vals.reshape(cout, kh, kw, -1)[..., :cin]
    return jnp.transpose(vals, (1, 2, 3, 0))


def _conv_xla_fused(mode: QuantMode, x, b_planes, geometry, stride, padding,
                    stats, col_scale, bias):
    """The compiled form of the fused conv, on every backend.

    Per-tensor statistics make quantization elementwise, so the padded
    input quantizes ONCE to int8 codes (padding first, in f32: a padded
    zero is +1 for BNN, 0 for ternary, as in the im2col oracle), the
    packed filters decode to int8 codes inside the trace, and XLA's
    integer convolution sums the ±1/0 products in int32 on the MXU.
    Those sums are the popcount core's integers (for BNN, the ±1 dot
    ``k_valid - 2 * popcount``), and the epilogue keeps its multiply
    order, so the output is bit-identical to the popcount kernels.
    """
    from repro.kernels import ops

    kh, kw, _, _ = geometry
    xp, _ = conv_spatial_pad(x.astype(jnp.float32), kh, kw, stride, padding)
    codes = quantize_patch_values(xp, mode, stats.get("thr"))
    acc = jax.lax.conv_general_dilated(
        codes.astype(jnp.int8), _filter_codes(b_planes, mode, geometry),
        (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    alpha = jnp.reshape(stats["scale"], (1, 1))
    return ops._scale_epilogue_f32(acc, alpha, col_scale, bias)


# ---------------------------------------------------------------------------
# Pallas backend: patch coordinates from program_id, quantize + pack the
# tile in VMEM, chunked popcount, in-kernel epilogue
# ---------------------------------------------------------------------------

def _conv_pallas_fused(mode: QuantMode, x, b_planes, geometry, stride,
                       padding, stats, col_scale, bias, *, block_m: int,
                       block_n: int, block_kw: int, word_chunk: int,
                       interpret: bool | None):
    from repro.core import encoding
    from repro.kernels import ops

    kh, kw, cin, cout = geometry
    k_valid = kh * kw * cin
    xp, (oh, ow) = conv_spatial_pad(x.astype(jnp.float32), kh, kw,
                                    stride, padding)
    bsz = xp.shape[0]
    m = bsz * oh * ow
    b_conv = tuple(b_planes)      # already per-patch-position layout
    words = int(b_conv[0].shape[-1])                    # kh*kw*ceil(cin/32)
    product = ops._PRODUCT_FNS[mode]

    # Same clamps as lowbit_matmul_call: the inner loop consumes
    # word_chunk words per step, the outer loop block_kw words per block.
    block_kw = ceil_to(min(block_kw, max(word_chunk, words)), word_chunk)
    wordsp = ceil_to(words, block_kw)
    mp, np_ = ceil_to(m, block_m), ceil_to(cout, block_n)
    b_ops = [pad2d(bp, np_, wordsp) for bp in b_conv]
    col_ops = [pad2d(col_scale, 1, np_)]
    if bias is not None:
        col_ops.append(pad2d(bias, 1, np_))
    stat_ops = []
    if mode != QuantMode.BNN:
        stat_ops.append(jnp.reshape(stats["thr"], (1, 1)))
    stat_ops.append(jnp.reshape(stats["scale"], (1, 1)))

    grid = (mp // block_m, np_ // block_n)
    x_spec = pl.BlockSpec(xp.shape, lambda i, j: (0, 0, 0, 0))
    b_spec = pl.BlockSpec((block_n, wordsp), lambda i, j: (j, 0))
    s_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    c_spec = pl.BlockSpec((1, block_n), lambda i, j: (0, j))
    o_spec = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
    nb, ns = len(b_ops), len(stat_ops)

    def kernel(*refs):
        x_ref = refs[0]
        b_refs = refs[1:1 + nb]
        s_refs = refs[1 + nb:1 + nb + ns]
        c_refs = refs[1 + nb + ns:-1]
        o_ref = refs[-1]

        # -- patch coordinates for this m block (A-operand load path) --
        patch = gather_patch_tile(x_ref[...], pl.program_id(0),
                                  block_m=block_m, m=m, oh=oh, ow=ow,
                                  stride=stride, kh=kh, kw=kw)

        # -- quantize + pack the tile in VMEM (same ops as encoding) ---
        if mode == QuantMode.BNN:
            a_planes = [encoding.pack_bits(patch < 0)]
        else:
            thr = s_refs[0][0, 0]
            t = jnp.sign(patch) * (jnp.abs(patch) > thr)
            a_planes = [encoding.pack_bits(t > 0), encoding.pack_bits(t < 0)]
        a_planes = [jnp.pad(p.reshape(block_m, words),
                            ((0, 0), (0, wordsp - words)))
                    for p in a_planes]
        b_vals = [r[...] for r in b_refs]    # (block_n, wordsp)

        # -- chunked popcount reduction --------------------------------
        def outer(kb, acc):
            a_blk = [jax.lax.dynamic_slice_in_dim(p, kb * block_kw,
                                                  block_kw, 1)
                     for p in a_planes]
            b_blk = [jax.lax.dynamic_slice_in_dim(p, kb * block_kw,
                                                  block_kw, 1)
                     for p in b_vals]

            def inner(s, acc2):
                a_sl = [jax.lax.dynamic_slice_in_dim(
                    p, s * word_chunk, word_chunk, 1)[:, None, :]
                    for p in a_blk]
                b_sl = [jax.lax.dynamic_slice_in_dim(
                    p, s * word_chunk, word_chunk, 1)[None, :, :]
                    for p in b_blk]
                return acc2 + jnp.sum(product(a_sl, b_sl), axis=-1)

            return jax.lax.fori_loop(0, block_kw // word_chunk, inner, acc)

        acc = jax.lax.fori_loop(0, wordsp // block_kw, outer,
                                jnp.zeros((block_m, block_n), jnp.int32))

        # -- eq. (6) finalization + eq. (2) epilogue, in-kernel --------
        val = (jnp.int32(k_valid) - 2 * acc) if mode == QuantMode.BNN else acc
        o_ref[...] = scale_epilogue(val.astype(jnp.float32),
                                    [s_refs[-1]], c_refs)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=([x_spec] + [b_spec] * nb + [s_spec] * ns
                  + [c_spec] * len(col_ops)),
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpreted_only(interpret, "pallas"),
    )(xp, *b_ops, *stat_ops, *col_ops)
    return out[:m, :cout].reshape(bsz, oh, ow, cout)


# ---------------------------------------------------------------------------
# Registration — (mode, backend, fused=True, layout="im2col_fused").
# The dense (MXU) conv kernel lives in kernels/dense_fused.py: it shares
# gather_patch_tile/quantize_patch_values above but unpacks the weight
# planes to ±1/0 bf16 tiles in VMEM and rides jnp.dot.
# ---------------------------------------------------------------------------

def _resolve_conv_tiles(mode: QuantMode, backend: str, x_shape, geometry,
                        stride: int, padding: str, tiles):
    if tiles is not None:
        return tiles
    m, n, k, tag = conv_problem_dims(x_shape, geometry, stride, padding)
    return tune_cache.plan_for(mode, backend, fused=True, m=m, n=n, k=k,
                               layout=registry.LAYOUT_IM2COL,
                               geom=tag).tiles


def _register_conv_kernels():
    M = QuantMode

    def make_pallas(mode):
        def fn(x, b_planes, geometry, stride, padding, stats, col_scale,
               bias, *, interpret=None, tiles=None):
            t = _resolve_conv_tiles(mode, "pallas", x.shape, geometry,
                                    stride, padding, tiles)
            return _conv_pallas_fused(mode, x, b_planes, geometry, stride,
                                      padding, stats, col_scale, bias,
                                      interpret=interpret,
                                      **t.kernel_kwargs())
        return fn

    def make_xla(mode):
        def fn(x, b_planes, geometry, stride, padding, stats, col_scale,
               bias, *, interpret=None, tiles=None):
            del interpret, tiles      # XLA picks the conv's tiling
            return _conv_xla_fused(mode, x, b_planes, geometry, stride,
                                   padding, stats, col_scale, bias)
        return fn

    for mode in (M.BNN, M.TNN, M.TBN):
        registry.register(
            mode, "pallas", fused=True, layout=registry.LAYOUT_IM2COL,
            epilogue="in-kernel", compute="vpu-popcount",
            tunable=CONV_PALLAS_SPACE,
            description="patch gather + quantize + pack in VMEM; popcount "
                        "core; epilogue in-kernel",
        )(make_pallas(mode))
        registry.register(
            mode, "xla", fused=True, layout=registry.LAYOUT_IM2COL,
            epilogue="post-core", compute="mxu-int8",
            description="quantize-once int8 codes; decoded int8 filters; "
                        "lax conv with int32 sums",
        )(make_xla(mode))


_register_conv_kernels()
