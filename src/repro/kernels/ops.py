"""Public entry points for the low-bit matmul kernels.

The deployment API is two calls:

* ``pack_weights(w, mode)`` (== :meth:`QTensor.from_dense`) — offline
  packing, the paper's Algorithm 2 PackedB.  Returns a :class:`QTensor`:
  bit planes / affine payload + scale/bias as pytree leaves, mode /
  logical shape / conv geometry as static aux data.
* ``qmm(x, qt)`` — float activations x packed weights -> float32, ONE
  jitted computation (quantize -> pack -> popcount matmul -> eq. (2)
  scale/bias epilogue).  Mode, depth, scales, bias and geometry all
  travel inside the QTensor — consumers never re-thread ``mode=`` or
  ``k_valid=``.

Kernel selection goes through :mod:`repro.kernels.registry` — one
``(mode, backend, fused)`` table replacing the old per-function if/elif
ladders.  Four backends per low-bit mode:

* ``pallas``  — the TPU kernels of this package, validated on CPU in
  interpret mode (the TARGET implementation);
* ``xla``     — a production pure-jnp path with the same popcount
  formulation, written as a k-chunked ``lax.scan`` so the (m, n, chunk)
  broadcast never exceeds a VMEM-sized working set;
* ``dense``   — a beyond-paper TPU alternative: keep the *storage* packed
  (the memory win) and ride the MXU — the fused kernels
  (kernels/dense_fused.py) unpack bit-plane words to ±1/0 bf16 tiles in
  VMEM, directly ahead of the dot; the unfused entry keeps the
  materializing HBM unpack as the bit-exact oracle;
* ``indexed`` — the redundancy-exploiting segment-index formulation of
  Dehghankar et al. (arXiv 2411.06360): per-(row, segment) subset-sum
  tables replace per-column popcounts (kernels/indexed_matmul.py),
  with optional pack-time index payload on the QTensor.

The affine u8/u4 modes dispatch through the same registry (``(int8/
int4, "xla"/"pallas", fused)`` cells — the eq. (3) zero-point core plus
the shared eq. (2) epilogue), so ``qmm`` and ``core/policy.py`` treat
them like any other mode x backend cell.

Plus the float-in/float-out ``quantized_matmul`` with straight-through
(STE) gradients for QAT.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

# Leaf first: QuantMode/DEFAULT_BACKEND must be bound before the core
# import below re-enters this (partially initialized) module through the
# core -> qlinear -> kernels cycle.
from repro.kernels.modes import DEFAULT_BACKEND, QuantMode
from repro.kernels import registry
from repro.kernels._matmul_common import TileConfig
from repro.kernels.qtensor import PAYLOAD_KEYS, QTensor
from repro.tune import cache as tune_cache
from repro.tune.space import AFFINE_SPACE, PALLAS_SPACE, XLA_SPACE
from repro import obs
from repro.resilience import faults

from repro.core import encoding, quantize
from repro.kernels import ref as kref
from repro.kernels.bnn_matmul import bnn_matmul_pallas, bnn_matmul_fused_pallas
from repro.kernels.tnn_matmul import tnn_matmul_pallas, tnn_matmul_fused_pallas
from repro.kernels.tbn_matmul import tbn_matmul_pallas, tbn_matmul_fused_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.int4_matmul import (
    int4_matmul_pallas, pack_nibbles_rows, pack_nibbles_cols,
)

__all__ = [
    "QuantMode", "QTensor", "qmm", "qconv", "pack_weights",
    "quantize_activations",
    "packed_matmul", "quantized_matmul", "lowbit_matmul",
    "int8_affine_matmul", "int4_affine_matmul", "DEFAULT_BACKEND",
    "qmm_trace_count", "qconv_trace_count", "has_conv_kernel",
    "bnn_matmul_xla_fused", "tnn_matmul_xla_fused", "tbn_matmul_xla_fused",
]

_WORD_CHUNK = 8  # uint32 words per scan step on the xla path (256 k-elems)

# Which planes each mode consumes on the ACTIVATION side (weights use
# qtensor.PAYLOAD_KEYS — the container's single source of truth).  The
# sides differ for TBN: ternary activations x binary weights.  The
# affine modes carry the quantized grid plus its zero point — the
# eq. (3) core needs both operands' zeros.
_A_KEYS: Dict[QuantMode, Tuple[str, ...]] = {
    QuantMode.BNN: ("bits",),
    QuantMode.TNN: ("plus", "minus"),
    QuantMode.TBN: ("plus", "minus"),
    QuantMode.INT8: ("q", "zero"),
    QuantMode.INT4: ("q", "zero"),
}


# ---------------------------------------------------------------------------
# XLA production paths (k-chunked popcount scans)
# ---------------------------------------------------------------------------

def _chunked_bitwise_matmul(product_fn, a_ops, b_ops, *, word_chunk=_WORD_CHUNK,
                            epilogue=None):
    """acc[m, n] = sum over kw-chunks of product_fn(a_chunk, b_chunk).

    a_ops: list of (m, kw) uint32; b_ops: list of (n, kw) uint32.
    Scans the word axis so the broadcast intermediate is (m, n, wc).

    ``epilogue`` (optional) maps the final int32 scan carry to the float
    output *inside the same traced computation*, so XLA fuses the
    dequantization multiply into the consumer of the scan's last
    iteration — the int32 accumulator is never materialized in HBM as a
    separate pass.
    """
    m, kw = a_ops[0].shape
    n = b_ops[0].shape[0]
    wc = min(word_chunk, kw)
    kwp = -(-kw // wc) * wc
    a_ops = [jnp.pad(a, ((0, 0), (0, kwp - kw))) for a in a_ops]
    b_ops = [jnp.pad(b, ((0, 0), (0, kwp - kw))) for b in b_ops]
    steps = kwp // wc

    # (steps, m/n, wc) views so scan slices are contiguous loads.
    a_sc = [a.reshape(m, steps, wc).transpose(1, 0, 2) for a in a_ops]
    b_sc = [b.reshape(n, steps, wc).transpose(1, 0, 2) for b in b_ops]

    def step(acc, ops):
        a_ch, b_ch = ops
        contrib = product_fn([x[:, None, :] for x in a_ch],
                             [x[None, :, :] for x in b_ch])
        return acc + jnp.sum(contrib, axis=-1), None

    acc0 = jnp.zeros((m, n), jnp.int32)
    acc, _ = jax.lax.scan(step, acc0, (a_sc, b_sc))
    return acc if epilogue is None else epilogue(acc)


def _pc(x):
    return jax.lax.population_count(x).astype(jnp.int32)


def _bnn_product(a_sl, b_sl):
    return _pc(jnp.bitwise_xor(a_sl[0], b_sl[0]))


def _tnn_product(a_sl, b_sl):
    ap, am = a_sl
    bp, bm = b_sl
    return _pc((ap & bp) | (am & bm)) - _pc((ap & bm) | (am & bp))


def _tbn_product(a_sl, b_sl):
    ap, am = a_sl
    (bb,) = b_sl
    nbb = jnp.bitwise_not(bb)
    return _pc((ap | bb) & (am | nbb)) - _pc((ap | nbb) & (am | bb))


# Per-word signed contribution of each mode — shared with the fused conv
# kernels (kernels/conv_fused.py), which run the same popcount core over
# patch-gathered words.
_PRODUCT_FNS: Dict[QuantMode, Any] = {
    QuantMode.BNN: _bnn_product,
    QuantMode.TNN: _tnn_product,
    QuantMode.TBN: _tbn_product,
}


def bnn_matmul_xla(a_bits, b_bits_t, k_valid: int, *,
                   word_chunk: int = _WORD_CHUNK):
    pc = _chunked_bitwise_matmul(_bnn_product, [a_bits], [b_bits_t],
                                 word_chunk=word_chunk)
    return jnp.int32(k_valid) - 2 * pc


def tnn_matmul_xla(a_plus, a_minus, b_plus_t, b_minus_t, k_valid: int = 0, *,
                   word_chunk: int = _WORD_CHUNK):
    del k_valid
    return _chunked_bitwise_matmul(_tnn_product, [a_plus, a_minus],
                                   [b_plus_t, b_minus_t],
                                   word_chunk=word_chunk)


def tbn_matmul_xla(a_plus, a_minus, b_bits_t, k_valid: int = 0, *,
                   word_chunk: int = _WORD_CHUNK):
    del k_valid
    return _chunked_bitwise_matmul(_tbn_product, [a_plus, a_minus],
                                   [b_bits_t], word_chunk=word_chunk)


# ---------------------------------------------------------------------------
# Fused XLA paths: popcount scan + eq. (2) scale epilogue in one trace
# ---------------------------------------------------------------------------

def _scale_epilogue_f32(acc, row_scale, col_scale, bias):
    """Same multiply order as the unfused ``acc * a_scale * w_scale``
    epilogue, so fused and unfused results are bit-identical floats."""
    out = acc.astype(jnp.float32) * row_scale * col_scale
    if bias is not None:
        out = out + bias
    return out


def bnn_matmul_xla_fused(a_bits, b_bits_t, k_valid: int,
                         row_scale, col_scale, bias=None, *,
                         word_chunk: int = _WORD_CHUNK):
    def epi(pc):
        return _scale_epilogue_f32(jnp.int32(k_valid) - 2 * pc,
                                   row_scale, col_scale, bias)
    return _chunked_bitwise_matmul(_bnn_product, [a_bits], [b_bits_t],
                                   word_chunk=word_chunk, epilogue=epi)


def tnn_matmul_xla_fused(a_plus, a_minus, b_plus_t, b_minus_t, k_valid: int,
                         row_scale, col_scale, bias=None, *,
                         word_chunk: int = _WORD_CHUNK):
    del k_valid
    def epi(acc):
        return _scale_epilogue_f32(acc, row_scale, col_scale, bias)
    return _chunked_bitwise_matmul(_tnn_product, [a_plus, a_minus],
                                   [b_plus_t, b_minus_t],
                                   word_chunk=word_chunk, epilogue=epi)


def tbn_matmul_xla_fused(a_plus, a_minus, b_bits_t, k_valid: int,
                         row_scale, col_scale, bias=None, *,
                         word_chunk: int = _WORD_CHUNK):
    del k_valid
    def epi(acc):
        return _scale_epilogue_f32(acc, row_scale, col_scale, bias)
    return _chunked_bitwise_matmul(_tbn_product, [a_plus, a_minus],
                                   [b_bits_t], word_chunk=word_chunk,
                                   epilogue=epi)


# ---------------------------------------------------------------------------
# Kernel registry entries — normalized (a_planes, b_planes, ...) adapters
# around the mode-specific kernels above.  benchmarks/tests enumerate
# these; the ROADMAP's dense-Pallas and conv-im2col kernels plug in here.
#
# Tunable adapters take a ``tiles=`` keyword (TileConfig).  ``tiles=None``
# — the dispatch default — resolves the blocking from the autotuning plan
# cache at TRACE time (repro.tune.cache.plan_for: tuned plan on a cache
# hit, DEFAULT_TILES otherwise); the tuner passes explicit candidates.
# Resolution is deterministic per (shape-bucket, cache content), so
# repeated calls with the same shapes keep hitting one jit trace.
# ---------------------------------------------------------------------------

def _unpack_operand(planes, k: int, binary: bool):
    if binary:
        return encoding.unpack_binary(planes[0], k, jnp.bfloat16)
    return encoding.unpack_ternary(planes[0], planes[1], k, jnp.bfloat16)


def _resolve_tiles(mode: QuantMode, backend: str, fused: bool,
                   a_planes, b_planes, k: int,
                   tiles: Optional[TileConfig]) -> TileConfig:
    if tiles is not None:
        return tiles
    m = int(a_planes[0].shape[0])
    n = int(b_planes[0].shape[0])
    return tune_cache.plan_for(mode, backend, fused=fused,
                               m=m, n=n, k=int(k)).tiles


def _register_all_kernels():
    M = QuantMode

    def make_pallas(mode, kernel, fused):
        split = 2 if mode in (M.TNN, M.TBN) else 1  # a-side plane count

        def unfused_fn(a, b, k, *, interpret=None, tiles=None):
            t = _resolve_tiles(mode, "pallas", False, a, b, k, tiles)
            return kernel(*a[:split], *b, k, interpret=interpret,
                          **t.kernel_kwargs())

        def fused_fn(a, b, k, r, c, bias, *, interpret=None, tiles=None):
            t = _resolve_tiles(mode, "pallas", True, a, b, k, tiles)
            return kernel(*a[:split], *b, k, r, c, bias,
                          interpret=interpret, **t.kernel_kwargs())

        return fused_fn if fused else unfused_fn

    def make_xla(mode, kernel, fused):
        def unfused_fn(a, b, k, *, interpret=None, tiles=None):
            del interpret
            t = _resolve_tiles(mode, "xla", False, a, b, k, tiles)
            return kernel(*a, *b, k, word_chunk=t.word_chunk)

        def fused_fn(a, b, k, r, c, bias, *, interpret=None, tiles=None):
            del interpret
            t = _resolve_tiles(mode, "xla", True, a, b, k, tiles)
            return kernel(*a, *b, k, r, c, bias, word_chunk=t.word_chunk)

        return fused_fn if fused else unfused_fn

    pallas_kernels = {
        (M.BNN, False): bnn_matmul_pallas,
        (M.BNN, True): bnn_matmul_fused_pallas,
        (M.TNN, False): tnn_matmul_pallas,
        (M.TNN, True): tnn_matmul_fused_pallas,
        (M.TBN, False): tbn_matmul_pallas,
        (M.TBN, True): tbn_matmul_fused_pallas,
    }
    xla_kernels = {
        (M.BNN, False): bnn_matmul_xla,
        (M.BNN, True): bnn_matmul_xla_fused,
        (M.TNN, False): tnn_matmul_xla,
        (M.TNN, True): tnn_matmul_xla_fused,
        (M.TBN, False): tbn_matmul_xla,
        (M.TBN, True): tbn_matmul_xla_fused,
    }
    ternary_a = {M.BNN: False, M.TNN: True, M.TBN: True}
    ternary_b = {M.BNN: False, M.TNN: True, M.TBN: False}

    for mode in (M.BNN, M.TNN, M.TBN):
        registry.register(
            mode, "pallas", fused=False, epilogue="none",
            compute="vpu-popcount", tunable=PALLAS_SPACE,
            description="Pallas bit-plane kernel, int32 accumulator",
        )(make_pallas(mode, pallas_kernels[(mode, False)], fused=False))
        registry.register(
            mode, "pallas", fused=True, epilogue="in-kernel",
            compute="vpu-popcount", tunable=PALLAS_SPACE,
            description="Pallas kernel; eq. (2) epilogue at pid_k==num_k-1",
        )(make_pallas(mode, pallas_kernels[(mode, True)], fused=True))
        registry.register(
            mode, "xla", fused=False, epilogue="none",
            compute="vpu-popcount", tunable=XLA_SPACE,
            description="k-chunked lax.scan popcount path",
        )(make_xla(mode, xla_kernels[(mode, False)], fused=False))
        registry.register(
            mode, "xla", fused=True, epilogue="scan-carry",
            compute="vpu-popcount", tunable=XLA_SPACE,
            description="popcount scan; epilogue fused onto the final carry",
        )(make_xla(mode, xla_kernels[(mode, True)], fused=True))

        def dense_unfused(a, b, k, *, interpret=None, tiles=None, _m=mode):
            del interpret, tiles    # XLA picks the dense tiling itself
            av = _unpack_operand(a, k, binary=not ternary_a[_m])
            bv = _unpack_operand(b, k, binary=not ternary_b[_m])
            return jnp.dot(av, bv.T,
                           preferred_element_type=jnp.float32).astype(jnp.int32)

        # The materializing HBM unpack survives only as the UNFUSED
        # entry — the bit-exact oracle for the in-VMEM dense kernels of
        # kernels/dense_fused.py, which register the fused slots.
        registry.register(
            mode, "dense", fused=False, epilogue="none", compute="mxu-xla",
            description="materializing oracle: unpack the whole payload to "
                        "bf16 in HBM, then one XLA dot",
        )(dense_unfused)


_register_all_kernels()


# ---------------------------------------------------------------------------
# Affine (u8/u4) registry cells: eq. (3) zero-point core + eq. (2)
# epilogue, dispatched like every other (mode, backend, fused) cell
# ---------------------------------------------------------------------------

def _affine_core(mode: QuantMode, a_pl, b_pl, k_valid: int, *,
                 use_pallas: bool, interpret: bool):
    """int32 c~ per eq. (3).  ``a_pl``/``b_pl`` are the (grid, zero)
    operand pairs of ``_A_KEYS``/``_b_planes``: a_q (m, k) and b_q
    (k, n) u8/u4-valued, za/zb their zero points."""
    a_q, za = a_pl
    b_q, zb = b_pl
    if use_pallas:
        if mode == QuantMode.INT8:
            # gemmlowp's operands are *unsigned* 8-bit and the MXU takes
            # int8: shift both grids by -128 and their zero points with
            # them, (a - za) = (a-128) - (za-128), so eq. (3) is unchanged.
            a_q, b_q = (jnp.asarray(q, jnp.int32) - 128 for q in (a_q, b_q))
            za, zb = (jnp.asarray(z, jnp.int32) - 128 for z in (za, zb))
            acc = int8_matmul_pallas(a_q.astype(jnp.int8),
                                     b_q.astype(jnp.int8),
                                     interpret=interpret)
        else:
            acc = int4_matmul_pallas(pack_nibbles_rows(a_q),
                                     pack_nibbles_cols(b_q),
                                     interpret=interpret)
        rows = jnp.sum(a_q.astype(jnp.int32), axis=1)
        cols = jnp.sum(b_q.astype(jnp.int32), axis=0)
        za = jnp.asarray(za, jnp.int32)
        zb = jnp.asarray(zb, jnp.int32)
        return (acc - zb * rows[:, None] - za * cols[None, :]
                + jnp.int32(k_valid) * za * zb)
    ref_fn = (kref.int8_matmul_ref if mode == QuantMode.INT8
              else kref.int4_matmul_ref)
    return ref_fn(a_q, b_q, za, zb, k_valid)


def _register_affine_kernels():
    def make(mode, use_pallas, fused):
        def unfused_fn(a, b, k, *, interpret=None, tiles=None):
            del tiles                # the int kernels pick their own tiling
            return _affine_core(mode, a, b, k, use_pallas=use_pallas,
                                interpret=interpret)

        def fused_fn(a, b, k, r, c, bias, *, interpret=None, tiles=None):
            del tiles
            acc = _affine_core(mode, a, b, k, use_pallas=use_pallas,
                               interpret=interpret)
            return _scale_epilogue_f32(acc, r, c, bias)

        return fused_fn if fused else unfused_fn

    for mode in (QuantMode.INT8, QuantMode.INT4):
        for use_pallas in (False, True):
            backend = "pallas" if use_pallas else "xla"
            compute = f"int-{backend}"
            registry.register(
                mode, backend, fused=False, epilogue="none",
                compute=compute,
                description="eq. (3) zero-point core on the quantized grid",
            )(make(mode, use_pallas, fused=False))
            registry.register(
                mode, backend, fused=True, epilogue="post-core",
                compute=compute, tunable=AFFINE_SPACE,
                description="eq. (3) core + eq. (2) scale/bias epilogue "
                            "in one trace",
            )(make(mode, use_pallas, fused=True))


_register_affine_kernels()

# Registers the fused-im2col conv kernels (layout="im2col_fused"), the
# dense-backend MXU fusion kernels (both layouts) and the indexed-
# redundancy segment-gather kernels as import side effects.  Must come
# after _register_all_kernels() and after the core imports above so
# their lazy repro.core references always resolve; dense_fused imports
# conv_fused's shared patch-gather helpers, so the order below matters.
from repro.kernels import conv_fused as _conv_fused  # noqa: E402,F401
from repro.kernels import dense_fused as _dense_fused  # noqa: E402,F401
from repro.kernels import indexed_matmul as _indexed_matmul  # noqa: E402,F401


# ---------------------------------------------------------------------------
# Affine (u8/u4) full pipelines — thin registry-routed wrappers kept for
# the bench/test surface; dispatch lives in the registry cells above
# ---------------------------------------------------------------------------

def _affine_backend(mode: QuantMode, backend: str, *, fused: bool) -> str:
    """Effective affine backend: the requested one when registered,
    otherwise the "xla" reference cell (preserving the old anything-but-
    pallas -> reference behavior for backends like "dense")."""
    return backend if registry.has(mode, backend, fused=fused) else "xla"


def int8_affine_matmul(a_q, b_q, za, zb, k_valid: int, *,
                       backend: str = DEFAULT_BACKEND,
                       interpret: bool | None = None):
    """c~ per eq. (3).  a_q (m,k) u8-valued, b_q (k,n) u8-valued."""
    spec = registry.lookup(QuantMode.INT8,
                           _affine_backend(QuantMode.INT8, backend,
                                           fused=False), fused=False)
    return spec.fn((a_q, za), (b_q, zb), k_valid, interpret=interpret)


def int4_affine_matmul(a_q, b_q, za, zb, k_valid: int, *,
                       backend: str = DEFAULT_BACKEND,
                       interpret: bool | None = None):
    spec = registry.lookup(QuantMode.INT4,
                           _affine_backend(QuantMode.INT4, backend,
                                           fused=False), fused=False)
    return spec.fn((a_q, za), (b_q, zb), k_valid, interpret=interpret)


# ---------------------------------------------------------------------------
# Packing
# ---------------------------------------------------------------------------

def pack_weights(w: jnp.ndarray, mode: QuantMode, *,
                 per_channel: bool = True,
                 indexed_bits: Optional[int] = None) -> QTensor:
    """Offline weight packing (Algorithm 2's PackedB).

    ``w`` is (k, n) float.  Returns a :class:`QTensor` (see
    kernels/qtensor.py for the per-mode payload layout).

    ``indexed_bits`` (2/4/8) additionally stores the segment-index
    payload the "indexed" backend consumes zero-copy
    (kernels/indexed_matmul.py) — opt-in, since it grows the payload;
    without it the indexed kernels derive the indices in-trace,
    bit-identically."""
    qt = QTensor.from_dense(w, mode, per_channel=per_channel)
    if indexed_bits is not None:
        qt = _indexed_matmul.add_indexed_payload(qt, indexed_bits)
    return qt


def quantize_activations(x: jnp.ndarray, mode: QuantMode, *,
                         stats: Optional[Dict[str, Any]] = None
                         ) -> Dict[str, Any]:
    """Runtime activation quantization.  ``x`` is (m, k) float.

    Activations are transient (packed inside the fused trace, never
    stored), so they stay a plain dict of planes rather than a QTensor.

    ``stats`` optionally supplies externally-computed per-tensor
    statistics ({"thr", "scale"} for ternary modes, {"scale"} for BNN)
    instead of deriving them from ``x`` — the conv path uses this so the
    materializing oracle and the fused-im2col kernels quantize with the
    exact same scalars (conv_fused.conv_act_stats computes them once
    from the un-materialized input).
    """
    if mode in (QuantMode.F32, QuantMode.BF16):
        return {"x": x}
    if x.ndim == 2:
        # The per-tensor statistics are reductions over all of x, and
        # their order follows x's layout, which XLA may otherwise choose
        # to suit whichever kernel consumes the planes: pin x row-major
        # so every backend quantizes with bit-identical scalars.
        x = with_layout_constraint(x, Layout(major_to_minor=(0, 1)))
    if mode in (QuantMode.TNN, QuantMode.TBN):
        if stats is not None:
            t, _ = quantize.ternarize(x, threshold=stats["thr"])
            scale = stats["scale"]
        else:
            t, scale = quantize.ternarize(x)
        plus, minus = encoding.pack_ternary(t)
        return {"plus": plus, "minus": minus, "scale": scale}
    if mode == QuantMode.BNN:
        b, scale = quantize.binarize(x)
        if stats is not None:
            scale = stats["scale"]
        return {"bits": encoding.pack_binary(b), "scale": scale}
    if mode in (QuantMode.INT8, QuantMode.INT4):
        bits = 8 if mode == QuantMode.INT8 else 4
        q = quantize.affine_calibrate(x, bits)
        return {"q": quantize.affine_quantize(x, q),
                "scale": q.scale, "zero": q.zero_point}
    raise ValueError(mode)


def _b_planes(wb: QTensor, mode: QuantMode) -> Tuple[jnp.ndarray, ...]:
    """Weight-side operand tuple of a QTensor: the mode's payload planes,
    plus the zero point for the affine modes (the eq. (3) core consumes
    (grid, zero) pairs on both sides)."""
    planes = tuple(wb.payload[k] for k in PAYLOAD_KEYS[mode])
    if mode in (QuantMode.INT8, QuantMode.INT4):
        return planes + (wb.zero,)
    return planes


def packed_matmul(xa: Dict[str, Any], wb: QTensor,
                  mode: Optional[QuantMode] = None,
                  k_valid: Optional[int] = None, *,
                  backend: str = DEFAULT_BACKEND,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Integer core: packed activations x packed weights -> int32 (m, n).

    ``wb`` is a :class:`QTensor` (mode/k_valid come from it; the legacy
    plane-dict form is retired — migrate with
    :meth:`QTensor.from_legacy_dict`).  This is the unfused correctness
    oracle; the hot path is :func:`qmm`.
    """
    if not isinstance(wb, QTensor):
        raise TypeError(
            f"packed_matmul expects a QTensor weight operand (migrate "
            f"legacy packed dicts with QTensor.from_legacy_dict); got "
            f"{type(wb).__name__}")
    if mode is not None and mode != wb.mode:
        raise ValueError(f"mode mismatch: {mode} vs QTensor {wb.mode}")
    mode = wb.mode
    k_valid = wb.k_valid if k_valid is None else k_valid
    if not mode.is_lowbit:
        raise ValueError(f"packed_matmul only handles low-bit modes, got {mode}")
    spec = registry.lookup(mode, backend, fused=False)
    a_pl = tuple(xa[k] for k in _A_KEYS[mode])
    extra = {"payload": wb.payload} if spec.payload_aware else {}
    return spec.fn(a_pl, _b_planes(wb, mode), k_valid, interpret=interpret,
                   **extra)


# ---------------------------------------------------------------------------
# qmm — THE packed-inference entry point: float x QTensor -> float32,
# quantize -> pack -> popcount matmul -> scale/bias as one jitted call
# ---------------------------------------------------------------------------

def _as_row_scale(scale, m: int) -> jnp.ndarray:
    """Activation scale (scalar per-tensor or (m,) per-row) -> (m, 1) f32."""
    s = jnp.asarray(scale, jnp.float32)
    if s.ndim == 0:
        return jnp.full((m, 1), s)
    return s.reshape(m, 1)


def _as_col_vec(v, n: int) -> jnp.ndarray:
    """Weight scale / bias (scalar or (n,) per-channel) -> (1, n) f32."""
    x = jnp.asarray(v, jnp.float32)
    if x.ndim == 0:
        return jnp.full((1, n), x)
    return x.reshape(1, n)


# Retrace guards live in the obs registry now, labelled (mode, backend);
# a consumer reusing one QTensor across calls must not retrace (tests
# guard this).  ``always=True``: these are correctness counters consumed
# by the tier-1 suite, so they count even under REPRO_OBS=off — they
# fire at trace time only, never on the per-call hot path.
_QMM_TRACE_CTR = obs.get_registry().counter(
    "repro_qmm_traces_total",
    "qmm retraces by (mode, backend); counts at jax trace time",
    labels=("mode", "backend"), always=True)

_QMM_DISPATCH_CTR = obs.get_registry().counter(
    "repro_qmm_dispatch_total",
    "qmm host-side dispatches by (mode, backend, layout)",
    labels=("mode", "backend", "layout"))


# ---------------------------------------------------------------------------
# Graceful-degradation fallback chain (docs/resilience.md): when the
# fault plane injects "kernel.compile", dispatch walks pallas -> xla ->
# dense oracle instead of propagating.  Only the injected fault
# degrades: a real lowering error raises, so a kernel that does not
# compile for the device is never timed as if it had run.  The landed decision is cached per
# (op, mode, requested backend) ~ per KernelSpec, so the hot path never
# retries a dead backend per call: after the first degradation every
# subsequent call is one dict lookup straight to the surviving backend.
# All fallback targets are bit-exact with each other (the tier-1 suite
# pins fused == unfused == dense-oracle for every low-bit mode), so
# degrading changes latency, never numerics.
# ---------------------------------------------------------------------------

_FALLBACK_CTR = obs.get_registry().counter(
    "repro_kernel_fallback_total",
    "kernel dispatch degradations by (op, mode, from_backend, "
    "to_backend); fires once per cached decision, never per call",
    labels=("op", "mode", "from_backend", "to_backend"))

# (op, mode, requested backend) -> effective backend ("oracle" = the
# materializing pure-XLA reference path, the chain's last resort).
_FB_DECISION: Dict[Tuple[str, QuantMode, str], str] = {}

_GEMM_CHAIN = {"pallas": "xla", "dense": "xla", "indexed": "xla",
               "xla": "oracle"}
_CONV_CHAIN = {"pallas": "xla", "dense": "xla", "xla": "oracle"}
_AFFINE_CHAIN = {"pallas": "xla"}   # the xla cell IS the reference


def _fallback_next(mode: QuantMode, backend: str, *,
                   conv: bool = False) -> Optional[str]:
    """Next backend in the degradation chain, or None (chain exhausted
    / mode has no chain — float modes never enter one)."""
    if mode.is_lowbit:
        chain = _CONV_CHAIN if conv else _GEMM_CHAIN
    elif mode in (QuantMode.INT8, QuantMode.INT4):
        chain = _AFFINE_CHAIN
    else:
        return None
    return chain.get(backend)


def fallback_decisions() -> Dict[Tuple[str, QuantMode, str], str]:
    """Snapshot of the cached degradation decisions (tests/triage)."""
    return dict(_FB_DECISION)


def reset_fallbacks() -> None:
    """Drop every cached degradation decision (tests; or after an
    operator fixes the underlying backend and wants retries)."""
    _FB_DECISION.clear()


def _note_fallback(op: str, mode: QuantMode, requested: str,
                   from_b: str, to_b: str, err: Exception) -> None:
    import warnings

    _FB_DECISION[(op, mode, requested)] = to_b
    _FALLBACK_CTR.inc(op=op, mode=mode.value, from_backend=from_b,
                      to_backend=to_b)
    faults.emit_event("kernel_fallback", op=op, mode=mode.value,
                      requested=requested, from_backend=from_b,
                      to_backend=to_b,
                      error=f"{type(err).__name__}: {err}")
    warnings.warn(
        f"{op} backend {from_b!r} failed for mode={mode.value} "
        f"({type(err).__name__}: {err}); degrading to {to_b!r} and "
        f"caching the decision (ops.reset_fallbacks() retries)")


def qmm_trace_count(mode: QuantMode, backend: str = DEFAULT_BACKEND) -> int:
    """Deprecated read-through alias: use
    ``obs.get_registry().get("repro_qmm_traces_total")`` directly."""
    return int(_QMM_TRACE_CTR.value(mode=mode.value, backend=backend))


@functools.partial(jax.jit,
                   static_argnames=("backend", "interpret", "tiles"))
def _qmm_jit(x, qt: QTensor, backend: str, interpret: bool,
             tiles: Optional[TileConfig] = None, act_stats=None):
    _QMM_TRACE_CTR.inc(mode=qt.mode.value, backend=backend)  # trace time only
    m, k = x.shape
    n = qt.out_features
    mode = qt.mode

    if mode in (QuantMode.F32, QuantMode.BF16):
        w = qt.payload["w"]
        y = jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)
        y = y.astype(jnp.float32)
        return y if qt.bias is None else y + qt.bias

    # One registry path for every quantized mode: bit-plane popcount /
    # dense / indexed cells for the low-bit modes, the eq. (3) affine
    # cells for u8/u4 — quantize activations, look the cell up, run the
    # fused kernel (core + eq. (2) epilogue in the same trace).
    xa = quantize_activations(x.astype(jnp.float32), mode, stats=act_stats)
    row = _as_row_scale(xa["scale"], m)
    col = _as_col_vec(qt.scale, n)
    b2 = None if qt.bias is None else _as_col_vec(qt.bias, n)
    spec = registry.lookup(mode, backend, fused=True)
    a_pl = tuple(xa[kk] for kk in _A_KEYS[mode])
    extra = {"payload": qt.payload} if spec.payload_aware else {}
    return spec.fn(a_pl, _b_planes(qt, mode), k, row, col, b2,
                   interpret=interpret, tiles=tiles, **extra)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _qmm_oracle_jit(x, qt: QTensor, interpret: bool, act_stats=None):
    """The chain's last resort: the materializing dense oracle kernel
    ((mode, "dense", fused=False) — unpack the whole payload in HBM,
    one XLA dot) + the eq. (2) epilogue in plain jnp.  No Pallas, no
    scan carrying an epilogue — bit-identical to every fused path."""
    _QMM_TRACE_CTR.inc(mode=qt.mode.value, backend="oracle")  # trace time
    m, k = x.shape
    n = qt.out_features
    mode = qt.mode
    xa = quantize_activations(x.astype(jnp.float32), mode, stats=act_stats)
    spec = registry.lookup(mode, "dense", fused=False)
    a_pl = tuple(xa[kk] for kk in _A_KEYS[mode])
    acc = spec.fn(a_pl, _b_planes(qt, mode), k, interpret=interpret)
    row = _as_row_scale(xa["scale"], m)
    col = _as_col_vec(qt.scale, n)
    b2 = None if qt.bias is None else _as_col_vec(qt.bias, n)
    return _scale_epilogue_f32(acc, row, col, b2)


def qmm(x: jnp.ndarray, qt: QTensor, *, backend: Optional[str] = None,
        interpret: bool | None = None,
        act_stats: Optional[Dict[str, Any]] = None) -> jnp.ndarray:
    """Quantized matmul: float ``x`` (m, k) against an offline-packed
    :class:`QTensor` -> float32 (m, n), in ONE jitted computation.

    Everything layer-specific — mode, logical depth, weight scale, bias,
    conv geometry — travels inside ``qt``; the only knob at the call site
    is the backend (None -> DEFAULT_BACKEND).  For the low-bit modes the
    pipeline is ternarize/binarize -> bit-plane pack -> popcount matmul ->
    per-row activation scale x per-column weight scale (+ bias):

    * ``pallas``: the scale epilogue runs inside the matmul kernel at
      ``pid_k == num_k - 1`` (``*_fused_pallas``), float32 out;
    * ``xla``: the epilogue is fused onto the final ``lax.scan`` carry
      (``*_xla_fused``);
    * ``dense``: Pallas kernel unpacks the bit-plane words to ±1/0 bf16
      tiles in VMEM and feeds the MXU, epilogue at ``pid_k == num_k-1``
      (``dense_matmul_fused_pallas``) — the dense unpack never touches
      HBM;
    * ``indexed``: per-(row, segment) subset-sum tables + per-column
      index gathers replace the popcounts (kernels/indexed_matmul.py);
      pack-time ``idx{b}_*`` payload keys are consumed zero-copy when
      present, else the indices derive in-trace from the bit planes.

    Float modes are a dense dot (+ bias); u8/u4 run the affine eq. (3)
    pipeline through the same registry (cells for "xla"/"pallas"; other
    backends fall back to the reference cell).  Numerics match the
    unfused oracle exactly: the integer core is identical and the
    epilogue uses the same multiply order.

    Parameters
    ----------
    x : jnp.ndarray
        (m, k) float activations; k must equal ``qt.k_valid``.
    qt : QTensor
        Offline-packed weights (:func:`pack_weights` /
        :meth:`QTensor.from_dense`).  Mode, depth, scale, bias —
        and, for mesh-sharded containers, the payload partitioning
        (``qt.pspec``) — all ride inside it.
    backend : str, optional
        "pallas" | "xla" | "dense" | "indexed"; None ->
        :data:`DEFAULT_BACKEND`.
    interpret : bool, optional
        Pallas interpret mode; None (default) interprets on the CPU
        backend only (``_matmul_common.resolve_interpret``).
    act_stats : dict, optional
        Overrides the per-tensor activation quantization statistics
        (see :func:`quantize_activations`) — the materializing conv
        oracle passes the shared conv stats here so it stays
        bit-identical with the fused-im2col kernels.

    Returns
    -------
    jnp.ndarray
        (m, n) float32 output, bit-identical across fused/unfused and
        sharded/unsharded dispatch for the low-bit modes.

    Inside :func:`repro.parallel.sharding.use_mesh`, a container whose
    ``pspec`` names live mesh axes dispatches to the mesh-aware path
    (:mod:`repro.parallel.qmm_mesh`): n-sharded planes run the fused
    kernel per output slice, k-sharded planes psum int16/int32 partial
    counts across devices and apply the eq. (2) epilogue after the
    reduction — outputs stay ``array_equal`` with this function's
    single-device result.
    """
    if not isinstance(qt, QTensor):
        raise TypeError(
            f"qmm expects a QTensor (use pack_weights/QTensor.from_dense, "
            f"or QTensor.from_legacy_dict for old packed dicts); got "
            f"{type(qt).__name__}")
    if x.ndim != 2:
        raise ValueError(f"qmm expects x of rank 2, got shape {x.shape}")
    if x.shape[-1] != qt.k_valid:
        raise ValueError(
            f"depth mismatch: x has k={x.shape[-1]} but QTensor was packed "
            f"with k_valid={qt.k_valid} (logical shape {qt.shape})")
    backend = backend or DEFAULT_BACKEND
    if qt.mode in (QuantMode.INT8, QuantMode.INT4):
        # Affine cells register for "xla"/"pallas" only; any other
        # backend (a policy may say "dense"/"indexed" for its low-bit
        # layers) falls back to the reference cell, preserving the old
        # anything-but-pallas -> reference behavior.
        backend = _affine_backend(qt.mode, backend, fused=True)
    requested = backend
    backend = _FB_DECISION.get(("qmm", qt.mode, requested), requested)
    _QMM_DISPATCH_CTR.inc(mode=qt.mode.value, backend=backend,
                          layout=registry.LAYOUT_GEMM)
    # a stable name for the call's ops in compiled programs and device
    # traces (metadata only: the compiled program is otherwise the same)
    with jax.named_scope(f"qmm[{qt.mode.value}]"):
        return _qmm_dispatch(x, qt, requested, backend, interpret, act_stats)


def _qmm_dispatch(x, qt: QTensor, requested: str, backend: str,
                  interpret, act_stats):
    """``qmm`` past its checks: the mesh path, or the backend chain."""
    if qt.is_lowbit:
        from repro.parallel import qmm_mesh, sharding

        ctx = sharding.active()
        if ctx is not None:
            plan = qmm_mesh.shard_plan(qt, ctx)
            if plan is not None:
                # The mesh path keeps the requested backend: the chain
                # is single-device scope and "oracle" is not a registry
                # cell the sharded kernels can consume.
                return qmm_mesh.qmm_sharded(x, qt, plan, ctx.mesh,
                                            backend=requested,
                                            interpret=interpret,
                                            act_stats=act_stats)
    while True:
        try:
            faults.maybe_raise("kernel.compile", op="qmm",
                               mode=qt.mode.value, backend=backend)
            if backend == "oracle":
                return _qmm_oracle_jit(x, qt, interpret=interpret,
                                       act_stats=act_stats)
            tiles = None
            if qt.is_lowbit or qt.mode in (QuantMode.INT8, QuantMode.INT4):
                if tune_cache.get_policy() == "on_first_use":
                    # Tune this shape before resolving, so even the very
                    # first call dispatches tuned tiles — a warm plan
                    # cache makes this a pure dict lookup per call.
                    from repro.tune import tuner
                    tuner.ensure_plan(qt.mode, backend, fused=True,
                                      m=int(x.shape[0]), n=qt.out_features,
                                      k=qt.k_valid, interpret=interpret)
                # Resolve the blocking OUTSIDE the jitted body and pass
                # it as a static argument: the plan is part of the jit
                # cache key, so a plan-cache update retraces (tuned
                # tiles really take effect) while a stable plan keeps
                # hitting one trace per shape.
                tiles = tune_cache.plan_for(qt.mode, backend, fused=True,
                                            m=int(x.shape[0]),
                                            n=qt.out_features,
                                            k=qt.k_valid).tiles
            return _qmm_jit(x, qt, backend=backend, interpret=interpret,
                            tiles=tiles, act_stats=act_stats)
        except faults.InjectedFault as e:
            nxt = _fallback_next(qt.mode, backend)
            if nxt is None:
                raise
            _note_fallback("qmm", qt.mode, requested, backend, nxt, e)
            backend = nxt


# ---------------------------------------------------------------------------
# qconv — packed conv through the fused-im2col kernels (layout
# "im2col_fused" in the registry): the patch matrix is never materialized
# ---------------------------------------------------------------------------

_QCONV_TRACE_CTR = obs.get_registry().counter(
    "repro_qconv_traces_total",
    "qconv retraces by (mode, backend); counts at jax trace time",
    labels=("mode", "backend"), always=True)

_QCONV_DISPATCH_CTR = obs.get_registry().counter(
    "repro_qconv_dispatch_total",
    "qconv host-side dispatches by (mode, backend, layout)",
    labels=("mode", "backend", "layout"))


def qconv_trace_count(mode: QuantMode, backend: str = DEFAULT_BACKEND) -> int:
    """Deprecated read-through alias: use
    ``obs.get_registry().get("repro_qconv_traces_total")`` directly."""
    return int(_QCONV_TRACE_CTR.value(mode=mode.value, backend=backend))


def has_conv_kernel(mode: QuantMode, backend: str) -> bool:
    """True when a fused-im2col conv kernel is registered for (mode,
    backend) — what conv2d_packed's auto-dispatch consults."""
    return registry.has(mode, backend, fused=True,
                        layout=registry.LAYOUT_IM2COL)


@functools.partial(jax.jit,
                   static_argnames=("backend", "stride", "padding",
                                    "interpret", "tiles"))
def _qconv_jit(x, qt: QTensor, act_stats, backend: str, stride: int,
               padding: str, interpret: bool,
               tiles: Optional[TileConfig] = None):
    _QCONV_TRACE_CTR.inc(mode=qt.mode.value, backend=backend)  # trace time
    spec = registry.lookup(qt.mode, backend, fused=True,
                           layout=registry.LAYOUT_IM2COL)
    cout = qt.geometry[3]
    col = _as_col_vec(qt.scale, cout)
    b2 = None if qt.bias is None else _as_col_vec(qt.bias, cout)
    # Weight planes in the per-patch-position layout every conv kernel
    # streams: zero-copy from the pack-time positional payload (or the
    # contiguous payload when Cin is a word multiple); only legacy
    # containers fall back to an in-trace repack.
    return spec.fn(x.astype(jnp.float32), _conv_fused.conv_weight_planes(qt),
                   qt.geometry, stride, padding, act_stats, col, b2,
                   interpret=interpret, tiles=tiles)


@functools.partial(jax.jit,
                   static_argnames=("stride", "padding", "interpret"))
def _qconv_oracle_jit(x, qt: QTensor, act_stats, stride: int, padding: str,
                      interpret: bool):
    """Conv chain last resort: materialize the im2col patch matrix and
    run the gemm oracle on it — bit-identical to the fused-im2col
    kernels (per-tensor quantization commutes with patch gathering)."""
    from repro.core.conv import im2col   # lazy: core.conv imports ops

    _QCONV_TRACE_CTR.inc(mode=qt.mode.value, backend="oracle")  # trace time
    kh, kw_, cin, cout = qt.geometry
    patches, (b, oh, ow) = im2col(x.astype(jnp.float32), kh, kw_,
                                  stride, padding)
    y = _qmm_oracle_jit(patches, qt, interpret=interpret,
                        act_stats=act_stats)
    return y.reshape(b, oh, ow, cout)


def qconv(x: jnp.ndarray, qt: QTensor, *, stride: int = 1,
          padding: str = "SAME", backend: Optional[str] = None,
          interpret: bool | None = None,
          act_stats: Optional[Dict[str, Any]] = None) -> jnp.ndarray:
    """Fused-im2col packed conv: float ``x`` (B, H, W, Cin) against a
    conv QTensor (``pack_conv_filters``) -> float32 (B, OH, OW, Cout) in
    ONE jitted computation that never materializes the im2col patch
    matrix — the kernels compute patch coordinates in their A-operand
    load path and quantize/pack activation tiles on the fly.

    Bit-identical to the materializing oracle (``im2col`` +
    :func:`qmm` with the same ``act_stats``): per-tensor quantization
    commutes with patch gathering, the popcount core sums the same
    integers, and the epilogue uses the same multiply order.

    Parameters
    ----------
    x : jnp.ndarray
        (B, H, W, Cin) float input image, NHWC; Cin must match the
        container's geometry.
    qt : QTensor
        Conv-packed low-bit weights (``pack_conv_filters``) carrying
        the (kh, kw, cin, cout) ``geometry`` aux and, when ``cin`` is
        not a word multiple, the positional planes the kernels stream.
    stride : int
        Spatial stride (same for both dims).
    padding : str
        "SAME" or "VALID".
    backend : str, optional
        "pallas" | "xla" | "dense"; None -> :data:`DEFAULT_BACKEND`.
        The fused-im2col kernel for (mode, backend) must be registered
        (:func:`has_conv_kernel`).
    interpret : bool, optional
        Pallas interpret mode; None (default) interprets on the CPU
        backend only (``_matmul_common.resolve_interpret``).
    act_stats : dict, optional
        Pre-computed shared activation statistics
        (``conv_fused.conv_act_stats``); None derives them from ``x``.

    Returns
    -------
    jnp.ndarray
        (B, OH, OW, Cout) float32 feature map.

    Inside :func:`repro.parallel.sharding.use_mesh`, a container whose
    ``pspec`` names a live mesh axis for cout runs one fused-im2col
    kernel per output-channel slice (replicated input, no collective;
    :mod:`repro.parallel.qmm_mesh`), ``array_equal`` with the
    single-device result.
    """
    if not isinstance(qt, QTensor):
        raise TypeError(f"qconv expects a QTensor, got {type(qt).__name__}")
    if qt.geometry is None:
        raise ValueError("qconv needs a QTensor packed with "
                         "pack_conv_filters (geometry aux missing)")
    if not qt.is_lowbit:
        raise ValueError(f"qconv only handles low-bit modes, got {qt.mode}")
    if x.ndim != 4:
        raise ValueError(f"qconv expects x of rank 4 (B, H, W, Cin), got "
                         f"shape {x.shape}")
    kh, kw_, cin, _ = qt.geometry
    if x.shape[-1] != cin:
        raise ValueError(f"channel mismatch: x has Cin={x.shape[-1]} but "
                         f"QTensor geometry is {qt.geometry}")
    backend = backend or DEFAULT_BACKEND
    requested = backend
    backend = _FB_DECISION.get(("qconv", qt.mode, requested), requested)
    _QCONV_DISPATCH_CTR.inc(mode=qt.mode.value, backend=backend,
                            layout=registry.LAYOUT_IM2COL)
    # the activation statistics and the kernel under one stable name
    # (metadata only, as in qmm)
    with jax.named_scope(f"qconv[{qt.mode.value}]"):
        return _qconv_dispatch(x, qt, stride, padding, requested, backend,
                               interpret, act_stats)


def _qconv_dispatch(x, qt: QTensor, stride: int, padding: str,
                    requested: str, backend: str, interpret, act_stats):
    """``qconv`` past its checks: statistics, then the mesh path or the
    backend chain."""
    from repro.kernels import conv_fused

    kh, kw_ = qt.geometry[:2]
    if act_stats is None:
        act_stats = conv_fused.conv_act_stats(x, qt.mode, kh, kw_,
                                              stride, padding)
    from repro.parallel import qmm_mesh, sharding

    ctx = sharding.active()
    if ctx is not None:
        plan = qmm_mesh.shard_plan_conv(qt, ctx)
        if plan is not None:
            # Mesh path keeps the requested backend (chain is
            # single-device scope, see qmm).
            return qmm_mesh.qconv_sharded(x, qt, plan, ctx.mesh, act_stats,
                                          backend=requested, stride=stride,
                                          padding=padding,
                                          interpret=interpret)
    m, n, k, tag = conv_fused.conv_problem_dims(x.shape, qt.geometry,
                                                stride, padding)
    while True:
        try:
            faults.maybe_raise("kernel.compile", op="qconv",
                               mode=qt.mode.value, backend=backend)
            if backend == "oracle":
                return _qconv_oracle_jit(x, qt, act_stats, stride=stride,
                                         padding=padding,
                                         interpret=interpret)
            if tune_cache.get_policy() == "on_first_use":
                from repro.tune import tuner
                tuner.ensure_plan(qt.mode, backend, fused=True,
                                  interpret=interpret,
                                  conv=tuner.ConvProblem.from_input(
                                      x.shape, qt.geometry, stride, padding))
            # Like qmm: resolve the plan OUTSIDE the jitted body and
            # pass the tiles as a static argument, so a plan-cache
            # update retraces while a stable plan keeps hitting one
            # trace per conv geometry.
            tiles = tune_cache.plan_for(qt.mode, backend, fused=True,
                                        m=m, n=n, k=k,
                                        layout=registry.LAYOUT_IM2COL,
                                        geom=tag).tiles
            return _qconv_jit(x, qt, act_stats, backend=backend,
                              stride=stride, padding=padding,
                              interpret=interpret, tiles=tiles)
        except faults.InjectedFault as e:
            nxt = _fallback_next(qt.mode, backend, conv=True)
            if nxt is None:
                raise
            _note_fallback("qconv", qt.mode, requested, backend, nxt, e)
            backend = nxt


def fused_qmm(x: jnp.ndarray, wb, mode: Optional[QuantMode] = None,
              bias: Optional[jnp.ndarray] = None, *,
              backend: str = DEFAULT_BACKEND,
              interpret: bool | None = None) -> jnp.ndarray:
    """DEPRECATED legacy shim for the pre-QTensor API — call
    ``qmm(x, qt)`` directly (``QTensor.from_legacy_dict`` migrates old
    packed dicts).  Kept for one release; emits a DeprecationWarning and
    delegates to :func:`qmm`."""
    import warnings

    warnings.warn(
        "ops.fused_qmm is deprecated and will be removed in the next "
        "release: call ops.qmm(x, qt) with a QTensor "
        "(QTensor.from_legacy_dict migrates legacy packed dicts)",
        DeprecationWarning, stacklevel=2)
    if isinstance(wb, QTensor):
        qt = wb
        if mode is not None and mode != qt.mode:
            raise ValueError(f"mode mismatch: {mode} vs QTensor {qt.mode}")
    else:
        if mode is None:
            raise ValueError("legacy dict input needs an explicit mode")
        if not mode.is_lowbit:
            raise ValueError(f"fused_qmm only handles low-bit modes, got {mode}")
        qt = QTensor.from_legacy_dict(wb, mode, k_valid=x.shape[-1])
    if bias is not None:
        qt = qt.replace(bias=bias)
    return qmm(x, qt, backend=backend, interpret=interpret)


# ---------------------------------------------------------------------------
# Float-facing quantized matmul with STE gradients (QAT)
# ---------------------------------------------------------------------------

def _qmm_fwd_value(x, w, mode: QuantMode, backend: str, interpret: bool):
    if mode == QuantMode.F32:
        return jnp.dot(x, w)
    if mode == QuantMode.BF16:
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    # Every quantized mode rides the fused registry pipeline: quantize
    # -> pack -> core (popcount / indexed / eq. (3) affine) -> eq. (2)
    # scale in one trace (weights are re-packed per call in QAT;
    # inference should pack once and call qmm directly).
    qt = QTensor.from_dense(w, mode)
    return qmm(x, qt, backend=backend, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def quantized_matmul(x, w, mode: QuantMode = QuantMode.TNN,
                     backend: str = DEFAULT_BACKEND, interpret: bool | None = None):
    """y ~= x @ w computed through the selected quantized pipeline.

    Gradients are straight-through at matmul granularity (standard for
    BNN/TNN QAT): backward treats the whole pipeline as ``x @ w``, with a
    hard-tanh clip mask on x for the binary/ternary modes (XNOR-Net).
    """
    return _qmm_fwd_value(x, w, mode, backend, interpret)


def _qmm_fwd(x, w, mode, backend, interpret):
    y = _qmm_fwd_value(x, w, mode, backend, interpret)
    return y, (x, w)


def _qmm_bwd(mode, backend, interpret, res, g):
    x, w = res
    g = g.astype(jnp.float32)
    gx = jnp.dot(g, w.T.astype(jnp.float32))
    gw = jnp.dot(x.T.astype(jnp.float32), g)
    if mode.is_lowbit:
        gx = gx * (jnp.abs(x) <= 1.0)      # clip-range STE
    return gx.astype(x.dtype), gw.astype(w.dtype)


quantized_matmul.defvjp(_qmm_fwd, _qmm_bwd)


def lowbit_matmul(a: jnp.ndarray, b: jnp.ndarray, mode: QuantMode, *,
                  backend: str = DEFAULT_BACKEND,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Exact integer matmul of {-1,0,1}-valued dense matrices through the
    packed pipeline (test/bench entry; no scales)."""
    k = a.shape[-1]
    if mode == QuantMode.BNN:
        xa = {"bits": encoding.pack_binary(a)}
        wb = {"bits": encoding.pack_binary(b.T)}
    elif mode == QuantMode.TNN:
        p, m_ = encoding.pack_ternary(a)
        wp, wm = encoding.pack_ternary(b.T)
        xa = {"plus": p, "minus": m_}
        wb = {"plus": wp, "minus": wm}
    elif mode == QuantMode.TBN:
        p, m_ = encoding.pack_ternary(a)
        xa = {"plus": p, "minus": m_}
        wb = {"bits": encoding.pack_binary(b.T)}
    else:
        raise ValueError(mode)
    qt = QTensor(payload=wb, scale=None, mode=mode,
                 shape=(int(k), int(b.shape[-1])))
    return packed_matmul(xa, qt, backend=backend, interpret=interpret)
