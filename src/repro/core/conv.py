"""GeMM-based convolution — the paper's CNN deployment path (§I, §II).

``im2col`` unrolls the feature map so a conv becomes C = A @ B with
A = patches (B*OH*OW, Hk*Wk*Cin) and B = filters (Hk*Wk*Cin, Cout); the
low-bit GeMM kernels then apply unchanged.  This is exactly how the paper
runs TNN/TBN/BNN conv layers on ARM, and eq. (5)'s input-channel bound is
enforced here for the int16-fidelity mode.

Two regimes, mirroring core/qlinear.py:

* ``conv2d_quantized`` — QAT/training forward (on-the-fly quantization,
  STE gradients; the low-bit forward itself rides the fused pipeline via
  ``ops.quantized_matmul``);
* ``pack_conv_filters`` + ``conv2d_packed`` — deployment: filters are
  bit-plane packed once, offline, into a :class:`QTensor` whose
  ``geometry`` aux records (kh, kw, cin, cout).  Each conv then
  dispatches to a fused-im2col kernel (``ops.qconv``, registry layout
  ``im2col_fused``) when one is registered for (mode, backend) — patch
  extraction folds into the kernel's A-operand load path and the patch
  matrix never exists in HBM.  ``fused=False`` forces the materializing
  path (im2col + ONE fused ``ops.qmm`` call), which is kept as the
  bit-exact correctness oracle: both paths quantize with the same
  scalar statistics (``conv_fused.conv_act_stats``).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quantize
from repro.kernels import ops
from repro.kernels.conv_fused import conv_act_stats, conv_spatial_pad
from repro.kernels.modes import DEFAULT_BACKEND, QuantMode
from repro.kernels.qtensor import QTensor

__all__ = ["im2col", "conv2d_quantized", "check_conv_depth",
           "pack_conv_filters", "conv2d_packed"]


def im2col(x: jnp.ndarray, kh: int, kw: int, stride: int = 1,
           padding: str = "SAME") -> Tuple[jnp.ndarray, Tuple[int, int, int]]:
    """x (B, H, W, C) -> (B*OH*OW, kh*kw*C), plus (B, OH, OW).

    Built from kh*kw static slices (differentiable, fusion-friendly); the
    column order is (dy, dx, c), matching the filter reshape below.
    Spatial padding comes from ``conv_fused.conv_spatial_pad`` — the same
    helper the fused-im2col kernels use, so the two paths can never
    disagree about the patch grid.
    """
    b, _, _, c = x.shape
    x, (oh, ow) = conv_spatial_pad(x, kh, kw, stride, padding)

    cols = []
    for dy in range(kh):
        for dx in range(kw):
            patch = jax.lax.slice(
                x, (0, dy, dx, 0),
                (b, dy + (oh - 1) * stride + 1, dx + (ow - 1) * stride + 1, c),
                (1, stride, stride, 1))
            cols.append(patch)                       # (B, OH, OW, C)
    patches = jnp.concatenate(cols, axis=-1)          # (B, OH, OW, kh*kw*C)
    return patches.reshape(b * oh * ow, kh * kw * c), (b, oh, ow)


def check_conv_depth(c_in: int, kh: int, kw: int, *, accum_bits: int = 16,
                     lowbit: bool = True) -> None:
    """Raise if the GeMM depth would overflow the paper's accumulator
    (eq. (4)-(5)).  Only binding for the int16-fidelity configuration."""
    kmax = quantize.k_max(1 if lowbit else 8, accum_bits, signed_unit=lowbit)
    if c_in * kh * kw > kmax:
        raise ValueError(
            f"conv depth {c_in}*{kh}*{kw} = {c_in * kh * kw} exceeds "
            f"k_max={kmax} for {accum_bits}-bit accumulation (paper eq. (5))")


def conv2d_quantized(x: jnp.ndarray, filters: jnp.ndarray,
                     mode: QuantMode = QuantMode.TNN, *,
                     stride: int = 1, padding: str = "SAME",
                     backend: str = DEFAULT_BACKEND,
                     paper_accum_i16: bool = False) -> jnp.ndarray:
    """Quantized conv: x (B,H,W,Cin), filters (kh,kw,Cin,Cout) fp master.

    Forward = im2col + quantized GeMM (with STE grads), i.e. the paper's
    deployment recipe verbatim.
    """
    kh, kw, cin, cout = filters.shape
    if paper_accum_i16 and mode.is_lowbit:
        check_conv_depth(cin, kh, kw)
    a, (b, oh, ow) = im2col(x, kh, kw, stride, padding)
    w2 = filters.reshape(kh * kw * cin, cout)
    if mode in (QuantMode.F32, QuantMode.BF16):
        y = jnp.dot(a, w2)
    else:
        y = ops.quantized_matmul(a, w2, mode, backend)
    return y.reshape(b, oh, ow, cout)


# ---------------------------------------------------------------------------
# Packed (deployment) conv: pack filters once, fused GeMM per call
# ---------------------------------------------------------------------------

def pack_conv_filters(filters: jnp.ndarray, mode: QuantMode,
                      bias: Optional[jnp.ndarray] = None) -> QTensor:
    """Offline filter packing (Algorithm 2's PackedB for conv layers).

    ``filters`` (kh, kw, cin, cout) float -> :class:`QTensor` whose
    ``geometry`` aux carries the static shape needed to rebuild the
    im2col GeMM at apply time (no per-call dict surgery).
    """
    if not mode.is_lowbit:
        raise ValueError(f"pack_conv_filters only handles low-bit modes, "
                         f"got {mode}")
    kh, kw, cin, cout = filters.shape
    w2 = filters.reshape(kh * kw * cin, cout).astype(jnp.float32)
    return QTensor.from_dense(w2, mode, bias=bias,
                              geometry=(kh, kw, cin, cout))


def conv2d_packed(x: jnp.ndarray, packed: QTensor, *,
                  stride: int = 1, padding: str = "SAME",
                  backend: str = DEFAULT_BACKEND,
                  paper_accum_i16: bool = False,
                  fused: Optional[bool] = None) -> jnp.ndarray:
    """Deployment conv.  ``packed`` comes from :func:`pack_conv_filters`;
    mode, depth, scale, bias and geometry all ride inside it — repeated
    calls with the same QTensor hit the same jit cache entry (no
    retrace, no container rebuild).

    ``fused=None`` (default) dispatches to the fused-im2col kernel
    (``ops.qconv``) whenever one is registered for (mode, backend): the
    patch matrix is never materialized.  ``fused=False`` forces the
    materializing oracle — im2col + ONE fused ``ops.qmm`` call — whose
    output is bit-identical to the fused path (both quantize with the
    shared ``conv_act_stats`` scalars).
    """
    if packed.geometry is None:
        raise ValueError("conv2d_packed needs a QTensor packed with "
                         "pack_conv_filters (geometry aux missing)")
    kh, kw, cin, cout = packed.geometry
    if paper_accum_i16:
        check_conv_depth(cin, kh, kw)
    if fused is None:
        fused = packed.is_lowbit and ops.has_conv_kernel(packed.mode, backend)
    if fused:
        y = ops.qconv(x, packed, stride=stride, padding=padding,
                      backend=backend)
        return y.astype(x.dtype)
    stats = None
    if packed.is_lowbit:
        stats = conv_act_stats(x.astype(jnp.float32), packed.mode, kh, kw,
                               stride, padding)
    a, (b, oh, ow) = im2col(x.astype(jnp.float32), kh, kw, stride, padding)
    y = ops.qmm(a, packed, backend=backend, act_stats=stats)
    return y.reshape(b, oh, ow, cout).astype(x.dtype)
