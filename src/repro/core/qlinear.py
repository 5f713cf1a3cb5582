"""QuantLinear — the projection primitive backing every model in this repo.

Two regimes, matching how low-bit networks are actually deployed:

* **QAT / training**: parameters are fp32 master weights; the forward pass
  quantizes weights *and* activations on the fly and runs the low-bit
  pipeline with straight-through gradients (ops.quantized_matmul).  This is
  the standard BNN/TNN/TBN training setup ([21],[25],[28]).

* **Packed inference**: ``pack()`` converts master weights into a
  :class:`~repro.kernels.qtensor.QTensor` once, offline — the paper's
  Algorithm 2 PackedB, with mode / depth / scale / bias riding inside
  the container.  ``apply_packed`` is then a single ``ops.qmm`` call:
  runtime activation quantization, the integer core and the scale/bias
  epilogue execute as one jitted computation for EVERY mode (low-bit
  popcount, u8/u4 affine, float passthrough).  Packed weights are 16x
  (binary) / 8x (ternary) smaller than bf16, which is the technique's
  headline win for weight-streaming-bound decode on TPU.

The overflow guard of eq. (4)/(5) is enforced here: in int16-fidelity
mode a reduction deeper than k_max is a configuration error.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core import quantize
from repro.kernels import ops
from repro.kernels.modes import DEFAULT_BACKEND, QuantMode
from repro.kernels.qtensor import QTensor

__all__ = ["QuantLinear", "linear_init", "linear_apply"]


def _flatten_leading(x: jnp.ndarray):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


@dataclasses.dataclass(frozen=True)
class QuantLinear:
    d_in: int
    d_out: int
    mode: QuantMode = QuantMode.BF16
    use_bias: bool = False
    backend: str = DEFAULT_BACKEND
    # int16-fidelity accumulation (the paper's register width).  Purely a
    # validation mode; the TPU kernels accumulate in int32.
    paper_accum_i16: bool = False

    def __post_init__(self):
        if self.paper_accum_i16 and self.mode.is_lowbit:
            kmax = quantize.k_max(1, 16, signed_unit=True)
            if self.d_in > kmax:
                raise ValueError(
                    f"d_in={self.d_in} exceeds k_max={kmax} for 16-bit "
                    f"accumulation (paper eq. (4)); shrink the layer or "
                    f"use int32 accumulation")

    # -- parameters ---------------------------------------------------------

    def init(self, key, dtype=jnp.float32) -> Dict[str, Any]:
        std = (2.0 / (self.d_in + self.d_out)) ** 0.5
        p = {"w": (jax.random.normal(key, (self.d_in, self.d_out)) * std).astype(dtype)}
        if self.use_bias:
            p["b"] = jnp.zeros((self.d_out,), dtype)
        return p

    # -- QAT / training forward --------------------------------------------

    def apply(self, params: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
        x2, lead = _flatten_leading(x)
        w = params["w"]
        if self.mode == QuantMode.BF16:
            y = jnp.dot(x2.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        elif self.mode == QuantMode.F32:
            y = jnp.dot(x2.astype(jnp.float32), w.astype(jnp.float32))
        else:
            y = ops.quantized_matmul(x2, w.astype(jnp.float32), self.mode,
                                     self.backend)
        if self.use_bias:
            y = y + params["b"]
        return y.reshape(*lead, self.d_out).astype(x.dtype)

    # -- packed inference ----------------------------------------------------

    def pack(self, params: Dict[str, Any]) -> QTensor:
        """Master weights -> QTensor (Algorithm 2; bias travels inside)."""
        return QTensor.from_dense(
            params["w"].astype(jnp.float32), self.mode,
            bias=params["b"] if self.use_bias else None)

    def apply_packed(self, packed: QTensor, x: jnp.ndarray) -> jnp.ndarray:
        # One fused call for every mode: quantize -> core -> scale/bias —
        # mode, depth, scale and bias all come from the QTensor, so the
        # epilogue runs inside the kernel/trace instead of a separate
        # int32 -> float32 broadcast pass.
        x2, lead = _flatten_leading(x)
        y = ops.qmm(x2.astype(jnp.float32), packed, backend=self.backend)
        return y.reshape(*lead, self.d_out).astype(x.dtype)


# Convenience functional forms used by the model code -----------------------

def linear_init(key, d_in: int, d_out: int, dtype=jnp.float32):
    return QuantLinear(d_in, d_out).init(key, dtype)


def linear_apply(params, x, mode: QuantMode = QuantMode.BF16,
                 backend: str = DEFAULT_BACKEND):
    d_in, d_out = params["w"].shape
    layer = QuantLinear(d_in, d_out, mode=mode,
                        use_bias="b" in params, backend=backend)
    return layer.apply(params, x)
