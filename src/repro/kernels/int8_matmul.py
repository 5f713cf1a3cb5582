"""u8 (gemmlowp-style) matmul Pallas kernel — the paper's U8 baseline.

ARM original: UMLAL/UMLAL2 8-bit multiply-accumulate into 32-bit lanes.
TPU version: the MXU natively does int8 x int8 -> int32, so the kernel is
a standard tiled matmul of int8 operands with
``preferred_element_type=int32``.  gemmlowp's unsigned operands arrive
shifted by -128 (ops.py folds the shift into the zero points).  The
zero-point correction terms of eq. (3) are rank-1 and O(mk)/O(nk); they
are applied *outside* the kernel (ops.py), exactly mirroring gemmlowp's
output pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._matmul_common import ceil_to, pad2d, resolve_interpret

__all__ = ["int8_matmul_pallas"]


def int8_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(bm, bk) @ (bk, bn) of int8-range int32 values on the int8 MXU,
    int32 accumulation (the MXU takes no int32 operands)."""
    return jax.lax.dot_general(
        a.astype(jnp.int8), b.astype(jnp.int8), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def int8_matmul_pallas(
    a_q: jnp.ndarray,   # (m, k) int8 (quantized values)
    b_q: jnp.ndarray,   # (k, n) int8
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Raw accumulator A_q @ B_q in int32 (first term of eq. (3)); the
    zero padding of k adds nothing."""
    m, k = a_q.shape
    _, n = b_q.shape
    block_k = min(block_k, max(128, k))

    mp, np_, kp = ceil_to(m, block_m), ceil_to(n, block_n), ceil_to(k, block_k)
    a_p = pad2d(a_q, mp, kp)
    b_p = pad2d(b_q, kp, np_)

    grid = (mp // block_m, np_ // block_n, kp // block_k)
    if a_q.dtype != jnp.int8 or b_q.dtype != jnp.int8:
        raise TypeError(f"int8_matmul_pallas takes int8 operands, got "
                        f"{a_q.dtype} and {b_q.dtype}")

    def kernel(a_ref, b_ref, o_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += int8_dot(a_ref[...], b_ref[...])

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_k, block_n), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a_p, b_p)
    return out[:m, :n]
