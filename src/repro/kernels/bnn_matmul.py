"""Binary (BNN) matmul Pallas kernel — paper §III-B adapted to TPU.

ARM original: 16x8 microkernel; per k-step load one 8-bit column strip of
A (two 128-bit regs) and one 8-bit row strip of B (64-bit reg), EOR + CNT
+ SADDW into 16 int16 accumulators.

TPU version: (block_m x block_n) int32 VMEM accumulator; per inner step
XOR a (bm, 1, wc) uint32 slice of A against a (1, bn, wc) slice of B,
popcount on the VPU, reduce the wc axis.  eq. (6) finalization
``c = k_valid - 2 * sum(popcount)`` happens on the last k grid step.

``bnn_matmul_fused_pallas`` additionally applies the eq. (2) scale
epilogue (per-row activation scale x per-column weight scale, optional
bias) inside the same kernel invocation, emitting float32 directly — the
int32 accumulator never round-trips through HBM.  The float accumulator
is exact: every partial popcount sum is an integer <= k_valid < 2^24.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._matmul_common import (
    DEFAULT_TILES,
    lowbit_matmul_call,
    chunked_reduce,
    popcount_i32,
    scale_epilogue,
)

_TILES = DEFAULT_TILES["bnn"]

__all__ = ["bnn_matmul_pallas", "bnn_matmul_fused_pallas"]


def _bnn_product(a_sl, b_sl):
    x = jnp.bitwise_xor(a_sl[0], b_sl[0])
    return popcount_i32(x)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_valid", "block_m", "block_n", "block_kw", "word_chunk", "interpret",
    ),
)
def bnn_matmul_pallas(
    a_bits: jnp.ndarray,       # (m, kw) uint32
    b_bits_t: jnp.ndarray,     # (n, kw) uint32
    k_valid: int,
    *,
    block_m: int = _TILES.block_m,
    block_n: int = _TILES.block_n,
    block_kw: int = _TILES.block_kw,
    word_chunk: int = _TILES.word_chunk,
    interpret: bool | None = None,
) -> jnp.ndarray:

    def body(pid_k, num_k, a_refs, b_refs, r_refs, c_refs, o_ref):
        @pl.when(pid_k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        acc = chunked_reduce(a_refs, b_refs, _bnn_product,
                             word_chunk=word_chunk, acc_dtype=jnp.int32)
        o_ref[...] += acc

        @pl.when(pid_k == num_k - 1)
        def _finalize():
            o_ref[...] = jnp.int32(k_valid) - 2 * o_ref[...]

    return lowbit_matmul_call(
        body, [a_bits], [b_bits_t],
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_valid", "block_m", "block_n", "block_kw", "word_chunk", "interpret",
    ),
)
def bnn_matmul_fused_pallas(
    a_bits: jnp.ndarray,       # (m, kw) uint32
    b_bits_t: jnp.ndarray,     # (n, kw) uint32
    k_valid: int,
    row_scale: jnp.ndarray,    # (m, 1) float32
    col_scale: jnp.ndarray,    # (1, n) float32
    bias: jnp.ndarray | None = None,   # (1, n) float32
    *,
    block_m: int = _TILES.block_m,
    block_n: int = _TILES.block_n,
    block_kw: int = _TILES.block_kw,
    word_chunk: int = _TILES.word_chunk,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """eq. (6) + eq. (2) in one pass: float32 (m, n) output."""

    def body(pid_k, num_k, a_refs, b_refs, r_refs, c_refs, o_ref):
        @pl.when(pid_k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        acc = chunked_reduce(a_refs, b_refs, _bnn_product,
                             word_chunk=word_chunk, acc_dtype=jnp.int32)
        o_ref[...] += acc.astype(jnp.float32)

        @pl.when(pid_k == num_k - 1)
        def _finalize():
            val = jnp.float32(k_valid) - 2.0 * o_ref[...]
            o_ref[...] = scale_epilogue(val, r_refs, c_refs)

    cols = [col_scale] if bias is None else [col_scale, bias]
    return lowbit_matmul_call(
        body, [a_bits], [b_bits_t],
        row_operands=[row_scale], col_operands=cols,
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        interpret=interpret,
        acc_dtype=jnp.float32,
    )
