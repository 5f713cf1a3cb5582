"""Ternary (TNN) matmul Pallas kernel — paper §III-C adapted to TPU.

ARM original: A packed as interleaved (plus, minus) 8-row bit strips, two
128-bit regs per k-step; products via AND/OR, CNT popcounts per plane,
SSUBL difference, ADD accumulate.

TPU version: the two planes are separate uint32 operands (the paper's
interleaving is a register-feeding trick; on TPU the BlockSpec pipeline
streams both planes independently).  Per inner step:

    z+ = (a+ & b+) | (a- & b-)
    z- = (a+ & b-) | (a- & b+)
    acc += popcount(z+) - popcount(z-)        (eq. 7)

Pad words are (0,0) == ternary zero, so no k correction is needed.

``tnn_matmul_fused_pallas`` folds the eq. (2) scale epilogue (per-row
activation scale x per-column weight scale, optional bias) into the last
k grid step and emits float32 directly.  Exact: every partial sum is an
integer of magnitude <= k_valid < 2^24, representable in float32.
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

_TILES = DEFAULT_TILES["tnn"]

__all__ = ["tnn_matmul_pallas", "tnn_matmul_fused_pallas"]


def _tnn_product(a_sl, b_sl):
    ap, am = a_sl
    bp, bm = b_sl
    zp = (ap & bp) | (am & bm)
    zm = (ap & bm) | (am & bp)
    return popcount_i32(zp) - popcount_i32(zm)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_valid", "block_m", "block_n", "block_kw", "word_chunk", "interpret",
    ),
)
def tnn_matmul_pallas(
    a_plus: jnp.ndarray, a_minus: jnp.ndarray,     # (m, kw) uint32
    b_plus_t: jnp.ndarray, b_minus_t: jnp.ndarray,  # (n, kw) uint32
    k_valid: int = 0,
    *,
    block_m: int = _TILES.block_m,
    block_n: int = _TILES.block_n,
    block_kw: int = _TILES.block_kw,
    word_chunk: int = _TILES.word_chunk,
    interpret: bool | None = None,
) -> jnp.ndarray:
    del k_valid  # exact without correction; kept for a uniform signature

    def body(pid_k, num_k, a_refs, b_refs, r_refs, c_refs, o_ref):
        @pl.when(pid_k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += chunked_reduce(a_refs, b_refs, _tnn_product,
                                     word_chunk=word_chunk,
                                     acc_dtype=jnp.int32)

    return lowbit_matmul_call(
        body, [a_plus, a_minus], [b_plus_t, b_minus_t],
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_valid", "block_m", "block_n", "block_kw", "word_chunk", "interpret",
    ),
)
def tnn_matmul_fused_pallas(
    a_plus: jnp.ndarray, a_minus: jnp.ndarray,      # (m, kw) uint32
    b_plus_t: jnp.ndarray, b_minus_t: jnp.ndarray,  # (n, kw) uint32
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
    """eq. (7) + eq. (2) in one pass: float32 (m, n) output."""
    del k_valid  # exact without correction; kept for a uniform signature

    def body(pid_k, num_k, a_refs, b_refs, r_refs, c_refs, o_ref):
        @pl.when(pid_k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        acc = chunked_reduce(a_refs, b_refs, _tnn_product,
                             word_chunk=word_chunk, acc_dtype=jnp.int32)
        o_ref[...] += acc.astype(jnp.float32)

        @pl.when(pid_k == num_k - 1)
        def _finalize():
            o_ref[...] = scale_epilogue(o_ref[...], r_refs, c_refs)

    cols = [col_scale] if bias is None else [col_scale, bias]
    return lowbit_matmul_call(
        body, [a_plus, a_minus], [b_plus_t, b_minus_t],
        row_operands=[row_scale], col_operands=cols,
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        interpret=interpret,
        acc_dtype=jnp.float32,
    )
