"""Ternary-binary (TBN) matmul Pallas kernel — paper §III-D adapted to TPU.

A is ternary (two planes, packed like TNN); B is binary (one plane,
packed like BNN).  Products use the OR/AND/ORN identities of Table I:

    z+ = (a+ | b) & (a- | ~b)
    z- = (a+ | ~b) & (a- | b)
    acc += popcount(z+) - popcount(z-)

A's pad words are (0,0) which force z+ == z- == 0 regardless of B's pad
bits, so the result is exact with no k correction.

``tbn_matmul_fused_pallas`` folds the eq. (2) scale epilogue (per-row
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

_TILES = DEFAULT_TILES["tbn"]

__all__ = ["tbn_matmul_pallas", "tbn_matmul_fused_pallas"]


def _tbn_product(a_sl, b_sl):
    ap, am = a_sl
    (bb,) = b_sl
    nbb = jnp.bitwise_not(bb)
    zp = (ap | bb) & (am | nbb)
    zm = (ap | nbb) & (am | bb)
    return popcount_i32(zp) - popcount_i32(zm)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_valid", "block_m", "block_n", "block_kw", "word_chunk", "interpret",
    ),
)
def tbn_matmul_pallas(
    a_plus: jnp.ndarray, a_minus: jnp.ndarray,   # (m, kw) uint32
    b_bits_t: jnp.ndarray,                       # (n, kw) uint32
    k_valid: int = 0,
    *,
    block_m: int = _TILES.block_m,
    block_n: int = _TILES.block_n,
    block_kw: int = _TILES.block_kw,
    word_chunk: int = _TILES.word_chunk,
    interpret: bool | None = None,
) -> jnp.ndarray:
    del k_valid

    def body(pid_k, num_k, a_refs, b_refs, r_refs, c_refs, o_ref):
        @pl.when(pid_k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += chunked_reduce(a_refs, b_refs, _tbn_product,
                                     word_chunk=word_chunk,
                                     acc_dtype=jnp.int32)

    return lowbit_matmul_call(
        body, [a_plus, a_minus], [b_bits_t],
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_valid", "block_m", "block_n", "block_kw", "word_chunk", "interpret",
    ),
)
def tbn_matmul_fused_pallas(
    a_plus: jnp.ndarray, a_minus: jnp.ndarray,   # (m, kw) uint32
    b_bits_t: jnp.ndarray,                       # (n, kw) uint32
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
    """Table I products + eq. (2) in one pass: float32 (m, n) output."""
    del k_valid

    def body(pid_k, num_k, a_refs, b_refs, r_refs, c_refs, o_ref):
        @pl.when(pid_k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        acc = chunked_reduce(a_refs, b_refs, _tbn_product,
                             word_chunk=word_chunk, acc_dtype=jnp.int32)
        o_ref[...] += acc.astype(jnp.float32)

        @pl.when(pid_k == num_k - 1)
        def _finalize():
            o_ref[...] = scale_epilogue(o_ref[...], r_refs, c_refs)

    cols = [col_scale] if bias is None else [col_scale, bias]
    return lowbit_matmul_call(
        body, [a_plus, a_minus], [b_bits_t],
        row_operands=[row_scale], col_operands=cols,
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        interpret=interpret,
        acc_dtype=jnp.float32,
    )
