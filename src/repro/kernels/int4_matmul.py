"""u4 matmul Pallas kernel — the paper's U4 baseline ([20], 24x8 microkernel).

ARM original: 4-bit values widened to 8 bits on load, UMLAL into *16-bit*
lanes (hence the tight k_max = 291 of Table II).

TPU version: operands arrive nibble-packed (two 4-bit values per uint8
along k, halving HBM traffic); the kernel splits each byte into its two
nibbles in VMEM and feeds the int8 MXU with int32 accumulation.  The paper's 16-bit accumulator
trick does not pay on the MXU (accumulation width is fixed), so k_max
ceases to be a real constraint — recorded as a hardware-adaptation
difference; the int16 fidelity semantics live in ref.py.

Packing: element 2t sits in the low nibble, 2t+1 in the high nibble.
A packs along its k axis (axis 1); B packs along its k axis (axis 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._matmul_common import ceil_to, pad2d, resolve_interpret
from repro.kernels.int8_matmul import int8_dot

__all__ = ["int4_matmul_pallas", "pack_nibbles_rows", "pack_nibbles_cols"]


def pack_nibbles_rows(a_q: jnp.ndarray) -> jnp.ndarray:
    """(m, k) u4-valued -> (m, k/2) uint8, k padded to even."""
    m, k = a_q.shape
    if k % 2:
        a_q = jnp.pad(a_q, ((0, 0), (0, 1)))
        k += 1
    v = a_q.astype(jnp.uint8).reshape(m, k // 2, 2)
    return (v[..., 0] | (v[..., 1] << 4)).astype(jnp.uint8)


def pack_nibbles_cols(b_q: jnp.ndarray) -> jnp.ndarray:
    """(k, n) u4-valued -> (k/2, n) uint8."""
    k, n = b_q.shape
    if k % 2:
        b_q = jnp.pad(b_q, ((0, 1), (0, 0)))
        k += 1
    v = b_q.astype(jnp.uint8).reshape(k // 2, 2, n)
    return (v[:, 0, :] | (v[:, 1, :] << 4)).astype(jnp.uint8)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k2", "interpret"),
)
def int4_matmul_pallas(
    a_packed: jnp.ndarray,   # (m, k/2) uint8
    b_packed: jnp.ndarray,   # (k/2, n) uint8
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k2: int = 256,     # packed bytes per step == 512 u4 values
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Raw accumulator A_q @ B_q in int32 over nibble-packed operands."""
    m, k2 = a_packed.shape
    _, n = b_packed.shape
    block_k2 = min(block_k2, max(128, k2))

    mp, np_, k2p = ceil_to(m, block_m), ceil_to(n, block_n), ceil_to(k2, block_k2)
    a_p = pad2d(a_packed, mp, k2p)
    b_p = pad2d(b_packed, k2p, np_)

    grid = (mp // block_m, np_ // block_n, k2p // block_k2)

    def kernel(a_ref, b_ref, o_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        # Element 2t sits in the low nibble and 2t+1 in the high one on
        # both operands, so the k sum splits into a low-nibble and a
        # high-nibble dot — no lane interleave needed to unpack.
        a = a_ref[...].astype(jnp.int32)
        b = b_ref[...].astype(jnp.int32)
        o_ref[...] += int8_dot(a & 0xF, b & 0xF) + int8_dot(a >> 4, b >> 4)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k2), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_k2, block_n), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a_p, b_p)
    return out[:m, :n]
