"""Dense-backend MXU fusion: in-VMEM bit-plane unpack kernels.

The ``dense`` backend keeps the paper's packed *storage* (the memory
win) but rides the MXU instead of the VPU popcount formulation.  Before
this module it did so by materializing the full ±1/0 operand matrices in
HBM (``encoding.unpack_*`` on the whole payload) and handing XLA a plain
``jnp.dot`` — the unpack round-tripped every weight through HBM at its
dense width on every call, and the eq. (2) epilogue was only fused by
XLA's fusion heuristics.

The kernels here do what the paper's core claim implies for an MXU
target: the packed uint32 bit-plane words are what travels HBM -> VMEM,
and the decode to ±1/0 bf16 tiles happens *in-register*, immediately
ahead of the multiply —

* **gemm** (``dense_matmul_fused_pallas``): the standard (m-blocks,
  n-blocks, k-blocks) grid of ``lowbit_matmul_call``; per inner step one
  bit plane of every word of each operand's (block, block_kw) tile
  decodes to a ±1/0 bf16 tile and feeds ``jnp.dot`` with float32
  accumulation (exact: all products are ±1/0 integers and every partial
  sum is < 2^24) — 32 dots of depth block_kw per k block, ``word_chunk``
  (at most 32) of them unrolled per loop iteration — with the eq. (2)
  scale/bias epilogue applied at ``pid_k == num_k - 1``: the unpacked
  operands and the accumulator never touch HBM;
* **im2col_fused** (``dense_conv_fused_pallas``): the fused conv layout
  — patch coordinates from ``program_id`` via the shared
  ``conv_fused.gather_patch_tile``, the raw activation tile quantized to
  ±1/0 values in VMEM (per-tensor stats commute with gathering), the
  positional weight planes unpacked to bf16 beside it, one MXU dot per
  grid cell, epilogue in-kernel.  The im2col patch matrix never exists.

Both register under ``(mode, "dense", fused=True)`` for their layout
with a declared ``TuningSpace`` (``DENSE_SPACE``/``CONV_DENSE_SPACE``),
closing the last untunable fused cell of the registry matrix.  The
materializing unpack survives as the *unfused* dense entry — the
bit-exact oracle these kernels are tested against (identical integer
accumulators, identical epilogue multiply order => ``array_equal``).

Binary padding note: zero pad bits decode to **+1** (not 0), so the
BNN gemm kernel masks the A-side values past the logical depth
``k_valid`` before the dot; ternary planes pad to (0,0) == value 0 and
need no mask (which also covers TBN: a zero A value annihilates the B
pad).  The conv kernel zero-pads the gathered *value* tile instead and
slices the unpacked weight words back to Cin per position.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry
from repro.kernels._matmul_common import (
    ceil_to,
    lowbit_matmul_call,
    pad2d,
    scale_epilogue,
)
from repro.kernels.conv_fused import (
    _resolve_conv_tiles,
    conv_spatial_pad,
    gather_patch_tile,
    interpreted_only,
    quantize_patch_values,
)
from repro.kernels.modes import QuantMode
from repro.tune.space import CONV_DENSE_SPACE, DENSE_SPACE

__all__ = ["dense_matmul_fused_pallas", "dense_conv_fused_pallas"]

# Which side carries two (plus, minus) planes vs one sign plane.
_TERNARY_A = {QuantMode.BNN: False, QuantMode.TNN: True, QuantMode.TBN: True}
_TERNARY_B = {QuantMode.BNN: False, QuantMode.TNN: True, QuantMode.TBN: False}


def _unpack_bits(words: jnp.ndarray) -> jnp.ndarray:
    """(..., w) uint32 -> (..., w*32) {0,1} int32, LSB-first — the
    in-register form of ``encoding.unpack_bits`` (no depth slice)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1],
                        words.shape[-1] * 32).astype(jnp.int32)


def _bit_plane_vals(planes, bit, ternary: bool) -> jnp.ndarray:
    """Bit ``bit`` of every word of the (rows, words) plane tile(s) ->
    ±1/0 bf16 values, one per word.  Slicing the depth by bit plane
    instead of by word keeps the word axis on lanes — no lane reshape —
    and a dot over one bit plane of both operands sums the same products
    as a dot over their unpacked words, in a different order (exact:
    integer partial sums < 2^24)."""
    sh = jnp.asarray(bit).astype(jnp.uint32)

    def bits(p):
        return ((p >> sh) & jnp.uint32(1)).astype(jnp.int32)

    if ternary:
        vals = bits(planes[0]) - bits(planes[1])
    else:
        vals = 1 - 2 * bits(planes[0])
    return vals.astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# gemm layout
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("mode", "k_valid", "block_m", "block_n", "block_kw",
                     "word_chunk", "interpret"),
)
def dense_matmul_fused_pallas(
    mode: QuantMode,
    a_planes,                  # tuple of (m, kw) uint32
    b_planes,                  # tuple of (n, kw) uint32  (B transposed)
    k_valid: int,
    row_scale: jnp.ndarray,    # (m, 1) float32
    col_scale: jnp.ndarray,    # (1, n) float32
    bias: jnp.ndarray | None = None,   # (1, n) float32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_kw: int = 128,
    word_chunk: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Packed planes -> in-VMEM unpack -> MXU dot -> eq. (2), one pass.

    Float32 accumulation of ±1/0 products is exact (integers < 2^24),
    so the result is bit-identical to the materializing dense oracle.
    """
    ternary_a, ternary_b = _TERNARY_A[mode], _TERNARY_B[mode]

    def body(pid_k, num_k, a_refs, b_refs, r_refs, c_refs, o_ref):
        @pl.when(pid_k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        a = [r[...] for r in a_refs]           # (bm, bkw) uint32
        b = [r[...] for r in b_refs]           # (bn, bkw) uint32
        bkw = a[0].shape[-1]
        # Bit ``bit`` of word ``w`` is logical depth (word0 + w)*32 + bit.
        word_k = (pid_k * bkw + jax.lax.broadcasted_iota(
            jnp.int32, (1, bkw), 1)) * 32

        def step(i, acc):
            for j in range(bc):                # unrolled by hand (Mosaic
                bit = i * bc + j               # takes no partial unroll)
                av = _bit_plane_vals(a, bit, ternary_a)   # (bm, bkw) bf16
                bv = _bit_plane_vals(b, bit, ternary_b)   # (bn, bkw) bf16
                if not ternary_a:
                    # BNN: zero pad bits decode to +1 on BOTH operands,
                    # so zero the A side past the logical depth (ternary
                    # planes pad to value 0 and cover every other mode).
                    av = jnp.where(word_k + bit < k_valid, av,
                                   jnp.bfloat16(0))
                acc = acc + jax.lax.dot_general(
                    av, bv, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return acc

        bc = math.gcd(32, max(1, word_chunk))  # bit planes per iteration
        acc = jax.lax.fori_loop(0, 32 // bc, step,
                                jnp.zeros(o_ref.shape, jnp.float32))
        o_ref[...] += acc

        @pl.when(pid_k == num_k - 1)
        def _finalize():
            o_ref[...] = scale_epilogue(o_ref[...], r_refs, c_refs)

    cols = [col_scale] if bias is None else [col_scale, bias]
    return lowbit_matmul_call(
        body, list(a_planes), list(b_planes),
        row_operands=[row_scale], col_operands=cols,
        block_m=block_m, block_n=block_n, block_kw=block_kw,
        interpret=interpret,
        acc_dtype=jnp.float32,
    )


# ---------------------------------------------------------------------------
# im2col_fused layout
# ---------------------------------------------------------------------------

def dense_conv_fused_pallas(
    mode: QuantMode,
    x: jnp.ndarray,            # (B, H, W, Cin) float
    b_planes,                  # positional planes, (cout, kh*kw*cw) uint32
    geometry,                  # (kh, kw, cin, cout)
    stride: int,
    padding: str,
    stats,                     # conv_act_stats output
    col_scale: jnp.ndarray,    # (1, cout) float32
    bias: jnp.ndarray | None,  # (1, cout) float32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_kw: int = 512,       # accepted for TileConfig uniformity;
    word_chunk: int = 8,       # the conv grid tiles only (m, n)
    interpret: bool | None = None,
) -> jnp.ndarray:
    del block_kw, word_chunk
    kh, kw, cin, cout = geometry
    cw = -(-cin // 32)
    xp, (oh, ow) = conv_spatial_pad(x.astype(jnp.float32), kh, kw,
                                    stride, padding)
    bsz = xp.shape[0]
    m = bsz * oh * ow
    words = kh * kw * cw
    ternary_b = _TERNARY_B[mode]
    # Same in-kernel clamp as the gemm kernel: never tile past the
    # (sublane-aligned) patch-row / cout extents.
    block_m = min(block_m, ceil_to(m, 8))
    block_n = min(block_n, ceil_to(cout, 8))

    mp, np_ = ceil_to(m, block_m), ceil_to(cout, block_n)
    b_ops = [pad2d(bp, np_, words) for bp in b_planes]
    col_ops = [pad2d(col_scale, 1, np_)]
    if bias is not None:
        col_ops.append(pad2d(bias, 1, np_))
    stat_ops = []
    if mode != QuantMode.BNN:
        stat_ops.append(jnp.reshape(stats["thr"], (1, 1)))
    stat_ops.append(jnp.reshape(stats["scale"], (1, 1)))

    grid = (mp // block_m, np_ // block_n)
    x_spec = pl.BlockSpec(xp.shape, lambda i, j: (0, 0, 0, 0))
    b_spec = pl.BlockSpec((block_n, words), lambda i, j: (j, 0))
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

        # -- A: raw patch gather + quantize to ±1/0 values, in VMEM ----
        patch = gather_patch_tile(x_ref[...], pl.program_id(0),
                                  block_m=block_m, m=m, oh=oh, ow=ow,
                                  stride=stride, kh=kh, kw=kw)
        thr = None if mode == QuantMode.BNN else s_refs[0][0, 0]
        av = quantize_patch_values(patch, mode, thr)
        av = av.reshape(block_m, kh * kw * cin).astype(jnp.bfloat16)

        # -- B: positional word planes -> ±1/0 bf16, in-register -------
        def bits3(b_ref):
            w3 = b_ref[...].reshape(block_n, kh * kw, cw)
            return _unpack_bits(w3)[..., :cin]      # drop in-word pads

        if ternary_b:
            bv = bits3(b_refs[0]) - bits3(b_refs[1])
        else:
            bv = 1 - 2 * bits3(b_refs[0])
        bv = bv.reshape(block_n, kh * kw * cin).astype(jnp.bfloat16)

        # -- MXU dot + eq. (2), in-kernel ------------------------------
        acc = jnp.dot(av, bv.T, preferred_element_type=jnp.float32)
        o_ref[...] = scale_epilogue(acc, [s_refs[-1]], c_refs)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=([x_spec] + [b_spec] * nb + [s_spec] * ns
                  + [c_spec] * len(col_ops)),
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpreted_only(interpret, "dense"),
    )(xp, *b_ops, *stat_ops, *col_ops)
    return out[:m, :cout].reshape(bsz, oh, ow, cout)


# ---------------------------------------------------------------------------
# Registration — (mode, "dense", fused=True) for gemm AND im2col_fused
# ---------------------------------------------------------------------------

def _register_dense_kernels():
    # Plan resolution reuses the shared helpers (ops._resolve_tiles /
    # conv_fused._resolve_conv_tiles) so the plan-key schema lives in
    # one place; ops imports lazily (it imports this module at the end
    # of its own body, so it is fully bound by first kernel dispatch).

    def make_gemm(mode):
        def fn(a, b, k, r, c, bias, *, interpret=None, tiles=None):
            from repro.kernels import ops

            t = ops._resolve_tiles(mode, "dense", True, a, b, k, tiles)
            return dense_matmul_fused_pallas(mode, tuple(a), tuple(b), k,
                                             r, c, bias,
                                             interpret=interpret,
                                             **t.kernel_kwargs())
        return fn

    def make_conv(mode):
        def fn(x, b_planes, geometry, stride, padding, stats, col_scale,
               bias, *, interpret=None, tiles=None):
            t = _resolve_conv_tiles(mode, "dense", x.shape, geometry,
                                    stride, padding, tiles)
            return dense_conv_fused_pallas(mode, x, b_planes, geometry,
                                           stride, padding, stats,
                                           col_scale, bias,
                                           interpret=interpret,
                                           **t.kernel_kwargs())
        return fn

    for mode in (QuantMode.BNN, QuantMode.TNN, QuantMode.TBN):
        registry.register(
            mode, "dense", fused=True, epilogue="in-kernel",
            compute="mxu-dense", tunable=DENSE_SPACE,
            description="bit-plane unpack to ±1/0 bf16 in VMEM; MXU dot; "
                        "eq. (2) at pid_k==num_k-1",
        )(make_gemm(mode))
        registry.register(
            mode, "dense", fused=True, layout=registry.LAYOUT_IM2COL,
            epilogue="in-kernel", compute="mxu-dense",
            tunable=CONV_DENSE_SPACE,
            description="patch gather + quantize + weight unpack in VMEM; "
                        "MXU dot; epilogue in-kernel",
        )(make_conv(mode))


_register_dense_kernels()
