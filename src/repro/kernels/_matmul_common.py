"""Shared plumbing for the low-bit Pallas matmul kernels.

TPU mapping of the paper's blocked GeMM (Algorithm 2):

* the 16x8 register microkernel becomes a (block_m x block_n) int32
  accumulator tile that lives in VMEM and is revisited across the k grid
  dimension (k is the innermost grid axis, so Pallas keeps the output
  block resident while the reduction streams through);
* PackNRowsA / PackNColsB become the uint32 bit-plane layout of
  ``encoding.py`` plus ``BlockSpec.index_map`` tiling — the Pallas
  pipeline's HBM->VMEM double buffering plays the role of the paper's
  L1/L2 cache blocking (k_blk/m_blk/n_blk);
* the paper's register outer product becomes one (bm, bn) VPU update
  per uint32 word: the word's A column broadcast along lanes against its
  B row broadcast along sublanes.  Both tiles are transposed once per
  grid step into VMEM scratch so that the word loop indexes sublanes
  only (a dynamic lane offset that is not a multiple of 128 does not
  compile for the chip); ``word_chunk`` words are unrolled per loop
  iteration.

Blocks obey the TPU (8, 128) rule: the k-word block is either the whole
padded word extent or a multiple of 128 words, and row/column blocks are
multiples of 8 (clamped to the sublane-aligned problem, so a decode-
sized m never pads up to a 128-row block).

``interpret=None`` (every kernel's default) resolves through
:func:`resolve_interpret`: the Pallas interpreter on the CPU backend,
compiled Mosaic kernels everywhere else.

Fused epilogue
--------------
``lowbit_matmul_call`` can additionally stream *epilogue operands* into
the kernel: per-row vectors (shape (m, 1), e.g. the activation scale)
and per-column vectors (shape (1, n), e.g. the weight scale and bias).
They get their own BlockSpecs — (block_m, 1) revisited along j/s and
(1, block_n) revisited along i/s — so a kernel body can finalize the
int32 accumulator into scaled float output at ``pid_k == num_k - 1``
without a second pass over the (m, n) result in HBM.  This is how the
``*_fused`` kernels fold the dequantization of eq. (2) into the matmul.

Inputs are padded to block multiples here (pad words are all-zero, which
is exact for every encoding — see encoding.py) and the output is sliced
back.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def resolve_interpret(interpret: bool | None) -> bool:
    """The one platform decision for Pallas interpret mode: an explicit
    value wins (compile tests pass False on a CPU host); None runs the
    interpreter only where Mosaic cannot compile, the CPU backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


@dataclasses.dataclass(frozen=True, order=True)
class TileConfig:
    """One blocking choice for a low-bit matmul kernel.

    ``block_m/block_n/block_kw`` are the Pallas grid tile sizes of
    :func:`lowbit_matmul_call`; ``word_chunk`` is the number of uint32
    words consumed per inner k step (the VPU analogue of the paper's
    8-byte NEON k-step) — the scan width of the XLA kernels and the
    unroll factor of the Pallas inner loops.  The XLA scan kernels honour
    only ``word_chunk``; the Pallas kernels honour all four.
    """
    block_m: int = 128
    block_n: int = 128
    block_kw: int = 256
    word_chunk: int = 8

    def kernel_kwargs(self) -> Dict[str, int]:
        return {"block_m": self.block_m, "block_n": self.block_n,
                "block_kw": self.block_kw, "word_chunk": self.word_chunk}

    def to_json(self) -> Dict[str, int]:
        return self.kernel_kwargs()

    @classmethod
    def from_json(cls, d: Dict[str, int]) -> "TileConfig":
        return cls(block_m=int(d["block_m"]), block_n=int(d["block_n"]),
                   block_kw=int(d["block_kw"]),
                   word_chunk=int(d["word_chunk"]))


# The seed blocking of each mode's kernels (previously triplicated as
# literal defaults in bnn/tnn/tbn_matmul.py).  BNN streams one bit plane
# per operand so it affords a deeper k block than the two-plane ternary
# kernels at the same VMEM budget.  The autotuner's deterministic
# fallback (repro/tune/cache.py) reads this same table.
DEFAULT_TILES: Dict[str, TileConfig] = {
    "bnn": TileConfig(block_m=128, block_n=128, block_kw=512, word_chunk=8),
    "tnn": TileConfig(block_m=128, block_n=128, block_kw=256, word_chunk=8),
    "tbn": TileConfig(block_m=128, block_n=128, block_kw=256, word_chunk=8),
    # Affine u8/u4 registry cells: the kernels pick their own tiling,
    # but the plan-cache fallback needs an entry per registered mode.
    "int8": TileConfig(),
    "int4": TileConfig(),
}


def psum_accum_dtype(k_bits: int) -> jnp.dtype:
    """Narrowest signed integer dtype that can carry a cross-device
    popcount partial through a ``psum`` without overflow.

    A per-shard signed contribution is bounded by the padded bit depth
    (ternary/TBN partials lie in ``[-k_bits, k_bits]``; the BNN
    ``-2 * popcount`` convention doubles that), and the all-reduce sum
    of all shards is bounded by the same global total — so ``2 *
    k_bits`` bounds every intermediate.  int16 halves the bytes the
    reduction moves; deeper problems fall back to int32.
    """
    return jnp.dtype(jnp.int16) if 2 * k_bits < 2 ** 15 \
        else jnp.dtype(jnp.int32)


def pad2d(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


def lowbit_matmul_call(
    kernel_body,
    a_operands: Sequence[jnp.ndarray],   # each (m, kw) uint32
    b_operands: Sequence[jnp.ndarray],   # each (n, kw) uint32  (B transposed)
    *,
    row_operands: Sequence[jnp.ndarray] = (),   # each (m, 1), epilogue input
    col_operands: Sequence[jnp.ndarray] = (),   # each (1, n), epilogue input
    block_m: int,
    block_n: int,
    block_kw: int,
    interpret: bool | None,
    acc_dtype=jnp.int32,
):
    """Run ``kernel_body`` over a (m/bm, n/bn, kw/bkw) grid.

    ``kernel_body(pid_k, num_k, a_refs, b_refs, r_refs, c_refs, o_ref)``
    must initialize o_ref at pid_k == 0, accumulate, and finalize at
    pid_k == num_k - 1.  ``r_refs`` / ``c_refs`` hold the (block_m, 1) /
    (1, block_n) tiles of the epilogue operands (empty tuples when none
    were passed).  The output buffer has dtype ``acc_dtype`` — int32 for
    the integer kernels, float32 when the epilogue rescales in-kernel.
    Returns the un-padded (m, n) result.
    """
    m, kw = a_operands[0].shape
    n = b_operands[0].shape[0]

    # (8, 128) block rule: row/column blocks are sublane multiples no
    # larger than the (aligned) problem — an untuned decode-sized m never
    # pads up to a 128-row block, and the paper's 24..96-wide layers keep
    # a block of their own width (a block that spans the whole padded
    # extent may take any width); the k-word block is a lane dimension
    # of the operand tiles, so it is the whole word extent or a multiple
    # of 128 words.
    block_m = min(ceil_to(block_m, 8), ceil_to(m, 8))
    block_n = min(ceil_to(block_n, 8), ceil_to(n, 8))
    block_kw = kw if block_kw >= kw else min(ceil_to(block_kw, 128), kw)

    mp, np_, kwp = ceil_to(m, block_m), ceil_to(n, block_n), ceil_to(kw, block_kw)
    a_ops = [pad2d(a, mp, kwp) for a in a_operands]
    b_ops = [pad2d(b, np_, kwp) for b in b_operands]
    r_ops = [pad2d(r, mp, 1) for r in row_operands]
    c_ops = [pad2d(c, 1, np_) for c in col_operands]

    grid = (mp // block_m, np_ // block_n, kwp // block_kw)
    num_k = grid[2]

    a_spec = pl.BlockSpec((block_m, block_kw), lambda i, j, s: (i, s))
    b_spec = pl.BlockSpec((block_n, block_kw), lambda i, j, s: (j, s))
    r_spec = pl.BlockSpec((block_m, 1), lambda i, j, s: (i, 0))
    c_spec = pl.BlockSpec((1, block_n), lambda i, j, s: (0, j))
    o_spec = pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j))

    na, nb, nr = len(a_ops), len(b_ops), len(r_ops)

    def _kernel(*refs):
        a_refs = refs[:na]
        b_refs = refs[na: na + nb]
        r_refs = refs[na + nb: na + nb + nr]
        c_refs = refs[na + nb + nr: -1]
        o_ref = refs[-1]
        kernel_body(pl.program_id(2), num_k, a_refs, b_refs,
                    r_refs, c_refs, o_ref)

    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=([a_spec] * len(a_ops) + [b_spec] * len(b_ops)
                  + [r_spec] * len(r_ops) + [c_spec] * len(c_ops)),
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), acc_dtype),
        interpret=resolve_interpret(interpret),
    )(*a_ops, *b_ops, *r_ops, *c_ops)
    return out[:m, :n]


def popcount_i32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.population_count(x).astype(jnp.int32)


def chunked_reduce(a_refs, b_refs, product_fn, *, word_chunk: int, acc_dtype):
    """The inner k loop of a low-bit microkernel.

    Transposes the (bm, bkw) / (bn, bkw) word tiles into (bkw, bm) /
    (bkw, bn) VMEM scratch, then walks the words: word ``w``'s A column
    (bm, 1) and B row (1, bn) broadcast into a (bm, bn) signed
    contribution via ``product_fn`` (per-word popcount formula, already
    int32) that sums into the accumulator.  ``word_chunk`` words are
    unrolled per loop iteration (the largest divisor of the block's word
    count not above it).
    """
    bm, bkw = a_refs[0].shape
    bn = b_refs[0].shape[0]
    wc = math.gcd(bkw, max(1, word_chunk))   # words per loop iteration

    def walk(*scratch):
        at_s, bt_s = scratch[:len(a_refs)], scratch[len(a_refs):]
        for src, dst in zip((*a_refs, *b_refs), scratch):
            dst[...] = src[...].T

        def body(i, acc):
            for j in range(wc):                  # unrolled by hand: Mosaic
                w = i * wc + j                   # takes no partial unroll
                a_sl = [r[pl.ds(w, 1), :].reshape(bm, 1) for r in at_s]
                b_sl = [r[pl.ds(w, 1), :] for r in bt_s]
                acc = acc + product_fn(a_sl, b_sl).astype(acc_dtype)
            return acc

        return jax.lax.fori_loop(0, bkw // wc, body,
                                 jnp.zeros((bm, bn), acc_dtype))

    scratch = ([pltpu.VMEM((bkw, bm), jnp.uint32)] * len(a_refs)
               + [pltpu.VMEM((bkw, bn), jnp.uint32)] * len(b_refs))
    return pl.run_scoped(walk, *scratch)


def scale_epilogue(acc_f32, r_refs, c_refs):
    """Apply the eq. (2) dequantization inside the kernel.

    ``acc_f32`` is the finalized (bm, bn) float32 integer count;
    ``r_refs = (row_scale,)`` and ``c_refs = (col_scale,)`` or
    ``(col_scale, bias)``.  The multiply order matches the unfused
    ``acc * a_scale * w_scale`` epilogue exactly (bit-identical floats).
    """
    out = acc_f32 * r_refs[0][...] * c_refs[0][...]
    if len(c_refs) > 1:
        out = out + c_refs[1][...]
    return out
