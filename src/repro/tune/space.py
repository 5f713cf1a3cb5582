"""Tuning spaces: the candidate blockings the autotuner may measure.

The paper's throughput comes from hardware-matched blocking — the 16x8
register microkernel and the L1/L2 cache block sizes of Algorithm 2 are
chosen for the Cortex-A73, and the 4-bit predecessor (arXiv:2009.06488)
makes the same point: block geometry, not the bit-trick alone, decides
speed.  Our Pallas/XLA kernels expose the analogous knobs as a
:class:`~repro.kernels._matmul_common.TileConfig`; a :class:`TuningSpace`
is the per-:class:`~repro.kernels.registry.KernelSpec` declaration of
which ``(block_m, block_n, block_kw, word_chunk)`` combinations are
worth trying.

Candidates are validated and *normalized* against the grid/padding
constraints of ``_matmul_common.lowbit_matmul_call`` before they are
measured:

* ``block_kw`` is clamped to ``ceil_to(min(block_kw, max(wc, kw)), wc)``
  — exactly the clamp the kernel applies, so two raw candidates that the
  kernel would execute identically dedupe to one measurement;
* ``block_m``/``block_n`` are clamped to the padded operand extents
  (sublane multiple 8 / lane multiple 128 — the TPU f32 tile minima), so
  a 128-row block is never measured against an 8-row matrix;
* XLA scan kernels honour only ``word_chunk`` (``kind="xla"``): the
  block axes collapse to the default and ``word_chunk`` is clamped to
  the word count like ``_chunked_bitwise_matmul`` does.

Every candidate list contains the mode's ``DEFAULT_TILES`` entry (first,
after normalization), so a tuned plan can never select a blocking worse
than the untuned default — at worst the default wins its own bake-off.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import List, Optional, Tuple

from repro.kernels._matmul_common import TileConfig, ceil_to

__all__ = ["TuningSpace", "PALLAS_SPACE", "XLA_SPACE", "CONV_PALLAS_SPACE",
           "DENSE_SPACE", "CONV_DENSE_SPACE", "INDEXED_SPACE",
           "AFFINE_SPACE", "words_for"]

_SUBLANE = 8      # f32 sublane multiple (second-to-last dim)
_LANE = 128       # lane multiple (last dim)


def words_for(k: int) -> int:
    """uint32 words covering a logical reduction depth of ``k``."""
    return max(1, ceil_to(k, 32) // 32)


@dataclasses.dataclass(frozen=True)
class TuningSpace:
    """Candidate axes for one kernel's blocking.

    ``kind`` selects the normalization semantics: ``"pallas"`` kernels
    honour all four axes, ``"xla"`` kernels only ``word_chunk``, and
    ``"indexed"`` kernels reinterpret ``block_kw`` as the segment width
    in *bits* (2/4/8) and ``word_chunk`` as the segments consumed per
    scan step (kernels/indexed_matmul.py).
    """
    kind: str = "pallas"                     # "pallas" | "xla" | "indexed"
    block_m: Tuple[int, ...] = (8, 32, 128)
    block_n: Tuple[int, ...] = (128, 256)
    block_kw: Tuple[int, ...] = (128, 256, 512)
    word_chunk: Tuple[int, ...] = (4, 8, 16)

    def __post_init__(self):
        if self.kind not in ("pallas", "xla", "indexed"):
            raise ValueError(f"unknown TuningSpace kind {self.kind!r}")
        for name in ("block_m", "block_n", "block_kw", "word_chunk"):
            vals = getattr(self, name)
            if not vals or any(v < 1 for v in vals):
                raise ValueError(f"TuningSpace.{name} must be non-empty "
                                 f"positive ints, got {vals}")
        if any(v % _SUBLANE for v in self.block_m):
            raise ValueError(f"block_m candidates must be multiples of "
                             f"{_SUBLANE}, got {self.block_m}")
        if any(v % _LANE for v in self.block_n):
            raise ValueError(f"block_n candidates must be multiples of "
                             f"{_LANE}, got {self.block_n}")

    # -- normalization -------------------------------------------------------

    def normalize(self, tc: TileConfig, m: int, n: int, k: int,
                  kw: Optional[int] = None) -> TileConfig:
        """The blocking the kernel would *actually* run for this shape —
        the dedupe key that keeps the measured set minimal.

        ``kw`` overrides the reduction word count when it differs from
        ``words_for(k)`` — the fused-im2col conv kernels pack each patch
        position word-aligned, so their axis has ``kh*kw*ceil(cin/32)``
        words (> ``ceil(k/32)`` whenever ``cin % 32 != 0``); without the
        override the ``block_kw`` candidates would clamp to the smaller
        count and collapse for every odd-channel geometry.
        """
        kw = words_for(k) if kw is None else kw
        if self.kind == "xla":
            d = TileConfig()
            return TileConfig(block_m=d.block_m, block_n=d.block_n,
                              block_kw=d.block_kw,
                              word_chunk=min(tc.word_chunk, kw))
        if self.kind == "indexed":
            # block_kw carries the segment width b (largest supported
            # width <= the raw value, so DEFAULT_TILES entries land on
            # b=8); word_chunk is segments per scan step, clamped to
            # the padded segment count like the xla word clamp.
            d = TileConfig()
            b = next((c for c in (8, 4, 2) if c <= tc.block_kw), 2)
            nseg = kw * (32 // b)
            return TileConfig(block_m=d.block_m, block_n=d.block_n,
                              block_kw=b,
                              word_chunk=min(tc.word_chunk, nseg))
        wc = tc.word_chunk
        bkw = ceil_to(min(tc.block_kw, max(wc, kw)), wc)
        bm = min(tc.block_m, ceil_to(m, _SUBLANE))
        bn = min(tc.block_n, ceil_to(n, _LANE))
        return TileConfig(block_m=bm, block_n=bn, block_kw=bkw,
                          word_chunk=wc)

    # -- enumeration ---------------------------------------------------------

    def candidates(self, m: int, n: int, k: int, *,
                   default: TileConfig,
                   kw: Optional[int] = None) -> List[TileConfig]:
        """Deduped, validated candidate list for one (m, n, k) problem.

        Candidate 0 is the **raw** default — bit-for-bit the blocking an
        untuned cache-miss dispatch executes (no normalization: Pallas
        pads m up to ``block_m``, so a clamped variant is a *different*,
        usually faster schedule and enters the bake-off as its own
        candidate).  Then the axis product, normalized and deduped, in
        declaration order.  Deterministic order + argmin-with-earliest-
        tie-break means repeated tuning runs on the same device pick the
        same plan, and the tuned plan can never lose to the true
        untuned baseline.
        """
        out: List[TileConfig] = [default]
        seen = set()
        if self.kind in ("xla", "indexed") or self.normalize(
                default, m, n, k, kw) == default:
            # the normalized form executes identically to the raw
            # default (xla/indexed kernels self-normalize internally;
            # pallas only when normalization was a no-op) — don't
            # measure it twice
            seen.add(self.normalize(default, m, n, k, kw))
        for bm, bn, bkw, wc in itertools.product(
                self.block_m, self.block_n, self.block_kw,
                self.word_chunk):
            eff = self.normalize(TileConfig(bm, bn, bkw, wc), m, n, k, kw)
            if eff not in seen:
                seen.add(eff)
                out.append(eff)
        return out


# The shared spaces the built-in kernels register with.  Small on
# purpose: the Pallas kernels run in interpret mode on CPU containers,
# so every extra candidate is a Python-loop grid sweep.
PALLAS_SPACE = TuningSpace(kind="pallas")
XLA_SPACE = TuningSpace(kind="xla",
                        block_m=(128,), block_n=(128,), block_kw=(256,),
                        word_chunk=(2, 4, 8, 16, 32))

# Space for the fused-im2col conv Pallas kernels (kernels/conv_fused.py).
# ``block_m`` blocks the *patch rows* (B*OH*OW) exactly like the GeMM m
# axis; ``block_kw`` is the patch-blocked reduction axis — the kernel's
# per-position packed words (kh*kw*ceil(Cin/32)) are consumed block_kw
# words per outer step, so conv depths (a few dozen to a few hundred
# words) want smaller k blocks than the LM projections.
CONV_PALLAS_SPACE = TuningSpace(kind="pallas",
                                block_m=(8, 32, 128),
                                block_n=(128, 256),
                                block_kw=(32, 128, 512),
                                word_chunk=(4, 8))

# Dense-backend (MXU) fused GeMM kernels (kernels/dense_fused.py): the
# grid axes mirror the popcount kernels, but each inner step decodes one
# bit plane of the (block, block_kw) word tiles to ±1/0 bf16 and feeds
# one MXU dot of depth block_kw — so block_kw sets the k extent of every
# dot and the VMEM-resident word depth between output revisits (below
# 128 words it runs as the whole word extent, the (8, 128) block rule),
# and word_chunk the bit planes unrolled per loop iteration.
DENSE_SPACE = TuningSpace(kind="pallas",
                          block_m=(8, 32, 128),
                          block_n=(128, 256),
                          block_kw=(8, 32, 128),
                          word_chunk=(4, 8))

# Indexed-redundancy backend (kernels/indexed_matmul.py): block_kw is
# the segment width in bits (2**b subset-sum slots per table, more
# columns amortized per table as b grows), word_chunk the segments per
# scan step (the (m, n, chunk) gather working set).  The block axes are
# single-candidate — the gather path has no m/n tiling of its own.
INDEXED_SPACE = TuningSpace(kind="indexed",
                            block_m=(8,), block_n=(128,),
                            block_kw=(2, 4, 8),
                            word_chunk=(8, 16, 32))

# Affine u8/u4 registry cells (ops.int8/int4_affine_matmul cores): the
# kernels have no externally tunable blocking (XLA / the Pallas int
# kernels pick their own tiling), but every fused registry entry
# declares a space so the tuner sweep and the no-opt-out invariant stay
# closed — one candidate, the default, which wins its own bake-off.
AFFINE_SPACE = TuningSpace(kind="xla",
                           block_m=(128,), block_n=(128,),
                           block_kw=(256,), word_chunk=(8,))

# The dense fused-im2col conv kernel tiles only the (patch-row, cout)
# grid — the whole positional word axis of a B tile unpacks beside the
# gathered patch tile, one dot per cell — so the kw axes stay single-
# candidate (the kernel accepts and ignores them).
CONV_DENSE_SPACE = TuningSpace(kind="pallas",
                               block_m=(8, 32, 128),
                               block_n=(128, 256),
                               block_kw=(512,),
                               word_chunk=(8,))
