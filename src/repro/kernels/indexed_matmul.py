"""Indexed-redundancy matmul backend — the fourth registry backend.

Dehghankar et al. (arXiv 2411.06360, "RSR") observe that a binary or
ternary weight matrix over a fixed reduction depth contains massive
*redundancy*: split the depth axis into segments of ``b`` bits and
every weight column restricted to one segment is one of only ``2**b``
possible sign patterns.  Instead of popcounting every (row, column)
pair, precompute — per activation row and segment — the subset-sum
table of all ``2**b`` patterns (``b`` doubling steps, not ``2**b``
sums), then reduce each column to a *table gather* keyed by the
segment's pattern index.  Per segment the popcount kernels do O(n)
bit-ops per activation row; the indexed kernel does O(2**b) adds to
build the table plus O(n) gathers — a win once n >> 2**b, i.e. for the
wide projection/classifier shapes of Table III.

Implementation notes:

* **Pack-time preprocessing** (:func:`add_indexed_payload`): the
  per-segment pattern indices of the weight planes, stored as extra
  QTensor payload keys (``idx{b}_plus``/``idx{b}_minus`` for TNN,
  ``idx{b}_bits`` for TBN/BNN) — (n, S) uint8, following the
  ``POS_PAYLOAD_KEYS`` precedent: ``to_legacy_dict`` filters them and
  migration re-derives.  Containers without the keys (or tuned to a
  different ``b``) fall back to an exact in-trace shift/mask derivation
  from the bit-plane words (:func:`segment_indices`) — zero-copy-or-
  derive, never wrong.
* **Kernel**: activation *values* are unpacked in-trace (±1/0 int32,
  zero past ``k_valid`` — exactness needs no eq. (6)-style correction
  because padded values contribute 0), reshaped to (m, S, b) segments,
  and a ``lax.scan`` walks chunks of segments: build the (m, chunk,
  2**b) subset-sum table by ``b`` doubling steps, gather per column via
  the segment indices, accumulate int32.  TNN weights combine as
  ``T[idx_plus] - T[idx_minus]``; binary weights (bit set == -1) as
  ``sum(segment) - 2 * T[idx_bits]``.  The fused entry applies the
  eq. (2) scale/bias epilogue on the final scan carry — the same
  ``ops._scale_epilogue_f32`` (same multiply order) as every other
  backend, so fused results are bit-identical floats with the popcount
  oracle.
* **Tuning** (:data:`repro.tune.space.INDEXED_SPACE`): ``block_kw``
  carries the segment width ``b`` (2/4/8 bits — divisors of 32, so
  segments never straddle word boundaries and the index of segment
  ``s`` of word ``w`` is ``(word >> (s*b)) & (2**b - 1)`` under the
  LSB-first packing of core/encoding.py) and ``word_chunk`` the
  segments per scan step (the (m, n, chunk) gather working set, the
  analogue of the popcount scan's word chunk).

Crossover intuition: larger ``b`` amortizes more columns per table but
pays ``2**b`` table slots per (row, segment); the bench family
``run_indexed_crossover`` (benchmarks/bench_matmul.py) measures
popcount vs indexed vs MXU-dense per Table-III shape so the plan cache
can pick per shape.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels._matmul_common import TileConfig
from repro.kernels.modes import QuantMode
from repro.tune.space import INDEXED_SPACE

__all__ = ["SEG_BITS_CHOICES", "seg_bits_for", "indexed_payload_keys",
           "segment_indices", "add_indexed_payload",
           "indexed_matmul", "indexed_matmul_fused"]

# Segment widths the kernel supports: divisors of 32 so a segment never
# straddles a packed-word boundary (the shift/mask derivation below and
# the stored payload agree bit-for-bit).
SEG_BITS_CHOICES = (8, 4, 2)


def seg_bits_for(tiles: Optional[TileConfig]) -> int:
    """Segment width selected by a blocking: the largest supported
    ``b <= tiles.block_kw`` (the INDEXED_SPACE normalization writes the
    chosen width into ``block_kw``; the raw DEFAULT_TILES entries are
    >= 8, so an untuned dispatch lands on b=8)."""
    bkw = tiles.block_kw if tiles is not None else TileConfig().block_kw
    for b in SEG_BITS_CHOICES:
        if b <= bkw:
            return b
    return SEG_BITS_CHOICES[-1]


def indexed_payload_keys(mode: QuantMode, seg_bits: int) -> Tuple[str, ...]:
    """Extra QTensor payload keys carrying the pack-time segment indices
    for (mode, seg_bits) — one per weight bit plane."""
    if mode == QuantMode.TNN:
        return (f"idx{seg_bits}_plus", f"idx{seg_bits}_minus")
    if mode in (QuantMode.TBN, QuantMode.BNN):
        return (f"idx{seg_bits}_bits",)
    raise ValueError(f"indexed payload is only defined for the bit-plane "
                     f"modes, got {mode}")


def segment_indices(words: jnp.ndarray, seg_bits: int) -> jnp.ndarray:
    """Per-segment pattern indices of packed bit-plane words.

    ``words`` is (n, kw) uint32, LSB-first (depth element ``w*32 + i``
    is bit ``i`` of word ``w``).  Returns (n, kw * (32 // seg_bits))
    uint8 where entry ``s`` of word ``w`` is the ``seg_bits``-wide
    pattern ``(word >> (s * seg_bits)) & (2**seg_bits - 1)`` — bit ``t``
    of the pattern is depth element ``w*32 + s*seg_bits + t``, matching
    the LSB-first doubling order of the subset-sum table.
    """
    if seg_bits not in SEG_BITS_CHOICES:
        raise ValueError(f"seg_bits must be one of {SEG_BITS_CHOICES}, "
                         f"got {seg_bits}")
    spw = 32 // seg_bits
    shifts = (jnp.arange(spw, dtype=jnp.uint32) * seg_bits)[None, None, :]
    mask = jnp.uint32((1 << seg_bits) - 1)
    segs = (words[:, :, None] >> shifts) & mask
    return segs.reshape(words.shape[0], -1).astype(jnp.uint8)


def add_indexed_payload(qt, seg_bits: int = 8):
    """Pack-time preprocessing: return ``qt`` with the per-plane segment
    indices added as extra payload keys (``idx{b}_*``), so serving never
    re-derives them in-trace.  Like the positional conv planes these are
    derived data: ``to_legacy_dict`` drops them and the kernel falls
    back to the exact in-trace derivation when they are absent."""
    from repro.kernels.qtensor import PAYLOAD_KEYS

    keys = indexed_payload_keys(qt.mode, seg_bits)  # validates the mode
    planes = [qt.payload[k] for k in PAYLOAD_KEYS[qt.mode]]
    extra = {ik: segment_indices(pl, seg_bits)
             for ik, pl in zip(keys, planes)}
    return qt.replace(payload={**qt.payload, **extra})


# ---------------------------------------------------------------------------
# Kernel core
# ---------------------------------------------------------------------------

def _activation_values(mode: QuantMode, a_planes, k: int,
                       depth: int) -> jnp.ndarray:
    """Unpack activation bit planes to ±1/0 int32 values, zero-padded to
    the packed ``depth`` (= kw * 32) so segments align with the weight
    word grid.  Padded values are 0, so they contribute nothing to any
    subset sum — exactness without a correction term."""
    from repro.core import encoding

    if mode == QuantMode.BNN:
        vals = encoding.unpack_binary(a_planes[0], k, jnp.int32)
    else:                                   # TNN / TBN: ternary a-side
        vals = encoding.unpack_ternary(a_planes[0], a_planes[1], k,
                                       jnp.int32)
    return jnp.pad(vals, ((0, 0), (0, depth - k)))


def _gather_tables(tables: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """tables (m, C, P) int32, idx (n, C) integer -> (m, n) int32:
    sum over the C segments of each column's table entry."""
    g = jnp.take_along_axis(tables[:, None, :, :],
                            idx.astype(jnp.int32)[None, :, :, None],
                            axis=-1)
    return jnp.sum(g[..., 0], axis=-1)


def _indexed_core(mode: QuantMode, a_planes, b_planes, k: int, *,
                  seg_bits: int, seg_chunk: int,
                  payload: Optional[Dict[str, jnp.ndarray]] = None,
                  epilogue=None):
    """acc[m, n] = sum over segment-chunks of table-gathered products.

    ``payload`` optionally carries the pack-time ``idx{b}_*`` planes; a
    missing (or differently-sized) payload derives the indices in-trace
    from ``b_planes`` — bit-identical by construction.
    """
    kw = int(b_planes[0].shape[-1])
    depth = kw * 32
    nseg = kw * (32 // seg_bits)

    keys = indexed_payload_keys(mode, seg_bits)
    if payload is not None and all(kk in payload for kk in keys):
        idx_planes: Sequence[jnp.ndarray] = [payload[kk] for kk in keys]
    else:
        idx_planes = [segment_indices(pl, seg_bits) for pl in b_planes]

    a_vals = _activation_values(mode, a_planes, int(k), depth)
    m = a_vals.shape[0]
    n = idx_planes[0].shape[0]

    chunk = max(1, min(int(seg_chunk), nseg))
    nseg_p = -(-nseg // chunk) * chunk
    steps = nseg_p // chunk
    a3 = jnp.pad(a_vals, ((0, 0), (0, (nseg_p - nseg) * seg_bits)))
    a_sc = a3.reshape(m, steps, chunk, seg_bits).transpose(1, 0, 2, 3)
    idx_sc = [jnp.pad(ix, ((0, 0), (0, nseg_p - nseg)))
              .reshape(n, steps, chunk).transpose(1, 0, 2)
              for ix in idx_planes]

    ternary_w = mode == QuantMode.TNN

    def step(acc, ops_):
        a_ch = ops_[0]                       # (m, chunk, seg_bits) int32
        idx_ch = ops_[1:]                    # per-plane (n, chunk)
        # Subset-sum table by LSB-first doubling: after step t, entry p
        # sums the activation values whose pattern bits 0..t are set in
        # p — so entry p of the full table is the dot of this segment's
        # activations with pattern p.
        tables = jnp.zeros((m, a_ch.shape[1], 1), jnp.int32)
        for t in range(seg_bits):
            tables = jnp.concatenate(
                [tables, tables + a_ch[:, :, t:t + 1]], axis=-1)
        if ternary_w:
            # w = plus_bit - minus_bit
            contrib = (_gather_tables(tables, idx_ch[0])
                       - _gather_tables(tables, idx_ch[1]))
        else:
            # binary plane: bit set == -1, clear == +1, so the segment
            # dot is sum(a) - 2 * (sum of a where the bit is set)
            total = jnp.sum(a_ch, axis=(1, 2))          # (m,)
            contrib = total[:, None] - 2 * _gather_tables(tables,
                                                          idx_ch[0])
        return acc + contrib, None

    acc0 = jnp.zeros((m, n), jnp.int32)
    acc, _ = jax.lax.scan(step, acc0, (a_sc, *idx_sc))
    return acc if epilogue is None else epilogue(acc)


# ---------------------------------------------------------------------------
# Registry adapters (normalized signatures + plan-cache tile resolution)
# ---------------------------------------------------------------------------

def indexed_matmul(mode: QuantMode, a_planes, b_planes, k: int, *,
                   seg_bits: int = 8, seg_chunk: int = 8,
                   payload: Optional[Dict[str, jnp.ndarray]] = None):
    """Unfused integer core: packed planes -> int32 (m, n), bit-exact
    with the popcount backends."""
    return _indexed_core(mode, a_planes, b_planes, k,
                         seg_bits=seg_bits, seg_chunk=seg_chunk,
                         payload=payload)


def indexed_matmul_fused(mode: QuantMode, a_planes, b_planes, k: int,
                         row_scale, col_scale, bias=None, *,
                         seg_bits: int = 8, seg_chunk: int = 8,
                         payload: Optional[Dict[str, jnp.ndarray]] = None):
    """Fused core + eq. (2) epilogue on the final scan carry (same
    multiply order as every other backend -> bit-identical floats)."""
    from repro.kernels import ops

    def epi(acc):
        return ops._scale_epilogue_f32(acc, row_scale, col_scale, bias)

    return _indexed_core(mode, a_planes, b_planes, k,
                         seg_bits=seg_bits, seg_chunk=seg_chunk,
                         payload=payload, epilogue=epi)


def _register_indexed_kernels():
    # Plan resolution reuses ops._resolve_tiles (lazy import: ops
    # imports this module at the end of its own body, so it is fully
    # bound by first dispatch) — the plan-key schema stays in one place.

    def make(mode, fused):
        def unfused_fn(a, b, k, *, interpret=None, tiles=None,
                       payload=None):
            del interpret
            from repro.kernels import ops

            t = ops._resolve_tiles(mode, "indexed", False, a, b, k, tiles)
            return indexed_matmul(mode, a, b, k,
                                  seg_bits=seg_bits_for(t),
                                  seg_chunk=t.word_chunk, payload=payload)

        def fused_fn(a, b, k, r, c, bias, *, interpret=None, tiles=None,
                     payload=None):
            del interpret
            from repro.kernels import ops

            t = ops._resolve_tiles(mode, "indexed", True, a, b, k, tiles)
            return indexed_matmul_fused(mode, a, b, k, r, c, bias,
                                        seg_bits=seg_bits_for(t),
                                        seg_chunk=t.word_chunk,
                                        payload=payload)

        return fused_fn if fused else unfused_fn

    for mode in (QuantMode.BNN, QuantMode.TNN, QuantMode.TBN):
        registry.register(
            mode, "indexed", fused=False, epilogue="none",
            compute="vpu-indexed", tunable=INDEXED_SPACE,
            payload_aware=True,
            description="RSR segment-index gather: 2^b subset-sum tables "
                        "replace per-column popcounts",
        )(make(mode, fused=False))
        registry.register(
            mode, "indexed", fused=True, epilogue="scan-carry",
            compute="vpu-indexed", tunable=INDEXED_SPACE,
            payload_aware=True,
            description="segment-index gather; eq. (2) epilogue fused "
                        "onto the final scan carry",
        )(make(mode, fused=True))


_register_indexed_kernels()
