"""Paged attention over the 2-bit ternary KV pages (Pallas TPU).

One kernel reads the packed pages of ``models/paged_kvcache.py``,
decodes their plane words in VMEM and attends, in place of a dense
``page_view`` followed by f32 einsums.  Per grid step ``(slot, row
block)`` it:

* **reads only live pages.**  A slot's live length comes from the step
  (``p0 + nvalid``, ring-capped at ``npp * page``).  The kernel walks
  the slot's page table (scalar prefetch) block by block up to that
  length, copying each block's pages from HBM while the previous block
  computes; a page past the length is not copied, and a dead row
  copies and computes nothing.
* **decodes plane words in VMEM.**  The pool keeps a page's plane words
  in one row of whole 128-lane tiles (:func:`page_lanes`): rows are the
  ``dw`` plus words then the ``dw`` minus words, lanes are (kv head,
  token), and pages narrower than 128 lanes share a row.  A block
  copies the rows of ``R // page`` pages (R lanes a row) and rotates
  each kv head's tokens of those pages into one R-lane tile (lane rolls
  by a shift read from the page table, no relayout); bit ``j`` of every
  word is shifted out into row ``j * 2 * dw + r`` of a ``(64 * dw, R)``
  {0, 1} matrix.  A dot sums over the head dim in any order, so the
  queries are permuted to that order (and the minus rows negated)
  before the kernel, and the P·V output is permuted back after it.
* **applies eq. (2) in the kernel.**  Scores are ``alpha_t * (q ·
  (plus - minus))``: the {0, 1} bits and the bf16 ``q`` enter the MXU
  exactly with f32 sums, and the per-token scale and ``dh ** -0.5``
  follow in f32.  An online softmax in f32 applies the masks of the
  dense path (``pos <= position``, never ``INVALID_POS``, the AL
  window) and the slot's live length.  For V the per-token scale is
  folded into the probabilities in f32, which enter the MXU as a bf16
  high part and a bf16 rest: against exact {0, 1} bits two passes keep
  f32 precision, where the dense path's f32 einsums make one bf16 pass
  on a TPU.

It serves the engine's two shapes (S = 1 decode, S = ``prefill_chunk``)
and a whole-prompt prefill; the query rows of one kv head are the ``S x
G`` rows of its group, padded to whole sublane tiles and tiled by
``_ROW_BLOCK``.  The tokens' positions and scales are gathered for
every table entry before the kernel (12 bytes a token, against 16 * dw
* KVp of plane words).  On the CPU backend the same kernel runs through
the Pallas interpreter (``resolve_interpret``).  The pool's kv heads
come in groups (its ``groups`` axis): the shards of a ``kv_heads`` split
under the mesh the pool was built in, one outside a mesh.  Under an
active mesh the kernel runs per shard in a ``shard_map`` split as the
pool is, so each shard reads its own group and no shard gathers
another's pages.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._matmul_common import resolve_interpret
from repro.parallel import sharding

__all__ = ["paged_attention", "page_lanes"]

# == models.paged_kvcache.INVALID_POS (importing it here would be a
# cycle: models -> attention -> this module)
INVALID_POS = 2 ** 30
_NEG = -1e30
_ROW_BLOCK = 256             # query rows of one kv head per grid step


def page_lanes(kh: int, page: int) -> Tuple[int, int]:
    """(w, ppr) of a plane row of the page pool: a page of ``kh`` kv
    heads x ``page`` tokens takes ``w`` lanes — ``kh * page`` rounded up
    to a power of two under 128, to whole 128s above — and a row of
    ``max(w, 128)`` lanes holds ``ppr`` pages.  A page copy moves whole
    rows: Mosaic copies no narrower lane slice."""
    n = kh * page
    w = 1 << (n - 1).bit_length() if n < 128 else -(-n // 128) * 128
    return w, max(1, 128 // w)


def _decode_bits(words: jnp.ndarray) -> jnp.ndarray:
    """(N, 2dw, T) uint32 plane words -> (N, 32 * 2dw, T) {0, 1} bf16,
    row ``j * 2dw + r`` = bit ``j`` of word row ``r`` (tokens stay on
    lanes; the merge of the bit and word axes is tile-aligned when
    2dw % 8 == 0)."""
    n, rows, t = words.shape
    bits = jnp.stack([(words >> jnp.uint32(j)) & jnp.uint32(1)
                      for j in range(32)], axis=1)
    return (bits.reshape(n, 32 * rows, t).astype(jnp.int32)
            .astype(jnp.float32).astype(jnp.bfloat16))


def _head_tiles(words: jnp.ndarray, segs, *, kh: int, page: int,
                w: int) -> jnp.ndarray:
    """(ppt, ..., R) plane rows of a block's pages -> (kh, ..., R): head
    ``h``'s tokens of page ``i`` — lanes ``[segs[i] * w + h * page, ...
    + page)`` of its row — rotate to lanes ``[i * page, (i + 1) *
    page)``.  ``segs[i]`` (the page's place in its row) is a traced
    scalar where rows hold several pages, else 0."""
    ppt, lanes, axis = words.shape[0], words.shape[-1], words.ndim - 2
    at = jax.lax.broadcasted_iota(jnp.int32, words.shape[1:], axis) // page
    heads = []
    for h in range(kh):
        out = None
        for i in range(ppt):
            shift = (i - h) * page - segs[i] * w
            if isinstance(shift, int):
                shift %= lanes
                piece = pltpu.roll(words[i], shift, axis) if shift else words[i]
            else:   # shift > -2 * lanes
                piece = pltpu.roll(words[i], (shift + 2 * lanes) % lanes, axis)
            out = piece if out is None else jnp.where(at == i, piece, out)
        heads.append(out)
    return jnp.stack(heads)


def _kernel(table_ref, len_ref, q0_ref, q_ref, pos_ref, scale_ref, k_hbm,
            v_hbm, o_ref, kbuf, vbuf, sem, m_ref, l_ref, *, kh: int,
            g: int, page: int, npp: int, ppt: int, rb: int,
            window: int, cap: float, scale: float):
    b, r = pl.program_id(0), pl.program_id(1)
    w, ppr = page_lanes(kh, page)
    n = len_ref[b]
    n_pages = (n + page - 1) // page
    n_blk = (n_pages + ppt - 1) // ppt

    def run(blk, slot, op):
        """Start (or wait for) the copies of the block's live pages of
        K and V into buffer ``slot``."""
        first = blk * ppt

        def copy(j, carry):
            row = table_ref[b * npp + first + j] // ppr
            for hbm, buf, i in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                getattr(pltpu.make_async_copy(
                    hbm.at[row, 0], buf.at[slot, j], sem.at[slot, i]),
                    op)()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(n_pages - first, ppt), copy, 0)

    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    @pl.when(n_blk > 0)
    def _first():
        run(0, 0, "start")

    def block(blk, carry):
        slot = blk % 2
        run(blk, slot, "wait")

        @pl.when(blk + 1 < n_blk)
        def _next():
            run(blk + 1, 1 - slot, "start")

        first = blk * ppt
        # a page past the table's end (the last block of a slot whose
        # npp is no multiple of ppt) is never live: any shift will do
        segs = ([0] * ppt if ppr == 1 else
                [table_ref[b * npp + jnp.minimum(first + i, npp - 1)] % ppr
                 for i in range(ppt)])
        # K and V rotated and decoded together: (kh, 2, 64 * dw, R)
        tiles = _head_tiles(jnp.stack([kbuf[slot], vbuf[slot]], axis=1),
                            segs, kh=kh, page=page, w=w)
        bits = _decode_bits(tiles.reshape((2 * kh,) + tiles.shape[2:]))
        bits = bits.reshape((kh, 2) + bits.shape[1:])
        _attend_block(bits[:, 0], bits[:, 1], pos_ref[blk],
                      scale_ref[blk], q_ref, o_ref, m_ref, l_ref,
                      first=first * page, n=n, q0=q0_ref[b], r=r, g=g,
                      rb=rb, window=window, cap=cap, scale=scale)
        return carry

    jax.lax.fori_loop(0, n_blk, block, 0)
    l = l_ref[...]
    o_ref[...] = jnp.where(l > 0, o_ref[...] / jnp.where(l > 0, l, 1.0), 0.0)


def _attend_block(kx, vx, pos_k, scales, q_ref, o_ref, m_ref, l_ref,
                  *, first, n, q0, r, g, rb, window, cap, scale):
    """The online-softmax update of every kv head by one block: head
    tiles (kh, 64 * dw, R) of K and V bits, the tokens' positions
    ``pos_k`` (1, R) and K and V scales ``scales`` (2, R) in (page,
    token) lanes; ``first`` the block's first token index.  Lanes past
    the block's pages carry ``INVALID_POS``."""
    lanes = pos_k.shape[-1]
    tok = first + jax.lax.broadcasted_iota(jnp.int32, (1, 1, lanes), 2)
    row = r * rb + jax.lax.broadcasted_iota(jnp.int32, (1, rb, 1), 1)
    pos_q = q0 + row // g                                   # (1, rb, 1)
    pos_k = pos_k[None]                                     # (1, 1, R)
    scales = scales[None]                                   # (1, 2, R)
    valid = (tok < n) & (pos_k != INVALID_POS) & (pos_k <= pos_q)
    if window:
        valid &= (pos_q - pos_k) < window                   # (1, rb, R)

    dims = (((2,), (1,)), ((0,), (0,)))
    s = jax.lax.dot_general(q_ref[...], kx, dims,
                            preferred_element_type=jnp.float32)
    s = s * (scales[:, 0:1] * scale)                        # (kh, rb, R)
    if cap:
        s = cap * jnp.tanh(s / cap)
    s = jnp.where(valid, s, _NEG)
    m_prev = m_ref[...]                                     # (kh, rb, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
    m_ref[...] = m_new
    # alpha_v * p in f32, into a bf16 MXU as two terms (high, rest): the
    # {0, 1} bits are exact, so the mix keeps f32 precision
    pv = jnp.where(valid, p * scales[:, 1:2], 0.0)   # a dead token's scale
    dims = (((2,), (2,)), ((0,), (0,)))
    hi = pv.astype(jnp.bfloat16)
    lo = (pv - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    acc = (jax.lax.dot_general(hi, vx, dims,
                               preferred_element_type=jnp.float32)
           + jax.lax.dot_general(lo, vx, dims,
                                 preferred_element_type=jnp.float32))
    o_ref[...] = corr * o_ref[...] + acc


def _attend(q, k_planes, v_planes, k_scale, v_scale, pos, table, p0,
            nvalid, *, window: int, cap: float):
    """The kernel on one shard: q (B, S, kh, G, dh) at positions ``p0[b]
    + s``; plane pools (n_rows, 1, 2dw, R), the shard's group of kv
    heads; the other leaves without the period dim."""
    assert k_planes.shape[1] == 1, (
        f"{k_planes.shape[1]} kv-head groups on one shard: a pool is read "
        f"under the mesh it was built under")
    b, s, kh, g, dh = q.shape
    rows, lanes = k_planes.shape[-2:]
    dw, page, npp = rows // 2, pos.shape[-1], table.shape[-1]
    w, ppr = page_lanes(kh, page)
    assert lanes == w * ppr, (k_planes.shape, q.shape, page)
    ext = 64 * dw
    # a block: the pages whose kv head's tokens fill one R-lane tile
    ppt = min(lanes // page, npp)
    n_blk = -(-npp // ppt)
    lens = jnp.where(nvalid > 0, jnp.minimum(p0 + nvalid, npp * page), 0)
    lens = lens.astype(jnp.int32)

    # queries: rows (s, g) of each kv head, in whole sublane tiles, head
    # dim in the kernel's bit order (bit j of word w of the plus/minus
    # plane), minus rows negated; the MXU takes them as bf16, exact for
    # bf16 queries
    rows_q = s * g
    rb = min(_ROW_BLOCK, -(-rows_q // 8) * 8)
    n_rb = -(-rows_q // rb)
    qh = q.transpose(0, 2, 1, 3, 4).reshape(b, kh, rows_q, dh)
    qh = jnp.pad(qh, ((0, 0), (0, 0), (0, n_rb * rb - rows_q),
                      (0, 32 * dw - dh)))
    qh = qh.reshape(b, kh, n_rb * rb, dw, 32).swapaxes(-1, -2)
    qx = jnp.stack([qh, -qh], axis=-2).reshape(b, kh, n_rb * rb, ext)
    qx = qx.astype(jnp.bfloat16)

    # per-token metadata of every table entry, (slot, block, row, lane):
    # positions (int32: as f32 bits a TPU would flush them, denormals,
    # to 0), and K and V scales; lanes past a block's pages are filler
    def meta(rows, fill):
        x = jnp.stack(rows, axis=1).reshape(b, len(rows), npp * page)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, n_blk * ppt * page - npp * page)),
                    constant_values=fill)
        x = x.reshape(b, len(rows), n_blk, ppt * page)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, lanes - ppt * page)),
                    constant_values=fill)
        return x.transpose(0, 2, 1, 3)

    pos_g = meta([pos[table]], INVALID_POS)
    scale_g = meta([k_scale[table], v_scale[table]], 0.0)

    any_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    q_spec = pl.BlockSpec((None, kh, rb, ext), lambda bi, ri, *_: (bi, 0, ri, 0))

    # a slot's metadata, every block of it (12 bytes a token)
    def meta_spec(x):
        return pl.BlockSpec((None,) + x.shape[1:],
                            lambda bi, ri, *_: (bi, 0, 0, 0))

    kernel = functools.partial(
        _kernel, kh=kh, g=g, page=page, npp=npp, ppt=ppt,
        rb=rb, window=window, cap=cap, scale=dh ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_rb),
            in_specs=[q_spec, meta_spec(pos_g), meta_spec(scale_g),
                      any_spec, any_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, ppt, rows, lanes), jnp.uint32),
                pltpu.VMEM((2, ppt, rows, lanes), jnp.uint32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kh, rb, 1), jnp.float32),
                pltpu.VMEM((kh, rb, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, kh, n_rb * rb, ext), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(None),
    )(table.reshape(-1), lens, p0.astype(jnp.int32), qx, pos_g, scale_g,
      k_planes, v_planes)

    # back to the head dim's order: plus rows less minus rows
    out = out[:, :, :rows_q].reshape(b, kh, s, g, 32, 2, dw)
    out = out[..., 0, :] - out[..., 1, :]                # (b,kh,s,g,32,dw)
    out = out.transpose(0, 2, 1, 3, 5, 4).reshape(b, s, kh, g, 32 * dw)
    return out[..., :dh]


def paged_attention(q: jnp.ndarray, entry: Dict[str, Any], p0: jnp.ndarray,
                    nvalid: jnp.ndarray, *, window: int = 0, cap: float = 0.0
                    ) -> jnp.ndarray:
    """Attention of S new tokens per slot over a packed paged entry.

    q (B, S, KVp, G, dh) roped queries of the tokens at positions
    ``p0[b] + s``; ``nvalid[b]`` of them are real (0: a dead row, whose
    output is 0).  ``entry`` is a packed page entry without the period
    dim, its new tokens already appended.  Returns (B, S, KVp, G, dh)
    f32.
    """
    args = (q, entry["k_planes"], entry["v_planes"], entry["k_scale"],
            entry["v_scale"], entry["pos"], entry["page_table"],
            p0.astype(jnp.int32), nvalid.astype(jnp.int32))
    fn = functools.partial(_attend, window=window, cap=cap)
    ctx = sharding.active()
    if ctx is None:
        return fn(*args)
    # One kernel per shard: the pool's kv-head groups split over the
    # kv_heads axes as the pool's own sharding splits them (so no shard
    # reads another's pages), slots over the remaining batch axes; the
    # positions and scales stay whole on every shard.
    groups = entry["k_planes"].shape[1]
    heads, batch = sharding.spec_for((groups, q.shape[0]),
                                     ("kv_heads", "batch"), ctx)
    P = jax.sharding.PartitionSpec
    spec_q = P(batch, None, heads, None, None)
    spec_pool = P(None, heads, None, None)
    spec_meta = P(None, None)
    spec_b = P(batch)
    return jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(spec_q, spec_pool, spec_pool, spec_meta, spec_meta,
                  spec_meta, P(batch, None), spec_b, spec_b),
        out_specs=spec_q, check_vma=False)(*args)
