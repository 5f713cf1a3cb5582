"""Attention: GQA / sliding-window / softcap, trainable + decode paths.

Projections route through the low-bit GeMM pipeline via the layer's
:class:`QuantPolicy` (the paper's technique applied to QKV/O).

Head layout under tensor parallelism
------------------------------------
The production mesh has a fixed 16-way model axis, but the assigned archs
have head counts like 24 (minitron) or 36 (starcoder2) and KV counts of
4/8.  We make every head dimension shardable by construction:

* KV heads are *replicated* into ``KVp = ceil_to(KV, tp)`` physical slots
  (``copies = KVp / KV`` identical copies per logical head — exactly what
  Megatron does for GQA with tp > kv);
* Q heads are laid out in groups of ``G = ceil((H/KV) / copies)`` per KV
  slot; surplus slots are *padding heads* whose Wq columns and Wo rows are
  zero, so the padded network is output-identical to the logical one
  (softmax over zero scores is uniform, but the zero Wo rows erase the
  contribution).  The FLOP overhead is visible in the roofline
  useful-FLOPs ratio and is a declared hillclimb lever.

With tp=1 the layout is the identity, so smoke tests exercise the same
code with zero overhead.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import QuantPolicy
from repro.kernels import ops
from repro.kernels.ops import QuantMode
from repro.kernels.paged_attention import paged_attention
from repro.kernels.qtensor import QTensor
from repro.models.common import (
    ModelConfig, ShardLayout, apply_rope, ceil_to, rms_norm, softcap,
)
from repro.parallel import sharding

__all__ = ["HeadLayout", "head_layout", "init_attention", "attention",
           "decode_attention", "paged_attention_step", "attend_page_view",
           "project"]


# ---------------------------------------------------------------------------
# Head layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HeadLayout:
    h: int          # logical Q heads
    kv: int         # logical KV heads
    hp: int         # physical Q heads (kvp * g)
    kvp: int        # physical KV slots
    g: int          # Q heads per KV slot
    q_src: Tuple[int, ...]    # physical q slot -> logical q head or -1 (pad)
    kv_src: Tuple[int, ...]   # physical kv slot -> logical kv head


def head_layout(h: int, kv: int, tp: int) -> HeadLayout:
    assert h % kv == 0, f"H={h} must be a multiple of KV={kv}"
    kvp = ceil_to(kv, tp) if tp > 1 else kv
    assert kvp % kv == 0, (
        f"KV={kv} does not divide its padded count {kvp} (tp={tp}); "
        f"choose tp so that ceil_to(kv, tp) is a kv multiple")
    copies = kvp // kv
    qpk = h // kv
    g = -(-qpk // copies)
    hp = kvp * g
    kv_src = tuple(s // copies for s in range(kvp))
    q_src = []
    for s in range(kvp):
        j, t = s // copies, s % copies
        for p in range(g):
            q = t * g + p
            q_src.append(j * qpk + q if q < qpk else -1)
    return HeadLayout(h=h, kv=kv, hp=hp, kvp=kvp, g=g,
                      q_src=tuple(q_src), kv_src=tuple(kv_src))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, layout: ShardLayout,
                   dtype=jnp.float32) -> Dict[str, Any]:
    """Physical (padded) attention weights.

    Random weights go to real head slots; padding slots are zero; KV
    copies are identical — output-exact vs the logical model.
    """
    d, dh = cfg.d_model, cfg.head_dim_
    hl = head_layout(cfg.num_heads, cfg.num_kv_heads, layout.tp)
    ks = jax.random.split(key, 4)
    std = d ** -0.5

    wq_log = jax.random.normal(ks[0], (d, hl.h, dh)) * std
    wk_log = jax.random.normal(ks[1], (d, hl.kv, dh)) * std
    wv_log = jax.random.normal(ks[2], (d, hl.kv, dh)) * std
    wo_log = jax.random.normal(ks[3], (hl.h, dh, d)) * std

    q_src = jnp.array([max(s, 0) for s in hl.q_src])
    q_real = jnp.array([s >= 0 for s in hl.q_src], jnp.float32)
    kv_src = jnp.array(hl.kv_src)

    wq = (wq_log[:, q_src, :] * q_real[None, :, None]).reshape(d, hl.hp * dh)
    wk = wk_log[:, kv_src, :].reshape(d, hl.kvp * dh)
    wv = wv_log[:, kv_src, :].reshape(d, hl.kvp * dh)
    # KV copies mean a logical kv head's V flows through `copies` slots; Wo
    # rows for the real q slots carry the logical rows, pads are zero.
    wo = (wo_log[q_src, :, :] * q_real[:, None, None]).reshape(hl.hp * dh, d)

    p = {"wq": {"w": wq.astype(dtype)}, "wk": {"w": wk.astype(dtype)},
         "wv": {"w": wv.astype(dtype)}, "wo": {"w": wo.astype(dtype)}}
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def project(params: Dict[str, Any] | QTensor, x: jnp.ndarray,
            mode: QuantMode, backend: str) -> jnp.ndarray:
    """QuantLinear forward on a {'w': ...} leaf (no bias), or on a packed
    :class:`QTensor` leaf (the paper's Algorithm 2 offline-packed
    weights, see models/packing.py) — detected by TYPE, with mode/depth/
    scale riding inside the container: serving streams 1/8 (ternary) or
    1/16 (binary) of the bf16 weight bytes per token."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if isinstance(params, QTensor):            # packed low-bit weights
        from repro.models.packing import packed_matmul_any
        y = packed_matmul_any(params, x2, backend)
        return y.reshape(*lead, params.out_features).astype(x.dtype)
    w = params["w"]
    if mode == QuantMode.BF16:
        y = jnp.dot(x2.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    elif mode == QuantMode.F32:
        y = jnp.dot(x2.astype(jnp.float32), w.astype(jnp.float32))
    else:
        y = ops.quantized_matmul(x2.astype(jnp.float32),
                                 w.astype(jnp.float32), mode, backend)
    return y.reshape(*lead, w.shape[-1]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Forward (training / prefill): block-causal attention
# ---------------------------------------------------------------------------

def _qkv(params, x, cfg: ModelConfig, hl: HeadLayout, positions,
         policy: QuantPolicy):
    b, s, _ = x.shape
    dh = cfg.head_dim_
    mode, backend = policy.attn_proj, policy.backend_for("attn_proj")
    # Keep the projection INPUT sequence-sharded: the partitioner would
    # otherwise all-gather the (B,S,D) hidden (2 GiB at chameleon
    # prefill) where gathering the projected q/k/v (head-sharded, 67 MiB)
    # is 15x cheaper.  Measured; do not remove.
    if s > 1:
        x = sharding.constrain(x, ("batch", "seq", None))
    q = project(params["wq"], x, mode, backend).reshape(b, s, hl.hp, dh)
    k = project(params["wk"], x, mode, backend).reshape(b, s, hl.kvp, dh)
    v = project(params["wv"], x, mode, backend).reshape(b, s, hl.kvp, dh)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # Megatron-style: sequence-parallel *between* blocks, head-parallel
    # *inside* attention — one all-gather here, head-sharded score math.
    q = sharding.constrain(q, ("batch", None, "heads", None))
    k = sharding.constrain(k, ("batch", None, "kv_heads", None))
    v = sharding.constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def _block_attend(q_blk, k_ctx, v_ctx, pos_q, pos_k, *, g: int,
                  window: int, cap: float, dh: int):
    """q_blk (B,Sq,HP,dh) vs k/v (B,Sk,KVP,dh) -> (B,Sq,HP,dh).

    Scores in fp32; causal (+ optional window) mask from positions.
    """
    b, sq, hp, _ = q_blk.shape
    kvp = k_ctx.shape[2]
    qg = q_blk.reshape(b, sq, kvp, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k_ctx.astype(jnp.float32)) * (dh ** -0.5)
    scores = softcap(scores, cap)
    mask = pos_q[:, None] >= pos_k[None, :]
    if window:
        mask &= (pos_q[:, None] - pos_k[None, :]) < window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_ctx.astype(jnp.float32))
    return out.reshape(b, sq, hp, dh)


def attention(params, x, positions, cfg: ModelConfig, layout: ShardLayout,
              *, window: int = 0, q_chunk: int = 512,
              cache_update=None) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Causal self-attention over x (B,S,D).

    Queries are processed in static blocks; each block attends only to its
    causal (and windowed) KV prefix via *static* slices, so the lowered
    HLO carries ~S^2/2 (or S*window) attention FLOPs, not S^2.

    If ``cache_update`` is a KV cache dict (prefill), the roped K/V are
    written into it and it is returned alongside the output.
    """
    b, s, d = x.shape
    dh = cfg.head_dim_
    hl = head_layout(cfg.num_heads, cfg.num_kv_heads, layout.tp)
    policy = cfg.policy
    q, k, v = _qkv(params, x, cfg, hl, positions, policy)

    qc = min(q_chunk, s)
    n_blocks = -(-s // qc)
    outs = []
    for i in range(n_blocks):
        q0 = i * qc
        q1 = min(s, q0 + qc)
        kv_hi = q1
        kv_lo = 0
        if window:
            kv_lo = max(0, (q0 - window) // qc * qc)
        q_blk = jax.lax.slice_in_dim(q, q0, q1, axis=1)
        k_ctx = jax.lax.slice_in_dim(k, kv_lo, kv_hi, axis=1)
        v_ctx = jax.lax.slice_in_dim(v, kv_lo, kv_hi, axis=1)
        pos_q = positions[q0:q1]
        pos_k = positions[kv_lo:kv_hi]
        outs.append(_block_attend(q_blk, k_ctx, v_ctx, pos_q, pos_k,
                                  g=hl.g, window=window,
                                  cap=cfg.attn_logit_softcap, dh=dh))
    out = jnp.concatenate(outs, axis=1).astype(x.dtype)
    y = project(params["wo"], out.reshape(b, s, hl.hp * dh),
                policy.attn_proj, policy.backend_for("attn_proj"))

    new_cache = None
    if cache_update is not None:
        lim = cache_update["k"].shape[1]
        if s >= lim:    # ring/window cache smaller than the prefill
            ks, vs = k[:, s - lim:], v[:, s - lim:]
            pw = positions[s - lim:]
            new_cache = {"k": to_cache(ks, cache_update["k"].dtype),
                         "v": to_cache(vs, cache_update["v"].dtype),
                         "pos": jnp.broadcast_to(pw, (b, lim))}
        else:
            nk = jax.lax.dynamic_update_slice_in_dim(
                cache_update["k"], to_cache(k, cache_update["k"].dtype), 0, axis=1)
            nv = jax.lax.dynamic_update_slice_in_dim(
                cache_update["v"], to_cache(v, cache_update["v"].dtype), 0, axis=1)
            npos = jax.lax.dynamic_update_slice_in_dim(
                cache_update["pos"], jnp.broadcast_to(positions, (b, s)), 0, axis=1)
            new_cache = {"k": nk, "v": nv, "pos": npos}
    return y, new_cache


# ---------------------------------------------------------------------------
# int8 KV cache (beyond-paper: the paper's low-bit storage idea applied
# to the *decode-dominant* byte stream).  Post-norm K/V values are O(1);
# a static scale with clip at ~3 sigma is the standard static-range KV
# quantization.  Scores/outputs run int8 x int8 -> int32 so the cache
# streams from HBM at 1 byte per element (the analyzer and the TPU both
# see int8 reads, not a widened copy).
# ---------------------------------------------------------------------------

KV_SCALE = 0.05


def to_cache(x: jnp.ndarray, dtype) -> jnp.ndarray:
    if dtype == jnp.int8:
        return jnp.clip(jnp.round(x.astype(jnp.float32) / KV_SCALE),
                        -127, 127).astype(jnp.int8)
    return x.astype(dtype)


def _int8_scores(qg, nk):
    q8 = jnp.clip(jnp.round(qg.astype(jnp.float32) / KV_SCALE),
                  -127, 127).astype(jnp.int8)
    acc = jnp.einsum("bkgd,blkd->bkgl", q8, nk,
                     preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (KV_SCALE * KV_SCALE)


def _int8_mix(probs, nv):
    p8 = jnp.round(probs * 127.0).astype(jnp.int8)
    acc = jnp.einsum("bkgl,blkd->bkgd", p8, nv,
                     preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (KV_SCALE / 127.0)


# ---------------------------------------------------------------------------
# Decode: one new token against a (possibly ring) KV cache
# ---------------------------------------------------------------------------

def decode_attention(params, x, cfg: ModelConfig, layout: ShardLayout,
                     cache: Dict[str, jnp.ndarray], step: jnp.ndarray,
                     *, window: int = 0) -> Tuple[jnp.ndarray, Dict]:
    """x (B,1,D); cache {k,v: (B,L,KVP,dh), pos: (B,L) int32}; step is a
    scalar or a per-slot (B,) vector (continuous batching decodes slots
    at different positions).

    For full caches L == max_seq; for windowed layers L == window and the
    slot is ``step % L`` (ring buffer).  Per-row cache writes are vmapped
    dynamic_update_slices -> an in-place scatter, never a full-cache
    rewrite.  Returns (y (B,1,D), new cache).
    """
    b, s1, d = x.shape
    assert s1 == 1
    dh = cfg.head_dim_
    hl = head_layout(cfg.num_heads, cfg.num_kv_heads, layout.tp)
    policy = cfg.policy
    step_v = jnp.broadcast_to(jnp.asarray(step, jnp.int32), (b,))
    positions = step_v[:, None]                       # (B, 1)
    q, k, v = _qkv(params, x, cfg, hl, positions, policy)

    l = cache["k"].shape[1]
    slot = jnp.where(jnp.int32(l) > step_v, step_v, step_v % l).astype(jnp.int32)

    def row_write(c, u, s):
        return jax.lax.dynamic_update_slice_in_dim(c, to_cache(u, c.dtype),
                                                   s, axis=0)

    nk = jax.vmap(row_write)(cache["k"], k, slot)
    nv = jax.vmap(row_write)(cache["v"], v, slot)
    npos = jax.vmap(row_write)(cache["pos"], positions.astype(jnp.int32), slot)
    new_cache = {"k": nk, "v": nv, "pos": npos}

    qg = q.reshape(b, hl.kvp, hl.g, dh)
    # Cache operands stream at their STORED width (bf16 or int8) with
    # wide accumulation — an explicit .astype(f32) before the dot would
    # double (or 4x, for int8) the decode cell's dominant memory term.
    if nk.dtype == jnp.int8:
        scores = _int8_scores(qg, nk) * (dh ** -0.5)
    else:
        scores = jnp.einsum("bkgd,blkd->bkgl", qg.astype(nk.dtype), nk,
                            preferred_element_type=jnp.float32) * (dh ** -0.5)
    scores = softcap(scores, cfg.attn_logit_softcap)
    valid = npos <= step_v[:, None]
    if window:
        valid &= (step_v[:, None] - npos) < window
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if nv.dtype == jnp.int8:
        out = _int8_mix(probs, nv)
    else:
        out = jnp.einsum("bkgl,blkd->bkgd", probs.astype(nv.dtype), nv,
                         preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, hl.hp * dh).astype(x.dtype)
    y = project(params["wo"], out, policy.attn_proj, policy.backend_for("attn_proj"))
    return y, new_cache


# ---------------------------------------------------------------------------
# Paged (ternary) cache: chunked-prefill / decode step against the pages
# ---------------------------------------------------------------------------

def paged_attention_step(params, x, cfg: ModelConfig, layout: ShardLayout,
                         entry: Dict[str, jnp.ndarray], step: jnp.ndarray,
                         *, window: int = 0) -> Tuple[jnp.ndarray, Dict]:
    """Write-then-attend over a paged cache entry (models/paged_kvcache).

    x (B,S,D) — S new tokens per slot (S=1 decode, S=prefill_chunk for a
    chunked-prefill call; the two shapes are the engine's only traces).
    ``step`` encodes per-row activity:

    * (B,)  int32 — decode: row b writes ONE token at position step[b];
      step[b] < 0 marks a dead row (free slot / row mid-prefill) that
      writes nothing and whose output is discarded;
    * (B,2) int32 — chunk: row b writes ``step[b,1]`` real tokens at
      positions ``step[b,0] ..``; rows with step[b,1] == 0 are dead.

    Dead/padding tokens scatter into the reserved scratch page with
    ``INVALID_POS``, so one static-shape call serves rows in different
    lifecycle phases without corrupting any live page.  Entries with
    packed planes attend through ``kernels/paged_attention.py`` (only
    live pages read, decoded in VMEM); oracle entries through
    :func:`attend_page_view`.  Both under the scope ``kv_page_view``.
    """
    from repro.models import paged_kvcache as paged
    b, s, d = x.shape
    dh = cfg.head_dim_
    hl = head_layout(cfg.num_heads, cfg.num_kv_heads, layout.tp)
    policy = cfg.policy
    step = jnp.asarray(step, jnp.int32)
    if step.ndim == 2:
        p0, nvalid = step[:, 0], step[:, 1]
    else:
        step_v = jnp.broadcast_to(step, (b,))
        p0 = jnp.maximum(step_v, 0)
        nvalid = jnp.where(step_v >= 0, 1, 0)
    offs = jnp.arange(s, dtype=jnp.int32)
    positions = p0[:, None] + offs[None, :]                    # (B, S)
    live = offs[None, :] < nvalid[:, None]
    q, k, v = _qkv(params, x, cfg, hl, jnp.where(live, positions, 0), policy)
    entry = paged.append_tokens(entry, k, v, positions, live)
    qg = q.reshape(b, s, hl.kvp, hl.g, dh)
    if paged.is_packed(entry):
        # packed planes: the fused kernel reads only live pages
        with jax.named_scope("kv_page_view"):
            out = paged_attention(qg, entry, p0, nvalid, window=window,
                                  cap=cfg.attn_logit_softcap)
    else:
        out = attend_page_view(qg, entry, positions, window=window,
                               cap=cfg.attn_logit_softcap)
    out = out.reshape(b, s, hl.hp * dh).astype(x.dtype)
    y = project(params["wo"], out, policy.attn_proj, policy.backend_for("attn_proj"))
    return y, entry


def attend_page_view(qg: jnp.ndarray, entry: Dict[str, jnp.ndarray],
                     positions: jnp.ndarray, *, window: int = 0,
                     cap: float = 0.0) -> jnp.ndarray:
    """qg (B,S,KVp,G,dh) at ``positions`` (B,S) against the dense
    ``page_view`` of every table entry, scores and mix in f32 einsums ->
    (B,S,KVp,G,dh) f32.  The oracle (bf16 pages) entries' attention, and
    the reference of ``kernels/paged_attention.py`` on packed entries."""
    from repro.models import paged_kvcache as paged
    kvp, dh = qg.shape[2], qg.shape[-1]
    kd, vd, pos_k = paged.page_view(entry, kvp, dh)            # (B,L,KVp,dh)
    scores = jnp.einsum("bskgd,blkd->bkgsl", qg.astype(jnp.float32),
                        kd.astype(jnp.float32)) * (dh ** -0.5)
    scores = softcap(scores, cap)
    valid = pos_k[:, None, :] <= positions[:, :, None]         # (B, S, L)
    if window:
        valid &= (positions[:, :, None] - pos_k[:, None, :]) < window
    scores = jnp.where(valid[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgsl,blkd->bskgd", probs, vd.astype(jnp.float32))
