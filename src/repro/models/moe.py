"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Design notes (these matter at the 256-chip scale):

* **Grouped (per-example) routing**: dispatch runs independently per
  batch row, so every sort/scatter is batched over the data-sharded axis
  and lowers to *local* ops — no global sort collectives appear in the
  SPMD partitioning.  Capacity is C = ceil(S * topk / E * cf) per
  example (GShard with group size = one sequence).
* **Sort-based, not one-hot**: the (T, E, C) one-hot dispatch einsum of
  the original GShard costs O(T^2) FLOPs at LM batch sizes; an argsort +
  scatter costs O(T log T + T d) and keeps the roofline's useful-FLOPs
  ratio honest.
* **Capacity dropping** with position priority (stable sort): overflow
  tokens are dropped exactly like GShard/Switch; the combine re-weights
  by the (renormalized) router probabilities.
* Expert projections run through the low-bit pipeline (vmap of
  ``quantized_matmul`` over the expert axis) when the policy asks for it
  — the paper's GeMM applied to each expert's up/gate/down.
* Router stays fp32 (standard for QNN MoEs).

Expert-parallelism note: expert weights are (E, d, f) with f sharded over
the model axis (TP-in-expert), which is divisibility-safe for any expert
count (8/16/60) on the fixed 16-way axis.  True EP (E sharded) is a
sharding-rule option used when E % tp == 0 (see parallel/sharding.py).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import QuantPolicy
from repro.kernels import ops
from repro.kernels.ops import QuantMode
from repro.kernels.qtensor import QTensor
from repro.models.common import ModelConfig
from repro.models.ffn import init_ffn, ffn
from repro.parallel import sharding

__all__ = ["init_moe", "moe_ffn"]


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    std_in, std_out = d ** -0.5, f ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * std_in).astype(jnp.float32),
        "gate": {"w": (jax.random.normal(ks[1], (e, d, f)) * std_in).astype(dtype)},
        "up": {"w": (jax.random.normal(ks[2], (e, d, f)) * std_in).astype(dtype)},
        "down": {"w": (jax.random.normal(ks[3], (e, f, d)) * std_out).astype(dtype)},
    }
    if cfg.shared_expert_d_ff:
        p["shared"] = init_ffn(ks[4], d, cfg.shared_expert_d_ff, dtype)
    return p


def _expert_matmul(w, h: jnp.ndarray, mode: QuantMode,
                   backend: str) -> jnp.ndarray:
    """h (E, C', k) @ w (E, k, n) -> (E, C', n), optionally quantized.

    ``w`` may be a stacked :class:`QTensor` of per-expert bit-planes
    (serving; see models/packing.py) — QTensor is a pytree, so vmap
    slices the expert axis off every leaf directly and each expert runs
    the popcount core."""
    if isinstance(w, QTensor):
        from repro.models.packing import packed_matmul_any
        y = jax.vmap(lambda hh, qt: packed_matmul_any(qt, hh, backend))(h, w)
        return y.astype(h.dtype)
    if isinstance(w, dict):
        w = w["w"]
    if mode in (QuantMode.BF16, QuantMode.F32):
        ct = jnp.bfloat16 if mode == QuantMode.BF16 else jnp.float32
        return jnp.einsum("eck,ekn->ecn", h.astype(ct), w.astype(ct),
                          preferred_element_type=jnp.float32).astype(h.dtype)
    qmm = jax.vmap(lambda a, b: ops.quantized_matmul(
        a.astype(jnp.float32), b.astype(jnp.float32), mode, backend))
    return qmm(h, w).astype(h.dtype)


def moe_ffn(params: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig,
            policy: QuantPolicy) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, D) -> (y (B, S, D), aux_loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    sk = s * k
    cap = max(k, int(-(-s * k * cfg.capacity_factor // e)))
    cap = min(cap, sk)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])                       # fp32 router
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                      # (B,S,K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)      # renormalize

    # ---- dispatch (per example; stable sort => position-priority drop) ----
    e_flat = top_i.reshape(b, sk)
    order = jnp.argsort(e_flat, axis=-1, stable=True)           # (B, SK)
    se = jnp.take_along_axis(e_flat, order, axis=-1)
    counts = jnp.sum(jax.nn.one_hot(e_flat, e, dtype=jnp.int32), axis=1)  # (B,E)
    starts = jnp.cumsum(counts, axis=-1) - counts               # exclusive
    pos = jnp.arange(sk)[None, :] - jnp.take_along_axis(starts, se, axis=-1)
    keep = pos < cap
    dest = se * cap + jnp.clip(pos, 0, cap - 1)                 # (B, SK)
    tok = order // k                                            # source token

    # vmap over the batch row: inside, gather/scatter index only (S, D)
    # tensors, so the SPMD partitioner keeps everything sharded over the
    # batch axis.  (An explicit x[bidx, tok] batched gather defeats the
    # partitioner and all-gathers the full global hidden — 24 GiB/device
    # at mixtral train_4k scale.  Measured; do not regress.)
    def _dispatch(x_s, tok_s, dest_s, keep_s):
        xs = x_s[tok_s] * keep_s[:, None].astype(x_s.dtype)     # (SK, D)
        return jnp.zeros((e * cap, d), x_s.dtype).at[dest_s].add(xs)

    buf = jax.vmap(_dispatch)(x, tok, dest, keep)               # (B, E*C, D)
    buf = sharding.constrain(buf, ("batch", None, None))

    # ---- expert computation (E leading for TP-friendly weight layout) ----
    h_in = buf.reshape(b, e, cap, d).transpose(1, 0, 2, 3).reshape(e, b * cap, d)
    # At decode (s == 1) the dispatch buffers are tiny (B*cap rows) —
    # REPLICATE them over the data axis instead of batch-sharding, so
    # the expert-weight dims can use "data" without a per-step regather
    # (the batch-vs-weight axis conflict measured at jamba decode:
    # 42 GiB/step of expert gathers).  For training/prefill the buffers
    # are huge and batch sharding is the right call.
    tok_axis = None if s == 1 else "batch"
    h_in = sharding.constrain(h_in, ("expert", tok_axis, None))
    mode, backend = policy.ffn_proj, policy.backend_for("ffn_proj")
    g = _expert_matmul(params["gate"], h_in, mode, backend)
    u = _expert_matmul(params["up"], h_in, mode, backend)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    # TP-in-expert: the expert hidden shards over "ffn" (model axis).
    h = sharding.constrain(h, ("expert", tok_axis, "ffn"))
    y_e = _expert_matmul(params["down"], h, mode, backend)  # (E, B*C, D)
    y_buf = y_e.reshape(e, b, cap, d).transpose(1, 0, 2, 3).reshape(b, e * cap, d)

    # ---- combine (vmapped for the same partitioning reason) ----
    w_sorted = jnp.take_along_axis(top_p.reshape(b, sk), order, axis=-1)

    def _combine(y_s, dest_s, tok_s, keep_s, w_s):
        contrib = (y_s[dest_s] * keep_s[:, None].astype(y_s.dtype)
                   * w_s[:, None].astype(y_s.dtype))            # (SK, D)
        return jnp.zeros((s, d), y_s.dtype).at[tok_s].add(contrib)

    y = jax.vmap(_combine)(y_buf, dest, tok, keep, w_sorted)    # (B, S, D)
    y = sharding.constrain(y, ("batch", None, None))

    if cfg.shared_expert_d_ff:
        y = y + ffn(params["shared"], x, policy)

    # ---- load-balancing aux loss (Switch eq. 4) ----
    frac_tokens = jnp.mean(jax.nn.one_hot(top_i, e, dtype=jnp.float32),
                           axis=(0, 1, 2))                      # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_loss
    return y, aux
