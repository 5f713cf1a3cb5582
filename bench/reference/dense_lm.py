"""Plain reference of a dense decoder LM with bfloat16 projections.

The model, as a configuration of ``bench/configs`` states it:

* token embedding; ``num_layers`` pre-norm blocks; final RMSNorm; LM head;
* attention: RMSNorm, Q/K/V projections, QK-norm (RMSNorm over the head
  dim) when ``qk_norm``, rotary embedding on split halves, grouped-query
  attention with a causal mask, output projection, residual add;
* FFN: RMSNorm, SwiGLU (``silu(gate) * up``), down projection, residual;
* every projection (``quant_policy`` ``"bf16"``) multiplies bfloat16
  inputs by bfloat16 weights with float32 sums;
* the KV cache (``kv_cache_dtype`` ``"tnn2"``) holds each token's K and
  V ternarized per token over all its KV heads (threshold
  ``0.7 * mean|x|``, scale the mean of the kept magnitudes), so
  attention reads ``alpha * t``.

Activations are stored in the configuration's ``dtype`` (bfloat16)
between operations, as the model states: the output of every norm,
projection, rotary embedding, attention and residual add is rounded to
it; arithmetic inside an operation is float32.

Weights are drawn again from the seed, one layer at a time, by the
benchmark's own generator (``benchkit.weights``), so the reference fits
on the chip beside nothing else and takes nothing the program made.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchkit import weights as W

SUPPORTED = {"layer_pattern": [[["A", "D"]]], "quant_policy": ["bf16"],
             "kv_cache_dtype": ["tnn2"]}


def check(cfg: Dict) -> None:
    for k, v in SUPPORTED.items():
        if cfg.get(k) not in v:
            raise NotImplementedError(
                f"dense_lm reference covers {k} in {v!r}, got {cfg.get(k)!r}")
    for k in ("num_experts", "sliding_window", "attn_logit_softcap",
              "final_logit_softcap"):
        if cfg.get(k):
            raise NotImplementedError(f"dense_lm reference: {k} unsupported")


def dims(cfg: Dict) -> Dict[str, int]:
    d = cfg["d_model"]
    dh = cfg.get("head_dim") or d // cfg["num_heads"]
    vp = -(-cfg["vocab_size"] // 128) * 128
    return {"d": d, "dh": dh, "h": cfg["num_heads"], "kv": cfg["num_kv_heads"],
            "ff": cfg["d_ff"], "vp": vp, "v": cfg["vocab_size"],
            "p": cfg["num_layers"]}


def shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    """Path -> shape of every weight, stacked layers leading."""
    z = dims(cfg)
    d, dh, p = z["d"], z["dh"], z["p"]
    out = {
        "embed": (z["vp"], d),
        "blocks/0/pre_mixer_norm/scale": (p, d),
        "blocks/0/mixer/wq/w": (p, d, z["h"] * dh),
        "blocks/0/mixer/wk/w": (p, d, z["kv"] * dh),
        "blocks/0/mixer/wv/w": (p, d, z["kv"] * dh),
        "blocks/0/mixer/wo/w": (p, z["h"] * dh, d),
        "blocks/0/pre_ffn_norm/scale": (p, d),
        "blocks/0/ffn/gate/w": (p, d, z["ff"]),
        "blocks/0/ffn/up/w": (p, d, z["ff"]),
        "blocks/0/ffn/down/w": (p, z["ff"], d),
        "final_norm/scale": (d,),
        "lm_head/w": (d, z["vp"]),
    }
    if cfg.get("qk_norm"):
        out["blocks/0/mixer/q_norm"] = (p, dh)
        out["blocks/0/mixer/k_norm"] = (p, dh)
    return out


# ------------------------------------------------------------- arithmetic

def bf(x):
    """Round to the activation dtype (bfloat16) and back."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def proj(x, w):
    """bfloat16 inputs and weights, float32 sums: one MXU pass gives
    every product exactly."""
    return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """x (S, H, dh), rotation on split halves."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def kv_ternary(x):
    """(S, KV, dh): per token over all KV heads -> alpha * t."""
    a = jnp.abs(x)
    thr = 0.7 * jnp.mean(a, axis=(-2, -1), keepdims=True)
    mask = a > thr
    alpha = jnp.sum(a * mask, axis=(-2, -1), keepdims=True) / jnp.maximum(
        jnp.sum(mask, axis=(-2, -1), keepdims=True), 1)
    return jnp.sign(x) * mask * alpha


# ---------------------------------------------------------------- layers

def _layer_weights(key, cfg: Dict, layer) -> Dict[str, jnp.ndarray]:
    out = {}
    for path, shape in shapes(cfg).items():
        if not path.startswith("blocks/"):
            continue
        w = W.leaf(key, path, shape[1:], jnp.bfloat16, layer=layer)
        name = path.split("/")[-2] if path.endswith("/w") else path.split("/")[-1]
        if path.endswith("/w"):
            out[name] = w
        else:
            out[path.split("/")[-2] if name == "scale" else name] = \
                w.astype(jnp.float32)
    return out


def _attend(q, k, v, g: int, q_block: int):
    """q (S, H, dh), k/v (S, KV, dh) -> (S, H*dh), causal."""
    s, h, dh = q.shape
    kv = k.shape[1]
    qg = q.reshape(s, kv, g, dh)
    outs = []
    for q0 in range(0, s, q_block):
        q1 = min(s, q0 + q_block)
        sc = jnp.einsum("qkgd,skd->kgqs", qg[q0:q1], k[:q1]) * dh ** -0.5
        mask = jnp.arange(q0, q1)[:, None] >= jnp.arange(q1)[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", pr, v[:q1]).reshape(
            q1 - q0, h * dh))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _layer(key, layer, x, cfg_items):
    cfg = dict(cfg_items)
    z = dims(cfg)
    eps, g = cfg["norm_eps"], z["h"] // z["kv"]
    w = _layer_weights(key, cfg, layer)
    s = x.shape[1]
    pos = jnp.arange(s)

    def one(xs):
        h = bf(rms(xs, w["pre_mixer_norm"], eps))
        q = bf(proj(h, w["wq"])).reshape(s, z["h"], z["dh"])
        k = bf(proj(h, w["wk"])).reshape(s, z["kv"], z["dh"])
        v = bf(proj(h, w["wv"])).reshape(s, z["kv"], z["dh"])
        if cfg.get("qk_norm"):
            q = bf(rms(q, w["q_norm"], eps))
            k = bf(rms(k, w["k_norm"], eps))
        q = bf(rope(q, pos, cfg["rope_theta"]))
        k = bf(rope(k, pos, cfg["rope_theta"]))
        o = bf(_attend(q, kv_ternary(k), kv_ternary(v), g, 512))
        xs = bf(xs + bf(proj(o, w["wo"])))
        h = bf(rms(xs, w["pre_ffn_norm"], eps))
        a = bf(jax.nn.silu(bf(proj(h, w["gate"])))
               * bf(proj(h, w["up"])))
        return bf(xs + bf(proj(a, w["down"])))

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, x)


def _items(cfg: Dict):
    keep = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
            "vocab_size", "num_layers", "norm_eps", "rope_theta", "qk_norm")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


def hidden(key, cfg: Dict, tokens: np.ndarray):
    """tokens (R, S) int32 -> final hidden states (R, S, d), layer by
    layer (weights drawn per layer)."""
    check(cfg)
    z = dims(cfg)
    emb = W.leaf(key, "embed", (z["vp"], z["d"]), jnp.bfloat16)
    x = jnp.take(emb, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    del emb
    items = _items(cfg)
    for layer in range(z["p"]):
        x = _layer(key, layer, x, items)
    return x


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _logits(key, x, cfg_items):
    cfg = dict(cfg_items)
    z = dims(cfg)
    head = W.leaf(key, "lm_head/w", (z["d"], z["vp"]), jnp.bfloat16)
    fn = W.leaf(key, "final_norm/scale", (z["d"],),
                jnp.bfloat16).astype(jnp.float32)
    return proj(rms(x, fn, cfg["norm_eps"]), head)[..., :z["v"]]


def gaps(key, cfg: Dict, hid, targets: np.ndarray):
    """Per position, how far the reference's logit of ``targets`` lies
    below its best (``targets`` < 0: position not compared).  Returns
    (R, S) float32 with NaN where not compared."""
    items = _items(cfg)
    out = []
    for i in range(hid.shape[0]):
        lg = _logits(key, hid[i], items)
        tgt = jnp.asarray(np.maximum(targets[i], 0))
        gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, tgt[:, None], axis=-1)[:, 0]
        out.append(np.where(targets[i] >= 0, np.asarray(gap), np.nan))
    return np.stack(out)


def served_gaps(key, cfg: Dict, seqs: Sequence[Tuple[np.ndarray, List[int]]],
                pad_to: int) -> List[np.ndarray]:
    """For each (prompt, served tokens): the gap of every served token.
    Sequences are padded at the end to ``pad_to`` (causal: padding
    changes no earlier position)."""
    r = len(seqs)
    toks = np.zeros((r, pad_to), np.int32)
    tgts = np.full((r, pad_to), -1, np.int64)
    for i, (prompt, served) in enumerate(seqs):
        full = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        toks[i, :len(full)] = full
        p = len(prompt)
        tgts[i, p - 1:p - 1 + len(served)] = served
    g = gaps(key, cfg, hidden(key, cfg, toks), tgts)
    return [g[i][tgts[i] >= 0] for i in range(r)]
