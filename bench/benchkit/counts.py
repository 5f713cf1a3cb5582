"""Operations and bytes a kernel call or a model step needs, from shapes.

Operations are dense-equivalent: a (m, k) x (k, n) product counts
``2 * m * k * n`` whatever computes it (popcount, unpack-to-MXU, int8),
so a share of the int8 peak reads the same work on every backend and
cannot pass 100% by construction.  Bytes are what the call has to move
at least once: packed weight planes and scales, activations in and out.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

WORD_BITS = 32
F32 = 4

# weight bit planes per low-bit mode (payload planes of a packed matrix)
PLANES = {"tnn": 2, "tbn": 1, "bnn": 1}


def words(k: int) -> int:
    return -(-k // WORD_BITS)


def qmm_ops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def packed_weight_bytes(k: int, n: int, mode: str) -> int:
    """Bit-plane words plus the per-channel f32 scale."""
    return PLANES[mode] * n * words(k) * 4 + n * F32


def qmm_bytes(m: int, k: int, n: int, mode: str) -> int:
    """f32 activations in, packed weights, f32 out."""
    return m * k * F32 + packed_weight_bytes(k, n, mode) + m * n * F32


def least_time_s(ops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    """Roofline floor of one call against the int8 peak and HBM."""
    return max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"])


def qconv_ops(b: int, oh: int, ow: int, kh: int, kw: int, cin: int,
              cout: int) -> int:
    return 2 * b * oh * ow * kh * kw * cin * cout


def qconv_bytes(b: int, h: int, w: int, oh: int, ow: int, kh: int, kw: int,
                cin: int, cout: int, mode: str) -> int:
    return (b * h * w * cin * F32
            + packed_weight_bytes(kh * kw * cin, cout, mode)
            + b * oh * ow * cout * F32)


# ------------------------------------------------------------------ LM

def lm_projections(cfg: Dict) -> List[Tuple[str, int, int]]:
    """(name, k, n) of one decoder layer's projections."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    dh = cfg.get("head_dim") or d // cfg["num_heads"]
    hq, hkv = cfg["num_heads"] * dh, cfg["num_kv_heads"] * dh
    return [("wq", d, hq), ("wk", d, hkv), ("wv", d, hkv), ("wo", hq, d),
            ("gate", d, ff), ("up", d, ff), ("down", ff, d)]


def lm_layer_params(cfg: Dict) -> int:
    return sum(k * n for _, k, n in lm_projections(cfg))


def lm_token_ops(cfg: Dict, ctx: float, head: bool) -> float:
    """Dense-equivalent operations of one token through the model at
    context ``ctx``: projections, attention scores and mixing
    (``4 * ctx * heads * head_dim`` a layer), and the LM head when the
    token's logits are used."""
    d = cfg["d_model"]
    dh = cfg.get("head_dim") or d // cfg["num_heads"]
    layer = 2 * lm_layer_params(cfg) + 4 * ctx * cfg["num_heads"] * dh
    ops = cfg["num_layers"] * layer
    if head:
        ops += 2 * d * cfg["vocab_size"]
    return ops


# ----------------------------------------------------------------- CNN

def cnn_layers(cfg: Dict) -> List[Dict]:
    """Per layer, in order: the convs (3x3 or ``kernel``, stride 1, SAME
    padding, a 2x2 max-pool after those that have one), then the fully
    connected layers over the flattened feature map; each with its
    precision from ``modes`` and the geometry it runs at."""
    modes = cfg["modes"]
    if len(modes) != len(cfg["convs"]) + len(cfg.get("fcs", [])):
        raise ValueError(f"{len(modes)} modes for "
                         f"{len(cfg['convs']) + len(cfg.get('fcs', []))} layers")
    out, h, w, cin = [], cfg["img_size"], cfg["img_size"], cfg["c_in"]
    for spec in cfg["convs"]:
        out.append({"kind": "conv", "mode": modes[len(out)], "h": h, "w": w,
                    "oh": h, "ow": w, "k": spec.get("kernel", 3),
                    "cin": cin, "cout": spec["c_out"],
                    "pool": bool(spec.get("pool", False))})
        cin = spec["c_out"]
        if spec.get("pool"):
            h, w = h // 2, w // 2
    k = h * w * cin
    for spec in cfg.get("fcs", []):
        out.append({"kind": "fc", "mode": modes[len(out)], "cin": k,
                    "cout": spec["d_out"]})
        k = spec["d_out"]
    return out


def cnn_image_ops(cfg: Dict) -> int:
    """Dense-equivalent operations of one image through every layer."""
    return sum(qconv_ops(1, L["oh"], L["ow"], L["k"], L["k"], L["cin"],
                         L["cout"]) if L["kind"] == "conv"
               else qmm_ops(1, L["cin"], L["cout"]) for L in cnn_layers(cfg))
