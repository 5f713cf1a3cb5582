"""Weights and inputs made on the device from ``--seed``.

Every leaf is drawn from its own key (the seed, the leaf's path, and
for stacked layers the layer index), so the whole tree comes from one
jitted call and the plain reference can draw one layer again, alone,
with the same values.  Matrices are N(0, 1/fan_in); norm scales are 1.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

NORM_LEAVES = ("scale", "q_norm", "k_norm")


def base_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def _path_id(path: str) -> int:
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def leaf(key, path: str, shape, dtype, layer=None):
    """One leaf (or one layer of a stacked leaf) from the seed key."""
    name = path.split("/")[-1]
    if name in NORM_LEAVES:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, _path_id(path))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    fan_in = shape[-1] if path == "embed" else shape[-2]
    return (jax.random.normal(k, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def stacked(path: str) -> bool:
    return path.startswith("blocks/")


def make_tree(key, shapes):
    """Build a parameter tree of ``shapes`` (ShapeDtypeStructs)."""
    def make(p, s):
        path = path_str(p)
        if stacked(path):
            return jnp.stack([leaf(key, path, s.shape[1:], s.dtype, layer=i)
                              for i in range(s.shape[0])])
        return leaf(key, path, s.shape, s.dtype)
    return jax.tree_util.tree_map_with_path(make, shapes)


def layer_tree(key, shapes, layer: int) -> Dict[str, Any]:
    """Layer ``layer`` of the stacked block leaves, plus nothing else."""
    out = {}
    for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        path = path_str(p)
        if stacked(path):
            out[path] = leaf(key, path, s.shape[1:], s.dtype, layer=layer)
    return out


# ----------------------------------------------------------------- CNN

def cnn_weights(key, cfg: Dict, layer: int):
    """Weights of layer ``layer``, f32 N(0, 1/fan_in): conv filters
    (k, k, cin, cout) or a fully connected (cin, cout) matrix."""
    from .counts import cnn_layers
    L = cnn_layers(cfg)[layer]
    shape = ((L["k"], L["k"], L["cin"], L["cout"]) if L["kind"] == "conv"
             else (L["cin"], L["cout"]))
    k = jax.random.fold_in(jax.random.fold_in(key, _path_id("conv")), layer)
    fan_in = 1
    for d in shape[:-1]:
        fan_in *= d
    return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5


def cnn_images(key, cfg: Dict, batch: int, index: int):
    k = jax.random.fold_in(jax.random.fold_in(key, _path_id("images")), index)
    return jax.random.normal(
        k, (batch, cfg["img_size"], cfg["img_size"], cfg["c_in"]), jnp.float32)
