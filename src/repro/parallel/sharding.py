"""Logical-axis sharding rules with shape-aware divisibility fallback.

The production mesh is fixed — (16, 16) "data" x "model" per pod, with an
optional leading "pod" axis — but the assigned architectures have head
counts, vocab sizes and batch sizes that do not all divide every axis.
Rather than hand-writing 40 sharding configs, every tensor names its dims
with *logical* axes and :func:`spec_for` resolves them:

* a logical axis maps to one or more mesh axes (rule table);
* a mesh axis is applied only if it divides the dim size and was not
  already used by another dim of the same tensor;
* anything else falls back to replication.

So ``batch=1`` (long_500k) silently replicates, ``seq=4096`` gets
sequence-parallelism over "model", padded head counts shard 16-way, and
all 40 (arch x shape) dry-run cells lower without per-cell surgery.

Parameters are resolved by *path* (``param_spec``), so models never carry
a parallel axis-annotation pytree.
"""

from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["Rules", "TRAIN_RULES", "SERVE_RULES", "SERVE_RULES_LOWBIT",
           "PREFILL_RULES",
           "use_mesh", "active", "spec_for", "constrain", "constrain_spec",
           "param_spec", "named_sharding", "param_shardings",
           "payload_plane_axes"]

AxisRule = Union[None, str, Tuple[str, ...]]


class Rules:
    """logical axis name -> mesh axes (in preference order)."""

    def __init__(self, table: Dict[str, AxisRule]):
        self.table = dict(table)

    def mesh_axes(self, logical: Optional[str]) -> Tuple[str, ...]:
        r = self.table.get(logical)
        if r is None:
            return ()
        return (r,) if isinstance(r, str) else tuple(r)

    def replaced(self, **kw) -> "Rules":
        t = dict(self.table)
        t.update(kw)
        return Rules(t)


# Training: ZeRO-3/FSDP over "data" for weights, TP over "model",
# sequence-parallel hidden states, batch over pod x data.
TRAIN_RULES = Rules({
    "batch": ("pod", "data"),
    "seq": "model",            # sequence parallelism between blocks
    "embed": None,             # hidden size (activations)
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ffn": "model",
    "vocab": "model",
    "expert": None,            # flip to "model" for true EP (E % tp == 0)
    "fsdp": "data",            # weight dim sharded ZeRO-3 style
    "ssm_heads": "model",
    "conv_dim": "model",
})

# Serving, dense archs: classic weight-stationary TP — weights live
# TP-16-sharded (fits every dense arch: chameleon-34B = 4.25 GiB/dev
# bf16), batch over data, per-step collectives are only the tiny
# attention/ffn output psums.  We measured the alternatives at
# chameleon decode_32k (EXPERIMENTS.md §Perf cell C): sharding the ffn
# weight dim over ("model","data") re-gathers 22 MiB/matmul (3.1
# GiB/step); sharding the contraction (d) dim over "data" cannot avoid
# gathers either, because the batch is data-sharded and no pure-psum
# schedule exists.
SERVE_RULES = TRAIN_RULES.replaced(fsdp=None, seq=None)

# Serving, MoE archs: expert weights do NOT fit TP-16 (mixtral:
# 15.75 GiB/dev) — shard the expert ffn dim over both axes and pay the
# per-step data-axis regather (the price of fitting; measured 3 GiB/step
# at mixtral decode).  Dense (shared/attention) weights stay TP-only.
SERVE_RULES_MOE = SERVE_RULES.replaced(ffn=("model", "data"))

# Serving, offline-packed low-bit archs (QTensor payloads): unlike the
# dense case above, FSDP-style sharding of the *packed* planes over
# "data" is free at decode — the bit-plane words are 1/8 (ternary) to
# 1/16 (binary) of the bf16 weight bytes, activations enter the
# mesh-aware qmm replicated (parallel/qmm_mesh.py), and the only
# per-step collective is a psum over int16/int32 partial counts, not a
# weight regather.  Column-parallel planes (wq/wk/wv/gate/up) keep
# n-sharding over "model"; row-parallel planes (wo/down) k-word-shard
# over "model"; this ruleset additionally spreads the k words of the
# column-parallel planes over "data".
SERVE_RULES_LOWBIT = SERVE_RULES.replaced(fsdp="data")

# Prefill: like serving but context-parallel — a 32k prompt's residual
# stream is sharded over "model" between blocks (2 GiB/dev -> 128 MiB/dev
# for chameleon prefill_32k); attention gathers K/V per block internally.
PREFILL_RULES = SERVE_RULES.replaced(seq="model")

# FSDP-only training (§Perf hillclimb lever): NO tensor parallelism —
# the "model" axis joins "data" as pure data parallelism (batch 256 ->
# 1 row/device) and weights shard over both axes ZeRO-3 style, gathered
# at use.  Napkin math for why this wins on small-d models: Megatron-TP
# moves ~6 * B_local*S*D bytes of activations per layer per step across
# the model axis, FSDP moves ~2 * layer_weight_bytes; at tinyllama scale
# (D=2048, B_local*S = 64k tokens) activations outweigh weights ~8x.
TRAIN_RULES_FSDP = TRAIN_RULES.replaced(
    batch=("pod", "data", "model"),
    seq=None, heads=None, kv_heads=None, ffn=None, vocab="model",
    fsdp=("data", "model"), ssm_heads=None, conv_dim=None)

# Hybrid (§Perf iteration 2): data-parallel attention (its weights are
# small, its TP activation all-reduces are not), tensor-parallel expert
# FFNs (their weights dominate the byte budget).
TRAIN_RULES_HYBRID = TRAIN_RULES.replaced(
    seq=None, heads=None, kv_heads=None)

# True expert parallelism for serving archs whose expert count divides
# the model axis (jamba: E=16): each model-shard owns whole experts,
# dispatch moves ACTIVATIONS (all-to-all, ~2 MiB at decode) instead of
# re-gathering expert weights (43 GiB/step measured at jamba decode).
SERVE_RULES_EP = SERVE_RULES.replaced(expert="model", ffn="data",
                                      heads=None, kv_heads=None)

RULESETS = {
    "train": TRAIN_RULES,
    "prefill": PREFILL_RULES,
    "serve": SERVE_RULES,
    "serve_lowbit": SERVE_RULES_LOWBIT,
    "serve_ep": SERVE_RULES_EP,
    "train_fsdp": TRAIN_RULES_FSDP,
    "train_hybrid": TRAIN_RULES_HYBRID,
}


class _Active:
    def __init__(self, mesh: Mesh, rules: Rules):
        self.mesh = mesh
        self.rules = rules
        self.axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))


_ACTIVE: contextvars.ContextVar[Optional[_Active]] = \
    contextvars.ContextVar("repro_mesh", default=None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Rules = TRAIN_RULES):
    tok = _ACTIVE.set(_Active(mesh, rules))
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _ACTIVE.reset(tok)


def active() -> Optional[_Active]:
    return _ACTIVE.get()


def spec_for(shape: Sequence[int], logical_axes: Sequence[Optional[str]],
             ctx: Optional[_Active] = None) -> P:
    """Resolve logical axes -> PartitionSpec with divisibility fallback."""
    ctx = ctx or active()
    if ctx is None:
        return P(*([None] * len(shape)))
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    used = set()
    out = []
    for dim, logical in zip(shape, logical_axes):
        assigned = []
        for ax in ctx.rules.mesh_axes(logical):
            size = ctx.axis_sizes.get(ax)
            if size is None or ax in used:
                continue
            cur = int(np.prod([ctx.axis_sizes[a] for a in assigned], initial=1))
            if dim % (cur * size) == 0:
                assigned.append(ax)
                used.add(ax)
        if not assigned:
            out.append(None)
        elif len(assigned) == 1:
            out.append(assigned[0])
        else:
            out.append(tuple(assigned))
    return P(*out)


def named_sharding(shape, logical_axes, ctx=None) -> Optional[NamedSharding]:
    ctx = ctx or active()
    if ctx is None:
        return None
    return NamedSharding(ctx.mesh, spec_for(shape, logical_axes, ctx))


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op outside use_mesh()."""
    ctx = active()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec_for(x.shape, logical_axes, ctx)))


def constrain_spec(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint by explicit PartitionSpec (e.g. from
    param_spec, for gradients); no-op outside use_mesh()."""
    ctx = active()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding by path
# ---------------------------------------------------------------------------

# (path regex, logical axes per dim) — first match wins (with a rank
# check).  Paths look like "blocks/0/mixer/wq/w" (joined tree path).
# The payload/(plus|minus|bits) entries cover OFFLINE-PACKED projection
# weights (QTensor leaves, models/packing.py): planes are (n, k/32)
# uint32 with n = the weight's output dim, scales are (n,).  The payload
# segment is optional so legacy dict-packed trees resolve identically.
_PARAM_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"embed$",              ("vocab", "fsdp")),
    (r"lm_head/w$",          ("fsdp", "vocab")),
    (r"(wq|wk|wv)/w$",       ("fsdp", "heads")),
    (r"wo/w$",               ("heads", "fsdp")),
    (r"router$",             ("fsdp", None)),
    (r"(gate|up)/w$",        ("fsdp", "ffn")),          # dense FFN (2D)
    (r"down/w$",             ("ffn", "fsdp")),
    (r"in_proj/w$",          ("fsdp", "conv_dim")),
    (r"out_proj/w$",         ("ssm_heads", "fsdp")),
    (r"conv_w$",             (None, "conv_dim")),
    (r"conv_b$",             ("conv_dim",)),
    (r"(A_log|D|dt_bias)$",  ("ssm_heads",)),
    # ssm gated norm (din,).  Anchored to a path segment: the moment
    # fallback of param_spec strips "/scale", and "pre_mixer_norm" must
    # not match — sharding an RMSNorm scale splits the norm's sum of
    # squares over devices, another summation order than one chip's.
    (r"(^|/)norm$",          ("conv_dim",)),
    # ---- packed bit-planes (serving) ----
    (r"(wq|wk|wv)/(?:payload/)?(plus|minus|bits)$", ("heads", "fsdp")),
    (r"(wq|wk|wv)/scale$",   ("heads",)),
    (r"wo/(?:payload/)?(plus|minus|bits)$", (None, "heads")),
    (r"wo/scale$",           (None,)),
    (r"(gate|up)/(?:payload/)?(plus|minus|bits)$", ("ffn", "fsdp")),
    (r"(gate|up)/scale$",    ("ffn",)),
    (r"(gate|up)/scale$",    ("expert", "ffn")),        # expert scales (2D)
    (r"down/(?:payload/)?(plus|minus|bits)$", (None, "ffn")),
    (r"down/scale$",         (None,)),
    (r"down/scale$",         ("expert", None)),
    (r"in_proj/(?:payload/)?(plus|minus|bits)$", ("conv_dim", "fsdp")),
    (r"in_proj/scale$",      ("conv_dim",)),
    (r"out_proj/(?:payload/)?(plus|minus|bits)$", (None, "ssm_heads")),
    (r"out_proj/scale$",     (None,)),
)

# MoE expert tensors are 3D; matched before the 2D rules by rank check.
_PARAM_RULES_3D: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"(gate|up)/w$",        ("expert", "fsdp", "ffn")),
    (r"down/w$",             ("expert", "ffn", "fsdp")),
    (r"(gate|up)/(?:payload/)?(plus|minus|bits)$", ("expert", "ffn", None)),
    (r"down/(?:payload/)?(plus|minus|bits)$", ("expert", None, "ffn")),
)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            # GetAttrKey — custom pytree nodes (QTensor.payload/.scale/…)
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _match_rules(s: str, leaf, ndim: int, ctx) -> Optional[P]:
    if ndim == 3:
        for pat, axes in _PARAM_RULES_3D:
            if re.search(pat, s):
                return spec_for(leaf.shape, axes, ctx)
    for pat, axes in _PARAM_RULES:
        if re.search(pat, s) and len(axes) == ndim:
            return spec_for(leaf.shape, axes, ctx)
    # scanned (stacked-over-periods) params carry a leading period dim.
    if ndim >= 1 and re.search(r"blocks/", s):
        for pat, axes in (_PARAM_RULES_3D if ndim == 4 else ()):
            if re.search(pat, s):
                return P(*((None,) + tuple(spec_for(leaf.shape[1:], axes, ctx))))
        for pat, axes in _PARAM_RULES:
            if re.search(pat, s) and len(axes) == ndim - 1:
                return P(*((None,) + tuple(spec_for(leaf.shape[1:], axes, ctx))))
    return None


def param_spec(path, leaf, ctx: Optional[_Active] = None) -> P:
    s = _path_str(path)
    ndim = np.ndim(leaf) if not hasattr(leaf, "ndim") else leaf.ndim
    # Direct rules first — the packed QTensor scale leaves ("wq/scale",
    # (n,)) have their own entries and must not be mistaken for moments.
    spec = _match_rules(s, leaf, ndim, ctx)
    if spec is not None:
        return spec
    # int8-quantized optimizer moments (optim.adamw.Q8): the q/scale
    # leaves keep the parameter's rank, so the parameter's own rule
    # applies — strip the trailing component and resolve normally (the
    # ZeRO-3 moment shards exactly like its parameter; scale's reduced
    # last dim falls back to replicated via the divisibility check).
    if s.endswith("/.q") or s.endswith("/q") \
            or s.endswith("/.scale") or s.endswith("/scale"):
        spec = _match_rules(s.rsplit("/", 1)[0], leaf, ndim, ctx)
        if spec is not None:
            return spec
    return P(*([None] * ndim))


def _single_axis(entry: AxisRule) -> Optional[str]:
    """Collapse a (possibly multi-axis) spec entry to one mesh axis name.

    The mesh-aware qmm partitions each payload-plane dim over at most
    one named axis (axis_index/psum address a single axis); when the
    rule table assigned several, the first (highest-preference) one
    wins and the rest replicate.
    """
    if entry is None or isinstance(entry, str):
        return entry
    return entry[0] if entry else None


def payload_plane_axes(path: str, plane,
                       ctx: Optional[_Active] = None
                       ) -> Optional[Tuple[Optional[str], Optional[str]]]:
    """Mesh axes of a packed payload plane's trailing (n, k-words) dims.

    ``path`` is the joined tree path of the plane leaf (e.g.
    ``"blocks/0/mixer/wq/payload/bits"``), ``plane`` the (…, n, kw)
    uint32 array.  Resolves through the same payload-plane rule table
    as :func:`param_spec` — so the axes recorded on a QTensor
    (``QTensor.pspec``) always agree with the sharding its planes were
    committed with — and returns the last two spec entries collapsed
    to single axis names, or None when no rule matches / no mesh is
    active / both dims replicate.
    """
    ctx = ctx or active()
    if ctx is None:
        return None
    ndim = plane.ndim if hasattr(plane, "ndim") else np.ndim(plane)
    spec = _match_rules(path, plane, ndim, ctx)
    if spec is None or len(tuple(spec)) < 2:
        return None
    n_ax, k_ax = (_single_axis(e) for e in tuple(spec)[-2:])
    if n_ax is None and k_ax is None:
        return None
    return (n_ax, k_ax)


def param_shardings(params, ctx: Optional[_Active] = None):
    """pytree of NamedShardings matching ``params`` (for jit in_shardings)."""
    ctx = ctx or active()
    assert ctx is not None, "param_shardings requires use_mesh()"
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(ctx.mesh, param_spec(path, leaf, ctx)),
        params)
