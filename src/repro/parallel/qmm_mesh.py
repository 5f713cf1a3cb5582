"""Mesh-aware low-bit matmul: shard packed bit-plane words, psum ints.

This is the paper's accumulate-in-integer design lifted across devices.
A :class:`~repro.kernels.qtensor.QTensor` packed under an active mesh
records the mesh axes of its payload planes' (n, k-words) dims
(``QTensor.pspec``, set by models/packing.py through the payload-plane
rules of parallel/sharding.py).  When :func:`repro.kernels.ops.qmm`
runs inside :func:`repro.parallel.sharding.use_mesh`, it dispatches
here instead of the single-device kernels:

* activations enter the ``shard_map`` **replicated** — per-tensor
  quantization statistics (core/quantize.py returns scalar scales) are
  then identical on every device, so each shard packs bit-identical
  activation planes and no cross-device epilogue disagreement exists;
* **n-sharded** planes (column-parallel: wq/wk/wv/gate/up) run the
  fused kernel on their output slice — no collective at all;
* **k-sharded** planes (row-parallel: wo/down, and the fsdp axis of
  SERVE_RULES_LOWBIT) slice their word range out of the replicated
  activation planes, run the *unfused* popcount core, and all-reduce
  the signed partial counts with ``lax.psum`` **as integers** (int16
  when the depth allows, else int32) — the eq. (2) epilogue (BNN's
  ``k_valid - 2*popcount`` correction, the row x column scales, bias)
  folds in strictly *after* the reduction.

Why the epilogue commutes: the integer partials of disjoint word
ranges sum exactly (integer addition is associative), zero pad words
contribute zero in every encoding, and the single deferred epilogue
uses the same multiply order as the fused single-device kernels — so
k-sharded outputs are bit-identical to the unsharded oracle, and the
reduction moves 2-byte (or 4-byte) counts instead of f32 outputs.

Everything here is trace-time Python dispatch: the mesh, the shard
plan and the tile config are static jit arguments, so a re-sharded
container or a new mesh is a new trace and a stable plan keeps hitting
one trace per shape.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels._matmul_common import TileConfig, psum_accum_dtype
from repro.kernels.modes import QuantMode
from repro.kernels.qtensor import QTensor
from repro.parallel import sharding
from repro import obs

# Host-side psum telemetry (process registry; no-ops when REPRO_OBS=off):
# one reduction per k-sharded qmm_sharded dispatch, wire bytes = the
# per-device integer partial buffer the psum moves (m x n_local x
# itemsize) — the quantity the sharded bench family's wire-bytes ratio
# is computed from.
_PSUM_CTR = obs.get_registry().counter(
    "repro_mesh_psum_total",
    "integer psum reductions issued by qmm_sharded",
    labels=("mode", "acc_dtype"))
_PSUM_BYTES_CTR = obs.get_registry().counter(
    "repro_mesh_psum_wire_bytes_total",
    "bytes moved per device by qmm_sharded psum reductions",
    labels=("mode",))

__all__ = ["ShardPlan", "shard_plan", "shard_plan_conv", "local_dims",
           "qmm_sharded", "qconv_sharded", "qmm_mesh_trace_count"]


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Static description of how one QTensor's planes split over a mesh.

    ``n_axis``/``k_axis`` are mesh axis names (or None) for the payload
    planes' output and k-word dims; ``acc_dtype`` names the integer
    dtype the k-axis psum moves (:func:`psum_accum_dtype`).
    """
    n_axis: Optional[str] = None
    k_axis: Optional[str] = None
    n_shards: int = 1
    k_shards: int = 1
    acc_dtype: str = "int32"


def _live_axis(ctx, ax, dim: int) -> Tuple[Optional[str], int]:
    """Validate a recorded axis against the *currently* active mesh: it
    must exist, have size > 1 and divide ``dim`` (a container packed on
    one mesh may be consumed under another, e.g. after an elastic
    rebuild)."""
    if not isinstance(ax, str):
        return None, 1
    size = ctx.axis_sizes.get(ax)
    if not size or size <= 1 or dim % size != 0:
        return None, 1
    return ax, int(size)


def _first_plane(qt: QTensor):
    from repro.kernels.qtensor import PAYLOAD_KEYS

    return qt.payload[PAYLOAD_KEYS[qt.mode][0]]


def shard_plan(qt: QTensor, ctx=None) -> Optional[ShardPlan]:
    """Resolve the QTensor's recorded ``pspec`` against the active mesh.

    Returns None (single-device dispatch) when no mesh is active, the
    container was never sharded, or no recorded axis is live on this
    mesh — so the mesh path degenerates to the ordinary one instead of
    failing.
    """
    ctx = ctx or sharding.active()
    if ctx is None or qt.pspec is None or not qt.is_lowbit:
        return None
    plane = _first_plane(qt)
    # Trailing (n, kw) dims — stacked-period containers resolve the
    # same way (scan slices the leading dim before qmm ever runs);
    # vmapped expert containers never carry a pspec (models/packing.py).
    n, kw = int(plane.shape[-2]), int(plane.shape[-1])
    n_ax, ns = _live_axis(ctx, qt.pspec[0], n)
    k_ax, ks = _live_axis(ctx, qt.pspec[1], kw)
    if n_ax is None and k_ax is None:
        return None
    acc = psum_accum_dtype(kw * 32)
    return ShardPlan(n_axis=n_ax, k_axis=k_ax, n_shards=ns, k_shards=ks,
                     acc_dtype=jnp.dtype(acc).name)


def shard_plan_conv(qt: QTensor, ctx=None) -> Optional[ShardPlan]:
    """Conv variant: only output-channel (cout) sharding — the fused
    im2col kernels gather patches along k, which does not word-slice."""
    ctx = ctx or sharding.active()
    if ctx is None or qt.pspec is None or not qt.is_lowbit \
            or qt.geometry is None:
        return None
    cout = int(qt.geometry[3])
    n_ax, ns = _live_axis(ctx, qt.pspec[0], cout)
    if n_ax is None:
        return None
    return ShardPlan(n_axis=n_ax, n_shards=ns)


def local_dims(qt: QTensor, ctx=None) -> Optional[Tuple[int, int]]:
    """Per-shard (n_local, k_local) of a sharded container — the problem
    size the autotuner should plan for (the kernels each device actually
    runs see these extents, not the global ones)."""
    plan = shard_plan(qt, ctx)
    if plan is None:
        return None
    kw = int(_first_plane(qt).shape[-1])
    n_local = qt.out_features // plan.n_shards
    k_local = (kw // plan.k_shards) * 32 if plan.k_axis else qt.k_valid
    return (n_local, int(k_local))


# (mode, backend) -> traces of the mesh-aware jitted bodies; like
# ops.qmm_trace_count, a consumer reusing one sharded QTensor across
# calls must keep hitting one trace.
_MESH_TRACES: collections.Counter = collections.Counter()


def qmm_mesh_trace_count(mode: QuantMode, backend: str) -> int:
    return _MESH_TRACES[(mode, backend)]


def _dense_partial(mode: QuantMode, a_loc, b_loc, bit0, k: int):
    """Signed integer partial for the dense (MXU) backend: unpack the
    local word range to ±1/0 values, zero the columns past the logical
    depth (binary pad bits decode to +1), one dot."""
    from repro.core import encoding

    kb = int(a_loc[0].shape[1]) * 32
    if mode == QuantMode.BNN:
        av = encoding.unpack_binary(a_loc[0], kb, jnp.bfloat16)
    else:
        av = encoding.unpack_ternary(a_loc[0], a_loc[1], kb, jnp.bfloat16)
    if mode == QuantMode.TNN:
        bv = encoding.unpack_ternary(b_loc[0], b_loc[1], kb, jnp.bfloat16)
    else:
        bv = encoding.unpack_binary(b_loc[0], kb, jnp.bfloat16)
    mask = ((bit0 + jnp.arange(kb)) < k)[None, :]
    av = av * mask.astype(av.dtype)
    return jnp.dot(av, bv.T,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("backend", "interpret", "mesh", "plan",
                              "tiles"))
def _qmm_mesh_jit(x, qt: QTensor, act_stats, *, backend: str,
                  interpret: bool, mesh: Mesh, plan: ShardPlan,
                  tiles: Optional[TileConfig]):
    from repro.kernels import ops, registry

    _MESH_TRACES[(qt.mode, backend)] += 1    # runs at trace time only
    mode = qt.mode
    m, k = x.shape
    n = qt.out_features
    n_ax, k_ax = plan.n_axis, plan.k_axis
    planes = ops._b_planes(qt, mode)
    kw_local = int(planes[0].shape[1]) // plan.k_shards
    col = ops._as_col_vec(qt.scale, n)
    b2 = None if qt.bias is None else ops._as_col_vec(qt.bias, n)
    acc_dt = jnp.dtype(plan.acc_dtype)
    has_bias, has_stats = b2 is not None, act_stats is not None

    plane_spec = P(n_ax, k_ax)
    col_spec = P(None, n_ax)

    def body(*operands):
        xx, b_pl, col_l = operands[0], operands[1], operands[2]
        i = 3
        bias_l = None
        if has_bias:
            bias_l, i = operands[i], i + 1
        stats_l = operands[i] if has_stats else None
        xa = ops.quantize_activations(xx.astype(jnp.float32), mode,
                                      stats=stats_l)
        row = ops._as_row_scale(xa["scale"], m)
        a_pl = tuple(xa[key] for key in ops._A_KEYS[mode])
        if k_ax is None:
            # Column-parallel only: the fused kernel on this n-slice.
            spec = registry.lookup(mode, backend, fused=True)
            return spec.fn(a_pl, b_pl, k, row, col_l, bias_l,
                           interpret=interpret, tiles=tiles)
        # Row-parallel: this device's word range of the (replicated)
        # activation planes against its resident weight words.
        w0 = jax.lax.axis_index(k_ax) * kw_local
        a_loc = tuple(jax.lax.dynamic_slice_in_dim(p, w0, kw_local, axis=1)
                      for p in a_pl)
        if backend == "dense":
            part = _dense_partial(mode, a_loc, b_pl, w0 * 32, k)
            correction = 0               # true signed dot, no popcount bias
        else:
            # Unfused popcount core with k_valid=0: BNN kernels then
            # return -2*popcount (corrected after the psum), ternary
            # kernels the exact signed partial.
            spec = registry.lookup(mode, backend, fused=False)
            part = spec.fn(a_loc, b_pl, 0, interpret=interpret, tiles=tiles)
            correction = k if mode == QuantMode.BNN else 0
        # THE point of this module: the cross-device reduction moves
        # integer partial counts, never f32 outputs.
        acc = jax.lax.psum(part.astype(acc_dt), k_ax).astype(jnp.int32)
        if correction:
            acc = jnp.int32(correction) + acc
        out = acc.astype(jnp.float32) * row * col_l     # eq. (2), deferred
        return out if bias_l is None else out + bias_l

    args = [x, planes, col]
    specs = [P(None, None), tuple(plane_spec for _ in planes), col_spec]
    if has_bias:
        args.append(b2)
        specs.append(col_spec)
    if has_stats:
        args.append(act_stats)
        specs.append(jax.tree.map(lambda _: P(), act_stats))
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                       out_specs=P(None, n_ax), check_vma=False)
    return fn(*args)


def qmm_sharded(x, qt: QTensor, plan: ShardPlan, mesh: Mesh, *,
                backend: str, interpret: bool | None = None,
                act_stats: Optional[Dict[str, Any]] = None):
    """Mesh-aware qmm entry (called by ops.qmm once a plan resolved).

    Resolves the autotuning plan for the per-shard *local* problem —
    the kernels each device runs see (m, n_local, k_local), so that is
    the shape the plan cache must answer for — then runs the jitted
    shard_map body.
    """
    from repro.tune import cache as tune_cache

    m = int(x.shape[0])
    kw = int(_first_plane(qt).shape[1])
    n_local = qt.out_features // plan.n_shards
    k_local = (kw // plan.k_shards) * 32 if plan.k_axis else qt.k_valid
    fused = plan.k_axis is None          # k-sharding runs the unfused core
    if tune_cache.get_policy() == "on_first_use":
        from repro.tune import tuner

        tuner.ensure_plan(qt.mode, backend, fused=fused, m=m, n=n_local,
                          k=int(k_local), interpret=interpret)
    tiles = tune_cache.plan_for(qt.mode, backend, fused=fused, m=m,
                                n=n_local, k=int(k_local)).tiles
    if plan.k_axis is not None:
        _PSUM_CTR.inc(mode=qt.mode.value, acc_dtype=plan.acc_dtype)
        _PSUM_BYTES_CTR.inc(
            m * n_local * jnp.dtype(plan.acc_dtype).itemsize,
            mode=qt.mode.value)
    return _qmm_mesh_jit(x, qt, act_stats, backend=backend,
                         interpret=interpret, mesh=mesh, plan=plan,
                         tiles=tiles)


@functools.partial(
    jax.jit, static_argnames=("backend", "stride", "padding", "interpret",
                              "mesh", "plan", "tiles"))
def _qconv_mesh_jit(x, qt: QTensor, act_stats, *, backend: str, stride: int,
                    padding: str, interpret: bool, mesh: Mesh,
                    plan: ShardPlan, tiles: Optional[TileConfig]):
    from repro.kernels import conv_fused, ops, registry

    _MESH_TRACES[(qt.mode, backend)] += 1    # runs at trace time only
    spec = registry.lookup(qt.mode, backend, fused=True,
                           layout=registry.LAYOUT_IM2COL)
    kh, kw_, cin, cout = qt.geometry
    geom_local = (kh, kw_, cin, cout // plan.n_shards)
    planes = conv_fused.conv_weight_planes(qt)
    col = ops._as_col_vec(qt.scale, cout)
    b2 = None if qt.bias is None else ops._as_col_vec(qt.bias, cout)
    n_ax = plan.n_axis
    has_bias = b2 is not None

    def body(*operands):
        xx, pl_l, col_l, stats_l = (operands[0], operands[1], operands[2],
                                    operands[-1])
        bias_l = operands[3] if has_bias else None
        return spec.fn(xx.astype(jnp.float32), pl_l, geom_local, stride,
                       padding, stats_l, col_l, bias_l,
                       interpret=interpret, tiles=tiles)

    plane_specs = jax.tree.map(
        lambda p: P(*((n_ax,) + (None,) * (p.ndim - 1))), planes)
    args = [x, planes, col]
    specs = [P(*([None] * x.ndim)), plane_specs, P(None, n_ax)]
    if has_bias:
        args.append(b2)
        specs.append(P(None, n_ax))
    args.append(act_stats)
    specs.append(jax.tree.map(lambda _: P(), act_stats))
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                       out_specs=P(None, None, None, n_ax),
                       check_vma=False)
    return fn(*args)


def qconv_sharded(x, qt: QTensor, plan: ShardPlan, mesh: Mesh, act_stats, *,
                  backend: str, stride: int, padding: str,
                  interpret: bool | None = None):
    """Mesh-aware qconv: each device runs the fused-im2col kernel over
    its cout slice (geometry shrinks to cout_local); the input image and
    the shared activation statistics are replicated, so no collective is
    needed at all."""
    from repro.kernels import conv_fused, registry
    from repro.tune import cache as tune_cache

    m, n, k, tag = conv_fused.conv_problem_dims(x.shape, qt.geometry,
                                                stride, padding)
    n_local = n // plan.n_shards
    tiles = tune_cache.plan_for(qt.mode, backend, fused=True, m=m,
                                n=n_local, k=k,
                                layout=registry.LAYOUT_IM2COL,
                                geom=tag).tiles
    return _qconv_mesh_jit(x, qt, act_stats, backend=backend, stride=stride,
                           padding=padding, interpret=interpret, mesh=mesh,
                           plan=plan, tiles=tiles)
