"""Mesh-aware low-bit qmm: shard-plan resolution, pspec plumbing, and
the 8-device subprocess checks (tests/sharded_check.py via the
session-scoped ``sharded_report`` fixture — multi-device CPU needs the
forced-device-count flag set before jax imports)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels._matmul_common import psum_accum_dtype
from repro.kernels.ops import QuantMode
from repro.kernels.qtensor import QTensor
from repro.launch.mesh import make_mesh
from repro.models import model as model_mod
from repro.models.common import ShardLayout
from repro.models.packing import pack_lm_params
from repro.parallel import qmm_mesh, sharding


class _Ctx:
    """Synthetic active-mesh stand-in with arbitrary axis sizes."""

    def __init__(self, sizes, rules=sharding.SERVE_RULES_LOWBIT):
        self.axis_sizes = dict(sizes)
        self.rules = rules
        self.mesh = None


# ------------------------------------------------------------ unit layer

def test_psum_accum_dtype_narrows_when_safe():
    # |partial| <= 2*k (BNN: popcount in [0, k] scaled by -2): int16
    # carries depths below 2**14, int32 everything else.
    assert psum_accum_dtype(256) == jnp.dtype(jnp.int16)
    assert psum_accum_dtype(2 ** 14 - 32) == jnp.dtype(jnp.int16)
    assert psum_accum_dtype(2 ** 14) == jnp.dtype(jnp.int32)
    assert psum_accum_dtype(1 << 20) == jnp.dtype(jnp.int32)


def test_payload_plane_axes_follow_param_rules():
    ctx = _Ctx({"data": 2, "model": 4})
    bits = jnp.zeros((64, 8), jnp.uint32)
    # column-parallel: n over model, k words over data (serve_lowbit)
    assert sharding.payload_plane_axes(
        "blocks/0/mixer/wq/payload/bits", bits, ctx) == ("model", "data")
    # row-parallel: k words over model — the int-psum path
    assert sharding.payload_plane_axes(
        "blocks/0/mlp/down/payload/minus", bits, ctx) == (None, "model")
    # indivisible dims fall back to replication -> no annotation
    odd = jnp.zeros((63, 7), jnp.uint32)
    assert sharding.payload_plane_axes(
        "blocks/0/mixer/wq/payload/bits", odd, ctx) is None
    # no rule match -> None
    assert sharding.payload_plane_axes(
        "blocks/0/mixer/unknown_leaf", bits, ctx) is None


def test_shard_plan_resolves_against_live_mesh_only():
    w = jnp.asarray(np.random.default_rng(0).standard_normal((256, 64)),
                    jnp.float32)
    qt = QTensor.from_dense(w, QuantMode.TNN)
    ctx = _Ctx({"data": 2, "model": 4})
    assert qmm_mesh.shard_plan(qt, ctx) is None          # never annotated

    sq = qt.replace(pspec=("model", "data"))
    plan = qmm_mesh.shard_plan(sq, ctx)
    assert (plan.n_axis, plan.k_axis) == ("model", "data")
    assert (plan.n_shards, plan.k_shards) == (4, 2)
    assert plan.acc_dtype == "int16"                     # 2*256 < 2**15
    assert qmm_mesh.local_dims(sq, ctx) == (16, 128)

    # axes recorded on a *different* mesh degrade gracefully: unknown or
    # size-1 axes are dead, indivisible axes are dead.
    assert qmm_mesh.shard_plan(qt.replace(pspec=("tp", "ep")), ctx) is None
    assert qmm_mesh.shard_plan(
        sq, _Ctx({"data": 1, "model": 1})) is None
    assert qmm_mesh.shard_plan(
        sq, _Ctx({"data": 2, "model": 5})).n_axis is None  # 64 % 5


def test_qtensor_aux_roundtrips_pspec():
    w = jnp.ones((64, 32), jnp.float32)
    qt = QTensor.from_dense(w, QuantMode.BNN).replace(pspec=("model", None))
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.pspec == ("model", None)
    # and an unannotated container stays distinguishable (new trace key)
    assert jax.tree_util.tree_structure(qt) != \
        jax.tree_util.tree_structure(qt.replace(pspec=None))


def test_pack_lm_params_records_pspec_on_1x1_mesh():
    """Packing under a real (1, 1) mesh exercises the annotation plumbing
    end to end: axes are recorded (size-1 axes divide everything) but the
    mesh dispatch stays inert (shard_plan rejects size-1 axes), so the
    packed tree must serve exactly like the unsharded one."""
    cfg = get_smoke("tinyllama-1.1b").with_(dtype=jnp.float32,
                                            quant_policy="tnn")
    layout = ShardLayout(tp=1)
    params = model_mod.init_lm(jax.random.PRNGKey(0), cfg, layout)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    with sharding.use_mesh(mesh, sharding.SERVE_RULES_LOWBIT):
        packed = pack_lm_params(params, cfg)
        qts = [t for t in jax.tree_util.tree_flatten(
                   packed, is_leaf=lambda t: isinstance(t, QTensor))[0]
               if isinstance(t, QTensor)]
        assert qts and all(t.pspec is not None for t in qts)
        assert all(qmm_mesh.shard_plan(t) is None for t in qts)
    # a minimal 2-D projection packed the same way serves identically
    # inside and outside the (inert) mesh scope
    from repro.kernels import ops
    w = jax.random.normal(jax.random.PRNGKey(1), (cfg.d_model, cfg.d_model))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, cfg.d_model))
    with sharding.use_mesh(mesh, sharding.SERVE_RULES_LOWBIT):
        qt_m = pack_lm_params({"wq": {"w": w}}, cfg)["wq"]
        assert qt_m.pspec is not None
        y_mesh = np.asarray(ops.qmm(x, qt_m, backend="xla"))
    qt_p = pack_lm_params({"wq": {"w": w}}, cfg)["wq"]
    assert qt_p.pspec is None
    np.testing.assert_array_equal(
        y_mesh, np.asarray(ops.qmm(x, qt_p, backend="xla")))


# ----------------------------------------------- 8-device subprocess layer

def test_sharded_qmm_matches_single_device_oracle(sharded_report):
    assert sharded_report["qmm_sharded_matches_oracle"] == "ok", \
        sharded_report["qmm_sharded_matches_oracle"]


def test_k_shard_reduction_psums_integers(sharded_report):
    assert sharded_report["k_psum_is_integer"] == "ok", \
        sharded_report["k_psum_is_integer"]


def test_sharded_qconv_matches_single_device_oracle(sharded_report):
    assert sharded_report["qconv_sharded_matches_oracle"] == "ok", \
        sharded_report["qconv_sharded_matches_oracle"]


def test_watchdog_rebuild_migrates_inflight_requests(sharded_report):
    """Rebuild with work in flight: queued + mid-decode requests all
    migrate to the new engine and resolve there with status "ok" and
    the single-device tokens (docs/resilience.md)."""
    assert sharded_report["watchdog_rebuild_inflight"] == "ok", \
        sharded_report["watchdog_rebuild_inflight"]


def test_paged_attention_kernel_shards_over_kv_heads(sharded_report):
    """The packed paged cache's attention kernel under an 8-device mesh:
    one kernel per (kv-head, slot) shard, the single-device output, and
    a tnn2 mesh engine that decodes the single-device tokens."""
    assert sharded_report["paged_attention_sharded"] == "ok", \
        sharded_report["paged_attention_sharded"]
