"""Dense-backend MXU fusion (kernels/dense_fused.py) vs the
materializing oracles, plus the pack-time positional conv weight layout.

The fused dense kernels unpack bit-plane words to ±1/0 bf16 tiles in
VMEM and feed ``jnp.dot`` — float32 accumulation of ±1/0 products is
exact (integers < 2^24), and the eq. (2) epilogue uses the same multiply
order as the unfused chain, so outputs must be **bit-identical**
(array_equal, not allclose) to

* gemm: quantize_activations + the unfused materializing dense kernel +
  the float scale epilogue (three separate passes);
* conv: the materializing ``im2col + ops.qmm`` oracle
  (``conv2d_packed(fused=False)``), which shares ``conv_act_stats``.

Also covered: retrace guards (one trace per shape / conv geometry on the
dense backend), the registry invariant that no Pallas/MXU compute path
opts out of autotuning, dense plan consultation at trace time, and the
positional weight payload stored at pack time for ``Cin % 32 != 0``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv
from repro.kernels import conv_fused, ops, registry
from repro.kernels.dense_fused import dense_matmul_fused_pallas
from repro.kernels.ops import QuantMode
from repro.kernels.qtensor import PAYLOAD_KEYS, POS_PAYLOAD_KEYS, QTensor
from repro.tune import cache as plan_cache
from repro.tune import tuner
from repro.tune.__main__ import main as tune_cli

MODES = [QuantMode.TNN, QuantMode.TBN, QuantMode.BNN]

# k not a word multiple, m/n off the block grid, aligned control, and a
# multi-k-block shape (block_kw clamps make num_k > 1 under tiny tiles).
SHAPES = [
    (5, 96, 7),
    (16, 33, 8),      # k == 33: one full word + 1 trailing bit
    (37, 129, 24),
    (64, 256, 32),    # aligned control
]

CONV_CASES = [
    # (x shape,        filter shape,   stride, padding)
    ((2, 7, 6, 9),     (3, 3, 9, 4),   1, "SAME"),
    ((2, 8, 8, 32),    (3, 3, 32, 8),  2, "SAME"),
    ((1, 9, 11, 5),    (3, 3, 5, 7),   1, "VALID"),
    ((1, 10, 10, 3),   (5, 5, 3, 6),   2, "SAME"),
    ((1, 6, 6, 33),    (1, 1, 33, 4),  1, "SAME"),
]


@pytest.fixture
def tcache(tmp_path):
    prev_env = os.environ.get(plan_cache.ENV_CACHE_PATH)
    cache = plan_cache.set_cache_path(str(tmp_path / "plans.json"))
    yield cache
    plan_cache.set_policy("off")
    plan_cache.set_cache_path(prev_env)


def _unfused_dense_oracle(x, qt, bias=None):
    """The three-pass chain over the MATERIALIZING dense kernel — the
    independent reference the in-VMEM kernels must match bit for bit."""
    xa = ops.quantize_activations(x, qt.mode)
    acc = ops.packed_matmul(xa, qt, backend="dense")
    y = acc.astype(jnp.float32) * xa["scale"] * qt.scale[None, :]
    if bias is not None:
        y = y + bias[None, :]
    return y


# ---------------------------------------------------------------------------
# registry invariants
# ---------------------------------------------------------------------------

def test_dense_fused_registered_for_both_layouts():
    for mode in MODES:
        for layout in (registry.LAYOUT_GEMM, registry.LAYOUT_IM2COL):
            spec = registry.lookup(mode, "dense", fused=True, layout=layout)
            assert spec.compute == "mxu-dense"
            assert spec.epilogue == "in-kernel"
            assert spec.tunable is not None
        # the materializing oracle stays as the unfused entry
        oracle = registry.lookup(mode, "dense", fused=False)
        assert oracle.compute == "mxu-xla" and oracle.tunable is None


def test_no_kernel_compute_path_opts_out_of_tuning():
    """Every KernelSpec with a Pallas/MXU compute path — anything that
    applies its epilogue in-kernel or drives the MXU from a fused kernel
    — must declare a TuningSpace: ``tunable=None`` silently opts out of
    per-shape tiling."""
    specs = registry.available()
    assert specs
    for spec in specs:
        if spec.epilogue == "in-kernel" or spec.compute == "mxu-dense":
            assert spec.tunable is not None, spec.key
    # the registry matrix is closed: every fused (mode, backend, layout)
    # cell is tunable, but the xla conv cells, whose int8 convolution
    # XLA tiles itself
    for spec in registry.available(fused=True):
        if spec.compute == "mxu-int8":
            assert spec.key[1:] == ("xla", True, registry.LAYOUT_IM2COL)
            assert spec.tunable is None, spec.key
        else:
            assert spec.tunable is not None, spec.key


# ---------------------------------------------------------------------------
# gemm: bit-exact vs the unfused materializing oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("shape", SHAPES)
def test_dense_fused_gemm_bit_exact(mode, shape, rng):
    m, k, n = shape
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (m, k), jnp.float32)
    qt = ops.pack_weights(jax.random.normal(k2, (k, n), jnp.float32), mode)
    want = np.asarray(_unfused_dense_oracle(x, qt))
    got = np.asarray(ops.qmm(x, qt, backend="dense"))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(
        got, want, err_msg=f"{mode} {shape}: in-VMEM dense kernel diverged "
                           f"from the materializing oracle")


@pytest.mark.parametrize("mode", MODES)
def test_dense_fused_gemm_bias_epilogue(mode, rng):
    """Bias rides the in-kernel epilogue.  allclose (not array_equal):
    XLA contracts the in-kernel ``acc * r * c + bias`` into an FMA while
    the three-dispatch oracle rounds the multiply first — a 1-ULP
    divergence the popcount kernels' bias test also tolerates (the
    scale-only epilogue stays bit-identical, asserted above)."""
    m, k, n = 9, 70, 11
    k1, k2, k3 = jax.random.split(rng, 3)
    x = jax.random.normal(k1, (m, k), jnp.float32)
    bias = jax.random.normal(k3, (n,), jnp.float32)
    qt = ops.pack_weights(jax.random.normal(k2, (k, n), jnp.float32), mode)
    want = np.asarray(_unfused_dense_oracle(x, qt, bias))
    got = np.asarray(ops.qmm(x, qt.replace(bias=bias), backend="dense"))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mode", MODES)
def test_dense_fused_matches_popcount_backends_bit_exact(mode, rng):
    """Same integer core (±1/0 sums), same epilogue order — the dense
    kernel must agree with the xla popcount backend to the bit, not just
    to float tolerance."""
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (13, 85), jnp.float32)
    qt = ops.pack_weights(jax.random.normal(k2, (85, 17), jnp.float32), mode)
    np.testing.assert_array_equal(
        np.asarray(ops.qmm(x, qt, backend="dense")),
        np.asarray(ops.qmm(x, qt, backend="xla")))


@pytest.mark.parametrize("mode", MODES)
def test_dense_fused_multi_kstep_epilogue(mode, rng):
    """Tiny k blocks force num_k > 1: the in-kernel epilogue must fire
    exactly once, after the float accumulator has seen every k block —
    and BNN's pad mask must track the k grid position."""
    m, k, n = 20, 320, 12     # 10 words -> num_k = 5 at block_kw=2
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (m, k), jnp.float32)
    qt = ops.pack_weights(jax.random.normal(k2, (k, n), jnp.float32), mode)
    want = np.asarray(_unfused_dense_oracle(x, qt))
    xa = ops.quantize_activations(x, mode)
    a_pl = tuple(xa[key] for key in ops._A_KEYS[mode])
    row = ops._as_row_scale(xa["scale"], m)
    col = ops._as_col_vec(qt.scale, n)
    got = dense_matmul_fused_pallas(
        mode, a_pl, ops._b_planes(qt, mode), k, row, col, None,
        block_m=8, block_n=128, block_kw=2, word_chunk=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_bnn_pad_mask_covers_ragged_depth(rng):
    """BNN zero pad bits decode to +1 on both operands — exactly the
    case the in-kernel A-side mask exists for.  k one past a word
    boundary maximizes the pad run."""
    for k in (1, 31, 33, 65):
        k1, k2 = jax.random.split(jax.random.fold_in(rng, k))
        x = jax.random.normal(k1, (6, k), jnp.float32)
        qt = ops.pack_weights(jax.random.normal(k2, (k, 5), jnp.float32),
                              QuantMode.BNN)
        np.testing.assert_array_equal(
            np.asarray(ops.qmm(x, qt, backend="dense")),
            np.asarray(_unfused_dense_oracle(x, qt)), err_msg=f"k={k}")


# ---------------------------------------------------------------------------
# im2col_fused: bit-exact vs the materializing conv oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", CONV_CASES,
                         ids=[f"{c[0]}x{c[1]}s{c[2]}{c[3]}"
                              for c in CONV_CASES])
def test_dense_conv_fused_bit_exact(mode, case):
    xs, fs, stride, padding = case
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    x = jax.random.normal(k1, xs)
    f = jax.random.normal(k2, fs)
    packed = conv.pack_conv_filters(f, mode)
    want = conv.conv2d_packed(x, packed, stride=stride, padding=padding,
                              backend="dense", fused=False)
    got = conv.conv2d_packed(x, packed, stride=stride, padding=padding,
                             backend="dense", fused=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want),
        err_msg=f"{mode} dense {case}: fused conv diverged from the "
                f"materializing oracle")


@pytest.mark.parametrize("mode", MODES)
def test_dense_conv_bias_epilogue_bit_exact(mode, rng):
    xs, fs, stride, padding = CONV_CASES[0]
    k1, k2, k3 = jax.random.split(rng, 3)
    x = jax.random.normal(k1, xs)
    f = jax.random.normal(k2, fs)
    bias = jax.random.normal(k3, (fs[-1],))
    packed = conv.pack_conv_filters(f, mode, bias=bias)
    want = conv.conv2d_packed(x, packed, backend="dense", fused=False)
    got = conv.conv2d_packed(x, packed, backend="dense", fused=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# retrace guards: one trace per shape / conv geometry
# ---------------------------------------------------------------------------

def test_dense_qmm_single_trace_per_shape(rng):
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (12, 64), jnp.float32)
    qt = ops.pack_weights(jax.random.normal(k2, (64, 16), jnp.float32),
                          QuantMode.TNN)
    ops.qmm(x, qt, backend="dense").block_until_ready()     # warm
    before = ops.qmm_trace_count(QuantMode.TNN, "dense")
    for _ in range(4):
        ops.qmm(x, qt, backend="dense").block_until_ready()
    assert ops.qmm_trace_count(QuantMode.TNN, "dense") == before, \
        "dense qmm retraced on a repeated shape"


def test_dense_qconv_single_trace_per_geometry(rng):
    k1, k2 = jax.random.split(rng)
    f = jax.random.normal(k1, (3, 3, 6, 8))
    x = jax.random.normal(k2, (2, 7, 7, 6))
    packed = conv.pack_conv_filters(f, QuantMode.TNN)
    conv.conv2d_packed(x, packed, backend="dense").block_until_ready()
    before = ops.qconv_trace_count(QuantMode.TNN, "dense")
    for _ in range(4):
        conv.conv2d_packed(x, packed, backend="dense").block_until_ready()
    assert ops.qconv_trace_count(QuantMode.TNN, "dense") == before, \
        "dense qconv retraced on a repeated conv geometry"
    conv.conv2d_packed(x[:, :5], packed, backend="dense")
    assert ops.qconv_trace_count(QuantMode.TNN, "dense") == before + 1


# ---------------------------------------------------------------------------
# autotuning coverage
# ---------------------------------------------------------------------------

def test_dense_tune_one_measures_candidates(tcache):
    plan, report = tuner.tune_one(QuantMode.TNN, "dense", fused=True,
                                  m=8, n=32, k=96, reps=1, warmup=1)
    assert plan.source == "tuned" and plan.backend == "dense"
    assert len(report["candidates"]) >= 2       # default + alternatives
    assert report["best_index"] >= 0


def test_dense_dispatch_consults_plan_cache(tcache):
    """A cached dense plan with a distinctive blocking must change what
    tiles=None dispatch lowers — and match an explicit tiles= call."""
    from repro.kernels._matmul_common import DEFAULT_TILES, TileConfig

    m, n, k = 16, 128, 256
    tuned = TileConfig(block_m=8, block_n=128, block_kw=2, word_chunk=1)
    tcache.put(plan_cache.Plan(
        mode=QuantMode.TNN, backend="dense", fused=True,
        device_kind=plan_cache.device_kind(),
        m_bucket=plan_cache.bucket_m(m), n=n, k=k, tiles=tuned))
    spec = registry.lookup(QuantMode.TNN, "dense", fused=True)
    a_pl, b_pl, row, col = tuner._make_problem(QuantMode.TNN, m, n, k, 0)

    def jx(tiles):
        return str(jax.make_jaxpr(lambda: spec.fn(
            a_pl, b_pl, k, row, col, None, tiles=tiles))())

    assert jx(None) == jx(tuned)
    assert jx(None) != jx(DEFAULT_TILES["tnn"])


def test_dense_tuning_preserves_numerics(tcache, rng):
    k1, k2 = jax.random.split(rng)
    w = jax.random.normal(k1, (96, 24))
    x = jax.random.normal(k2, (10, 96))
    for mode in MODES:
        qt = ops.pack_weights(w, mode)
        y0 = np.asarray(ops.qmm(x, qt, backend="dense"))
        tuner.ensure_plan(mode, "dense", fused=True, m=10, n=24, k=96,
                          reps=1, warmup=1)
        y1 = np.asarray(ops.qmm(x, ops.pack_weights(w, mode),
                                backend="dense"))
        np.testing.assert_array_equal(y0, y1, err_msg=str(mode))


def test_cli_dense_sweep_second_run_byte_identical(tcache, capsys):
    argv = ["--shapes", "8x32x96", "--conv-shapes", "1x6x6x8x16x3",
            "--modes", "tnn", "--backends", "dense",
            "--reps", "1", "--warmup", "1", "--cache", tcache.path]
    assert tune_cli(argv) == 0
    out1 = capsys.readouterr().out
    assert "measured=2" in out1
    assert "tnn/dense/fused" in out1
    assert "im2col_fused/3x3s1same" in out1
    bytes1 = open(tcache.path, "rb").read()
    assert b'"backend": "dense"' in bytes1
    assert tune_cli(argv) == 0
    out2 = capsys.readouterr().out
    assert "measured=0" in out2 and "cached=2" in out2
    assert open(tcache.path, "rb").read() == bytes1


# ---------------------------------------------------------------------------
# positional conv weight payload (pack-time layout)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_positional_planes_stored_and_zero_copy(mode, rng):
    f = jax.random.normal(rng, (3, 3, 9, 4))        # cin % 32 != 0
    qt = conv.pack_conv_filters(f, mode)
    pos_keys = POS_PAYLOAD_KEYS[mode]
    assert all(k in qt.payload for k in pos_keys)
    planes = conv_fused.conv_weight_planes(qt)
    # zero-copy: the resolved planes ARE the stored payload leaves
    for plane, key in zip(planes, pos_keys):
        assert plane is qt.payload[key]


@pytest.mark.parametrize("mode", MODES)
def test_positional_planes_match_in_trace_repack(mode, rng):
    """The pack-time layout must be bit-identical to what the legacy
    in-trace repack derives from the contiguous-k payload."""
    f = jax.random.normal(rng, (3, 3, 9, 4))
    qt = conv.pack_conv_filters(f, mode)
    contiguous = tuple(qt.payload[k] for k in PAYLOAD_KEYS[mode])
    repacked = conv_fused._conv_weight_planes(contiguous, mode, qt.geometry)
    stored = conv_fused.conv_weight_planes(qt)
    assert len(repacked) == len(stored)
    for a, b in zip(stored, repacked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_word_multiple_cin_stores_no_extra_payload(rng):
    f = jax.random.normal(rng, (3, 3, 32, 8))       # cin % 32 == 0
    qt = conv.pack_conv_filters(f, QuantMode.TNN)
    assert sorted(qt.payload) == ["minus", "plus"]
    planes = conv_fused.conv_weight_planes(qt)
    assert planes[0] is qt.payload["plus"]          # contiguous IS positional


def test_legacy_dict_drops_positional_and_conv_stays_exact(rng):
    """to_legacy_dict stays at the legacy key set; a container migrated
    back (no positional payload) routes through the in-trace repack and
    produces bit-identical conv outputs on every backend."""
    k1, k2 = jax.random.split(rng)
    f = jax.random.normal(k1, (3, 3, 5, 4))
    x = jax.random.normal(k2, (1, 6, 6, 5))
    qt = conv.pack_conv_filters(f, QuantMode.TNN)
    legacy = qt.to_legacy_dict()
    assert not any(k.startswith("pos_") for k in legacy)
    migrated = QTensor.from_legacy_dict(legacy, QuantMode.TNN)
    assert not any(k.startswith("pos_") for k in migrated.payload)
    for backend in ("xla", "pallas", "dense"):
        np.testing.assert_array_equal(
            np.asarray(conv.conv2d_packed(x, migrated, backend=backend)),
            np.asarray(conv.conv2d_packed(x, qt, backend=backend)),
            err_msg=backend)


def test_positional_payload_checkpoints_and_jits(rng):
    """The extra payload leaves flow through jit like any other leaf —
    a conv QTensor with positional planes is a valid pytree argument."""
    k1, k2 = jax.random.split(rng)
    f = jax.random.normal(k1, (3, 3, 9, 4))
    x = jax.random.normal(k2, (1, 5, 5, 9))
    qt = conv.pack_conv_filters(f, QuantMode.TBN)
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    qt2 = jax.tree_util.tree_unflatten(treedef, leaves)
    np.testing.assert_array_equal(
        np.asarray(conv.conv2d_packed(x, qt2, backend="dense")),
        np.asarray(conv.conv2d_packed(x, qt, backend="dense")))
