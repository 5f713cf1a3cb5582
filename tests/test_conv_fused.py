"""Fused-im2col conv kernels (registry layout ``im2col_fused``) vs the
materializing ``im2col + ops.qmm`` oracle.

The fused kernels quantize + pack activations inside the kernel / trace
and gather packed patch words on the fly; the oracle materializes the
float patch matrix first.  Both consume the same per-tensor activation
statistics (``conv_fused.conv_act_stats``), so their outputs must be
**bit-identical** — asserted with array_equal, not allclose — for every
mode x backend x stride/padding/odd-geometry case.  The ``xla`` cell
convolves int8 ±1/0 codes with int32 sums and must also equal the
``pallas`` popcount cell.  Plus: dispatch (conv2d_packed auto-selects
the fused kernel), the retrace guard (one trace per conv geometry),
autotuning plans for conv problems (the ``pallas`` cell; the ``xla``
cell has no tuning space), and the engine/CLI integration points.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv
from repro.kernels import conv_fused, ops, registry
from repro.kernels.ops import QuantMode
from repro.tune import cache as plan_cache
from repro.tune import tuner
from repro.tune.__main__ import main as tune_cli

MODES = [QuantMode.TNN, QuantMode.TBN, QuantMode.BNN]
BACKENDS = ["xla", "pallas", "dense"]

# stride / padding / geometry coverage: odd channel counts (per-position
# repack path), word-aligned channels (zero-copy path), 1x1 kernels,
# strides that leave ragged SAME padding.
CASES = [
    # (x shape,        filter shape,   stride, padding)
    ((2, 7, 6, 9),     (3, 3, 9, 4),   1, "SAME"),
    ((2, 8, 8, 32),    (3, 3, 32, 8),  2, "SAME"),
    ((1, 9, 11, 5),    (3, 3, 5, 7),   1, "VALID"),
    ((1, 10, 10, 3),   (5, 5, 3, 6),   2, "SAME"),
    ((1, 6, 6, 33),    (1, 1, 33, 4),  1, "SAME"),
]


def _data(case, seed=0):
    xs, fs, stride, padding = case
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, xs), jax.random.normal(k2, fs),
            stride, padding)


@pytest.fixture
def tcache(tmp_path):
    prev_env = os.environ.get(plan_cache.ENV_CACHE_PATH)
    cache = plan_cache.set_cache_path(str(tmp_path / "plans.json"))
    yield cache
    plan_cache.set_policy("off")
    plan_cache.set_cache_path(prev_env)


# ---------------------------------------------------------------------------
# registry surface
# ---------------------------------------------------------------------------

def test_registry_has_im2col_fused_entries():
    for mode in MODES:
        for backend in BACKENDS:
            spec = registry.lookup(mode, backend, fused=True,
                                   layout=registry.LAYOUT_IM2COL)
            assert spec.layout == registry.LAYOUT_IM2COL
            assert spec.fused and spec.fn is not None
            assert ops.has_conv_kernel(mode, backend)
            if backend == "xla":                 # XLA tiles its own conv
                assert spec.compute == "mxu-int8" and spec.tunable is None
            else:
                assert spec.tunable is not None  # ROADMAP: no silent opt-out
    # the conv entries never shadow the GeMM entries
    for mode in MODES:
        for backend in BACKENDS:
            assert registry.lookup(mode, backend,
                                   fused=True).layout == registry.LAYOUT_GEMM
    table = registry.capability_table()
    assert "im2col_fused" in table and "layout" in table


# ---------------------------------------------------------------------------
# bit-exact equivalence vs the materializing oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", CASES,
                         ids=[f"{c[0]}x{c[1]}s{c[2]}{c[3]}" for c in CASES])
def test_fused_matches_materializing_oracle_bit_exact(mode, backend, case):
    x, f, stride, padding = _data(case)
    packed = conv.pack_conv_filters(f, mode)
    want = conv.conv2d_packed(x, packed, stride=stride, padding=padding,
                              backend=backend, fused=False)
    got = conv.conv2d_packed(x, packed, stride=stride, padding=padding,
                             backend=backend, fused=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want),
        err_msg=f"{mode} {backend} {case}: fused-im2col diverged from the "
                f"materializing oracle")


@pytest.mark.parametrize("mode", MODES)
def test_fused_bias_epilogue_bit_exact(mode, rng):
    x, f, stride, padding = _data(CASES[0], seed=3)
    bias = jax.random.normal(rng, (f.shape[-1],))
    packed = conv.pack_conv_filters(f, mode, bias=bias)
    for backend in BACKENDS:
        want = conv.conv2d_packed(x, packed, backend=backend, fused=False)
        got = conv.conv2d_packed(x, packed, backend=backend, fused=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"{mode} {backend} bias")


# The xla cell against the pallas popcount cell and the oracle: Cin of 3
# and 40 (not word multiples: the positional planes differ from the
# contiguous payload) and 128, SAME borders (BNN's padded taps are +1),
# VALID, stride 2, a bias.
XLA_CASES = [
    # (x shape,        filter shape,     stride, padding, bias)
    ((2, 7, 6, 3),     (3, 3, 3, 8),     1, "SAME",  False),
    ((1, 9, 8, 40),    (3, 3, 40, 16),   1, "VALID", True),
    ((1, 8, 8, 128),   (3, 3, 128, 24),  2, "SAME",  False),
    ((2, 6, 7, 40),    (3, 3, 40, 8),    2, "SAME",  True),
]


def _lax_zero_padded(x, packed, stride, padding):
    """The planted fault: quantize the unpadded input and let lax pad
    the int8 codes with 0, where BNN's padded taps must count +1."""
    kh, kw, _, _ = packed.geometry
    stats = conv_fused.conv_act_stats(x, packed.mode, kh, kw, stride, padding)
    codes = conv_fused.quantize_patch_values(x, packed.mode,
                                             stats.get("thr"))
    acc = jax.lax.conv_general_dilated(
        codes.astype(jnp.int8),
        conv_fused._filter_codes(conv_fused.conv_weight_planes(packed),
                                 packed.mode, packed.geometry),
        (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    cout = packed.geometry[3]
    bias = None if packed.bias is None else ops._as_col_vec(packed.bias, cout)
    return ops._scale_epilogue_f32(acc, stats["scale"].reshape(1, 1),
                                   ops._as_col_vec(packed.scale, cout), bias)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", XLA_CASES,
                         ids=[f"cin{c[0][3]}s{c[2]}{c[3]}"
                              f"{'bias' if c[4] else ''}" for c in XLA_CASES])
def test_xla_int8_conv_matches_popcount_cells(mode, case):
    xs, fs, stride, padding, with_bias = case
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    x, f = jax.random.normal(k1, xs), jax.random.normal(k2, fs)
    bias = jax.random.normal(k3, (fs[-1],)) if with_bias else None
    packed = conv.pack_conv_filters(f, mode, bias=bias)
    want = np.asarray(conv.conv2d_packed(x, packed, stride=stride,
                                         padding=padding, fused=False))
    for backend in ("xla", "pallas"):
        got = conv.conv2d_packed(x, packed, stride=stride, padding=padding,
                                 backend=backend, fused=True)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"{mode} {backend} {case}")
    if mode == QuantMode.BNN and padding == "SAME":
        fault = np.asarray(_lax_zero_padded(x, packed, stride, padding))
        assert fault.shape == want.shape
        assert not np.array_equal(fault, want), \
            "lax's zero padding went unseen at BNN's border taps"


def test_conv2d_packed_dispatches_fused_by_default():
    """With no ``fused=`` argument, conv2d_packed must route low-bit
    convs through ops.qconv (the registered im2col_fused kernel) — the
    zero-API-change dispatch the registry layout tag exists for."""
    x, f, stride, padding = _data(CASES[0], seed=5)
    packed = conv.pack_conv_filters(f, QuantMode.TNN)
    before = ops.qconv_trace_count(QuantMode.TNN, "xla")
    auto = conv.conv2d_packed(x, packed, backend="xla")
    assert ops.qconv_trace_count(QuantMode.TNN, "xla") >= before
    explicit = conv.conv2d_packed(x, packed, backend="xla", fused=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))


def test_qconv_rejects_bad_inputs(rng):
    x4 = jax.random.normal(rng, (1, 5, 5, 8))
    qt_lin = ops.pack_weights(jnp.ones((8, 4), jnp.float32), QuantMode.TNN)
    with pytest.raises(ValueError, match="geometry"):
        ops.qconv(x4, qt_lin)                      # no conv geometry aux
    packed = conv.pack_conv_filters(
        jax.random.normal(rng, (3, 3, 8, 4)), QuantMode.TNN)
    with pytest.raises(ValueError, match="rank 4"):
        ops.qconv(x4[0], packed)
    with pytest.raises(ValueError, match="channel mismatch"):
        ops.qconv(jax.random.normal(rng, (1, 5, 5, 9)), packed)
    with pytest.raises(TypeError):
        ops.qconv(x4, {"bits": None})


# ---------------------------------------------------------------------------
# shared activation statistics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_conv_act_stats_match_materialized_stats(mode, rng):
    """The O(|x|) multiplicity-weighted stats must equal (to float
    tolerance) the stats quantize_activations derives from the
    materialized im2col matrix — same mathematical quantity, summed
    without the kh*kw x duplication."""
    x = jax.random.normal(rng, (2, 9, 7, 5))
    for stride, padding in [(1, "SAME"), (2, "SAME"), (1, "VALID")]:
        a, _ = conv.im2col(x, 3, 3, stride, padding)
        ref = ops.quantize_activations(a, mode)["scale"]
        got = conv_fused.conv_act_stats(x, mode, 3, 3, stride, padding)
        np.testing.assert_allclose(np.asarray(got["scale"]),
                                   np.asarray(ref), rtol=1e-5,
                                   err_msg=f"{mode} {stride} {padding}")
        if mode != QuantMode.BNN:
            thr_ref = 0.7 * jnp.mean(jnp.abs(a))
            np.testing.assert_allclose(np.asarray(got["thr"]),
                                       np.asarray(thr_ref), rtol=1e-5)


# ---------------------------------------------------------------------------
# retrace guard: one trace per conv geometry
# ---------------------------------------------------------------------------

def test_qconv_single_trace_per_geometry(rng):
    k1, k2 = jax.random.split(rng)
    f = jax.random.normal(k1, (3, 3, 6, 8))
    x = jax.random.normal(k2, (2, 7, 7, 6))
    packed = conv.pack_conv_filters(f, QuantMode.TNN)
    conv.conv2d_packed(x, packed, backend="xla")          # warm
    before = ops.qconv_trace_count(QuantMode.TNN, "xla")
    for _ in range(4):
        conv.conv2d_packed(x, packed, backend="xla").block_until_ready()
    assert ops.qconv_trace_count(QuantMode.TNN, "xla") == before, \
        "qconv retraced on a repeated conv geometry"
    # a new image extent is a new geometry -> exactly one more trace
    conv.conv2d_packed(x[:, :5], packed, backend="xla")
    assert ops.qconv_trace_count(QuantMode.TNN, "xla") == before + 1


# ---------------------------------------------------------------------------
# autotuning integration
# ---------------------------------------------------------------------------

def test_conv_plan_roundtrip_and_key(tcache):
    prob = tuner.ConvProblem(batch=2, height=8, width=8, cin=16, cout=32,
                             kernel_h=3, kernel_w=3)
    m, n, k, tag = prob.dims()
    assert (m, n, k, tag) == (2 * 8 * 8, 32, 144, "3x3s1same")
    plan, measured = tuner.ensure_plan(QuantMode.TNN, "pallas", conv=prob,
                                       reps=1, warmup=1)
    assert measured and plan.layout == registry.LAYOUT_IM2COL
    assert plan.geom == "3x3s1same" and "im2col_fused" in plan.key
    # second call: pure cache hit; survives a JSON round-trip
    plan2, measured2 = tuner.ensure_plan(QuantMode.TNN, "pallas", conv=prob)
    assert not measured2 and plan2 == plan
    fresh = plan_cache.PlanCache(tcache.path).load()
    assert fresh.get(plan.key) == plan


def test_conv_dispatch_consults_plan_cache(tcache):
    """A cached conv plan with a distinctive word_chunk must change what
    tiles=None dispatch lowers — and match an explicit tiles= call."""
    prob = tuner.ConvProblem(batch=1, height=6, width=6, cin=8, cout=16,
                             kernel_h=3, kernel_w=3)
    m, n, k, tag = prob.dims()
    from repro.kernels._matmul_common import DEFAULT_TILES, TileConfig
    tuned = TileConfig(word_chunk=2)
    tcache.put(plan_cache.Plan(
        mode=QuantMode.TNN, backend="pallas", fused=True,
        device_kind=plan_cache.device_kind(),
        m_bucket=plan_cache.bucket_m(m), n=n, k=k, tiles=tuned,
        layout=registry.LAYOUT_IM2COL, geom=tag))
    spec = registry.lookup(QuantMode.TNN, "pallas", fused=True,
                           layout=registry.LAYOUT_IM2COL)
    x, b_pl, stats, col = tuner._make_conv_problem(QuantMode.TNN, prob, 0)

    def jx(tiles):
        return str(jax.make_jaxpr(lambda: spec.fn(
            x, b_pl, prob.geometry, prob.stride, prob.padding, stats,
            col, None, tiles=tiles))())

    assert jx(None) == jx(tuned)
    assert jx(None) != jx(DEFAULT_TILES["tnn"])


def test_conv_tuning_preserves_numerics(tcache, rng):
    """A tuned conv plan only re-tiles the schedule — outputs stay
    bit-identical to the untuned dispatch."""
    k1, k2 = jax.random.split(rng)
    f = jax.random.normal(k1, (3, 3, 16, 8))
    x = jax.random.normal(k2, (1, 6, 6, 16))
    packed = conv.pack_conv_filters(f, QuantMode.TBN)
    y0 = np.asarray(conv.conv2d_packed(x, packed, backend="pallas"))
    prob = tuner.ConvProblem.from_input(x.shape, packed.geometry)
    tuner.ensure_plan(QuantMode.TBN, "pallas", conv=prob, reps=1, warmup=1)
    y1 = np.asarray(conv.conv2d_packed(x, packed, backend="pallas"))
    np.testing.assert_array_equal(y0, y1)


def test_on_first_use_policy_tunes_conv_shapes(tcache, rng):
    plan_cache.set_policy("on_first_use")
    k1, k2 = jax.random.split(rng)
    f = jax.random.normal(k1, (3, 3, 8, 16))
    x = jax.random.normal(k2, (1, 5, 5, 8))
    packed = conv.pack_conv_filters(f, QuantMode.TNN)
    conv.conv2d_packed(x, packed, backend="pallas").block_until_ready()
    probs = [p for p in tcache.plans().values()
             if p.layout == registry.LAYOUT_IM2COL]
    assert probs and all(p.source == "tuned" for p in probs)


def test_collect_problems_reports_conv_geometry(rng):
    k1, k2 = jax.random.split(rng)
    params = {
        "proj": ops.pack_weights(jax.random.normal(k1, (32, 8)),
                                 QuantMode.TNN),
        "conv": conv.pack_conv_filters(
            jax.random.normal(k2, (3, 3, 4, 8)), QuantMode.BNN),
    }
    probs = tuner.collect_problems(params)
    assert (QuantMode.TNN, 32, 8, None) in probs
    assert (QuantMode.BNN, 36, 8, (3, 3, 4, 8)) in probs


def test_engine_autotune_sweeps_conv_problems(tcache, rng, monkeypatch):
    """ServeConfig.tune_conv_inputs: an offline sweep must persist
    im2col_fused plans for every conv-packed QTensor in the params at
    the configured input extents (exercised through Engine._autotune's
    own code path, with a minimal stand-in for the engine state, on the
    tunable ``pallas`` conv cell)."""
    from repro.kernels import modes
    from repro.serving.engine import Engine, ServeConfig

    monkeypatch.setattr(modes, "DEFAULT_BACKEND", "pallas")

    params = {"conv": conv.pack_conv_filters(
        jax.random.normal(rng, (3, 3, 8, 16)), QuantMode.TNN)}

    class _Stub:
        pass

    stub = _Stub()
    stub.params = params
    stub.scfg = ServeConfig(num_slots=2, pack_params=True,
                            autotune="offline",
                            tune_conv_inputs=((1, 6, 6),))
    stub._buckets = lambda: [8]
    Engine._autotune(stub)
    plans = plan_cache.PlanCache(tcache.path).load().plans()
    convs = [p for p in plans.values()
             if p.layout == registry.LAYOUT_IM2COL]
    assert convs, "offline sweep produced no conv plans"
    assert all(p.geom == "3x3s1same" and p.source == "tuned"
               for p in convs)


def test_cli_conv_sweep_second_run_byte_identical(tcache, capsys):
    argv = ["--shapes", "8x32x96", "--conv-shapes", "1x6x6x8x16x3",
            "--modes", "tnn", "--backends", "pallas",
            "--reps", "1", "--warmup", "1", "--cache", tcache.path]
    assert tune_cli(argv) == 0
    out1 = capsys.readouterr().out
    assert "measured=2" in out1 and "im2col_fused/3x3s1same" in out1
    bytes1 = open(tcache.path, "rb").read()
    assert tune_cli(argv) == 0
    out2 = capsys.readouterr().out
    assert "measured=0" in out2 and "cached=2" in out2
    assert open(tcache.path, "rb").read() == bytes1


def test_untunable_xla_conv_cell_plans_without_measuring(tcache, monkeypatch):
    """The ``xla`` conv cell declares no tuning space: ``plan_for`` and
    ``ensure_plan`` still answer with a plan for it, and time nothing."""
    def no_timing(*a, **k):
        raise AssertionError("an untunable cell was measured")

    monkeypatch.setattr(tuner, "measure", no_timing)
    prob = tuner.ConvProblem(batch=1, height=6, width=6, cin=8, cout=16,
                             kernel_h=3, kernel_w=3)
    m, n, k, tag = prob.dims()
    reports = {}
    plan, _ = tuner.ensure_plan(QuantMode.TNN, "xla", conv=prob,
                                reports=reports)
    assert plan.backend == "xla" and plan.layout == registry.LAYOUT_IM2COL
    assert plan.geom == tag and plan.source == "default"
    assert all(r.get("untunable") and not r["candidates"]
               for r in reports.values())
    looked_up = plan_cache.plan_for(QuantMode.TNN, "xla", fused=True, m=m,
                                    n=n, k=k, layout=registry.LAYOUT_IM2COL,
                                    geom=tag)
    assert looked_up.tiles == plan.tiles
