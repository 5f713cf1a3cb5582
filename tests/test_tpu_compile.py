"""Compile-only guard: the main-path kernels lower for a described TPU v5e.

Nothing here runs on a chip.  A ``v5e:2x2`` topology is described inside
a module-scoped fixture (never at import: only one process at a time may
load the TPU library, and every test worker imports this file), and each
test compiles its program for the described devices with
``interpret=False`` — what Mosaic would refuse on the chip (unaligned
lane slices, block shapes off the (8, 128) tiling, unsupported ops)
fails here at no chip time.  Widths are tinyllama-1.1b's FFN
(k = d_model = 2048, n = d_ff = 5632) at a decode m (8) and a prefill
m (256).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, registry
from repro.kernels._matmul_common import DEFAULT_TILES
from repro.kernels.modes import QuantMode
from repro.kernels.qtensor import QTensor
from repro.parallel import qmm_mesh

K, N = 2048, 5632
LOWBIT = (QuantMode.TNN, QuantMode.TBN, QuantMode.BNN)
_A_PLANES = {QuantMode.BNN: 1, QuantMode.TNN: 2, QuantMode.TBN: 2}
_B_PLANES = {QuantMode.BNN: 1, QuantMode.TNN: 2, QuantMode.TBN: 1}


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep the cache off.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed_struct(mode, sharding, shape=(K, N)):
    """Shape-only QTensor of a (k, n) weight, every leaf on ``sharding``."""
    qt = jax.eval_shape(lambda w: QTensor.from_dense(w, mode),
                        jax.ShapeDtypeStruct(shape, jnp.float32))
    return jax.tree.map(lambda l: _struct(l.shape, l.dtype, sharding), qt)


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("mode", LOWBIT, ids=lambda md: md.value)
@pytest.mark.parametrize("backend", ["pallas", "dense"])
def test_fused_gemm_kernel_compiles_for_v5e(one_chip, backend, mode, m):
    kw = K // 32
    spec = registry.lookup(mode, backend, fused=True)
    a = tuple(_struct((m, kw), jnp.uint32, one_chip)
              for _ in range(_A_PLANES[mode]))
    b = tuple(_struct((N, kw), jnp.uint32, one_chip)
              for _ in range(_B_PLANES[mode]))
    row = _struct((m, 1), jnp.float32, one_chip)
    col = _struct((1, N), jnp.float32, one_chip)

    def fn(a, b, row, col):
        return spec.fn(a, b, K, row, col, None, interpret=False,
                       tiles=DEFAULT_TILES[mode.value])

    compiled = jax.jit(fn).lower(a, b, row, col).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", LOWBIT, ids=lambda md: md.value)
def test_xla_qmm_compiles_for_v5e(one_chip, mode):
    x = _struct((8, K), jnp.float32, one_chip)
    qt = _packed_struct(mode, one_chip)
    compiled = ops._qmm_jit.lower(x, qt, backend="xla",
                                  interpret=False).compile()
    assert compiled.memory_analysis() is not None


def test_xla_qmm_quantizes_a_row_major_activation(one_chip):
    # The xla scan consumes the activation planes word-major, and XLA
    # would otherwise make a fused producer (the FFN's silu(gate) * up)
    # column-major, reordering the per-tensor statistics reductions: the
    # popcount and MXU engines then quantize with different scale bits
    # and their tokens drift apart.
    qt = _packed_struct(QuantMode.TNN, one_chip, shape=(N, K))
    gate = _struct((256, N), jnp.bfloat16, one_chip)

    def down(g, u, q):
        h = (jax.nn.silu(g) * u).astype(jnp.float32)
        return ops._qmm_jit(h, q, backend="xla", interpret=False)

    text = jax.jit(down).lower(gate, gate, qt).compile().as_text()
    layouts = set(re.findall(rf"f32\[256,{N}\]\{{([0-9,]+):", text))
    assert layouts == {"1,0"}, layouts


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("mode", [QuantMode.INT8, QuantMode.INT4],
                         ids=lambda md: md.value)
def test_affine_pallas_qmm_compiles_for_v5e(one_chip, mode, m):
    # The int8 MXU takes int8 operands only: gemmlowp's u8 grids arrive
    # shifted by -128, u4 nibbles split into two int8 dots.
    x = _struct((m, K), jnp.float32, one_chip)
    qt = _packed_struct(mode, one_chip)
    compiled = ops._qmm_jit.lower(x, qt, backend="pallas",
                                  interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_k_sharded_qmm_compiles_on_four_v5e_chips(topo, backend):
    # model=4 splits the 64 words of k=2048 into 16 per shard: the
    # per-device kernel sees a (m, 16) word block, whole on its lane axis.
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    repl = NamedSharding(mesh, P())
    plane = NamedSharding(mesh, P(None, "model"))
    mode = QuantMode.TNN
    qt = _packed_struct(mode, repl)
    qt = qt.replace(payload={key: _struct(v.shape, v.dtype, plane)
                             for key, v in qt.payload.items()},
                    pspec=(None, "model"))
    plan = qmm_mesh.ShardPlan(k_axis="model", k_shards=4, acc_dtype="int16")
    x = _struct((8, K), jnp.float32, repl)
    compiled = qmm_mesh._qmm_mesh_jit.lower(
        x, qt, None, backend=backend, interpret=False, mesh=mesh, plan=plan,
        tiles=DEFAULT_TILES[mode.value]).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    if backend == "pallas":
        assert "tpu_custom_call" in text


# --------------------------------------------------------------------------
# Named scopes (``qmm[<mode>]``, ``qconv[<mode>]``, ``kv_page_view``) are
# metadata only: the program compiled with them is the program compiled
# without them once each instruction's metadata and the source tables
# are stripped.


def _without_metadata(text):
    text = re.sub(r", metadata=\{[^{}]*\}", "", text)
    # keep the module's header line, drop the source tables after it
    return text.split("\n", 1)[0] + text[text.index("\n%"):]


def _compiled_both_ways(monkeypatch, fn, *args):
    import contextlib

    def compiled():     # a new function each time: jit traces afresh
        return jax.jit(lambda *a: fn(*a)).lower(*args).compile().as_text()

    scoped = compiled()
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = compiled()
    return scoped, plain


def test_qmm_scope_is_metadata_only(one_chip, monkeypatch):
    x = _struct((8, K), jnp.float32, one_chip)
    qt = _packed_struct(QuantMode.TNN, one_chip)
    scoped, plain = _compiled_both_ways(
        monkeypatch, lambda a, q: ops.qmm(a, q, backend="xla"), x, qt)
    assert 'qmm[tnn]/' in scoped and "qmm[" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)


def test_qconv_scope_is_metadata_only(one_chip, monkeypatch):
    # the CNN cell's conv 2: batch 1024, 32x32, 128 -> 128 channels, tnn
    from repro.core.conv import pack_conv_filters

    w = jax.ShapeDtypeStruct((3, 3, 128, 128), jnp.float32)
    qt = jax.eval_shape(lambda w: pack_conv_filters(w, QuantMode.TNN), w)
    qt = jax.tree.map(lambda l: _struct(l.shape, l.dtype, one_chip), qt)
    x = _struct((1024, 32, 32, 128), jnp.float32, one_chip)
    scoped, plain = _compiled_both_ways(
        monkeypatch, lambda a, q: ops.qconv(a, q, backend="xla"), x, qt)
    assert 'qconv[tnn]/' in scoped and "qconv[" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)


def _compile_paged_kernel(monkeypatch):
    """The paged attention kernel compiles for the chip here too,
    where the platform decision would interpret it (CPU backend)."""
    from repro.kernels import paged_attention

    monkeypatch.setattr(paged_attention, "resolve_interpret", lambda i: False)


# the LM cell's cache: 32 slots x 2048 positions in 16-token pages, 8 KV
# heads of 128 packed into 4 words a plane, 8 query heads a KV head
SLOTS, NPP, PAGE, KVP, G, DH = 32, 2048 // 16, 16, 8, 8, 128


def _paged_cfg(kvp, dh):
    from repro.models.common import ModelConfig
    return ModelConfig(name="paged", family="dense", num_layers=1,
                       d_model=kvp * 8 * dh, num_heads=kvp * 8,
                       num_kv_heads=kvp, d_ff=1, vocab_size=1,
                       kv_cache_dtype="tnn2", layer_pattern=(("A", "D"),))


def _paged_entry(kvp, dh, slots, max_len, page, sharding):
    """Shape-only packed entry (no period dim), built under the active
    mesh (if any) so its kv-head groups follow it, on ``sharding``."""
    from repro.models import paged_kvcache as paged
    from repro.models.common import ShardLayout

    entry = jax.eval_shape(lambda: {k: v[0] for k, v in paged.init_paged_caches(
        _paged_cfg(kvp, dh), ShardLayout(tp=1), slots, max_len,
        page_size=page)[0].items()})
    return {k: _struct(v.shape, v.dtype, sharding(k, v)) for k, v in
            entry.items()}


@pytest.mark.parametrize("cell,s", [("lm", 1), ("lm", 32), ("tinyllama", 1)],
                         ids=["decode", "chunk", "tinyllama-decode"])
def test_paged_attention_kernel_compiles_for_v5e(one_chip, monkeypatch, cell,
                                                 s):
    # the LM cell: one (8, 128) uint32 tile a page, copies and decode
    # tile-aligned; tinyllama (4 kv heads of 64, 16-token pages): two
    # pages a 128-lane row, each head's tokens rolled by a shift read
    # from the page table
    from repro.kernels.paged_attention import paged_attention

    _compile_paged_kernel(monkeypatch)
    kvp, dh, slots, max_len = ((KVP, DH, SLOTS, NPP * PAGE) if cell == "lm"
                               else (4, 64, 8, 512))
    entry = _paged_entry(kvp, dh, slots, max_len, PAGE,
                         lambda *_: one_chip)
    q = _struct((slots, s, kvp, G, dh), jnp.bfloat16, one_chip)
    rows = _struct((slots,), jnp.int32, one_chip)
    compiled = jax.jit(paged_attention).lower(q, entry, rows, rows).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_on_four_v5e_chips_gathers_no_pages(topo,
                                                            monkeypatch):
    # The LM cell's cache built under a 4-way "model" mesh keeps its kv
    # heads in 4 groups of 2 (32 lanes a page, 4 pages a row), sharded
    # as paged_logical_axes says; the kernel's shard_map splits them the
    # same way, so the compiled step moves no page between chips.
    from repro.kernels.paged_attention import paged_attention
    from repro.models.kvcache import cache_logical_axes
    from repro.parallel import sharding

    _compile_paged_kernel(monkeypatch)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    axes = {k: ax[1:] for k, ax in
            cache_logical_axes(_paged_cfg(KVP, DH))[0].items()}
    with sharding.use_mesh(mesh, sharding.RULESETS["serve_lowbit"]):
        entry = _paged_entry(
            KVP, DH, SLOTS, NPP * PAGE, PAGE, lambda k, v: NamedSharding(
                mesh, sharding.spec_for(v.shape, axes[k])))
        assert entry["k_planes"].shape[1:] == (4, 8, 128)
        assert entry["k_planes"].sharding.spec[1] == "model"
        q = _struct((SLOTS, 1, KVP, G, DH), jnp.bfloat16,
                    NamedSharding(mesh, P(None, None, "model")))
        rows = _struct((SLOTS,), jnp.int32, NamedSharding(mesh, P()))
        text = jax.jit(paged_attention).lower(
            q, entry, rows, rows).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-to-all" not in text


def test_page_view_scope_is_metadata_only(one_chip, monkeypatch):
    from repro.models import paged_kvcache as paged

    entry = _paged_entry(KVP, DH, SLOTS, NPP * PAGE, PAGE, lambda *_: one_chip)
    scoped, plain = _compiled_both_ways(
        monkeypatch, lambda e: paged.page_view(e, KVP, DH), entry)
    assert "kv_page_view/" in scoped and "kv_page_view" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)


def _renamed(text):
    """Instruction names numbered in order of appearance."""
    names = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: names.setdefault(m.group(1), f"%i{len(names)}"),
                  _without_metadata(text))


def test_decode_step_scopes_rename_only(one_chip, monkeypatch):
    # A whole decode step over the 2-bit paged cache, with the paged
    # attention kernel in it: the scopes may renumber instruction names,
    # and change nothing else.
    _compile_paged_kernel(monkeypatch)
    from repro.configs import get_smoke
    from repro.models import model as model_mod
    from repro.models.common import ShardLayout
    from repro.models.kvcache import init_caches
    from repro.serving.engine import make_serve_step

    cfg = get_smoke("tinyllama-1.1b").with_(kv_cache_dtype="tnn2")
    layout = ShardLayout(tp=1)
    key = jax.random.PRNGKey(0)
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _struct(a.shape, a.dtype, one_chip), t)
    params = on_chip(jax.eval_shape(
        lambda k: model_mod.init_lm(k, cfg, layout), key))
    caches = on_chip(jax.eval_shape(
        lambda: init_caches(cfg, layout, 4, 128, page_size=16)))
    args = (params, caches, _struct((4, 1), jnp.int32, one_chip),
            _struct((4,), jnp.int32, one_chip), on_chip(key))
    scoped, plain = _compiled_both_ways(
        monkeypatch, make_serve_step(cfg, layout), *args)
    assert "kv_page_view/" in scoped and "kv_page_view" not in plain
    assert "tpu_custom_call" in scoped
    assert _renamed(scoped) == _renamed(plain)


def test_packed_decode_step_builds_no_dense_view(one_chip, monkeypatch):
    # The packed path attends through the kernel: no (B, npp * page,
    # KVp, dh) view, and no per-bit widening of the plane words, is left
    # in the compiled step; the oracle (bf16 pages) path still builds it.
    _compile_paged_kernel(monkeypatch)
    from repro.configs import get_smoke
    from repro.models import model as model_mod
    from repro.models.common import ShardLayout
    from repro.models.kvcache import init_caches
    from repro.serving.engine import make_serve_step

    layout = ShardLayout(tp=1)
    key = jax.random.PRNGKey(0)
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _struct(a.shape, a.dtype, one_chip), t)
    texts = {}
    for kvd in ("tnn2", "tnn2-oracle"):
        cfg = get_smoke("tinyllama-1.1b").with_(kv_cache_dtype=kvd)
        params = on_chip(jax.eval_shape(
            lambda k: model_mod.init_lm(k, cfg, layout), key))
        caches = on_chip(jax.eval_shape(
            lambda: init_caches(cfg, layout, 4, 128, page_size=16)))
        args = (params, caches, _struct((4, 1), jnp.int32, one_chip),
                _struct((4,), jnp.int32, one_chip), on_chip(key))
        texts[kvd] = jax.jit(make_serve_step(cfg, layout)).lower(
            *args).compile().as_text()
    # the smoke model: 2 kv heads of 16; 4 slots of 8 pages of 16 tokens.
    # (B, npp * page, KVp, dh) in any dtype: a dense view of the packed
    # pages decodes through u32[4,8,16,2,16] (a u32 a bit) to f32[4,128,2,16]
    view = r"\[4,(128|8,16),2,16\]"
    assert re.search(view, texts["tnn2-oracle"])
    assert not re.search(view, texts["tnn2"])
    assert "tpu_custom_call" in texts["tnn2"]
