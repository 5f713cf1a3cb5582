"""8-device validation of the mesh-aware low-bit serving path.

Multi-device CPU execution needs ``--xla_force_host_platform_device_
count`` in XLA_FLAGS *before* jax is imported, which a pytest process
(jax already imported by conftest) cannot do — so this script is the
actual test body and tests/test_sharded_qmm.py runs it once in a
subprocess (session-scoped fixture) and asserts on the JSON report it
prints.  It is also directly runnable:

    JAX_PLATFORMS=cpu PYTHONPATH=src python tests/sharded_check.py

Checks (each entry in the report is "ok" or an error string):

* n-, k- and n+k-sharded ``ops.qmm`` are ``array_equal`` with the
  single-device fused oracle for BNN/TNN/TBN on every backend, at a
  depth (k=250) whose pad bits land inside the last k-shard;
* the k-sharded reduction really psums INTEGER partial accumulators
  (int16 here — 2*k < 2**15) — asserted on the jaxpr, not inferred;
* cout-sharded ``ops.qconv`` matches the single-device conv;
* an Engine on an 8-device (2, 4) mesh decodes the same tokens as the
  single-device engine, its watchdog flags a silent device, and
  ``rebuild_after_loss`` re-packs onto the surviving (1, 4) mesh with
  identical decode output;
* the mesh bodies trace once per (mode, shape) — no per-call retrace;
* the packed paged cache's attention kernel runs per shard (kv heads
  over "model", slots over "data") with the single-device output, and a
  tnn2 mesh engine decodes the single-device tokens.
"""

import json
import os
import sys
import traceback

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.configs import get_smoke                      # noqa: E402
from repro.core.conv import pack_conv_filters            # noqa: E402
from repro.kernels import ops                            # noqa: E402
from repro.kernels.modes import QuantMode                # noqa: E402
from repro.kernels.qtensor import QTensor                # noqa: E402
from repro.launch.mesh import make_serve_mesh            # noqa: E402
from repro.models import model as model_mod              # noqa: E402
from repro.models.common import ShardLayout              # noqa: E402
from repro.parallel import qmm_mesh, sharding            # noqa: E402
from repro.runtime.fault_tolerance import WatchdogConfig  # noqa: E402
from repro.serving import (                              # noqa: E402
    Engine, Request, SamplerConfig, ServeConfig)

REPORT = {}
M, K, N = 5, 250, 64           # k=250 -> 8 words, 6 pad bits in the last
MODES = (QuantMode.BNN, QuantMode.TNN, QuantMode.TBN)
BACKENDS = ("xla", "pallas", "dense")


def check(name):
    def deco(fn):
        try:
            fn()
            REPORT[name] = "ok"
        except Exception:
            REPORT[name] = traceback.format_exc(limit=8)
        return fn
    return deco


def _mesh():
    return make_serve_mesh(model=4, data=2)


@check("devices")
def _devices():
    assert jax.device_count() == 8, jax.device_count()


@check("qmm_sharded_matches_oracle")
def _qmm_equal():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((N,)), jnp.float32)
    mesh = _mesh()
    # pspec set directly: n over "model" (64/4), k words over "model"
    # (8/4 -> the 6 pad bits sit inside the last shard) or "data" (8/2).
    cases = {"n": ("model", None), "k": (None, "model"),
             "nk": ("model", "data")}
    for mode in MODES:
        qt = QTensor.from_dense(w, mode, bias=bias)
        for backend in BACKENDS:
            oracle = np.asarray(ops.qmm(x, qt, backend=backend))
            for label, pspec in cases.items():
                sq = qt.replace(pspec=pspec)
                with sharding.use_mesh(mesh, sharding.SERVE_RULES_LOWBIT):
                    assert qmm_mesh.shard_plan(sq) is not None, \
                        (mode, label)
                    got = np.asarray(ops.qmm(x, sq, backend=backend))
                assert np.array_equal(got, oracle), \
                    f"{mode}/{backend}/{label}: max diff " \
                    f"{np.abs(got - oracle).max()}"


@check("k_psum_is_integer")
def _int_psum():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    mesh = _mesh()
    for mode in MODES:
        qt = QTensor.from_dense(w, mode).replace(pspec=(None, "model"))
        with sharding.use_mesh(mesh, sharding.SERVE_RULES_LOWBIT):
            plan = qmm_mesh.shard_plan(qt)
            assert plan is not None and plan.k_axis == "model"
            # 2 * 256 rounded-up bits < 2**15 -> int16 on the wire
            assert plan.acc_dtype == "int16", plan.acc_dtype
            txt = str(jax.make_jaxpr(
                lambda xx: ops.qmm(xx, qt, backend="xla"))(x))
        psum_lines = [ln for ln in txt.splitlines() if "psum" in ln]
        assert psum_lines, "no psum in the k-sharded jaxpr"
        assert any("i16" in ln for ln in psum_lines), psum_lines
        assert not any("f32" in ln for ln in psum_lines), \
            f"float psum in: {psum_lines}"


@check("qconv_sharded_matches_oracle")
def _qconv_equal():
    rng = np.random.default_rng(2)
    kh, kw_, cin, cout = 3, 3, 5, 16
    x = jnp.asarray(rng.standard_normal((2, 6, 6, cin)), jnp.float32)
    f = jnp.asarray(rng.standard_normal((kh, kw_, cin, cout)), jnp.float32)
    mesh = _mesh()
    for mode in MODES:
        qt = pack_conv_filters(f, mode)
        oracle = np.asarray(ops.qconv(x, qt, backend="xla"))
        sq = qt.replace(pspec=("model", None))   # cout 16 over model=4
        with sharding.use_mesh(mesh, sharding.SERVE_RULES_LOWBIT):
            assert qmm_mesh.shard_plan_conv(sq) is not None, mode
            got = np.asarray(ops.qconv(x, sq, backend="xla"))
        assert np.array_equal(got, oracle), \
            f"{mode}: max diff {np.abs(got - oracle).max()}"


def _decode(eng, prompts):
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=np.asarray(p),
                           max_new_tokens=4))
    return {uid: r.tokens for uid, r in eng.run().items()}


@check("engine_mesh_serving")
def _engine():
    # d_model=128 / d_ff=256 so wo and down k-word-shard over model=4
    # (4 and 8 words) and the column planes n-shard + data-k-shard.
    cfg = get_smoke("tinyllama-1.1b").with_(
        dtype=jnp.float32, quant_policy="tnn", d_model=128, d_ff=256)
    layout = ShardLayout(tp=1)
    params = model_mod.init_lm(jax.random.PRNGKey(0), cfg, layout)
    base = dict(num_slots=2, max_len=16, prefill_bucket=8,
                sampler=SamplerConfig(temperature=0.0), pack_params=True)
    prompts = [[3, 1, 4], [1, 5, 9, 2]]

    single = _decode(Engine(params, cfg, layout, ServeConfig(**base),
                            seed=0), prompts)
    mesh = _mesh()
    eng = Engine(params, cfg, layout, ServeConfig(**base, mesh=mesh),
                 seed=0)
    # the packed tree really is sharded: some QTensor carries a pspec
    leaves = jax.tree_util.tree_flatten(
        eng.params, is_leaf=lambda t: isinstance(t, QTensor))[0]
    qts = [t for t in leaves if isinstance(t, QTensor)]
    assert any(t.pspec and t.pspec[1] for t in qts), \
        "no k-sharded container in the packed tree"
    assert _decode(eng, prompts) == single, "mesh decode diverged"

    # trace stability: a second batch through the same engine must not
    # retrace the mesh bodies.
    traces = {b: qmm_mesh.qmm_mesh_trace_count(QuantMode.TNN, b)
              for b in BACKENDS}
    assert _decode(eng, prompts) == single
    after = {b: qmm_mesh.qmm_mesh_trace_count(QuantMode.TNN, b)
             for b in BACKENDS}
    assert after == traces, (traces, after)

    # watchdog over the mesh devices: everyone but device 7 heartbeats.
    t = [0.0]
    wd = eng.make_watchdog(WatchdogConfig(dead_after_s=5.0),
                           clock=lambda: t[0])
    for h in range(7):
        wd.heartbeat(h, 0.1)
    t[0] = 10.0
    for h in range(7):
        wd.heartbeat(h, 0.1)
    report = wd.check()
    assert report.dead == [7], report.dead

    # elastic rebuild on the survivors: (2, 4) -> (1, 4), same tokens.
    dead_dev = list(mesh.devices.flat)[7]
    eng2 = eng.rebuild_after_loss([dead_dev])
    new_mesh = eng2.scfg.mesh
    assert new_mesh.devices.shape == (1, 4), new_mesh.devices.shape
    assert dead_dev not in set(new_mesh.devices.flat)
    assert _decode(eng2, prompts) == single, "rebuilt decode diverged"


@check("watchdog_rebuild_inflight")
def _engine_inflight():
    # Watchdog -> rebuild WITH WORK IN FLIGHT: the old engine is killed
    # mid-serve (some requests queued, some mid-decode) and every
    # unfinished request must migrate to the rebuilt engine and resolve
    # there with a definite status and the single-device tokens.
    cfg = get_smoke("tinyllama-1.1b").with_(
        dtype=jnp.float32, quant_policy="tnn", d_model=128, d_ff=256)
    layout = ShardLayout(tp=1)
    params = model_mod.init_lm(jax.random.PRNGKey(0), cfg, layout)
    base = dict(num_slots=2, max_len=16, prefill_bucket=8,
                sampler=SamplerConfig(temperature=0.0), pack_params=True)
    prompts = [[3, 1, 4], [1, 5, 9, 2], [2, 7, 1], [8, 2, 8, 1]]

    single = _decode(Engine(params, cfg, layout, ServeConfig(**base),
                            seed=0), prompts)
    eng = Engine(params, cfg, layout,
                 ServeConfig(**base, mesh=_mesh()), seed=0)
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=np.asarray(p),
                           max_new_tokens=4))
    # A few ticks: 2 slots busy decoding, 2 requests still queued.
    for _ in range(2):
        eng.step()
    assert any(u != -1 for u in eng._sched.slot_uid)
    assert eng._sched.queue

    # Fake-clock watchdog declares device 7 dead...
    t = [0.0]
    wd = eng.make_watchdog(WatchdogConfig(dead_after_s=5.0),
                           clock=lambda: t[0])
    for h in range(7):
        wd.heartbeat(h, 0.1)
    t[0] = 10.0
    for h in range(7):
        wd.heartbeat(h, 0.1)
    assert wd.check().dead == [7]

    # ...and the rebuild carries every unfinished request across.
    dead_dev = list(eng.scfg.mesh.devices.flat)[7]
    migrated = {r.uid for r in eng._sched.unfinished()}
    eng2 = eng.rebuild_after_loss([dead_dev])
    assert migrated == {r.uid for r in list(eng2._sched.queue)}, \
        (migrated, [r.uid for r in eng2._sched.queue])
    res = eng2.run()
    assert sorted(res) == sorted(migrated), (sorted(res), migrated)
    for uid, r in res.items():
        assert r.status == "ok", (uid, r.status)
        assert r.tokens == single[uid], uid
    eng.close()
    eng2.close()


@check("paged_attention_sharded")
def _paged_attention():
    # The packed paged cache's attention kernel under the low-bit serving
    # rules: 8 kv heads of 16-token pages, the cache built on the mesh so
    # its kv heads come in 4 groups over "model" (2 heads, 32 lanes a
    # page, 4 pages a plane row), slots 2 ways over "data", in a
    # shard_map that splits the pool as its sharding does (no gather);
    # the output of page_view + f32 einsums on one device.  And a tnn2
    # engine whose 2 kv heads cannot split decodes the single-device
    # tokens on the mesh.
    from repro.kernels.paged_attention import paged_attention
    from repro.models import paged_kvcache as paged
    from repro.models.attention import attend_page_view

    cfg = get_smoke("tinyllama-1.1b").with_(num_heads=8, num_kv_heads=8)
    layout = ShardLayout(tp=1)
    b, s, page, dh = 4, 8, 16, cfg.head_dim_
    rules = sharding.RULESETS["serve_lowbit"]
    with sharding.use_mesh(_mesh(), rules):
        entry = {k: v[0] for k, v in paged.init_paged_caches(
            cfg, layout, b, 32, page_size=page, prefill_chunk=s)[0].items()}
    assert entry["k_planes"].shape[1:] == (4, 2, 128), entry["k_planes"].shape
    pager = paged.EntryPager.from_entry(entry, b)
    lens = [8, 3, 0, 6]
    for slot, n in enumerate(lens):
        pager.ensure(slot, n)
    entry["page_table"] = pager.device_table(1)[0]
    key = jax.random.PRNGKey(3)
    kv = jax.random.normal(key, (b, s, 8, dh))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    entry = jax.jit(paged.append_tokens)(entry, kv, -kv, pos,
                                         pos < jnp.asarray(lens)[:, None])
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, s, 8, 1, dh))
    q = q.astype(jnp.bfloat16).astype(jnp.float32)
    p0 = jnp.zeros((b,), jnp.int32)
    nv = jnp.asarray(lens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(attend_page_view)(
            q, entry, jnp.broadcast_to(jnp.arange(s), (b, s))))
    with sharding.use_mesh(_mesh(), rules):
        fn = jax.jit(paged_attention)
        jaxpr = str(jax.make_jaxpr(fn)(q, entry, p0, nv))
        assert "shard_map" in jaxpr and "f32[2,8,2,1,8]" in jaxpr
        axes = paged.paged_logical_axes(cfg)[0]
        placed = {k: jax.device_put(v, sharding.named_sharding(
            v.shape, axes[k][1:])) for k, v in entry.items()}
        compiled = fn.lower(q, placed, p0, nv).compile()
        assert "all-gather" not in compiled.as_text()
        got = np.asarray(compiled(q, placed, p0, nv))
    live = np.asarray(nv) > 0
    assert np.all(got[~live] == 0)
    # the bound of tests/test_paged_kvcache.py (KERNEL_ATOL)
    np.testing.assert_allclose(got[live], want[live], rtol=0, atol=5e-5)

    tcfg = get_smoke("tinyllama-1.1b").with_(kv_cache_dtype="tnn2")
    params = model_mod.init_lm(key, tcfg, layout)
    base = dict(num_slots=2, max_len=16, page_size=4, prefill_chunk=4,
                sampler=SamplerConfig(temperature=0.0))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
    single = _decode(Engine(params, tcfg, layout, ServeConfig(**base),
                            seed=0), prompts)
    eng = Engine(params, tcfg, layout, ServeConfig(**base, mesh=_mesh()),
                 seed=0)
    assert _decode(eng, prompts) == single, "tnn2 mesh decode diverged"
    eng.close()


def main():
    for name, outcome in REPORT.items():
        if outcome != "ok":
            print(f"--- {name} ---\n{outcome}", file=sys.stderr)
    print(json.dumps(REPORT))
    return 0 if all(v == "ok" for v in REPORT.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
