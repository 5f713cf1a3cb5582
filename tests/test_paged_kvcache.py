"""Paged ternary KV cache (models/paged_kvcache.py).

Covers the satellite-3 numerics contract:

* pack -> append -> gather -> unpack round-trips BIT-EXACTLY against the
  dense oracle page mode for ternary-representable K/V;
* quantization error vs a bf16 cache is bounded (and the page machinery
  itself adds ZERO error on top of the TWN quantizer);
* ring / sliding-window ("AL") entries mask INVALID_POS correctly
  through the page indirection — the oracle paged decode reproduces a
  full-prefill f32 reference exactly, past the window, across chunk
  boundaries;
* host-side page accounting (PageAllocator / EntryPager) is exact:
  exhaustion and double frees raise, release balances to zero.
"""

import functools
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.encoding import packed_width
from repro.models import model as model_mod
from repro.models import paged_kvcache as paged
from repro.models.common import (KV_CACHE_FORMATS, ShardLayout,
                                 kv_cache_format)
from repro.models.kvcache import (INVALID_POS, cache_logical_axes,
                                  init_caches)

LAYOUT = ShardLayout(tp=1)


# ------------------------------------------------------------ formats

def test_kv_cache_format_registry():
    assert not kv_cache_format("bf16").paged
    assert not kv_cache_format("int8").paged
    assert kv_cache_format("tnn2").paged
    assert kv_cache_format("tnn2").storage_dtype is None       # packed planes
    assert kv_cache_format("tnn2-oracle").paged
    assert kv_cache_format("tnn2-oracle").storage_dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="unknown kv_cache_dtype"):
        kv_cache_format("fp4")
    # the registry and the resolver agree on the universe of names
    for name in KV_CACHE_FORMATS:
        assert kv_cache_format(name).name == name


def test_init_paged_rejects_ssm_and_bad_geometry():
    cfg = get_smoke("mamba2-1.3b").with_(kv_cache_dtype="tnn2")
    with pytest.raises(NotImplementedError, match="SSM"):
        init_caches(cfg, LAYOUT, 2, 32)
    cfg = get_smoke("tinyllama-1.1b")
    with pytest.raises(ValueError):
        paged.init_paged_caches(cfg, LAYOUT, 2, 32, page_size=0)


def test_paged_logical_axes_cover_all_leaves():
    """specs.py looks each cache leaf up in cache_logical_axes — the
    paged axes dict must be a superset of both payload layouts."""
    for kvd in ("tnn2", "tnn2-oracle"):
        cfg = get_smoke("tinyllama-1.1b").with_(kv_cache_dtype=kvd)
        caches = jax.eval_shape(lambda c=cfg: init_caches(c, LAYOUT, 2, 32))
        axes = cache_logical_axes(cfg)
        for entry, ax in zip(caches, axes):
            for key, leaf in entry.items():
                assert key in ax
                assert len(ax[key]) == leaf.ndim


# ------------------------------------------------------- round-trip

def _strip_period(entry):
    """Entries carry a leading num_periods dim; append/view run inside
    the layer scan where it is stripped."""
    return {k: v[0] for k, v in entry.items()}


def _paged_pair(cfg, batch, max_len, page_size):
    packed = _strip_period(paged.init_paged_caches(
        cfg, LAYOUT, batch, max_len, page_size=page_size)[0])
    oracle = _strip_period(paged.init_paged_caches(
        cfg, LAYOUT, batch, max_len, page_size=page_size, oracle=True)[0])
    return packed, oracle


def _backed(entry, batch, hi):
    """Give every slot pages for positions [0, hi) via an EntryPager."""
    pager = paged.EntryPager.from_entry(entry, batch)
    for b in range(batch):
        pager.ensure(b, hi)
    entry = dict(entry)
    entry["page_table"] = pager.device_table(1)[0]
    return entry, pager


def test_oracle_roundtrip_bit_exact(rng):
    """Ternary-representable tokens (values in {-a, 0, +a}, a a power of
    two) survive quantize-at-append EXACTLY: the TWN threshold keeps all
    nonzeros, alpha recovers a, and pack/scatter/gather/unpack is
    lossless — so the packed view equals the oracle (dense bf16) view
    bit for bit."""
    cfg = get_smoke("tinyllama-1.1b")
    b, s, dh = 2, 12, cfg.head_dim_
    from repro.models.attention import head_layout
    kvp = head_layout(cfg.num_heads, cfg.num_kv_heads, LAYOUT.tp).kvp
    packed, oracle = _paged_pair(cfg, b, 32, page_size=8)
    packed, _ = _backed(packed, b, s)
    oracle, _ = _backed(oracle, b, s)

    keys = jax.random.split(rng, 4)
    def ternary_field(key_t, key_a):
        t = jax.random.randint(key_t, (b, s, kvp, dh), -1, 2)
        t = t.at[..., 0].set(1)                       # >= 1 nonzero / token
        alpha = 2.0 ** jax.random.randint(key_a, (b, s), -2, 2)
        return (t * alpha[..., None, None]).astype(jnp.float32)

    k = ternary_field(keys[0], keys[1])
    v = ternary_field(keys[2], keys[3])
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    live = jnp.ones((b, s), bool)

    packed = paged.append_tokens(packed, k, v, positions, live)
    oracle = paged.append_tokens(oracle, k, v, positions, live)
    kp, vp, pos_p = paged.page_view(packed, kvp, dh)
    ko, vo, pos_o = paged.page_view(oracle, kvp, dh)

    np.testing.assert_array_equal(np.asarray(pos_p), np.asarray(pos_o))
    written = np.asarray(pos_p[:, :s])
    np.testing.assert_array_equal(written, np.asarray(positions))
    assert np.all(np.asarray(pos_p[:, s:]) == INVALID_POS)
    # bit-exact: vs the oracle pages AND vs the original values
    np.testing.assert_array_equal(np.asarray(kp[:, :s]),
                                  np.asarray(ko[:, :s], np.float32))
    np.testing.assert_array_equal(np.asarray(vp[:, :s]),
                                  np.asarray(vo[:, :s], np.float32))
    np.testing.assert_array_equal(np.asarray(kp[:, :s]), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(vp[:, :s]), np.asarray(v))


def test_quantization_error_bounded(rng):
    """On arbitrary (gaussian) K/V the page machinery adds ZERO error on
    top of the TWN quantizer — the decoded view equals alpha * t exactly
    — and the quantizer itself beats the zero predictor."""
    cfg = get_smoke("tinyllama-1.1b")
    b, s, dh = 2, 16, cfg.head_dim_
    from repro.models.attention import head_layout
    kvp = head_layout(cfg.num_heads, cfg.num_kv_heads, LAYOUT.tp).kvp
    packed, _ = _paged_pair(cfg, b, 32, page_size=8)
    packed, _ = _backed(packed, b, s)

    k = jax.random.normal(rng, (b, s, kvp, dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, kvp, dh),
                          jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    live = jnp.ones((b, s), bool)
    packed = paged.append_tokens(packed, k, v, positions, live)
    kd, vd, _ = paged.page_view(packed, kvp, dh)

    for x, got in ((k, kd[:, :s]), (v, vd[:, :s])):
        t, alpha = paged.ternarize_tokens(x)
        ref = t * alpha[..., None, None]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        err = np.linalg.norm(np.asarray(got) - np.asarray(x))
        assert err < np.linalg.norm(np.asarray(x))     # bounded: beats 0
        assert np.all(np.asarray(alpha) > 0)


def test_dead_tokens_route_to_scratch(rng):
    """live=False tokens (chunk padding, inactive rows) must land on the
    scratch page with INVALID_POS and never dirty an allocated page."""
    cfg = get_smoke("tinyllama-1.1b")
    b, s, dh = 2, 8, cfg.head_dim_
    from repro.models.attention import head_layout
    kvp = head_layout(cfg.num_heads, cfg.num_kv_heads, LAYOUT.tp).kvp
    packed, _ = _paged_pair(cfg, b, 32, page_size=8)
    packed, _ = _backed(packed, b, s)

    k = jax.random.normal(rng, (b, s, kvp, dh), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    live = jnp.zeros((b, s), bool).at[0].set(True)     # row 1 entirely dead
    out = paged.append_tokens(packed, k, k, positions, live)
    _, _, pos = paged.page_view(out, kvp, dh)
    assert np.all(np.asarray(pos[1]) == INVALID_POS)   # dead row untouched
    np.testing.assert_array_equal(np.asarray(pos[0, :s]),
                                  np.asarray(positions[0]))
    # unallocated tables resolve to scratch: a fresh entry's view is all
    # INVALID_POS, so every `pos <= step` mask rejects it
    fresh, _ = _paged_pair(cfg, b, 32, page_size=8)
    _, _, pos0 = paged.page_view(fresh, kvp, dh)
    assert np.all(np.asarray(pos0) == INVALID_POS)


def test_al_ring_window_exact_vs_full_prefill(rng):
    """Sliding-window correctness THROUGH the page indirection: on an
    AL+A pattern (gemma2 smoke, window 64) with a 90-token prompt
    prefilled in 8-token chunks, the oracle paged decode reproduces the
    f32 full-prefill reference logits — past the window, across ring
    wrap-around and chunk boundaries.  (Observed bit-exact; the bound
    leaves headroom for backend reassociation only.)"""
    cfg = get_smoke("gemma2-27b")
    assert any(m == "AL" for m, _ in cfg.layer_pattern)
    params = model_mod.init_lm(rng, cfg, LAYOUT)
    b, L, plen, chunk, page = 2, 128, 90, 8, 8
    toks = np.asarray(
        jax.random.randint(jax.random.fold_in(rng, 7), (b, plen), 0,
                           cfg.vocab_size), np.int32)

    # dense f32-path prefill: the ground truth
    dense = init_caches(cfg, LAYOUT, b, L, dtype=jnp.bfloat16)
    lg_d, _ = model_mod.prefill(params, {"tokens": jnp.asarray(toks)},
                                dense, cfg, LAYOUT)
    ref_last = np.asarray(lg_d)[:, -1]
    nxt = np.argmax(ref_last, -1).astype(np.int32)
    toks91 = np.concatenate([toks, nxt[:, None]], axis=1)
    ref_caches = init_caches(cfg, LAYOUT, b, L, dtype=jnp.bfloat16)
    lg_ref, _ = model_mod.prefill(params, {"tokens": jnp.asarray(toks91)},
                                  ref_caches, cfg, LAYOUT)
    ref_decode = np.asarray(lg_ref)[:, -1]

    # oracle paged: chunked prefill then one decode step
    cfgp = cfg.with_(kv_cache_dtype="tnn2-oracle")
    caches = init_caches(cfgp, LAYOUT, b, L, page_size=page,
                         prefill_chunk=chunk)
    pagers = paged.make_pagers(caches, b)
    for start in range(0, plen, chunk):
        n = min(chunk, plen - start)
        tk = np.zeros((b, chunk), np.int32)
        tk[:, :n] = toks[:, start:start + n]
        for slot in range(b):
            for pg in pagers:
                pg.ensure(slot, start + n)
        caches = paged.sync_page_tables(caches, pagers)
        step2 = jnp.asarray(np.tile([[start, n]], (b, 1)).astype(np.int32))
        lg, caches = model_mod.decode_step(
            params, {"tokens": jnp.asarray(tk)}, caches, step2, cfgp, LAYOUT)
        last_n = n
    paged_last = np.asarray(lg)[:, last_n - 1]

    for slot in range(b):
        for pg in pagers:
            pg.ensure(slot, plen + 1)
    caches = paged.sync_page_tables(caches, pagers)
    lg2, _ = model_mod.decode_step(
        params, {"tokens": jnp.asarray(nxt[:, None])}, caches,
        jnp.full((b,), plen, jnp.int32), cfgp, LAYOUT)
    paged_decode = np.asarray(lg2)[:, 0]

    assert np.abs(paged_last - ref_last).max() <= 1e-4
    assert np.abs(paged_decode - ref_decode).max() <= 1e-4
    # the AL ring really is smaller than the prompt (indirection tested)
    al_entry = caches[0]
    n_pages, page_sz, npp = paged.entry_geometry(al_entry)
    assert npp * page_sz < plen


# ------------------------------------------------------- accounting

def test_page_allocator_accounting():
    alloc = paged.PageAllocator(5)                     # pages 1..4 usable
    assert (alloc.n_free, alloc.n_used) == (4, 0)
    got = alloc.alloc(3)
    assert sorted(got) == [1, 2, 3]
    assert (alloc.n_free, alloc.n_used) == (1, 3)
    with pytest.raises(RuntimeError, match="exhausted"):
        alloc.alloc(2)
    alloc.free(got[:2])
    assert (alloc.n_free, alloc.n_used) == (3, 1)
    with pytest.raises(RuntimeError, match="free"):
        alloc.free([got[0]])                           # double free
    with pytest.raises(RuntimeError, match="free"):
        alloc.free([4])                                # never allocated
    alloc.free([got[2]])
    assert (alloc.n_free, alloc.n_used) == (4, 0)      # balanced


def test_entry_pager_ring_cap_and_release():
    pager = paged.EntryPager(num_slots=2, npp=3, page=4, n_pages=7)
    pager.ensure(0, 5)                                 # 2 pages back 0..4
    assert len(pager.owned[0]) == 2
    pager.ensure(0, 100)                               # ring-capped at npp
    assert len(pager.owned[0]) == 3
    pager.ensure(1, 12)
    assert pager.alloc.n_used == 6
    assert pager.dirty
    table = pager.device_table(2)
    assert table.shape == (2, 2, 3)
    assert not pager.dirty
    np.testing.assert_array_equal(np.asarray(table[0]),
                                  np.asarray(table[1]))
    assert np.all(np.asarray(table) > 0)               # scratch never mapped
    freed = pager.release(0)
    assert len(freed) == 3 and pager.dirty
    assert np.all(pager.table[0] == 0)
    assert pager.alloc.n_used == 3
    pager.release(1)
    assert pager.alloc.n_used == 0
    assert pager.alloc.n_free == 6                     # balanced to zero
    assert pager.release(0) == []                      # idempotent


def test_reset_pages_poisons_positions():
    cfg = get_smoke("tinyllama-1.1b")
    entry = paged.init_paged_caches(cfg, LAYOUT, 1, 16, page_size=8)[0]
    entry = dict(entry)
    entry["pos"] = entry["pos"].at[:, 2].set(0)        # fake stale content
    out = paged.reset_pages(entry, [2])
    assert np.all(np.asarray(out["pos"][:, 2]) == INVALID_POS)
    assert paged.reset_pages(entry, []) is entry       # no-op fast path


def test_tree_nbytes_counts_packed_vs_dense():
    cfg = get_smoke("tinyllama-1.1b")
    b, L = 4, 64
    packed = jax.eval_shape(
        lambda: init_caches(cfg.with_(kv_cache_dtype="tnn2"), LAYOUT, b, L))
    dense = jax.eval_shape(
        lambda: init_caches(cfg, LAYOUT, b, L, dtype=jnp.bfloat16))
    # plane words pack 32 lanes into 4 bytes vs 2 bytes/lane bf16
    assert paged.tree_nbytes(packed) < paged.tree_nbytes(dense)
    dw = packed_width(cfg.head_dim_)
    assert dw == -(-cfg.head_dim_ // 32)


# --------------------------------------------- paged attention kernel
#
# kernels/paged_attention.py against ``attend_page_view`` (page_view +
# f32 einsums, the oracle entries' attention) on the same packed entry.
# Both see bf16-exact queries and the kernel's dot operands are exact
# ({0, 1} bits, bf16 queries, alpha_v * p as two bf16 terms), so only
# the order of f32 sums differs.  The largest difference over the cases
# below, outputs of scale 0.5-1.5, read 1.1e-6 to 5.5e-6 on three seeds
# (interpret mode, CPU); one bf16 term for alpha_v * p read 7.1e-4 to
# 3.6e-3, and a wrong mask, page or scale moves outputs by 0.1 and more.
KERNEL_ATOL = 5e-5


def _kernel(*args, **kw):
    from repro.kernels.paged_attention import paged_attention
    return _jitted(paged_attention, ("window", "cap"))(*args, **kw)


@functools.lru_cache(maxsize=None)
def _jitted(fn, static=()):
    """One jit per function, so a shape the file has seen compiles once."""
    return jax.jit(fn, static_argnames=static)


def _written_entry(cfg, lens, *, max_len, page, chunk, key, entry_idx=0,
                   extra=0):
    """Packed entry (no period dim) whose slot ``b`` holds positions
    ``[0, lens[b])`` of random K/V, appended ``chunk`` tokens at a time;
    every slot gets pages for ``extra`` positions more than it holds."""
    from repro.models.attention import head_layout
    b = len(lens)
    kvp = head_layout(cfg.num_heads, cfg.num_kv_heads, LAYOUT.tp).kvp
    entry = _strip_period(paged.init_paged_caches(
        cfg, LAYOUT, b, max_len, page_size=page,
        prefill_chunk=chunk)[entry_idx])
    pager = paged.EntryPager.from_entry(entry, b)
    append = _jitted(paged.append_tokens)
    for i, start in enumerate(range(0, max(lens), chunk)):
        for slot, n in enumerate(lens):
            if start < n:
                pager.ensure(slot, min(start + chunk, n))
        entry["page_table"] = pager.device_table(1)[0]
        pos = jnp.broadcast_to(start + jnp.arange(chunk, dtype=jnp.int32),
                               (b, chunk))
        live = pos < jnp.asarray(lens)[:, None]
        kk, kv = jax.random.split(jax.random.fold_in(key, i))
        shape = (b, chunk, kvp, cfg.head_dim_)
        entry = append(entry, jax.random.normal(kk, shape),
                       jax.random.normal(kv, shape), pos, live)
    for slot, n in enumerate(lens):
        if n:
            pager.ensure(slot, n + extra)
    entry["page_table"] = pager.device_table(1)[0]
    return entry, pager


def _queries(cfg, b, s, key):
    from repro.models.attention import head_layout
    hl = head_layout(cfg.num_heads, cfg.num_kv_heads, LAYOUT.tp)
    q = jax.random.normal(key, (b, s, hl.kvp, hl.g, cfg.head_dim_))
    return q.astype(jnp.bfloat16).astype(jnp.float32)


def _rows(lens, s):
    """(p0, nvalid) of the step that wrote each slot's last tokens: one
    token (s == 1) or the last chunk of ``s``; 0 tokens for a dead row."""
    p0 = [max(n - 1, 0) if s == 1 else (max(n - 1, 0) // s) * s for n in lens]
    nv = [n - p if n else 0 for n, p in zip(lens, p0)]
    return jnp.asarray(p0, jnp.int32), jnp.asarray(nv, jnp.int32)


def _kernel_vs_view(cfg, entry, lens, s, key, window=0):
    from repro.models.attention import attend_page_view
    q = _queries(cfg, len(lens), s, key)
    p0, nv = _rows(lens, s)
    kw = dict(window=window, cap=cfg.attn_logit_softcap)
    got = np.asarray(_kernel(q, entry, p0, nv, **kw))
    positions = p0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    want = np.asarray(_jitted(attend_page_view, ("window", "cap"))(
        q, entry, positions, **kw))
    live = np.asarray(nv) > 0
    assert np.all(got[~live] == 0)                     # dead rows: 0
    err = np.abs(got[live] - want[live]).max()
    assert err <= KERNEL_ATOL, err
    assert np.abs(want[live]).max() > 0.1              # not vacuous
    return got


@pytest.mark.parametrize("s", [1, 8], ids=["decode", "chunk"])
@pytest.mark.parametrize("lens", [(0, 1, 5, 16, 21), (8, 0, 24, 3, 40)],
                         ids=["short", "long"])
def test_kernel_matches_page_view(rng, lens, s):
    """Dead rows, slots with 0, 1, partial and full pages, at the
    engine's two shapes (S=1 decode, S=chunk)."""
    cfg = get_smoke("tinyllama-1.1b")
    entry, _ = _written_entry(cfg, list(lens), max_len=64, page=8, chunk=8,
                              key=rng)
    _kernel_vs_view(cfg, entry, list(lens), s, jax.random.fold_in(rng, 9))


@pytest.mark.parametrize("s", [1, 8], ids=["decode", "chunk"])
def test_kernel_al_ring_past_wraparound(rng, s):
    """An AL ring (gemma2 smoke: window 64, softcap 50) past wrap-around:
    the ring holds 72 positions, slots hold 100, 71 and 30."""
    cfg = get_smoke("gemma2-27b")
    assert cfg.layer_pattern[0][0] == "AL" and cfg.attn_logit_softcap
    lens = [100, 71, 30]
    entry, _ = _written_entry(cfg, lens, max_len=128, page=8, chunk=8,
                              key=rng)
    _, page, npp = paged.entry_geometry(entry)
    assert npp * page < max(lens)
    _kernel_vs_view(cfg, entry, lens, s, jax.random.fold_in(rng, 9),
                    window=cfg.sliding_window)


def test_kernel_ignores_stale_freed_pages(rng):
    """A slot's pages, released (positions poisoned by reset_pages, plane
    words and scales left stale) and handed to another slot that writes
    fewer tokens: the stale tokens never count."""
    cfg = get_smoke("tinyllama-1.1b")
    entry, pager = _written_entry(cfg, [30, 0], max_len=64, page=8,
                                  chunk=8, key=rng)
    entry = paged.reset_pages(entry, pager.release(0))
    from repro.models.attention import head_layout
    kvp = head_layout(cfg.num_heads, cfg.num_kv_heads, LAYOUT.tp).kvp
    pager.ensure(1, 11)
    assert set(pager.owned[1]) <= set(range(1, 5))     # the freed pages
    entry["page_table"] = pager.device_table(1)[0]
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    live = jnp.zeros((2, 16), bool).at[1, :11].set(True)
    kv = jax.random.normal(rng, (2, 16, kvp, cfg.head_dim_))
    entry = paged.append_tokens(entry, kv, -kv, pos, live)
    _kernel_vs_view(cfg, entry, [0, 11], 1, jax.random.fold_in(rng, 9))


def test_kernel_output_ignores_garbage_past_live_length(rng):
    """Plane words, scales (NaN) and positions (0) of garbage in every
    page past a slot's live pages — allocated ahead, unallocated
    (scratch) — and past its length inside its last live page leave the
    kernel's output as it was, bit for bit."""
    from repro.kernels import paged_attention as kernel
    assert kernel.INVALID_POS == paged.INVALID_POS
    cfg = get_smoke("tinyllama-1.1b")
    lens = [0, 1, 5, 16, 21]
    entry, pager = _written_entry(cfg, lens, max_len=64, page=8, chunk=8,
                                  key=rng, extra=16)
    n_pages, page, _ = paged.entry_geometry(entry)
    used = np.zeros((n_pages, page), bool)             # live (page, token)
    for slot, n in enumerate(lens):
        for t in range(n):
            used[pager.owned[slot][t // page], t % page] = True
    assert not used[paged.SCRATCH_PAGE].any()
    assert any(len(o) * page > n + page for o, n in zip(pager.owned, lens)
               if n)                                   # pages past length
    from repro.models.attention import head_layout
    kvp = head_layout(cfg.num_heads, cfg.num_kv_heads, LAYOUT.tp).kvp
    kh = kvp // entry["k_planes"].shape[1]
    w, ppr = kernel.page_lanes(kh, page)
    lane_used = np.zeros(entry["k_planes"].shape, bool)  # (row, grp, word, lane)
    for p, t in zip(*np.nonzero(used)):
        for h in range(kvp):
            lane_used[p // ppr, h // kh, :,
                      (p % ppr) * w + (h % kh) * page + t] = True
    bad = dict(entry)
    keys = jax.random.split(rng, 2)
    for key, name in zip(keys, ("k", "v")):
        junk = jax.random.bits(key, entry[f"{name}_planes"].shape, jnp.uint32)
        bad[f"{name}_planes"] = jnp.where(lane_used, entry[f"{name}_planes"],
                                          junk)
        bad[f"{name}_scale"] = jnp.where(used, entry[f"{name}_scale"],
                                         jnp.nan)
    bad["pos"] = jnp.where(used, entry["pos"], 0)
    q = _queries(cfg, len(lens), 1, jax.random.fold_in(rng, 9))
    p0, nv = _rows(lens, 1)
    clean = np.asarray(_kernel(q, entry, p0, nv))
    dirty = np.asarray(_kernel(q, bad, p0, nv))
    np.testing.assert_array_equal(dirty, clean)
    _kernel_vs_view(cfg, entry, lens, 1, jax.random.fold_in(rng, 9))
