"""Paged KV cache storing K/V in the paper's 2-bit ternary encoding.

The dense slab cache (models/kvcache.py) allocates ``num_slots x
max_len`` bf16/int8 rows up front.  This module replaces the slab with a
vLLM-style **page pool** plus a per-slot **page table**, and stores the
page payload in the paper's ternary bit planes (§III-A): each cached
token's K (and V) vector is TWN-quantized at append time — the same
``0.7 * mean|x|`` threshold / masked-mean scale as
:func:`repro.core.quantize.ternarize`, per token — packed into
``(plus, minus)`` uint32 words along the head dim, and decoded on read
as ``alpha * (plus - minus)`` — the eq. (2) scale epilogue applied to
cache reads instead of weights.  Cache HBM per token drops from
``2 * KVp * dh * 2`` bytes (bf16) to ``2 * KVp * ceil(dh/32) * 2 * 4``
bytes of plane words + 8 bytes of scale — ~8x for production head dims.

Device layout per attention pattern entry (leading dim = num_periods,
stripped by the layer scan exactly like the dense cache):

* packed (``kv_cache_dtype="tnn2"``)::

      k_planes/v_planes  (P, n_rows, groups, 2 * dw, R)  uint32
      k_scale/v_scale    (P, n_pages, page)                f32
      pos                (P, n_pages, page)       int32 = INVALID
      page_table         (P, B, npp)              int32 = 0

  with ``dw = packed_width(head_dim)``: a page's plane words are rows of
  ``R`` lanes (whole 128s) per group of kv heads — rows the ``dw`` plus
  words then the ``dw`` minus words, lanes (kv head, token) — so the
  attention kernel's page copies are tile-aligned.  A page takes ``w``
  lanes of ``kh * page`` (``kernels.paged_attention.page_lanes``, kh =
  KVp / groups) and ``R // w`` pages share a row where ``w`` < 128:
  page ``p`` is row ``p // ppr`` at lanes ``(p % ppr) * w``.  At 8 kv
  heads of 128 and 16-token pages a page is exactly one (8, 128)
  uint32 tile.  ``groups`` is the number of shards of a ``kv_heads``
  split under the mesh the caches are built in (1 outside a mesh).
  Scales live in page metadata (one f32 row per page — the "per-page
  scale table");

* oracle (``"tnn2-oracle"``): same indirection with dense bf16
  ``k``/``v`` pages — bit-comparable reference for the page/table/mask
  machinery with quantization switched off.

**Page 0 is a reserved scratch page**: unallocated page-table entries
point at it and every dead token (chunk padding, inactive batch rows)
is scattered into it with ``pos = INVALID_POS``, so static-shape
in-trace writes need no conditionals and no mask ever accepts scratch
content.  The free list hands out pages 1..n_pages-1; the pool is sized
so a slot's worst case (``ceil(max_len / page)`` pages) always fits,
and the host-side :class:`PageAllocator` keeps exact accounting (the
serving tests assert it balances to zero after drain).

Sliding-window ("AL") entries keep a *ring* of pages: logical position
``p`` lives at slot ``p % (npp * page)``.  The ring capacity is
``window + prefill_chunk - 1`` (page-rounded), not ``window``: a
write-then-attend chunk writes all its tokens before attending, so any
key inside the window of *any* query of the chunk must survive the
chunk's own ring overwrites (see docs/serving.md).

Sharding: page payloads shard their kv-head ``groups`` axis on
"kv_heads" — the split they were built for, which the attention
kernel's shard_map follows — and replicate word/page axes, like the
QTensor payload planes of parallel/qmm_mesh.py, which replicate plane
words within a shard and split only head/feature dims.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoding import pack_ternary, packed_width, unpack_bits
from repro.kernels.paged_attention import page_lanes
from repro.models.common import ModelConfig, ShardLayout
from repro.parallel import sharding
from repro.resilience import faults

__all__ = [
    "INVALID_POS", "SCRATCH_PAGE", "is_paged", "is_packed", "entry_geometry",
    "head_groups", "init_paged_caches", "paged_logical_axes", "ternarize_tokens",
    "append_tokens", "page_view", "PageAllocator", "PagePoolExhausted",
    "EntryPager", "make_pagers", "sync_page_tables", "reset_pages",
    "tree_nbytes",
]


class PagePoolExhausted(RuntimeError):
    """Page allocation failed: not enough free pages for the request.

    A typed subclass so the scheduler can catch exhaustion specifically
    (preempt + backoff re-admission, docs/resilience.md) while every
    other allocator invariant violation (double free, foreign free)
    still propagates as a plain RuntimeError."""

# Canonical here (kvcache.py re-exports it) to keep the import graph
# acyclic: kvcache -> attention -> paged_kvcache.
INVALID_POS = 2 ** 30
SCRATCH_PAGE = 0


def is_paged(entry: Any) -> bool:
    """True for a paged cache entry (detected by its page_table leaf)."""
    return isinstance(entry, dict) and "page_table" in entry


def is_packed(entry: Any) -> bool:
    """True for a paged entry with packed ternary planes (not oracle)."""
    return is_paged(entry) and "k_planes" in entry


def entry_geometry(entry) -> Tuple[int, int, int]:
    """(n_pages, page, npp) from leaf shapes — valid with or without the
    leading period dim (the layer scan strips it)."""
    npp = entry["page_table"].shape[-1]
    n_pages, page = entry["pos"].shape[-2:]
    return n_pages, page, npp


def head_groups(kvp: int) -> int:
    """Groups of the ``kvp`` kv heads a packed pool keeps apart: the
    shards of the active mesh's ``kv_heads`` split (1 outside a mesh)."""
    ctx = sharding.active()
    if ctx is None:
        return 1
    axes = sharding.spec_for((kvp,), ("kv_heads",), ctx)[0]
    axes = () if axes is None else (axes,) if isinstance(axes, str) else axes
    return int(np.prod([ctx.axis_sizes[a] for a in axes], initial=1))


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------

def init_paged_caches(cfg: ModelConfig, layout: ShardLayout, batch: int,
                      max_len: int, *, page_size: int = 16,
                      prefill_chunk: int = 32,
                      oracle: bool = False) -> List[Dict[str, Any]]:
    """Paged caches for every pattern entry (attention mixers only)."""
    from repro.models.attention import head_layout   # late: avoids a cycle
    if any(m == "M" for m, _ in cfg.layer_pattern):
        raise NotImplementedError(
            "paged (tnn2) KV caches cover attention mixers only; pattern "
            f"{cfg.layer_pattern} has an SSM ('M') entry whose recurrent "
            "state has no page structure — serve it with a dense cache")
    if page_size < 1 or prefill_chunk < 1:
        raise ValueError(f"page_size={page_size} / prefill_chunk="
                         f"{prefill_chunk} must be >= 1")
    hl = head_layout(cfg.num_heads, cfg.num_kv_heads, layout.tp)
    dh = cfg.head_dim_
    dw = packed_width(dh)
    p_dim = cfg.num_periods
    caches: List[Dict[str, Any]] = []
    for mixer, _ in cfg.layer_pattern:
        cap = max_len
        if mixer == "AL" and cfg.sliding_window:
            cap = min(cfg.sliding_window + prefill_chunk - 1, max_len)
        npp = -(-cap // page_size)
        n_pages = 1 + batch * npp                 # + the scratch page
        entry: Dict[str, Any] = {
            "pos": jnp.full((p_dim, n_pages, page_size), INVALID_POS,
                            jnp.int32),
            "page_table": jnp.zeros((p_dim, batch, npp), jnp.int32),
        }
        if oracle:
            shape = (p_dim, n_pages, page_size, hl.kvp, dh)
            entry["k"] = jnp.zeros(shape, jnp.bfloat16)
            entry["v"] = jnp.zeros(shape, jnp.bfloat16)
        else:
            groups = head_groups(hl.kvp)
            w, ppr = page_lanes(hl.kvp // groups, page_size)
            wshape = (p_dim, -(-n_pages // ppr), groups, 2 * dw, w * ppr)
            entry["k_planes"] = jnp.zeros(wshape, jnp.uint32)
            entry["v_planes"] = jnp.zeros(wshape, jnp.uint32)
            entry["k_scale"] = jnp.zeros((p_dim, n_pages, page_size),
                                         jnp.float32)
            entry["v_scale"] = jnp.zeros((p_dim, n_pages, page_size),
                                         jnp.float32)
        caches.append(entry)
    return caches


def paged_logical_axes(cfg: ModelConfig) -> List[Dict[str, Any]]:
    """Logical axes per paged leaf (superset of packed + oracle keys)."""
    axes = {
        "pos": (None, None, None),
        "page_table": (None, "batch", None),
        "k": (None, None, None, "kv_heads", None),
        "v": (None, None, None, "kv_heads", None),
        # (period, row, kv-head group, word, lane)
        "k_planes": (None, None, "kv_heads", None, None),
        "v_planes": (None, None, "kv_heads", None, None),
        "k_scale": (None, None, None),
        "v_scale": (None, None, None),
    }
    return [dict(axes) for _ in cfg.layer_pattern]


# ---------------------------------------------------------------------------
# Quantize-at-append (in-trace)
# ---------------------------------------------------------------------------

def ternarize_tokens(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token TWN quantizer over the trailing (heads, dh) axes.

    Vectorized :func:`repro.core.quantize.ternarize`: threshold
    ``0.7 * mean|x|`` and scale ``alpha = E[|x| : |x| > thr]`` computed
    per token (the per-tensor stats of ``conv_act_stats`` at token
    granularity).  Returns (t in {-1,0,+1} f32, alpha (...,) f32).
    """
    xf = x.astype(jnp.float32)
    ax = (-2, -1)
    absx = jnp.abs(xf)
    thr = 0.7 * jnp.mean(absx, axis=ax, keepdims=True)
    mask = absx > thr
    t = jnp.sign(xf) * mask
    denom = jnp.maximum(jnp.sum(mask, axis=ax), 1)
    alpha = jnp.sum(jnp.where(mask, absx, 0.0), axis=ax) / denom
    return t, alpha


def append_tokens(entry: Dict[str, Any], k: jnp.ndarray, v: jnp.ndarray,
                  positions: jnp.ndarray, live: jnp.ndarray
                  ) -> Dict[str, Any]:
    """Scatter S new tokens per slot into the entry's pages (in-trace).

    k/v (B,S,KVp,dh) roped projections; positions (B,S) absolute int32;
    live (B,S) bool — False for chunk padding and rows not writing this
    call.  Dead tokens route to the scratch page with ``INVALID_POS``.
    Entry leaves here carry NO period dim (called inside the layer scan).
    """
    n_pages, page, npp = entry_geometry(entry)
    l_cap = npp * page
    pos32 = positions.astype(jnp.int32)
    # Of two tokens in this call hitting the same ring slot (a chunk
    # longer than an AL ring), only the later one may land — mirrors the
    # sequential one-token-per-step ring writes of decode_attention.
    last = jnp.max(jnp.where(live, pos32, -1), axis=1, keepdims=True)
    live = live & (pos32 + l_cap > last)
    slot = pos32 % l_cap
    lp, off = slot // page, slot % page
    pid = jnp.take_along_axis(entry["page_table"], lp, axis=1)
    pid = jnp.where(live, pid, SCRATCH_PAGE)
    out = dict(entry)
    out["pos"] = entry["pos"].at[pid, off].set(
        jnp.where(live, pos32, INVALID_POS))
    if is_packed(entry):
        kvp, groups = k.shape[-2], entry["k_planes"].shape[-3]
        kh = kvp // groups
        w, ppr = page_lanes(kh, page)
        head = jnp.arange(kvp, dtype=jnp.int32)
        row = (pid // ppr)[..., None]
        lane = ((pid % ppr) * w + off)[..., None] + (head % kh) * page
        for name, val in (("k", k), ("v", v)):
            t, alpha = ternarize_tokens(val)
            words = jnp.concatenate(pack_ternary(t), axis=-1)  # (B,S,KVp,2dw)
            out[f"{name}_planes"] = entry[f"{name}_planes"].at[
                row, head // kh, :, lane].set(words)
            out[f"{name}_scale"] = (
                entry[f"{name}_scale"].at[pid, off].set(alpha))
    else:
        out["k"] = entry["k"].at[pid, off].set(k.astype(entry["k"].dtype))
        out["v"] = entry["v"].at[pid, off].set(v.astype(entry["v"].dtype))
    return out


def page_view(entry: Dict[str, Any], kvp: int, dh: int
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense per-slot gather view of every table entry (in-trace).

    -> (k, v, pos): k/v (B, L_cap, KVp, dh), pos (B, L_cap) with
    ``L_cap = npp * page``; ``kvp`` is the entry's kv heads (a packed
    pool's shape does not give them).  The oracle entries' attention reads it;
    packed entries are read by ``kernels/paged_attention.py`` instead,
    and their view — ``unpack_bits(plus) - unpack_bits(minus)`` times
    the per-token scale, pad bits (0,0) = exact 0 — is the kernel's
    reference in the tests.  Unallocated logical pages resolve to the
    scratch page, whose positions stay ``INVALID_POS`` and fail every
    ``pos <= step`` mask.  Its ops carry the scope ``kv_page_view``
    (metadata only).
    """
    with jax.named_scope("kv_page_view"):
        return _page_view(entry, kvp, dh)


def _page_view(entry, kvp, dh):
    n_pages, page, npp = entry_geometry(entry)
    table = entry["page_table"]                    # (B, npp)
    b = table.shape[0]
    pos = entry["pos"][table].reshape(b, npp * page)
    if is_packed(entry):
        groups, rows = entry["k_planes"].shape[-3:-1]
        kh, dw = kvp // groups, rows // 2
        w, ppr = page_lanes(kh, page)
        lane = (table % ppr)[..., None] * w + jnp.arange(kh * page)

        def dec(name):
            x = entry[f"{name}_planes"][table // ppr]  # (B,npp,grp,2dw,R)
            x = jnp.take_along_axis(x, lane[:, :, None, None, :], axis=-1)
            x = x.reshape(b, npp, groups, rows, kh, page).transpose(
                0, 1, 5, 2, 4, 3).reshape(b, npp, page, kvp, rows)
            val = (unpack_bits(x[..., :dw], dh)
                   - unpack_bits(x[..., dw:], dh)).astype(jnp.float32)
            scale = entry[f"{name}_scale"][table]
            return (val * scale[..., None, None]).reshape(
                b, npp * page, val.shape[-2], dh)
        k, v = dec("k"), dec("v")
    else:
        k = entry["k"][table].reshape(b, npp * page, kvp, dh)
        v = entry["v"][table].reshape(b, npp * page, kvp, dh)
    return k, v, pos


# ---------------------------------------------------------------------------
# Host-side page bookkeeping (the scheduler's side of the cache)
# ---------------------------------------------------------------------------

class PageAllocator:
    """Free-list allocator over the data pages ``1..n_pages-1``.

    Pure host code; raises on exhaustion (the pool is provisioned so a
    correct scheduler never hits it) and on double/foreign frees, so the
    serving tests can assert exact balance-to-zero accounting.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, 0, -1))   # pop() -> low pids
        self._used: set = set()
        self.high_water = 0        # max |used| ever (obs page gauges)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return len(self._used)

    def alloc(self, n: int = 1) -> List[int]:
        if faults.fire("pages.exhausted", want=n):
            raise PagePoolExhausted(
                f"page pool exhausted (injected): want {n}, have "
                f"{len(self._free)} free of {self.n_pages - 1}")
        if n > len(self._free):
            raise PagePoolExhausted(
                f"page pool exhausted: want {n}, have {len(self._free)} "
                f"free of {self.n_pages - 1}")
        out = [self._free.pop() for _ in range(n)]
        self._used.update(out)
        if len(self._used) > self.high_water:
            self.high_water = len(self._used)
        return out

    def free(self, pids: Sequence[int]) -> None:
        for p in pids:
            if p not in self._used:
                raise RuntimeError(f"double/foreign free of page {p}")
            self._used.discard(p)
            self._free.append(p)


class EntryPager:
    """Host mirror of ONE paged entry: allocator + per-slot page lists.

    The device ``page_table`` leaf is rebuilt from :attr:`table` when
    :attr:`dirty` (see :func:`sync_page_tables`) — page allocation and
    reclamation are host decisions, page *content* writes are in-trace.
    """

    def __init__(self, num_slots: int, npp: int, page: int, n_pages: int):
        self.npp, self.page = npp, page
        self.alloc = PageAllocator(n_pages)
        self.table = np.zeros((num_slots, npp), np.int32)
        self.owned: List[List[int]] = [[] for _ in range(num_slots)]
        self.dirty = True

    @classmethod
    def from_entry(cls, entry: Dict[str, Any], num_slots: int) -> "EntryPager":
        n_pages, page, npp = entry_geometry(entry)
        return cls(num_slots, npp, page, n_pages)

    def ensure(self, slot: int, hi: int) -> None:
        """Back positions [0, hi) of ``slot`` (ring-capped at npp pages);
        pages are handed out in logical order so table[slot, j] is the
        j-th logical page."""
        need = min(-(-hi // self.page), self.npp)
        while len(self.owned[slot]) < need:
            (pid,) = self.alloc.alloc(1)
            self.table[slot, len(self.owned[slot])] = pid
            self.owned[slot].append(pid)
            self.dirty = True

    def release(self, slot: int) -> List[int]:
        """Reclaim all of ``slot``'s pages; returns the freed pids (the
        caller must poison their positions via :func:`reset_pages`)."""
        pids, self.owned[slot] = self.owned[slot], []
        if pids:
            self.table[slot, :] = 0
            self.alloc.free(pids)
            self.dirty = True
        return pids

    def live_pages(self, lengths: np.ndarray) -> int:
        """Pages the paged attention kernel reads for slots whose live
        lengths are ``lengths`` (0: a dead row), summed over slots."""
        n = -(-np.asarray(lengths, np.int64) // self.page)
        return int(np.minimum(n, self.npp).sum())

    def device_table(self, num_periods: int) -> jnp.ndarray:
        self.dirty = False
        t = jnp.asarray(self.table)
        return jnp.broadcast_to(t[None], (num_periods,) + t.shape)

    def stats(self) -> Dict[str, int]:
        return {"total": self.alloc.n_pages - 1,
                "used": self.alloc.n_used, "free": self.alloc.n_free,
                "high_water": self.alloc.high_water}


def make_pagers(caches: Sequence[Any], num_slots: int
                ) -> List[Optional[EntryPager]]:
    return [EntryPager.from_entry(e, num_slots) if is_paged(e) else None
            for e in caches]


def sync_page_tables(caches: Sequence[Any],
                     pagers: Sequence[Optional[EntryPager]]) -> List[Any]:
    """Push dirty host tables into the device cache pytree (new list)."""
    out = []
    for e, pg in zip(caches, pagers):
        if pg is not None and pg.dirty:
            e = dict(e)
            e["page_table"] = pg.device_table(e["pos"].shape[0])
        out.append(e)
    return out


def reset_pages(entry: Dict[str, Any], pids: Sequence[int]) -> Dict[str, Any]:
    """Poison freed pages' positions (host-side, between steps) so a
    later owner can never read a stale in-window position through its
    fresh page table before overwriting every row."""
    if not len(pids):
        return entry
    out = dict(entry)
    out["pos"] = entry["pos"].at[:, jnp.asarray(list(pids), jnp.int32)].set(
        INVALID_POS)
    return out


def tree_nbytes(tree: Any) -> int:
    """Total payload bytes of a cache pytree — works on concrete arrays
    and on ``jax.eval_shape`` ShapeDtypeStructs (the serving bench uses
    the latter so the HBM ratio is deterministic)."""
    return int(sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree_util.tree_leaves(tree)))
