"""Continuous-batching scheduler: the host-side state machine the Engine
delegates to.

Two strategies share one slot model (queue -> slot -> result):

* :class:`BucketScheduler` — the legacy dense-cache path: a free slot
  admits ONE request per tick by running its whole prompt through the
  bucket-padded ``prefill`` jit and row-inserting the caches
  (``_tree_set_row``).  Kept bit-for-bit so existing dense engines and
  their step-count tests are unchanged.
* :class:`ChunkedScheduler` — the paged-cache path: admission is free
  (no device work), prompts advance ``prefill_chunk`` tokens per tick
  through ONE batched ``chunk_step`` call shared by every prefilling
  slot (per-row ``(start, n)`` step vectors — no per-prompt padding to a
  bucket), interleaved with one ``serve_step`` call for the slots
  already decoding.  Page allocation/reclamation is host-side through
  the per-entry :class:`~repro.models.paged_kvcache.EntryPager`s; page
  *content* writes stay in-trace.

Slot lifecycle (chunked)::

    queued --admit--> PREFILL --chunks done--> DECODE --eos/max/evict--> free
       |                 |                        |
       +--- deadline/cancel() -> Result(status="expired"/"cancelled"),
            pages reclaimed, positions poisoned (reset_pages)

Every tick runs at most two jitted calls — one (B, prefill_chunk) chunk
and one (B, 1) decode — so the engine traces exactly two shapes no
matter how requests overlap.

With obs on, each tick is an ``engine/tick`` profiler span (stat
``tick``) holding one ``sched/*`` span per host step: ``expire``,
``admit`` (``uids``), ``release`` (``uid``), ``page_sync``, ``inputs``,
``prefill_wait``, ``logits_pull`` (``bytes``, ``uids``),
``numeric_guard``, ``token_wait`` and ``emit``.  They add no device op
and no sync (docs/observability.md, "Profiler hooks").
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import paged_kvcache as paged
from repro.models.kvcache import INVALID_POS
from repro.resilience import faults

__all__ = ["Request", "Result", "Scheduler", "BucketScheduler",
           "ChunkedScheduler"]


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (S,) int32 token ids
    max_new_tokens: int = 32
    # Absolute deadline on the engine's clock (time.monotonic unless the
    # engine was built with an injected clock); None = wait forever.
    deadline: Optional[float] = None
    cancelled: bool = False
    # Preemption bookkeeping (docs/resilience.md): how often this
    # request was bumped from a slot (page exhaustion), and the
    # engine-clock instant before which admission must not retry it
    # (capped exponential backoff; None = admissible now).
    retries: int = 0
    not_before: Optional[float] = None

    def cancel(self) -> None:
        """Withdraw the request: evicted (queued or running) on the next
        scheduler tick with ``Result.status == "cancelled"``."""
        self.cancelled = True


@dataclasses.dataclass
class Result:
    uid: int
    tokens: List[int]
    # "ok" | "expired" | "cancelled" | "rejected" (backpressure /
    # overlong prompt — never ran) | "numeric_error" (NaN/Inf logits
    # quarantine) | "error" (step exception quarantine).  Every status
    # is DEFINITE: a submitted request always ends in exactly one.
    status: str = "ok"


def _tree_set_row(tree, row_tree, b: int):
    """Write row_tree (batch size 1 on axis 1-after-period) into slot b.

    Cache leaves are (P, B, ...); row leaves are (P, 1, ...).
    """
    return jax.tree.map(
        lambda full, row: jax.lax.dynamic_update_slice(
            full, row.astype(full.dtype),
            (0, b) + (0,) * (full.ndim - 2)),
        tree, row_tree)


class Scheduler:
    """Shared slot state + request lifecycle; subclasses supply the
    prefill/decode device work.  The engine is duck-typed: the scheduler
    reads/writes ``eng.params``, ``eng.caches``, ``eng.key`` and calls
    its jitted fns — permission to mutate is the delegation contract."""

    def __init__(self, engine, clock=None):
        self.eng = engine
        self.clock = clock or time.monotonic
        b = engine.scfg.num_slots
        self.queue: deque = deque()
        self.slot_uid: List[int] = [-1] * b            # -1 = free
        self.slot_pos = np.zeros(b, np.int32)          # next write position
        self.slot_remaining = np.zeros(b, np.int32)
        self.slot_tokens: List[List[int]] = [[] for _ in range(b)]
        self.last_token = np.zeros(b, np.int32)
        self.slot_req: List[Optional[Request]] = [None] * b
        self.results: Dict[int, Result] = {}
        self.ticks = 0                                 # step() calls
        # uid -> [pre-sampling logits row per step] when the engine was
        # built with ServeConfig.trace_logits (None otherwise).
        self.logit_trace: Optional[Dict[int, List[np.ndarray]]] = (
            {} if engine.scfg.trace_logits else None)

    # ------------------------------------------------------------ lifecycle

    def submit(self, req: Request) -> None:
        scfg = self.eng.scfg
        if scfg.max_queue is not None and len(self.queue) >= scfg.max_queue:
            # Backpressure: the request never enters the system.  A
            # definite Result is still minted so callers always get one.
            self._reject(req)
            return
        self.queue.append(req)
        self.eng.obs.on_submit(req.uid)

    def step(self) -> bool:
        """One tick: expire/cancel, admit+prefill, decode.  Returns True
        while any request is queued or in flight."""
        self.ticks += 1
        with obs.annotate("engine/tick", tick=self.ticks):
            with obs.annotate("sched/expire"):
                self.expire()
            faults.maybe_stall("step.stall")
            self.admit_once()
            # Fired between admission and decode so in-flight slots exist
            # when the loss lands — the hardest spot to recover from.
            faults.maybe_raise("device.loss")
            self.decode_once()
            if self.eng.obs.enabled:
                self.eng.obs.tick(len(self.queue),
                                  sum(1 for u in self.slot_uid if u != -1),
                                  self.page_stats())
        return bool(self.queue or any(u != -1 for u in self.slot_uid))

    def page_stats(self) -> List:
        return []                 # paged schedulers override

    def expire(self) -> None:
        """Evict cancelled / past-deadline requests — queued ones before
        they ever touch a slot, running ones with their partial tokens —
        and reclaim whatever they hold."""
        now: Optional[float] = None
        kept: deque = deque()
        for req in self.queue:
            status = self._dead_status(req, now)
            if status is None:
                kept.append(req)
            else:
                self.results[req.uid] = Result(req.uid, [], status=status)
                self.eng.obs.on_queue_drop(req.uid, status)
        self.queue = kept
        for b in range(len(self.slot_uid)):
            if self.slot_uid[b] == -1:
                continue
            status = self._dead_status(self.slot_req[b], now)
            if status is not None:
                self.finish(b, status=status)

    def _dead_status(self, req: Request, now) -> Optional[str]:
        if req.cancelled:
            return "cancelled"
        if req.deadline is not None:
            if now is None:
                now = self.clock()
            if now > req.deadline:
                return "expired"
        return None

    def finish(self, b: int, status: str = "ok") -> None:
        self.results[self.slot_uid[b]] = Result(
            self.slot_uid[b], self.slot_tokens[b], status=status)
        self.eng.obs.on_finish(self.slot_uid[b], status,
                               len(self.slot_tokens[b]))
        self._free(b)

    def _free(self, b: int) -> None:
        """Empty slot ``b`` and give back what it holds."""
        uid = self.slot_uid[b]
        self.slot_uid[b] = -1
        self.slot_tokens[b] = []
        self.slot_req[b] = None
        with obs.annotate("sched/release", uid=uid):
            self.release(b)

    def release(self, b: int) -> None:          # pages, in the paged case
        pass

    def trace(self, uid: int, row) -> None:
        if self.logit_trace is not None:
            self.logit_trace.setdefault(uid, []).append(
                np.asarray(row, np.float32).copy())

    # ------------------------------------------------------- degradation

    def _reject(self, req: Request) -> None:
        """Resolve a request as "rejected" without it ever holding a slot
        or a page (queue overflow, overlong prompt)."""
        self.results[req.uid] = Result(req.uid, [], status="rejected")
        self.eng.obs.on_queue_drop(req.uid, "rejected")

    def _pop_ready(self) -> Optional[Request]:
        """Pop the first queued request whose backoff window has passed.

        Requests still inside ``not_before`` are rotated to the back (so
        one backing-off head never starves the rest); returns None when
        the queue is empty or everything is waiting out a backoff.
        """
        now: Optional[float] = None
        for _ in range(len(self.queue)):
            req = self.queue[0]
            if req.not_before is not None:
                if now is None:
                    now = self.clock()
                if now < req.not_before:
                    self.queue.rotate(-1)
                    continue
                req.not_before = None
            return self.queue.popleft()
        return None

    def preempt(self, b: int, cause: str = "page_exhausted") -> None:
        """Bump slot ``b``'s request back to the queue (no Result): pages
        are reclaimed now and admission retries it after a capped
        exponential backoff.  Partial decode output is discarded — a
        retried request replays from its prompt, so results stay
        deterministic rather than resuming from reclaimed state."""
        scfg = self.eng.scfg
        req = self.slot_req[b]
        req.retries += 1
        delay = min(scfg.retry_backoff_s * (2 ** (req.retries - 1)),
                    scfg.retry_backoff_cap_s)
        req.not_before = self.clock() + delay
        self.eng.obs.on_preempt(req.uid, cause, req.retries, delay)
        self._free(b)
        self.queue.append(req)

    def quarantine(self, exc: BaseException) -> None:
        """Containment for a step() that raised (``Engine.run``): every
        in-flight request resolves as "error" and its pages come back, so
        the queue keeps draining on later ticks instead of wedging."""
        in_flight = sum(1 for u in self.slot_uid if u != -1)
        self.eng.obs.on_step_error(exc, in_flight)
        for b in range(len(self.slot_uid)):
            if self.slot_uid[b] != -1:
                self.finish(b, status="error")

    def shutdown(self) -> None:
        """Engine.close() path: release every occupied slot's resources
        WITHOUT minting Results (close abandons work, it doesn't resolve
        it — ``unfinished()`` is how callers migrate the remainder)."""
        for b in range(len(self.slot_uid)):
            if self.slot_uid[b] != -1:
                self._free(b)

    def unfinished(self) -> List[Request]:
        """Queued plus in-flight requests, admission order first — what
        ``Engine.rebuild_after_loss`` migrates to the replacement."""
        out = list(self.queue)
        out.extend(r for r in self.slot_req if r is not None)
        return out

    def admit_once(self) -> None:
        raise NotImplementedError

    def decode_once(self) -> None:
        raise NotImplementedError

    def _emit_decoded(self, rows: List[int], nxt, last_logits) -> None:
        """Wait for a decode step's tokens and hand them to slots
        ``rows``.  The NaN/Inf guard's reduce is dispatched behind the
        step before the wait and pulled after it, so the wait for the
        step is all in ``sched/token_wait``."""
        scfg = self.eng.scfg
        fin = None
        if scfg.numeric_guard:
            with obs.annotate("sched/numeric_guard"):
                fin = jnp.all(jnp.isfinite(last_logits), axis=-1)
        with obs.annotate("sched/token_wait"):
            nxt = np.asarray(nxt)
        if fin is not None:
            with obs.annotate("sched/numeric_guard"):
                fin = np.asarray(fin)
        with obs.annotate("sched/emit"):
            if self.logit_trace is not None:
                lg = np.asarray(last_logits)
                for b in rows:
                    self.trace(self.slot_uid[b], lg[b])
            for b in rows:
                if fin is not None and not fin[b]:
                    # Poisoned logits: the sampled token is garbage —
                    # resolve the stream instead of emitting NaN-derived
                    # tokens.
                    self.finish(b, status="numeric_error")
                    continue
                self.slot_tokens[b].append(int(nxt[b]))
                self.last_token[b] = nxt[b]
                self.slot_pos[b] += 1
                self.slot_remaining[b] -= 1
                self.eng.obs.on_decode_token(self.slot_uid[b])
                if (self.slot_remaining[b] <= 0
                        or int(nxt[b]) == scfg.eos_id
                        or self.slot_pos[b] >= scfg.max_len):
                    self.finish(b)


# ---------------------------------------------------------------------------
# Legacy dense path: bucket prefill, one prompt per tick per free slot
# ---------------------------------------------------------------------------

class BucketScheduler(Scheduler):
    """Admit-by-bucket-prefill over dense slab caches (the pre-paged
    engine behaviour, preserved exactly — including its step counts)."""

    def admit_once(self) -> None:
        eng = self.eng
        for b in range(eng.scfg.num_slots):
            if self.slot_uid[b] != -1:
                continue
            req = self._pop_ready()
            if req is None:
                break
            prompt = np.asarray(req.prompt, np.int32)
            if len(prompt) > eng._buckets()[-1]:
                self._reject(req)
                continue
            eng.obs.on_admit(req.uid)
            # Claim the slot BEFORE any device work so a prefill that
            # raises still resolves through quarantine() instead of
            # silently losing the popped request.
            self.slot_uid[b] = req.uid
            self.slot_req[b] = req
            self.slot_tokens[b] = []
            bucket = next(s for s in eng._buckets() if s >= len(prompt))
            padded = np.zeros(bucket, np.int32)
            padded[-len(prompt):] = prompt      # right-aligned, left pad 0s
            batch = {"tokens": jnp.asarray(padded[None, :])}
            logits, row_caches = eng.prefill(
                eng.params, eng._prefill_caches[bucket], batch)
            # Left-pad slots must never be attended: poison their cache
            # positions so the `pos <= step` mask rejects them.  (SSM
            # archs have no position mask — serve those with exact-length
            # prompts / bucket == prompt length.)
            pad = bucket - len(prompt)
            if pad:
                row_caches = [
                    {**c, "pos": c["pos"].at[:, :, :pad].set(INVALID_POS)}
                    if isinstance(c, dict) and "pos" in c else c
                    for c in row_caches]
            eng.caches = [
                _tree_set_row(full, row, b)
                for full, row in zip(eng.caches, row_caches)]
            self.slot_pos[b] = bucket
            self.slot_remaining[b] = min(
                req.max_new_tokens, eng.scfg.max_len - bucket)
            lg_row = np.asarray(logits)[0, -1]
            eng.obs.on_prefill_tokens(len(prompt))
            if eng.scfg.numeric_guard and not np.isfinite(lg_row).all():
                self.finish(b, status="numeric_error")
                continue
            first = int(np.argmax(lg_row))
            self.trace(req.uid, lg_row)
            self.slot_tokens[b] = [first]
            self.last_token[b] = first
            eng.obs.on_first_token(req.uid)

    def decode_once(self) -> None:
        eng = self.eng
        live = [b for b in range(eng.scfg.num_slots)
                if self.slot_uid[b] != -1]
        if not live:
            return
        step = jnp.asarray(self.slot_pos, jnp.int32)   # per-slot positions
        toks = jnp.asarray(self.last_token[:, None])
        eng.key, sub = jax.random.split(eng.key)
        nxt, last_logits, eng.caches = eng.serve_step(
            eng.params, eng.caches, toks, step, sub)
        if faults.fire("logits.nan", op="decode", path="bucket"):
            last_logits = last_logits.at[live[0]].set(jnp.nan)
        self._emit_decoded(live, nxt, last_logits)


# ---------------------------------------------------------------------------
# Paged path: chunked prefill interleaved with decode
# ---------------------------------------------------------------------------

class ChunkedScheduler(Scheduler):
    """Per-tick continuous batching over paged (tnn2 / oracle) caches."""

    def __init__(self, engine, clock=None):
        super().__init__(engine, clock)
        b = engine.scfg.num_slots
        self.pagers = paged.make_pagers(engine.caches, b)
        self.slot_prompt: List[Optional[np.ndarray]] = [None] * b
        self.slot_done = np.zeros(b, np.int32)   # prompt tokens processed
        self.slot_phase: List[str] = ["free"] * b

    # ------------------------------------------------------------- pages

    def release(self, b: int) -> None:
        self.slot_phase[b] = "free"
        self.slot_prompt[b] = None
        for i, pg in enumerate(self.pagers):
            if pg is None:
                continue
            pids = pg.release(b)
            if pids:
                self.eng.caches[i] = paged.reset_pages(self.eng.caches[i],
                                                       pids)

    def _ensure(self, b: int, hi: int) -> None:
        for pg in self.pagers:
            if pg is not None:
                pg.ensure(b, hi)

    def _sync(self) -> None:
        with obs.annotate("sched/page_sync"):
            self.eng.caches = paged.sync_page_tables(self.eng.caches,
                                                     self.pagers)

    def _kv_pages(self, lengths: np.ndarray) -> Dict[str, int]:
        """Span stats of a step's page reads: the pages the packed
        entries' attention kernel reads for these live lengths
        (``kv_pages_live``) and the pages a dense view of every table
        entry would decode (``kv_pages_view``), summed over slots and
        entries.  Empty with obs off or no packed entry."""
        if not obs.obs_enabled():
            return {}
        live = view = 0
        for entry, pg in zip(self.eng.caches, self.pagers):
            if pg is not None and paged.is_packed(entry):
                live += pg.live_pages(lengths)
                view += len(lengths) * pg.npp
        return {"kv_pages_live": live, "kv_pages_view": view} if view else {}

    def page_stats(self) -> List[Optional[Dict[str, int]]]:
        return [pg.stats() if pg is not None else None
                for pg in self.pagers]

    # --------------------------------------------------------- admission

    def admit_once(self) -> None:
        scfg = self.eng.scfg
        with obs.annotate("sched/admit") as span:
            uids = []
            for b in range(scfg.num_slots):
                if self.slot_uid[b] != -1:
                    continue
                req = self._pop_ready()
                if req is None:
                    break
                prompt = np.asarray(req.prompt, np.int32).reshape(-1)
                if len(prompt) >= scfg.max_len:
                    # Needs room to decode at least one token: a definite
                    # "rejected" Result, not an exception out of step().
                    self._reject(req)
                    continue
                self.eng.obs.on_admit(req.uid)
                self.slot_uid[b] = req.uid
                self.slot_req[b] = req
                self.slot_prompt[b] = prompt
                self.slot_done[b] = 0
                self.slot_pos[b] = 0
                self.slot_tokens[b] = []
                self.slot_phase[b] = "prefill"
                uids.append(req.uid)
            if uids:
                span.set_metadata(uids=uids)
        self._prefill_round()

    def _prefill_round(self) -> None:
        scfg = self.eng.scfg
        chunk = scfg.prefill_chunk
        rows = [b for b in range(scfg.num_slots)
                if self.slot_phase[b] == "prefill"]
        if not rows:
            return
        toks = np.zeros((scfg.num_slots, chunk), np.int32)
        step2 = np.zeros((scfg.num_slots, 2), np.int32)
        live = []
        for b in rows:
            done = int(self.slot_done[b])
            n = min(chunk, len(self.slot_prompt[b]) - done)
            try:
                self._ensure(b, done + n)
            except paged.PagePoolExhausted:
                self.preempt(b, "page_exhausted")
                continue
            toks[b, :n] = self.slot_prompt[b][done:done + n]
            step2[b] = (done, n)
            live.append(b)
        rows = live
        if not rows:
            return
        self._sync()
        pages = self._kv_pages(np.where(step2[:, 1] > 0, step2.sum(1), 0))
        with obs.annotate("sched/inputs", step="chunk", **pages):
            toks_d, step2_d = jnp.asarray(toks), jnp.asarray(step2)
        logits, self.eng.caches = self.eng.chunk_step(
            self.eng.params, self.eng.caches, toks_d, step2_d)
        if faults.fire("logits.nan", op="prefill", path="chunked"):
            b0 = rows[0]
            logits = logits.at[b0, int(step2[b0, 1]) - 1].set(jnp.nan)
        # prompts this chunk completes take their greedy first token
        # from the last REAL chunk position (matches the bucket path's
        # argmax): only then does the tick wait for the chunk
        completing = [b for b in rows if self.slot_done[b] + step2[b, 1]
                      >= len(self.slot_prompt[b])]
        logits_np = None
        if completing:
            with obs.annotate("sched/prefill_wait"):
                logits.block_until_ready()
            with obs.annotate("sched/logits_pull", bytes=logits.nbytes,
                              uids=[self.slot_uid[b] for b in completing]):
                logits_np = np.asarray(logits)
        with obs.annotate("sched/emit"):
            for b in rows:
                n = int(step2[b, 1])
                self.slot_done[b] += n
                self.eng.obs.on_prefill_tokens(n)
                if b not in completing:
                    continue
                if (scfg.numeric_guard
                        and not np.isfinite(logits_np[b, n - 1]).all()):
                    self.finish(b, status="numeric_error")
                    continue
                first = int(np.argmax(logits_np[b, n - 1]))
                self.trace(self.slot_uid[b], logits_np[b, n - 1])
                plen = len(self.slot_prompt[b])
                self.slot_phase[b] = "decode"
                self.slot_pos[b] = plen
                self.slot_remaining[b] = min(
                    self.slot_req[b].max_new_tokens, scfg.max_len - plen)
                self.slot_tokens[b] = [first]
                self.last_token[b] = first
                self.eng.obs.on_first_token(self.slot_uid[b])
                if self.slot_remaining[b] <= 0:
                    self.finish(b)

    # ------------------------------------------------------------ decode

    def decode_once(self) -> None:
        scfg = self.eng.scfg
        rows = [b for b in range(scfg.num_slots)
                if self.slot_phase[b] == "decode"]
        if not rows:
            return
        step = np.full(scfg.num_slots, -1, np.int32)
        live = []
        for b in rows:
            try:
                self._ensure(b, int(self.slot_pos[b]) + 1)
            except paged.PagePoolExhausted:
                self.preempt(b, "page_exhausted")
                continue
            step[b] = self.slot_pos[b]
            live.append(b)
        rows = live
        if not rows:
            return
        self._sync()
        pages = self._kv_pages(np.where(step >= 0, step + 1, 0))
        with obs.annotate("sched/inputs", step="decode", **pages):
            toks = jnp.asarray(np.where(step >= 0, self.last_token, 0)
                               .astype(np.int32)[:, None])
            self.eng.key, sub = jax.random.split(self.eng.key)
            step = jnp.asarray(step)
        nxt, last_logits, self.eng.caches = self.eng.serve_step(
            self.eng.params, self.eng.caches, toks, step, sub)
        if faults.fire("logits.nan", op="decode", path="chunked"):
            last_logits = last_logits.at[rows[0]].set(jnp.nan)
        self._emit_decoded(rows, nxt, last_logits)
