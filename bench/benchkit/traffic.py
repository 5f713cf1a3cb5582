"""The one traffic generator: a mix is a data file of parameters.

LM mixes (``"kind": "lm"``, ``"loop": "closed"``): the whole backlog is
queued before the window and the engine's slots pull from it.

* ``requests`` — how many requests the backlog holds;
* ``prompt_len`` / ``output_len`` — ``{"dist": "uniform"|"loguniform",
  "min": a, "max": b}``; ``output_len`` is ``max_new_tokens`` (a request
  emits ``max_new_tokens + 1`` tokens);
* ``block`` — the backlog is made of blocks of this many requests, each
  holding the same sizes (the mid-quantiles of the two distributions)
  in an order the seed picks; so every window that admits whole blocks
  does the same prefill work whatever the seed (default: one block of
  ``requests``);
* ``stagger`` — the first ``slots`` requests start part way through
  their output, as in a long-running server: each holds a context of
  the mix's mean steady-state length (its prompt, then tokens standing
  for output already served, to the mean prompt plus half the mean
  output) and has residual outputs spread evenly up to the mean output.
  They all finish prefill on the same tick, so the window opens with
  every slot decoding, and they finish at a steady rate.

CNN mixes (``"kind": "cnn"``): ``batch``, ``distinct_batches``.

Every seed gets the same multiset of sizes in another order, so two
seeds do the same amount of work; the seed picks the order and the
token ids.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class LMRequest:
    uid: int
    prompt: np.ndarray          # int32 token ids (first wave: with the
                                # tokens that stand for served output)
    max_new_tokens: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, stream])


def stratified(dist: Dict, n: int) -> np.ndarray:
    """``n`` sizes at the mid-quantiles of ``dist`` (a fixed multiset)."""
    lo, hi = int(dist["min"]), int(dist["max"])
    u = (np.arange(n) + 0.5) / n
    kind = dist.get("dist", "uniform")
    if kind == "uniform":
        v = lo + np.floor(u * (hi - lo + 1))
    elif kind == "loguniform":
        v = np.round(np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo))))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def blocked(dist: Dict, n: int, block: int, rng) -> np.ndarray:
    """``n`` sizes: blocks of the ``block`` mid-quantiles of ``dist``,
    each block in its own order drawn from ``rng``."""
    base = stratified(dist, block)
    return np.concatenate([base[rng.permutation(block)]
                           for _ in range(-(-n // block))])[:n]


def lm_requests(tr: Dict, seed: int, vocab: int, *, slots: int,
                max_len: int) -> List[LMRequest]:
    if tr.get("loop", "closed") != "closed":
        raise ValueError(f"unknown loop {tr['loop']!r}")
    n = int(tr["requests"])
    block = int(tr.get("block", n))
    prompts = blocked(tr["prompt_len"], n, block, _rng(seed, 1))
    outs = blocked(tr["output_len"], n, block, _rng(seed, 2))
    tok = _rng(seed, 3)
    reqs = [LMRequest(uid=i,
                      prompt=tok.integers(0, vocab, int(prompts[i]))
                      .astype(np.int32),
                      max_new_tokens=int(outs[i])) for i in range(n)]

    if tr.get("stagger"):
        first = min(slots, n)
        mean_out = float(np.mean(outs))
        ctx = int(round(np.mean(prompts) + mean_out / 2))
        order = _rng(seed, 5).permutation(first)
        for i in range(first):
            r = reqs[i]
            aged = max(ctx - len(r.prompt), 0)
            r.prompt = np.concatenate(
                [r.prompt, tok.integers(0, vocab, aged).astype(np.int32)])
            r.max_new_tokens = max(1, int(round((order[i] + 0.5) / first
                                                * mean_out)))

    for r in reqs:
        if len(r.prompt) + r.max_new_tokens + 1 > max_len:
            raise ValueError(
                f"request {r.uid}: prompt {len(r.prompt)} + output "
                f"{r.max_new_tokens + 1} exceeds max_len {max_len}")
    return reqs


def cnn_batches(tr: Dict) -> Dict[str, int]:
    return {"batch": int(tr["batch"]),
            "distinct_batches": int(tr.get("distinct_batches", 1))}


def max_request_tokens(reqs: List[LMRequest]) -> int:
    return max(len(r.prompt) + r.max_new_tokens + 1 for r in reqs)
