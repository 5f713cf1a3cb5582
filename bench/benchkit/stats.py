"""Window arithmetic on the harness's own host clock.

A rate is taken over all the work and all the time of the window; a
tail is the tail of every sample in it.  Percentiles interpolate
linearly between order statistics (``numpy.percentile``'s default).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


class TokenLedger:
    """Output tokens as a client sees them: each request's count after
    every engine tick, stamped with the time the tick returned.

    ``observe(uid, total, t, in_window)`` records that request ``uid``
    has emitted ``total`` tokens so far.  Tokens that appear in a window
    tick count toward the rate; a gap between consecutive tokens of one
    request counts toward the tail when both appeared in the window
    (several tokens in one tick are several tokens with gaps of 0).
    """

    def __init__(self) -> None:
        self.count: Dict[int, int] = {}
        self.last: Dict[int, float] = {}
        self.last_in_window: Dict[int, bool] = {}
        self.first_t: Dict[int, float] = {}
        self.tokens = 0
        self.gaps: List[float] = []

    def observe(self, uid: int, total: int, t: float, in_window: bool) -> int:
        new = total - self.count.get(uid, 0)
        if new <= 0:
            return 0
        self.count[uid] = total
        if uid not in self.first_t:
            self.first_t[uid] = t
        if in_window:
            self.tokens += new
            if self.last_in_window.get(uid):
                self.gaps.append(t - self.last[uid])
            self.gaps.extend([0.0] * (new - 1))
        self.last[uid] = t
        self.last_in_window[uid] = in_window
        return new
