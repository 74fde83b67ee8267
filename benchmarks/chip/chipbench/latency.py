"""Latency arithmetic, copied from the program's `gateway/metrics.py`
(exact percentile over raw samples with linear interpolation, gaps between
consecutive streamed tokens) and changed to time a request from when it
was due, not from when it was submitted: an open loop counts the wait that
a stall imposes on later requests."""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

import numpy as np


def percentile(xs: Sequence[float], p: float) -> Optional[float]:
    """Exact percentile over raw samples; None when there are none."""
    if len(xs) == 0:
        return None
    return float(np.percentile(np.asarray(xs, float), p))


def time_to_first_token(due: float, token_ts: Sequence[float]) -> float:
    """Seconds from due to the first token; inf if none ever came (a
    request that never answers misses every latency limit)."""
    return token_ts[0] - due if token_ts else float("inf")


def gaps_in_window(token_ts: Sequence[float], t0: float,
                   t1: float) -> List[float]:
    """Gaps between consecutive tokens of one request whose both ends lie
    in [t0, t1]."""
    return [b - a for a, b in zip(token_ts, token_ts[1:])
            if a >= t0 and b <= t1]


def tokens_in_window(all_ts: Iterable[Sequence[float]], t0: float,
                     t1: float) -> int:
    return sum(1 for ts in all_ts for t in ts if t0 <= t <= t1)
