"""Percentiles and rates over a measured window.

Every tail is taken over all samples of the window, never as a median of
per-chunk tails.  A request still waiting when the window closes enters
with its wait so far, so a stall cannot hide behind the window's edge.
"""
from __future__ import annotations

import numpy as np


def percentile(samples, q: float) -> "float | None":
    """The ``q``-th percentile (linear interpolation) of all samples, or
    None where there are none."""
    if len(samples) == 0:
        return None
    return float(np.percentile(np.asarray(samples, np.float64), q))


def ttft_samples(reqs, t0: float, t1: float) -> "list[float]":
    """First token minus due time, for every request due in [t0, t1); a
    request with no first token by t1 enters with t1 - due.  ``reqs``:
    (due, token_times) pairs on one clock."""
    out = []
    for due, tt in reqs:
        if not t0 <= due < t1:
            continue
        first = tt[0] if tt else None
        out.append((first if first is not None and first <= t1 else t1)
                   - due)
    return out


def wait_samples(pairs, t0: float, t1: float) -> "list[float]":
    """``start - due`` for every (due, start) due in [t0, t1); a start
    after t1 (or none, 0) enters as t1 - due."""
    return [((s if 0 < s <= t1 else t1) - due) for due, s in pairs
            if t0 <= due < t1]


def itl_samples(token_times, done: bool, t0: float, t1: float
                ) -> "list[float]":
    """Gaps between consecutive tokens whose later token falls in (t0, t1];
    a request still decoding at t1 adds the gap since its last token."""
    tt = list(token_times)
    out = [b - a for a, b in zip(tt, tt[1:]) if t0 < b <= t1]
    k = sum(1 for t in tt if t <= t1)
    finished = done and k == len(tt)
    if k and not finished and t1 > tt[k - 1]:
        out.append(t1 - tt[k - 1])
    return out


def tokens_in(token_times, t0: float, t1: float) -> int:
    return sum(1 for t in token_times if t0 < t <= t1)

