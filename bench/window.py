"""The measured window's arithmetic: what counts, and the statistics.

A run logs every token delivery as ``(t, request_id, n_tokens)`` — the
host time at which a tick ended and handed ``n_tokens`` new tokens of
that request to its client (the first delivery of a request carries its
first token) — and every request as ``(request_id, t_submit)``.  Only
what ends inside ``[t0, t1]`` counts:

* ``output_tok_s``: tokens delivered inside the window / its length;
* ``gap_p50_ms`` / ``gap_p95_ms``: for every request, each gap between
  consecutive deliveries whose end lies inside the window, pooled over
  all requests, then the 50th / 95th percentile of the pool;
* ``ttft_p50_s``: submission to first delivery, over the requests
  submitted inside the window (the run waits for their first tokens
  after the window closes, so none is missing).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

Delivery = Tuple[float, str, int]


def percentile(xs: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def tokens_in(deliveries: Iterable[Delivery], t0: float, t1: float) -> int:
    return sum(n for t, _, n in deliveries if t0 <= t <= t1)


def gaps_in(deliveries: Iterable[Delivery], t0: float, t1: float
            ) -> np.ndarray:
    """Per-request delivery gaps ending inside ``[t0, t1]``, pooled."""
    times: Dict[str, List[float]] = defaultdict(list)
    for t, rid, n in deliveries:
        if n > 0:
            times[rid].append(t)
    out = []
    for ts in times.values():
        ts = sorted(ts)
        out.extend(b - a for a, b in zip(ts, ts[1:]) if t0 <= b <= t1)
    return np.asarray(out, np.float64)


def first_tokens(deliveries: Iterable[Delivery]) -> Dict[str, float]:
    first: Dict[str, float] = {}
    for t, rid, n in deliveries:
        if n > 0 and (rid not in first or t < first[rid]):
            first[rid] = t
    return first


def ttfts(deliveries: Iterable[Delivery],
          submitted: Dict[str, float], t0: float, t1: float) -> np.ndarray:
    """Submission-to-first-token of the requests submitted in the window
    (a request with no first token yet is left out: the run waits for
    them before it reads this)."""
    first = first_tokens(deliveries)
    return np.asarray([first[r] - ts for r, ts in submitted.items()
                       if t0 <= ts <= t1 and r in first], np.float64)


def end_to_end(deliveries: List[Delivery], submitted: Dict[str, float],
               t0: float, t1: float) -> Dict[str, float]:
    """The window's end-to-end numbers (see module docstring) and the
    sample counts behind them."""
    gaps = gaps_in(deliveries, t0, t1)
    tt = ttfts(deliveries, submitted, t0, t1)
    out = {"output_tok_s": tokens_in(deliveries, t0, t1) / (t1 - t0),
           "n_gaps": int(gaps.size), "n_ttft": int(tt.size)}
    if gaps.size:
        out["gap_p50_ms"] = 1e3 * percentile(gaps, 50)
        out["gap_p95_ms"] = 1e3 * percentile(gaps, 95)
    if tt.size:
        out["ttft_p50_s"] = percentile(tt, 50)
    return out
