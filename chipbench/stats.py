"""End-to-end metric arithmetic on the client's clock (the percentile and
attainment rules of the repository's ``serving/metrics.py``, fed with wall
times taken at the client instead of the engine's modelled clock).
Imports nothing of JAX."""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def percentile(vals: Sequence[float], p: float) -> float:
    if not len(vals):
        raise ValueError("percentile of no samples")
    return float(np.percentile(np.asarray(vals, dtype=np.float64), p))


def quantiles(vals: Sequence[float], ps=(50, 99)) -> Dict[str, float]:
    return {f"p{p}": percentile(vals, p) for p in ps} if len(vals) else {}


def ttfts(streams, drain_end: float) -> List[float]:
    """TTFT of each stream from when it was due. One with no first token by
    the end of the drain failed: it is given the time up to the drain's end,
    the least it would have taken, so it sits at the top."""
    out = []
    for s in streams:
        first = s.first
        out.append((first if first is not None else drain_end) - s.due)
    return out


def tbt_gaps(streams, t0: float, t1: float) -> List[float]:
    """Every gap between successive token events of one stream whose later
    event was received inside the window ``[t0, t1)``."""
    gaps = []
    for s in streams:
        prev = None
        for t, n in s.events:
            if n <= 0:
                continue
            if prev is not None and t0 <= t < t1:
                gaps.append(t - prev)
            prev = t
    return gaps


def window_tokens(streams, t0: float, t1: float) -> int:
    return sum(n for s in streams for t, n in s.events if t0 <= t < t1)


def end_to_end(window_streams, all_streams, t0: float, t1: float,
               drain_end: float, limits: Dict[str, float]) -> Dict[str, float]:
    """The cell's client-side numbers. ``window_streams`` are the requests
    due in ``[t0, t1)``; ``all_streams`` also holds the pre-roll's, whose
    tokens received inside the window count toward the token rate and the
    gaps between tokens."""
    tt = ttfts(window_streams, drain_end)
    met = sum(1 for s, v in zip(window_streams, tt)
              if s.first is not None and v <= limits[s.slo_class])
    gaps = tbt_gaps(all_streams, t0, t1)
    out = dict(
        ttft_attainment=met / len(window_streams),
        output_tokens_per_s=window_tokens(all_streams, t0, t1) / (t1 - t0),
        n_ttft=len(tt), n_gaps=len(gaps))
    if tt:
        out.update(ttft_p90_s=percentile(tt, 90), ttft_p50_s=percentile(tt, 50))
    if gaps:
        out.update(tbt_p99_s=percentile(gaps, 99), tbt_p50_s=percentile(gaps, 50))
    return out
