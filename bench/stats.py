"""Order statistics and the request timings built on them.

``percentile`` interpolates linearly between closest ranks on the sorted
sample (numpy's default ``method="linear"``), in plain Python.  ``spread``
is a set of runs' spread as the bounds in ``BENCHMARK.json`` are set from.
"""

from __future__ import annotations

import statistics
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolated ``q``-th percentile (0..100); raises on no data."""
    if not xs:
        raise ValueError("percentile of empty sequence")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100], got {q}")
    s = sorted(float(x) for x in xs)
    pos = (q / 100.0) * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    frac = pos - lo
    return s[lo] * (1.0 - frac) + s[hi] * frac


def inter_token_gaps(requests, t_open: float, t_close: float) -> list[float]:
    """Every gap between consecutive tokens of a request whose later token
    came in ``[t_open, t_close]``."""
    out = []
    for r in requests:
        ts = r.token_t
        out.extend(b - a for a, b in zip(ts, ts[1:]) if t_open <= b <= t_close)
    return out


def tokens_in(requests, t_open: float, t_close: float) -> int:
    return sum(1 for r in requests for t in r.token_t if t_open <= t <= t_close)


def spread(xs: Sequence[float]) -> float:
    """The distance between the first and the third quartile of
    ``statistics.quantiles(xs, n=4)``, over the median."""
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / q2


def trimmed_spread(xs: Sequence[float]) -> float:
    """``spread`` without the value farthest from the median."""
    mid = statistics.median(xs)
    far = max(range(len(xs)), key=lambda i: abs(xs[i] - mid))
    return spread([x for i, x in enumerate(xs) if i != far])
