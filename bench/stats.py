"""Exact percentiles and the window rules every end-to-end metric uses.

Times are client-side ``time.perf_counter()`` seconds relative to the
window's start. A token counts for throughput when it reaches the client
inside ``[0, window)``. An inter-token gap counts when its later token
reaches the client inside the window. Time to first token is taken over
the requests due inside the window, each waited for through the grace
period; a request that got no token by the end of grace counts with the
time it had waited by then, and as failed.
"""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """Exact ``q``-th percentile (linear between order statistics) of the
    samples themselves, never of a histogram."""
    v = np.asarray(list(values), dtype=np.float64)
    if v.size == 0:
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(v, q))


def window_tokens(times: dict[int, list[float]], window: float) -> int:
    return sum(int(np.sum((np.asarray(t) >= 0) & (np.asarray(t) < window)))
               for t in times.values())


def window_gaps(times: dict[int, list[float]], window: float) -> list[float]:
    gaps: list[float] = []
    for t in times.values():
        t = np.asarray(t, dtype=np.float64)
        if t.size < 2:
            continue
        later = t[1:]
        keep = (later >= 0) & (later < window)
        gaps.extend((later - t[:-1])[keep].tolist())
    return gaps


def ttfts(due: dict[int, float], times: dict[int, list[float]],
          window: float, grace_end: float) -> tuple[list[float], int]:
    """(time to first token of each request due in the window, number of
    them that had no token by ``grace_end``)."""
    out, missing = [], 0
    for rid, d in due.items():
        if not 0 <= d < window:
            continue
        t = times.get(rid) or []
        if t:
            out.append(t[0] - d)
        else:
            out.append(grace_end - d)
            missing += 1
    return out, missing
