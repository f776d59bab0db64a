"""Arithmetic of the yardstick: percentiles, spreads, and the due-time
latencies of an open loop. Pure Python + NumPy, no JAX, so the tests pin it
on hand-made timelines."""

from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """q-th percentile (0..100), linear interpolation; None on no data."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median: the driver's measure
    of how far runs of the same code disagree. The quartiles are those of
    ``statistics.quantiles(values, n=4)``, as the driver takes them; numpy's
    lie closer together (six runs that read 0.8 % there read 2 % here)."""
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return float((q3 - q1) / abs(q2)) if q2 else None


def spread_without_farthest(values: Sequence[float]) -> Optional[float]:
    """The spread of a set with its run farthest from the median left out
    where that narrows it (the whole set's where it does not): what the
    driver holds against half a bound (the mean of two sets') when it asks
    whether the bound is too tight. One far-off run in a set does no harm
    there, two do."""
    if len(values) < 3:
        return None
    mid = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - mid))[:-1]
    both = [s for s in (spread(values), spread(kept)) if s is not None]
    return min(both) if both else None


def ttft_ms(due_s: Sequence[float], first_token_s: Sequence[Optional[float]],
            ) -> List[float]:
    """Time to first token of each request, in ms, from the time the request
    was DUE by the schedule (not from when the generator got round to
    submitting it: a stalled server delays the submit too, and timing from
    the submit would hide exactly that wait). A request that never showed a
    first token (failed, refused, or still waiting when the run gave up)
    counts as the largest value seen."""
    known = [(f - d) * 1e3 for d, f in zip(due_s, first_token_s)
             if f is not None]
    worst = max(known) if known else float("inf")
    return [(f - d) * 1e3 if f is not None else worst
            for d, f in zip(due_s, first_token_s)]


def token_gaps_ms(token_times_s: Iterable[Sequence[float]],
                  t0: float, t1: float) -> List[float]:
    """Gaps between successive tokens of one request becoming visible to
    the caller, over every request, for the gaps that END inside [t0, t1).
    The first token of a request ends no gap."""
    gaps: List[float] = []
    for times in token_times_s:
        for a, b in zip(times, times[1:]):
            if t0 <= b < t1:
                gaps.append((b - a) * 1e3)
    return gaps


def summarize_runs(runs: Sequence[Dict[str, float]]) -> Dict[str, dict]:
    """Median and spread of each metric over several runs of one cell."""
    names = sorted({k for r in runs for k in r})
    out = {}
    for name in names:
        vals = [r[name] for r in runs if name in r]
        out[name] = {"n": len(vals), "median": median(vals),
                     "spread": spread(vals),
                     "spread_without_farthest": spread_without_farthest(vals),
                     "values": vals}
    return out
