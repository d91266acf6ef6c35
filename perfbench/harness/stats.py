"""Metric arithmetic: percentiles, spreads, the knee of a rate sweep.
(The time-to-first-chunk and gap arithmetic follows ``bench_streaming.py``:
a stream's first chunk is timed from when the request was due, gaps are
between consecutive chunks of one stream.)"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float):
    """Nearest-rank percentile, ``q`` in (0, 100]; None without samples.
    A tail is the tail of all requests: a missing one counts as +inf."""
    xs = sorted(values)
    if not xs:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def spread(values) -> float:
    """Distance between the quartiles as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def stream_times(due: float, chunk_times: list) -> dict:
    """Time to the first chunk from when the request was due, and the gaps
    between consecutive chunks."""
    if not chunk_times:
        return {"ttfb": math.inf, "gaps": []}
    return {"ttfb": chunk_times[0] - due,
            "gaps": [b - a for a, b in zip(chunk_times, chunk_times[1:])]}


def knee(rows: list) -> float:
    """The highest offered rate of a sweep that was sustained: no request
    refused or failed, and the backlog at the end of the step no larger
    than at its middle.  ``rows``: ``{"rate", "failed", "backlog_mid",
    "backlog_end"}`` in rising order of rate; 0.0 when none held."""
    best = 0.0
    for r in sorted(rows, key=lambda r: r["rate"]):
        if r["failed"] or r["backlog_end"] > max(r["backlog_mid"], 1):
            break
        best = r["rate"]
    return best
