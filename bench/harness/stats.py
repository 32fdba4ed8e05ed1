"""Latency and rate arithmetic over the load generator's records.

Every latency is taken from the request's due time, so a stall that delays
later requests counts against them.  A request that was shed (HTTP 429) or
failed counts as missing every limit: its latency is infinite.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

INF = float("inf")


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it.  None for no values."""
    if not values:
        return None
    xs = sorted(values)
    idx = min(max(int(math.ceil(q / 100.0 * len(xs))) - 1, 0), len(xs) - 1)
    return xs[idx]


def ok(rec: Dict) -> bool:
    return rec["status"] == "ok"


def ttfts(records: List[Dict]) -> List[float]:
    """Seconds from due to the first content chunk; infinite for a shed or
    failed request, or one that streamed no content."""
    return [rec["chunks"][0] - rec["due"] if ok(rec) and rec["chunks"]
            else INF for rec in records]


def itls(records: List[Dict]) -> List[float]:
    """Every gap between consecutive content chunks of the completed
    requests, and one infinite gap for each shed or failed request."""
    out = []
    for rec in records:
        if not ok(rec):
            out.append(INF)
            continue
        c = rec["chunks"]
        out.extend(b - a for a, b in zip(c, c[1:]))
    return out


def tokens_in_window(records: List[Dict], t0: float, seconds: float) -> int:
    """Content tokens that reached a client inside the window."""
    return sum(t0 <= t < t0 + seconds for rec in records
               for t in rec["chunks"])


def missing_limit_s(seconds: float, grace_s: float) -> float:
    """The latency reported for a percentile that falls on a request that
    never completed: the longest wait the generator allows one."""
    return seconds + grace_s


def latency_ms(values: List[float], q: float, cap_s: float
               ) -> Optional[float]:
    p = percentile(values, q)
    if p is None:
        return None
    return 1000.0 * (cap_s if p == INF else p)


def gateway_stage_ms(records: List[Dict], key: str) -> Optional[float]:
    """Median of one stage of the gateway's timing payload (``timing`` in
    the last chunk of each completed stream), in milliseconds."""
    vals = [rec["final"]["timing"][key] for rec in records
            if ok(rec) and rec.get("final")
            and key in rec["final"].get("timing", {})]
    p = percentile(vals, 50)
    return None if p is None else 1000.0 * p
