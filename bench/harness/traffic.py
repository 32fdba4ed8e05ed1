"""The one traffic generator: a mix file of parameters in, a schedule of
requests out.  No JAX here: the load generator's process imports this.

Every seed gets the same multiset of sizes and gaps, in another order:
sizes and gaps are the quantiles of the mix's distributions at fixed
points, and ``--seed`` shuffles them, picks the filler words and the
order of topics.  So two seeds offer the same work, and runs of different
seeds spread no wider than runs of one.

A mix file holds:

* ``loop``: ``open`` (requests are sent when due, whatever the system
  does) or ``closed`` (``clients`` clients, each sending its next request
  when the last one has finished);
* ``arrivals`` (open loop): ``{"kind": "poisson", "rate_per_s": r}``, or
  ``{"kind": "bursts", "rate_per_s": r, "burst_min": a, "burst_max": b}``
  (bursts of a..b simultaneous requests, exponential gaps between bursts,
  ``r`` requests per second on average);
* ``lam``: ``{"values": [...]}``, drawn in equal shares;
* ``max_tokens``: ``{"kind": "lognormal", "median", "sigma", "min",
  "max"}``, ``{"kind": "uniform", "min", "max"}`` or ``{"kind": "const",
  "value"}``;
* ``words``: ``{"kind": "lognormal", "median", "sigma", "min", "max"}``:
  words per prompt, the first ones a topic phrase and the rest filler;
* ``check_tokens``: how many served tokens the correctness sample holds.

A mix of the routing hop alone (``"endpoint": "route"``, `harness.routing`)
has no ``max_tokens``; it gives ``wave``, the requests routed together,
``requests``, how many its schedule holds (the window starts over from the
first when it has routed them all), and ``check_requests``, how many
routed requests the correctness sample holds.
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Dict, List

#: topic phrases of the routing support set (the router's neighbourhoods)
TOPICS = ["python programming", "world history", "algebra proofs",
          "poetry writing", "biology facts"]

FILLER = ("the a of and to in is for on with that this by from at as be or "
          "are how what why when which explain describe compare list show "
          "give write prove find solve simple short long detailed example "
          "examples step steps first second next last more less best good "
          "new old data code text question answer result results method "
          "methods model models value values time number numbers word "
          "words line lines page chapter story poem proof rule rules case "
          "cases test tests").split()

#: requests in a schedule, at most; ample for any window up to 51 s
MAX_REQUESTS = 20000


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def _sizes(dist: Dict, n: int) -> List[int]:
    kind = dist["kind"]
    if kind == "const":
        return [int(dist["value"])] * n
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "uniform":
        return [lo + int(q * (hi - lo + 1)) for q in _quantiles(n)]
    if kind == "lognormal":
        nd = NormalDist(math.log(dist["median"]), dist["sigma"])
        return [min(hi, max(lo, int(round(math.exp(nd.inv_cdf(q))))))
                for q in _quantiles(n)]
    raise ValueError(f"unknown size distribution {kind!r}")


def _shares(values: List, n: int) -> List:
    return [values[i % len(values)] for i in range(n)]


def request_count(mix: Dict, seconds: float) -> int:
    """Requests a schedule holds: an open loop's arrivals over twice the
    window (the window takes what falls inside it); a closed loop's pool
    of requests, more than its clients can finish."""
    if "requests" in mix:
        return int(mix["requests"])
    if mix["loop"] == "open":
        return min(MAX_REQUESTS,
                   max(1, math.ceil(2 * mix["arrivals"]["rate_per_s"]
                                    * seconds)))
    return min(MAX_REQUESTS, max(64, 40 * int(mix["clients"])
                                 * max(1, math.ceil(seconds / 10))))


def _arrivals(mix: Dict, n: int, rng: random.Random) -> List[float]:
    arr = mix["arrivals"]
    rate = float(arr["rate_per_s"])
    if arr["kind"] == "poisson":
        gaps = [-math.log(1.0 - q) / rate for q in _quantiles(n)]
        rng.shuffle(gaps)
        out, t = [], 0.0
        for g in gaps:
            out.append(t)
            t += g
        return out
    if arr["kind"] == "bursts":
        lo, hi = int(arr["burst_min"]), int(arr["burst_max"])
        sizes = [lo + i % (hi - lo + 1) for i in range(n)]
        rng.shuffle(sizes)
        mean_burst = (lo + hi) / 2.0
        n_bursts = 0
        total = 0
        while total < n:
            total += sizes[n_bursts]
            n_bursts += 1
        gaps = [-math.log(1.0 - q) * mean_burst / rate
                for q in _quantiles(n_bursts)]
        rng.shuffle(gaps)
        out, t = [], 0.0
        for b in range(n_bursts):
            out.extend([t] * sizes[b])
            t += gaps[b]
        return out[:n]
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def schedule(mix: Dict, seed: int, seconds: float) -> List[Dict]:
    """The requests of one run, in the order they are due.  Each has
    ``i``, ``due`` (seconds from the window's start; 0 for every request
    of a closed loop, which sends them in order), ``text``, ``lam`` and
    ``max_tokens``."""
    rng = random.Random(int(seed))
    n = request_count(mix, seconds)
    lams = _shares(list(mix["lam"]["values"]), n)
    toks = (_sizes(mix["max_tokens"], n) if "max_tokens" in mix
            else [0] * n)
    words = _sizes(mix["words"], n)
    topics = _shares(list(range(len(TOPICS))), n)
    for xs in (lams, toks, words, topics):
        rng.shuffle(xs)
    due = _arrivals(mix, n, rng) if mix["loop"] == "open" else [0.0] * n
    reqs = []
    for i in range(n):
        topic = TOPICS[topics[i]].split()
        extra = max(0, words[i] - len(topic))
        text = " ".join(topic + [rng.choice(FILLER) for _ in range(extra)])
        reqs.append({"i": i, "due": due[i], "text": text,
                     "lam": float(lams[i]), "max_tokens": int(toks[i])})
    return reqs
