"""The generator gives every seed the same work in another order."""
import collections
import json
from pathlib import Path

import pytest

from harness import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


@pytest.mark.parametrize("name", ["chat", "batch", "burst-short"])
def test_seeds_share_sizes_and_gaps(name):
    mix = json.loads((MIXES / f"{name}.json").read_text())
    a = traffic.schedule(mix, 2 ** 31 + 11, 45)
    b = traffic.schedule(mix, 7, 45)
    assert len(a) == len(b) > 0
    for key in ("lam", "max_tokens"):
        assert (collections.Counter(r[key] for r in a)
                == collections.Counter(r[key] for r in b))
    assert [r["i"] for r in a] == list(range(len(a)))
    if mix["loop"] == "open":
        gaps = lambda s: sorted(round(y["due"] - x["due"], 9)  # noqa: E731
                                for x, y in zip(s, s[1:]))
        if mix["arrivals"]["kind"] == "poisson":
            # the same gaps; each schedule leaves out its own last one
            diff = collections.Counter(gaps(a)) - collections.Counter(gaps(b))
            assert sum(diff.values()) <= 1
        assert all(y["due"] >= x["due"] for x, y in zip(a, a[1:]))
        # every request of an open loop is due inside the window
        assert len(a) == round(mix["arrivals"]["rate_per_s"] * 45)
        assert all(0 <= r["due"] < 45 for r in a + b)
    assert a != b
    assert a == traffic.schedule(mix, 2 ** 31 + 11, 45)


def test_sizes_follow_the_mix():
    mix = json.loads((MIXES / "chat.json").read_text())
    reqs = traffic.schedule(mix, 1, 45)
    toks = [r["max_tokens"] for r in reqs]
    assert min(toks) >= 8 and max(toks) <= 64
    assert sorted(toks)[len(toks) // 2] == 24
    words = [len(r["text"].split()) for r in reqs]
    assert min(words) >= 4 and max(words) <= 64
    assert {r["lam"] for r in reqs} == {0.0, 100.0}
    rate = mix["arrivals"]["rate_per_s"]
    in_window = sum(r["due"] < 45 for r in reqs)
    assert in_window == len(reqs) == round(rate * 45)


def test_bursts_arrive_together():
    mix = json.loads((MIXES / "burst-short.json").read_text())
    reqs = traffic.schedule(mix, 3, 45)
    sizes = collections.Counter(r["due"] for r in reqs).values()
    assert min(sizes) >= 1 and max(sizes) <= 16
    assert sum(s >= 4 for s in sizes) >= len(sizes) - 1
