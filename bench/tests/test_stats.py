"""Percentiles, rates and the rule that a shed or failed request misses."""
import math

import pytest

from harness import stats


def rec(due, chunks, status="ok"):
    return {"due": due, "chunks": chunks, "status": status}


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([3.0], 99) == 3.0
    assert stats.percentile([], 50) is None


def test_ttft_is_taken_from_the_due_time():
    # sent late (1.5 s after due): the wait counts against the request
    r = rec(10.0, [12.0, 12.1, 12.3])
    assert stats.ttfts([r]) == [pytest.approx(2.0)]


def test_shed_and_failed_requests_miss_every_limit():
    recs = [rec(0.0, [0.1, 0.2]), rec(0.0, [], "shed"),
            rec(0.0, [0.5], "failed")]
    t = stats.ttfts(recs)
    assert t[0] == pytest.approx(0.1) and math.isinf(t[1]) and math.isinf(t[2])
    gaps = stats.itls(recs)
    assert gaps[0] == pytest.approx(0.1)
    assert sum(math.isinf(g) for g in gaps) == 2
    # the median falls on a miss: reported as the longest wait allowed
    assert stats.latency_ms(t, 50, cap_s=105.0) == 105000.0
    assert stats.latency_ms(t, 10, cap_s=105.0) == pytest.approx(100.0)


def test_tokens_are_counted_inside_the_window_only():
    recs = [rec(0.0, [9.9, 10.0, 10.5, 14.99, 15.0, 16.0])]
    assert stats.tokens_in_window(recs, t0=10.0, seconds=5.0) == 3


def test_gateway_stage_is_the_median_of_completed_streams():
    recs = [dict(rec(0, [1]), final={"timing": {"route_s": v}})
            for v in (0.010, 0.030, 0.020)]
    recs.append(dict(rec(0, [], "failed"), final=None))
    assert stats.gateway_stage_ms(recs, "route_s") == pytest.approx(20.0)
    assert stats.gateway_stage_ms(recs, "wave_close_s") is None
