"""Median time of the flush that routes a request's wave (the gateway's
``route_s``: tokenize, encode, the fused route and the results), in
milliseconds."""
from harness import stats


def read(run):
    return stats.gateway_stage_ms(run.records, "route_s")
