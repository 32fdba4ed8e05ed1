"""Median time to route one embedded wave, in milliseconds: the fused
dispatch of retrieval, neighbour utility and choice, and the copy of its
answers to the host (`RouterService.route_fused`)."""
from harness import stats


def read(run):
    if not run.waves:
        return None
    return 1000.0 * stats.percentile([w["route_s"] for w in run.waves], 50)
