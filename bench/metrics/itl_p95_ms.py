"""The 95th percentile of the gaps between consecutive content chunks, in
milliseconds, over every request due in the window; a shed or failed
request adds one gap that misses every limit."""
from harness import stats


def read(run):
    return stats.latency_ms(stats.itls(run.records), 95, run.missing_s)
