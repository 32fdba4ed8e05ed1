"""The 90th percentile of time to first token, in milliseconds, over every
request due in the window, from its due time to its first content chunk on
the client; a shed or failed request counts as missing every limit."""
from harness import stats


def read(run):
    return stats.latency_ms(stats.ttfts(run.records), 90, run.missing_s)
