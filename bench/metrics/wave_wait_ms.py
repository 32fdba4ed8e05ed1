"""Median wait of a request in the micro-batcher before its wave is flushed
(the gateway's ``wave_close_s``: submit to flush start), in milliseconds.
It holds the time the pump spends finishing the wave before."""
from harness import stats


def read(run):
    return stats.gateway_stage_ms(run.records, "wave_close_s")
