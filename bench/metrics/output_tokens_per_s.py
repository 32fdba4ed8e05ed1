"""Content tokens that reached the clients inside the window, over the
window's seconds."""
from harness import stats


def read(run):
    return stats.tokens_in_window(run.records, run.t0, run.seconds) / run.seconds
