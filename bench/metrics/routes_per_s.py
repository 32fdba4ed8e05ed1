"""Requests routed per second.  In a routing cell: every request of the
window's waves, the last one finished after the window closed, over the
seconds from the window's start to that last wave's end.  In a gateway
cell: the requests answered in full (their last chunk received), over the
seconds from the window's start to the latest end among them, so the work
in flight at the close is finished and counted there too.  Shed and failed
requests do not count; the result's ``failed`` reports them."""
from harness import stats


def read(run):
    if run.waves is not None:
        return len(run.records) / (run.t_end - run.t0)
    ends = [r["end"] for r in run.records if stats.ok(r)]
    if not ends:
        return 0.0
    return len(ends) / (max(ends) - run.t0)
