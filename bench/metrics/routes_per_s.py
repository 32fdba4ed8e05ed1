"""Requests routed per second: every request of the window's waves, the
last one finished after the window closed, over the seconds from the
window's start to that last wave's end."""


def read(run):
    if run.waves is None:
        return None
    return len(run.records) / (run.t_end - run.t0)
