"""The reduction from a trace's events to busy time and the breakdown."""
import pytest

from harness import trace

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, start_ms, dur_ms):
    return [plane, line, name, start_ms * 1e6, dur_ms * 1e6]


EVENTS = [
    # device 0: ops 0-2 and 1-3 overlap (busy 0-3), then 5-6, then 9-10
    ev(D0, "XLA Ops", "fusion.1", 0, 2), ev(D0, "XLA Ops", "dot.2", 1, 2),
    ev(D0, "XLA Ops", "fusion.1", 5, 1), ev(D0, "XLA Ops", "copy.3", 9, 1),
    ev(D0, "XLA Modules", "jit_decode", 0, 3),
    ev(D0, "XLA Modules", "jit_decode", 5, 1),
    ev(D0, "XLA Modules", "jit_route", 9, 1),
    # device 1 busy 2 ms in all
    ev(D1, "XLA Ops", "fusion.9", 0, 2),
    # host: what the host did in the gaps 3-5 and 6-9
    ev(HOST, "tf_pjrt", "PjitFunction(decode)", 2.5, 2.0),
    ev(HOST, "tf_pjrt", "argmax on host", 6.5, 2.0),
    ev(HOST, "tf_pjrt", "short", 6.0, 0.4),
]


def test_union_merges_overlaps():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [[0, 3], [5, 7]]


def test_busy_is_the_union_of_op_intervals_averaged_over_devices():
    r = trace.reduce(EVENTS, window_s=0.010)
    # device 0: 3 + 1 + 1 = 5 ms; device 1: 2 ms; mean 3.5 ms
    assert r["busy_s"] == pytest.approx(0.0035)
    assert r["window_s"] == 0.010
    one = trace.reduce([e for e in EVENTS if e[0] != D1], window_s=0.010)
    assert one["busy_s"] == pytest.approx(0.005)


def test_programs_are_ranked_by_device_time():
    r = trace.reduce(EVENTS, window_s=0.010)
    assert [n for n, _ in r["device_ops"]] == ["jit_decode", "jit_route"]
    assert r["device_ops"][0][1] == pytest.approx(0.004)


def test_idle_gaps_are_named_by_the_overlapping_host_event():
    r = trace.reduce(EVENTS, window_s=0.010)
    assert r["idle_gaps"][0] == ["argmax on host", pytest.approx(0.003)]
    assert r["idle_gaps"][1] == ["PjitFunction(decode)",
                                 pytest.approx(0.002)]


def test_no_device_events_reads_nothing_busy():
    r = trace.reduce([e for e in EVENTS if e[0] == HOST], window_s=1.0)
    assert r["busy_s"] == 0.0 and r["device_ops"] == []


def test_a_recorded_v5e_trace_reduces_as_the_chip_ran():
    """Six waves of route8 traced on one v5e chip: the two programs of a
    wave rank by device time, the device is busy a fifth of the slice,
    as in whole traced windows, and each idle gap is named by what the
    host was doing."""
    import gzip
    import json
    from pathlib import Path
    path = Path(__file__).parent / "data" / "trace_route8_v5e.json.gz"
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    r = trace.reduce(rec["events"], rec["window_s"], n_devices=1)
    names = [n.split("(")[0] for n, _ in r["device_ops"]]
    assert names == ["jit__serve_fused_jit", "jit_run"]
    # the two programs hold the busy time, give or take their launches
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(r["busy_s"],
                                                               rel=0.01)
    assert 0.15 < r["busy_s"] / r["window_s"] < 0.25
    assert r["idle_gaps"] and all(n != "no host event"
                                  for n, _ in r["idle_gaps"])
