"""The reduction from a trace's program spans to per-call times, device
time per call and idle put down to the innermost open span
(`harness.spans`), the six readings taken from it, and the report that
prints them (`bench/spans_report.py`)."""
import gzip
import json
from pathlib import Path

import pytest

import spans_report
from harness import spans

D0, HOST, MAIN = "/device:TPU:0", "/host:CPU", "python3"


def ev(plane, line, name, start_ms, dur_ms):
    return [plane, line, name, start_ms * 1e6, dur_ms * 1e6]


def host(name, start_ms, end_ms):
    return ev(HOST, MAIN, name, start_ms, end_ms - start_ms)


def program(name, start_ms, end_ms):
    return [ev(D0, "XLA Modules", name, start_ms, end_ms - start_ms),
            ev(D0, "XLA Ops", "fusion", start_ms, end_ms - start_ms)]


# two waves: the second wave's encode runs two chunks, and its encoder
# program carries another hash
EVENTS = [
    host("encode", 0, 4), host("encode/tokenize", 0, 0.5),
    host("encode/dispatch", 0.5, 1), host("encode/fetch", 1, 3),
    host("np.asarray(jax.Array)", 1, 3),
    host("route", 4, 10), host("route/dispatch", 5, 6),
    host("PjitFunction(_serve_fused_jit)", 5, 6),
    host("route/fetch", 6, 9.5),
    host("encode", 10, 13), host("encode/tokenize", 10, 10.4),
    host("encode/dispatch", 10.4, 10.6), host("encode/fetch", 10.6, 11.2),
    host("encode/dispatch", 11.2, 11.4), host("encode/fetch", 11.4, 12),
    host("route", 13, 20), host("route/dispatch", 14, 15),
    host("route/fetch", 15, 19),
    # the runtime on another thread after the last wave: no span open
    ev(HOST, "main/1", "PjitFunction(other)", 20, 2),
    *program("jit_query_encoder(111)", 1, 1.5),
    *program("jit__serve_fused_jit(9)", 6, 7),
    *program("jit_query_encoder(222)", 11, 11.3),
    *program("jit__serve_fused_jit(9)", 15, 16.5),
]


def read(name, evs):
    return spans.metrics(spans.reduce(evs)).get(name)


def test_calls_have_self_time_and_per_call_child_sums():
    calls = spans.reduce(EVENTS)["calls"]
    assert sorted(calls) == ["encode", "encode/dispatch", "encode/fetch",
                             "encode/tokenize", "route", "route/dispatch",
                             "route/fetch"]
    e1, e2 = calls["encode"]
    assert e1["s"] == pytest.approx(0.004)
    assert e1["self_s"] == pytest.approx(0.001)
    assert e2["children"]["encode/fetch"] == pytest.approx(0.0012)
    assert e2["children"]["encode/dispatch"] == pytest.approx(0.0004)
    assert e2["self_s"] == pytest.approx(0.001)
    r1, r2 = calls["route"]
    assert r1["self_s"] == pytest.approx(0.0015)
    assert r2["children"] == pytest.approx({"route/dispatch": 0.001,
                                            "route/fetch": 0.004})
    # runtime events are not spans, and do not count as children
    assert calls["route/dispatch"][0]["children"] == {}
    assert len(calls["encode/fetch"]) == 3


def test_programs_are_matched_by_name_whatever_their_hash():
    progs = spans.reduce(EVENTS)["programs"]
    assert progs == pytest.approx({"jit_query_encoder": 0.0008,
                                   "jit__serve_fused_jit": 0.0025})
    assert spans.program_name("jit_run(11936372406621654405)") == "jit_run"


def test_device_time_per_call():
    assert read("encoder_device_ms", EVENTS) == pytest.approx(0.4)
    assert read("route_device_ms", EVENTS) == pytest.approx(1.25)


def test_idle_goes_to_the_innermost_open_span_and_the_rest_outside():
    idle = spans.reduce(EVENTS)["idle"]
    want = {"encode/tokenize": 0.9, "encode/dispatch": 0.8,
            "encode/fetch": 2.5, "encode": 2.0, "route": 3.5,
            "route/dispatch": 2.0, "route/fetch": 5.0, "outside": 2.0}
    assert idle == pytest.approx({k: v / 1e3 for k, v in want.items()})
    # busy 3.3 ms of the 22 ms from the first event to the last
    assert sum(idle.values()) == pytest.approx(0.0187)


def test_the_six_metrics_read_the_medians():
    """Nearest-rank medians of two waves: the lower of the two."""
    got = {m: read(m, EVENTS) for m in (
        "tokenize_ms", "encode_fetch_ms", "route_host_ms", "route_fetch_ms")}
    assert got == pytest.approx({"tokenize_ms": 0.4, "encode_fetch_ms": 1.2,
                                 "route_host_ms": 2.5,
                                 "route_fetch_ms": 3.5})


def test_a_trace_without_spans_reads_nothing_and_idles_outside():
    """A program that has no spans gives no reading, and its whole idle
    time is outside every span."""
    bare = [e for e in EVENTS if not spans.is_span(e[2])]
    sp = spans.reduce(bare)
    assert sp["calls"] == {}
    assert spans.metrics(sp) == {} and spans.stages(sp) == {}
    f = spans.idle_summary(sp)
    assert f["in_spans_pct"] == 0.0
    assert list(f["by_span"]) == ["outside"]
    # without the spans the trace's first event starts at 1 ms
    assert f["idle_s"] == pytest.approx(0.0177)


def test_the_idle_summary_gives_each_span_with_its_share():
    f = spans.idle_summary(spans.reduce(EVENTS))
    assert f["idle_s"] == pytest.approx(0.0187)
    assert f["in_spans_pct"] == pytest.approx(100 * 16.7 / 18.7)
    assert f["by_span"]["route/fetch"] == pytest.approx(
        [0.005, 100 * 5 / 18.7])
    assert list(f["by_span"])[0] == "route/fetch"


def test_stages_split_a_wave_into_children_and_self_time():
    """Medians (nearest rank, the lower of two waves) of each child summed
    per call, the self time and the whole."""
    got = spans.stages(spans.reduce(EVENTS))
    assert got == pytest.approx({
        "encode/tokenize": 0.4, "encode/dispatch": 0.4,
        "encode/fetch": 1.2, "encode self": 1.0, "encode": 3.0,
        "route/dispatch": 1.0, "route/fetch": 3.5, "route self": 1.5,
        "route": 6.0})


def test_the_report_prints_one_line_per_trace_file(tmp_path, capsys):
    """``spans_report.py`` reads what ``run.py --trace-out`` writes."""
    files = []
    for name, evs in (("spans", EVENTS),
                      ("bare", [e for e in EVENTS
                                if not spans.is_span(e[2])])):
        files.append(tmp_path / f"{name}.json.gz")
        with gzip.open(files[-1], "wt") as f:
            json.dump({"window_s": 0.022, "events": evs}, f)
    assert spans_report.main([str(p) for p in files]) == 0
    with_spans, bare = [json.loads(line) for line
                        in capsys.readouterr().out.splitlines()]
    assert with_spans["waves"] == 2 and with_spans["window_s"] == 0.022
    assert with_spans["metrics"] == pytest.approx(
        spans.metrics(spans.reduce(EVENTS)))
    assert with_spans["idle"]["in_spans_pct"] == pytest.approx(
        100 * 16.7 / 18.7)
    assert bare["metrics"] == {} and bare["waves"] == 0


def test_a_recorded_v5e_trace_with_spans_reads_as_the_chip_printed():
    """Six waves of route8 traced on one v5e chip with the program's spans
    in: the six metrics of the slice lie within 10% of what the run printed
    over its whole window, the device time per call times the waves is the
    breakdown's program total, and most of the idle time lies inside a
    program span, the two fetches first."""
    from harness import trace
    path = Path(__file__).parent / "data" / "trace_route8_spans_v5e.json.gz"
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    evs = rec["events"]
    for m, printed in rec["printed"].items():
        assert read(m, evs) == pytest.approx(printed, rel=0.1), m
    sp = spans.reduce(evs)
    waves = len(sp["calls"]["route"])
    assert waves == len(sp["calls"]["encode"]) == 6
    ops = dict(trace.reduce(evs, rec["window_s"], n_devices=1)["device_ops"])
    for m, prog in (("encoder_device_ms", "jit_query_encoder"),
                    ("route_device_ms", "jit__serve_fused_jit")):
        total, = [s for n, s in ops.items() if n.startswith(prog + "(")]
        assert read(m, evs) * waves / 1e3 == pytest.approx(total, rel=1e-9)
    idle = sp["idle"]
    total = sum(idle.values())
    assert idle["outside"] < 0.1 * total
    assert sorted(idle, key=idle.get)[-2:] == ["encode/fetch", "route/fetch"]
