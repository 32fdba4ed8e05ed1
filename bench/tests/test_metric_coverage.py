"""Every metric that BENCHMARK.json applies to a cell reads a finite number
there, from the run data that each kind of cell hands its readers: the
routing cells' waves, and a gateway run's records and engine counters,
which has no waves.  A metric that reads nothing in a cell that must report
it leaves the result line without it, and the run is refused."""
import json
import math
from pathlib import Path

import pytest

import run
from harness import boot, spec

BENCH = Path(__file__).resolve().parents[1]
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
PEAKS = {"bf16_flops_per_s": 197e12}
TRACE = {"busy_s": 4.0, "window_s": 10.0}
T0 = 100.0


def configs():
    for p in sorted((BENCH / "configs").glob("*.json")):
        yield p.stem, json.loads(p.read_text())


def is_routing(w: dict) -> bool:
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return mix.get("endpoint", "chat") == "route"


ROUTING = [w["name"] for w in BENCHMARK["workloads"] if is_routing(w)]
GATEWAY = [name for name, cfg in configs() if "engine" in cfg]


def request_flops(cfg: dict) -> int:
    """A routed request: the encoder's layers at its padded length, and the
    exact scan of one embedding over the support rows."""
    enc, r = cfg["encoder"], cfg["router"]
    s, h, f = enc["max_tokens"], enc["hidden_size"], enc["intermediate_size"]
    return (enc["layers"] * s * (8 * h * h + 6 * h * f + 2 * s * h)
            + 2 * r["support_rows"] * r["embedding_dim"])


def gateway_cell(config: str, tmp_path) -> spec.Cell:
    """``config`` under the chat mix, as a cell that BENCHMARK.json would
    add: its metrics are found by the same rules as the listed cells'."""
    name = f"{config}.coverage"
    bench = dict(BENCHMARK, workloads=BENCHMARK["workloads"] + [
        {"name": name, "config": config, "traffic": "chat", "chips": 1,
         "why": "x"}])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return spec.load_cell(name, path)


def routing_run(cell) -> run.Run:
    return run.Run(records=[{"status": "ok"}] * 400, t0=T0, t_end=T0 + 10.25,
                   seconds=10.0, missing_s=70.0, engines=[],
                   waves=[{"encode_s": 0.002, "route_s": 0.003}] * 50,
                   peaks=PEAKS, config=cell.config, chips=1, trace=TRACE,
                   setup_s=17.0)


def served(i: int, status: str = "ok", end: float = None) -> dict:
    due = T0 + 0.1 * i
    chunks = [due + 0.3 + 0.02 * k for k in range(4)] if status == "ok" else []
    return {"i": i, "status": status, "due": due, "sent": due,
            "chunks": chunks, "tokens": list(range(len(chunks))),
            "served_by": "m" if status == "ok" else None,
            "end": end if end is not None else due + 0.5,
            "final": ({"timing": {"route_s": 0.004, "wave_close_s": 0.01}}
                      if status == "ok" else None)}


def gateway_run(cell, records) -> run.Run:
    cfg = cell.config
    engines = [{"name": e["name"],
                "delta": {"prefill_tokens": 800, "tokens_out": 2400,
                          "decode_steps": 400},
                "max_slots": int(cfg["engine"]["max_slots"]),
                "flops_per_token": boot.arch_module(e["arch"]).flops_per_token(
                    e["config"], cfg["engine"]["cache_len"] / 2)}
               for e in cfg["pool"]]
    return run.Run(records=records, t0=T0, t_end=None, seconds=10.0,
                   missing_s=70.0, engines=engines, waves=None, peaks=PEAKS,
                   config=cfg, chips=1, trace=TRACE, setup_s=80.0)


def reads_everything(cell, r) -> dict:
    metrics = cell.end_to_end + cell.per_layer
    got = spec.report(metrics, r)
    assert set(got) == {m.name for m in metrics}
    assert all(math.isfinite(v["value"]) and v["value"] > 0
               for v in got.values()), got
    return {k: v["value"] for k, v in got.items()}


def test_there_are_cells_of_both_kinds():
    assert ROUTING and GATEWAY


@pytest.mark.parametrize("name", ROUTING)
def test_routing_cell_reads_every_metric_as_before(name):
    cell = spec.load_cell(name)
    r = routing_run(cell)
    got = reads_everything(cell, r)
    assert {"encode_ms", "search_ms", "route_mfu_pct"} <= set(got)
    assert got["routes_per_s"] == len(r.records) / (r.t_end - r.t0)
    flops = len(r.records) * request_flops(cell.config)
    assert got["route_mfu_pct"] == 100.0 * flops / (
        (r.t_end - r.t0) * r.chips * r.peaks["bf16_flops_per_s"])


@pytest.mark.parametrize("config", GATEWAY)
def test_gateway_cell_reads_every_metric(config, tmp_path):
    cell = gateway_cell(config, tmp_path)
    names = {m.name for m in cell.end_to_end + cell.per_layer}
    assert {"routes_per_s", "setup_s", "route_mfu_pct",
            "device_idle_pct"} <= names
    assert not names & {"encode_ms", "search_ms"}
    r = gateway_run(cell, [served(i) for i in range(100)])
    got = reads_everything(cell, r)
    assert got["routes_per_s"] == 100 / (max(x["end"] for x in r.records) - T0)
    routed = 100 * request_flops(cell.config)
    engines = sum(3200 * e["flops_per_token"] for e in r.engines)
    assert got["route_mfu_pct"] == pytest.approx(
        100.0 * (routed + engines) / (10.0 * 197e12))
    assert got["route_mfu_pct"] <= 100.0


@pytest.mark.parametrize("config", GATEWAY)
def test_shed_and_failed_requests_do_not_count(config, tmp_path):
    cell = gateway_cell(config, tmp_path)
    ok = [served(i) for i in range(100)]
    late = [served(100, "shed", end=T0 + 30.0),
            served(101, "failed", end=T0 + 40.0)]
    rate = spec.load_reader("routes_per_s")
    mfu = spec.load_reader("route_mfu_pct")
    assert rate(gateway_run(cell, ok + late)) == rate(gateway_run(cell, ok))
    assert mfu(gateway_run(cell, ok + late)) == mfu(gateway_run(cell, ok))
    assert rate(gateway_run(cell, late)) == 0.0
