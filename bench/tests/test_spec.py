"""A new configuration, mix or metric is found by its name, from new files
alone; the cells of BENCHMARK.json all resolve."""
import json
import shutil
from pathlib import Path

import pytest

from harness import spec

BENCH = Path(__file__).resolve().parents[1]


def test_every_cell_of_the_benchmark_resolves():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        assert any(m.name == "setup_s" for m in cell.end_to_end)
    for c in bench["configs"]:
        assert (BENCH.parent / c["file"]).is_file()


def test_new_files_add_a_config_a_mix_and_a_metric(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    shutil.copy(BENCH / "configs" / "qwen3-mamba2.ivfpq100k.json",
                tmp_path / "configs" / "newcfg.json")
    (tmp_path / "traffic" / "newmix.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2, "lam": {"values": [0]},
         "max_tokens": {"kind": "const", "value": 4},
         "words": {"kind": "const", "value": 6}, "check_tokens": 10}))
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 2.0 * run.seconds\n")
    bench = {"workloads": [{"name": "newcfg.newmix", "config": "newcfg",
                            "traffic": "newmix", "chips": 1, "why": "x"}],
             "end_to_end": [{"name": "output_tokens_per_s", "unit": "tokens/s",
                             "better": "higher", "bound": 0.1,
                             "source": "host_clock"},
                            {"name": "setup_s", "unit": "s",
                             "better": "lower", "bound": 0.25,
                             "source": "host_clock"}],
             "per_layer": [{"name": "new_metric", "unit": "s",
                            "better": "lower", "source": "program_counter",
                            "layer": "engine", "moves": "setup_s",
                            "workloads": ["newcfg.newmix"]},
                           {"name": "route_ms", "unit": "ms",
                            "better": "lower", "source": "program_span",
                            "layer": "encode and route",
                            "moves": "setup_s", "workloads": ["other"]}]}
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = spec.load_cell("newcfg.newmix", path, tmp_path)
    assert cell.traffic["clients"] == 2
    assert [m.name for m in cell.per_layer] == ["new_metric"]
    run = type("Run", (), {"seconds": 3.0, "records": [], "t0": 0.0,
                           "setup_s": 9.0})()
    got = spec.report(cell.per_layer + cell.end_to_end, run)
    assert got["new_metric"] == {"value": 6.0, "unit": "s"}
    assert got["output_tokens_per_s"]["value"] == 0.0
    assert got["setup_s"]["value"] == 9.0


def test_a_reader_that_finds_nothing_is_left_out():
    m = spec.Metric("route_ms", "ms", "lower", "program_span", True, None,
                    spec.load_reader("route_ms"))
    run = type("Run", (), {"records": [{"status": "failed", "final": None,
                                         "chunks": []}]})()
    assert spec.report([m], run) == {}


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_metric")


def test_routing_readers_count_every_wave_over_all_its_time():
    cfg = json.loads((BENCH / "configs" / "knn100.exact100k.json")
                     .read_text())
    waves = [{"encode_s": 0.001, "route_s": 0.002}] * 3
    run = type("Run", (), {"records": [{}] * 24, "waves": waves, "t0": 1.0,
                           "t_end": 1.5, "chips": 1, "config": cfg,
                           "peaks": {"bf16_flops_per_s": 197e12}})()
    assert spec.load_reader("routes_per_s")(run) == 48.0
    mfu = spec.load_reader("route_mfu_pct")
    # 2 layers x 64 tokens x (8 h^2 + 6 h f + 2 s h) + 2 x 100,000 x 768
    per_request = 2 * 64 * (8 * 768 ** 2 + 6 * 768 * 1536 + 2 * 64 * 768) \
        + 2 * 100_000 * 768
    assert per_request == 1_676_132_352
    assert mfu(run) == pytest.approx(100 * 48 * per_request / 197e12)
