"""Finding a cell's files by the names in ``BENCHMARK.json``.

A configuration named ``c`` is ``bench/configs/c.json``, a traffic mix ``t``
is ``bench/traffic/t.json`` and a metric ``m`` is read by
``bench/metrics/m.py``.  A later change adds a configuration, a mix or a
metric as new files only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A cell, configuration, mix or metric that cannot be found."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    per_layer: bool
    workloads: Optional[List[str]]
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read`` function of ``metrics/<name>.py`` under ``bench_dir``,
    else under the benchmark's own directory."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(run)")
    return mod.read


def _metrics(entries, per_layer: bool, cell: str, bench_dir: Path):
    out = []
    for m in entries:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        out.append(Metric(m["name"], m["unit"], m["better"], m["source"],
                          per_layer, cells,
                          load_reader(m["name"], bench_dir)))
    return out


def load_cell(name: str, bench_json: Optional[Path] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration and
    mix (from ``bench_dir``) and metric readers."""
    bench_json = bench_json or ROOT / "BENCHMARK.json"
    if not bench_json.is_file():
        raise SpecError(f"no {bench_json}")
    spec = load_json(bench_json)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in {bench_json}; "
                        f"have {sorted(cells)}")
    w = cells[name]
    cfg_path = bench_dir / "configs" / f"{w['config']}.json"
    mix_path = bench_dir / "traffic" / f"{w['traffic']}.json"
    for p in (cfg_path, mix_path):
        if not p.is_file():
            raise SpecError(f"cell {name!r} needs {p}")
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=load_json(cfg_path), traffic=load_json(mix_path),
                end_to_end=_metrics(spec["end_to_end"], False, name,
                                    bench_dir),
                per_layer=_metrics(spec["per_layer"], True, name, bench_dir))


def make_cell(config: str, traffic: str,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """A cell of ``config`` and ``traffic`` that ``BENCHMARK.json`` need
    not list, with no metrics: for readings of cells held back."""
    cfg_path = bench_dir / "configs" / f"{config}.json"
    mix_path = bench_dir / "traffic" / f"{traffic}.json"
    for p in (cfg_path, mix_path):
        if not p.is_file():
            raise SpecError(f"no {p}")
    return Cell(name=f"{config}.{traffic}", config_name=config,
                traffic_name=traffic, chips=1, config=load_json(cfg_path),
                traffic=load_json(mix_path), end_to_end=[], per_layer=[])


def report(metrics: List[Metric], run) -> Dict[str, dict]:
    """Each metric's reading, leaving out those whose reader finds nothing."""
    out = {}
    for m in metrics:
        value = m.read(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
