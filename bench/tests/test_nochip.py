"""Without a TPU the benchmark exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path


ROOT = Path(__file__).resolve().parents[2]


def run_bench(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj)


def test_refuses_to_run_on_the_cpu():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = run_bench(ROOT, bench["workloads"][0]["name"])
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    no_result(out.stdout)


def test_refuses_in_a_bare_checkout(tmp_path):
    """Only BENCHMARK.json and the benchmark's files: still no result."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns(
            ".cache", "__pycache__"))
    out = run_bench(tmp_path, bench["workloads"][0]["name"])
    assert out.returncode != 0
    no_result(out.stdout)
