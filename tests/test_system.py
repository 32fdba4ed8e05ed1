"""End-to-end system behaviour: the paper's pipeline from benchmark
generation through routing evaluation to routed serving, plus launcher CLIs."""
import subprocess
import sys
import os

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))


def test_paper_pipeline_end_to_end():
    """kNN >= random + diagnostics agree with the paper's qualitative
    claims on a fresh benchmark."""
    from repro.core import eval as E
    from repro.core.diagnostics import locality_check, twonn_intrinsic_dim
    from repro.core.routers import make_router
    from repro.data.routing_bench import routerbench_tasks

    ds = routerbench_tasks()["arcc"]
    oracle = E.oracle_auc(ds)["auc"]
    rand = E.random_auc(ds)["auc"]
    knn = E.utility_auc(make_router("knn100").fit(ds), ds)["auc"]
    assert rand < knn <= oracle
    loc = locality_check(ds.embeddings, ds.scores)
    assert loc["pearson_r"] < -0.3
    assert twonn_intrinsic_dim(ds.embeddings) < 64


def test_train_cli_runs():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-4b",
         "--reduced", "--steps", "4", "--batch", "2", "--seq", "32"],
        capture_output=True, text=True, timeout=540, env=ENV, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss=" in out.stdout


def test_serve_cli_runs(tmp_path):
    # the entry point keeps its compile cache where the environment says
    # (else inside the checkout)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--requests", "4",
         "--max-new", "3", "--pool", "qwen3-4b", "mamba2-370m"],
        capture_output=True, text=True, timeout=540, cwd=ROOT,
        env=dict(ENV, JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[routing mix]" in out.stdout
    assert any(tmp_path.iterdir())
