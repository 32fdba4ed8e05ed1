"""`chip_smoke.py` rehearsed on the CPU: its phases at `reduced()` widths
with a small support set (kernels interpreted), its refusal to run without
a TPU, and the persistent compile cache its entry points share."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture
def smoke(monkeypatch):
    """The script as a module, its platform check steered to pass."""
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "backend", lambda: "tpu")
    return mod


def _fields(out: str, label: str) -> dict:
    line = next(ln for ln in out.splitlines()
                if ln.startswith(f"[smoke] {label}: "))
    return dict(kv.split("=", 1) for kv in line.split(": ", 1)[1].split())


def test_one_chip_phases_at_reduced_width(smoke, capsys):
    smoke.run_one_chip(published=False, support_rows=140)
    out = capsys.readouterr().out
    assert _fields(out, "router")["support_rows"] == "140"
    assert _fields(out, "router")["width"] == "768"
    for i in range(smoke.REQUESTS):
        assert _fields(out, f"request {i}")["tokens"] == str(smoke.MAX_TOKENS)
    mix = _fields(out, "routing mix")
    assert sum(int(v) for k, v in mix.items() if k != "waves") \
        == smoke.REQUESTS
    parity = _fields(out, "pallas vs fused")
    assert float(parity["ids_equal_up_to_ties"]) >= 0.99
    for name in smoke.POOL:
        assert f"first compile {name}" in out


def test_compare_topk_counts_identical_rows_as_ties(smoke):
    """Swapping two identical support rows is a tie, never a miss; a
    different row in their place is a miss under either count."""
    import numpy as np
    rows = np.array([[1.0, 0.0], [0.0, 1.0], [0.0, 1.0], [0.6, 0.8]],
                    np.float32)
    sc = np.array([[1.0, 0.5, 0.5, 0.1]], np.float32)
    ix = np.array([[0, 1, 2, 3]])
    tie = smoke.compare_topk(sc, ix, sc, np.array([[0, 2, 1, 3]]), rows)
    assert tie["ids_equal"] == 0.5 and tie["ids_equal_up_to_ties"] == 1.0
    miss = smoke.compare_topk(sc, ix, sc, np.array([[0, 1, 3, 2]]), rows)
    assert miss["ids_equal"] == miss["ids_equal_up_to_ties"] == 0.5
    assert tie["max_abs_score_diff"] == 0.0
    with pytest.raises(AssertionError):
        smoke.compare_topk(sc, ix, sc + 0.01, ix, rows)


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """The --chips 4 paths on four virtual CPU devices (a fresh process:
    the device count is fixed when JAX first starts)."""
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {str(SCRIPT)!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        smoke.backend = lambda: "tpu"
        smoke.run_four_chips(support_rows=140, router="knn100-ivfpq@m=24")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "bitwise_equal=True" in res.stdout
    assert float(_fields(res.stdout, "row-sharded ivfpq top-k")
                 ["ids_equal_up_to_ties"]) >= 0.99


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(SCRIPT)], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "not 'tpu'" in res.stderr


def test_compile_cache_defaults_to_a_fixed_checkout_dir(monkeypatch):
    from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_is_written_where_the_env_says(tmp_path):
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_second_process_loads_from_the_compile_cache(tmp_path):
    """What one process compiles, the next one with the same cache
    directory loads instead of compiling, as the smoke's counters show."""
    code = textwrap.dedent(f"""
        import importlib.util
        import jax, jax.numpy as jnp
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {str(SCRIPT)!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.compile_cache import enable_compile_cache
        compiles = smoke.count_compiles()
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.cos(x) + 1)(jnp.ones(8)).block_until_ready()
        print(compiles["lookups"], compiles["hits"], compiles["writes"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    runs = [subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
            for _ in range(2)]
    for res in runs:
        assert res.returncode == 0, res.stderr[-3000:]
    (look1, hits1, writes1), (look2, hits2, writes2) = (
        map(int, res.stdout.split()[-3:]) for res in runs)
    assert hits1 == 0 and writes1 == look1 > 0
    assert hits2 == look2 == look1 and writes2 == 0
