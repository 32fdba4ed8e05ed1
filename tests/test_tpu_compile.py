"""The main path's Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
v5e chip that is described, not attached (`jax.experimental.topologies`),
which refuses what interpret mode cannot see — unaligned blocks, scoped
VMEM overflows, primitives Mosaic does not lower.  The widths are the chip
smoke's (`chip_smoke.py`): 768-d query embeddings over 100,000 support rows,
the IVF lists of that index lane-padded to 128, PQ at m = 192, k = 100 with
an 8x re-rank shortlist, and qwen3-4b decode attention.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels

D = 768                  # query-encoder width
SUPPORT = 100_000        # support rows
K = 100
RERANK = 8
WAVE = 8                 # queries routed per smoke wave
NPROBE = 8
LISTS, LIST_LEN = 459, 512   # the smoke index's balanced lists, 128-padded
SLOTS = WAVE * NPROBE        # widest probe union one tile of 8 can need
PQ_M, PQ_NBITS = 192, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_tpu(one_chip, monkeypatch):
    """Shapes on the described chip, kernels compiled (not interpreted —
    the process's own backend is the CPU), persistent cache off: such a
    compile is written to the cache but cannot be read back without a
    chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel
        return compiled

    try:
        yield lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=one_chip), compile_
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
        jax.clear_caches()     # drop traces made with compiled kernels


def test_knn_topk_compiles(compile_for_tpu):
    from repro.kernels.knn_topk.ops import knn_topk
    shape, compile_ = compile_for_tpu
    compiled = compile_(lambda q, s: knn_topk(q, s, K, use_pallas=True),
                        shape((256, D), jnp.float32),
                        shape((SUPPORT, D), jnp.float32))
    assert compiled.memory_analysis() is not None


def test_ivf_kernel_compiles(compile_for_tpu):
    from repro.kernels.knn_ivf.kernel import ivf_topk_pallas
    shape, compile_ = compile_for_tpu
    compile_(lambda *a: ivf_topk_pallas(*a, k=K),
             shape((WAVE, D), jnp.float32),
             shape((LISTS, LIST_LEN, D), jnp.float32),
             shape((LISTS, LIST_LEN), jnp.int32),
             shape((LISTS, LIST_LEN), jnp.float32),
             shape((WAVE, NPROBE), jnp.int32),
             shape((1, SLOTS), jnp.int32), shape((1, SLOTS), jnp.int32))


def test_ivfpq_adc_kernel_compiles(compile_for_tpu):
    """The whole pallas-backend device tail: tables, ADC kernel (its
    per-subspace loop keeps VMEM inside the scoped limit at m = 192), un-sort
    and exact re-rank."""
    from repro.kernels.knn_ivf import ops
    shape, compile_ = compile_for_tpu
    mb = PQ_M * PQ_NBITS // 8
    compile_(lambda *a: ops._staged_tail(
                 *a, k=K, kk=RERANK * K, bq=WAVE, m=PQ_M, nbits=PQ_NBITS,
                 rerank=True, backend="pallas"),
             shape((WAVE, D), jnp.float32), shape((WAVE, D), jnp.float32),
             shape((WAVE, NPROBE), jnp.int32), shape((1, SLOTS), jnp.int32),
             shape((1, SLOTS), jnp.int32), shape((WAVE,), jnp.int32),
             shape((LISTS, mb, LIST_LEN), jnp.uint8),
             shape((LISTS, LIST_LEN), jnp.int32),
             shape((LISTS, LIST_LEN), jnp.float32),
             shape((LISTS, D), jnp.float32),
             shape((PQ_M, 2 ** PQ_NBITS, D // PQ_M), jnp.float32),
             shape((SUPPORT, D), jnp.float32))


def test_decode_attention_compiles(compile_for_tpu):
    """qwen3-4b widths: 32 query heads over 8 KV heads, head_dim 128, bf16,
    a 2048-token cache."""
    from repro.configs import get_config
    from repro.kernels.decode_attention.ops import decode_attention
    cfg = get_config("qwen3-4b")
    shape, compile_ = compile_for_tpu
    cache = shape((4, 2048, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    compile_(lambda q, k, v, pos: decode_attention(q, k, v, pos),
             shape((4, cfg.n_heads, cfg.head_dim), jnp.bfloat16), cache,
             cache, shape((), jnp.int32))
