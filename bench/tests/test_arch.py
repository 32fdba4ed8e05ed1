"""The reference architectures: sizes from the published configurations,
the weights' layout, and agreement with the program at a small size."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import boot, route_ref
from harness.peaks import peaks_for

BENCH = Path(__file__).resolve().parents[1]


def engine(name):
    for path in sorted((BENCH / "configs").glob("*.json")):
        for e in json.loads(path.read_text())["pool"]:
            if e["name"] == name and "config" in e:
                return e
    raise KeyError(name)


@pytest.mark.parametrize("name,params", [("qwen3-4b", 4_411_424_256),
                                         ("mamba2-370m", 419_825_152)])
def test_param_count_matches_the_chip(name, params):
    """Counts the program held on the chip at published widths."""
    e = engine(name)
    assert boot.arch_module(e["arch"]).param_count(e["config"]) == params


@pytest.mark.parametrize("name", ["qwen3-4b", "mamba2-370m",
                                  "h2o-danube-1.8b"])
def test_flops_per_token_is_twice_the_multiplied_weights(name):
    e = engine(name)
    arch = boot.arch_module(e["arch"])
    hf = e["config"]
    n = arch.param_count(hf)
    embed = hf.get("hidden_size", hf.get("d_model")) * hf["vocab_size"]
    f0 = arch.flops_per_token(hf, 0)
    # every weight but the embedding's (a gather) and the norms' is
    # multiplied once; a state-space layer adds its state update (< 15%)
    assert 0.99 * 2 * (n - embed) <= f0 <= 1.15 * 2 * (n - embed)
    assert arch.flops_per_token(hf, 64) >= f0


def test_peaks_table():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")


TINY = {
    "qwen3": {"model_type": "qwen3", "hidden_size": 64,
              "intermediate_size": 96, "num_hidden_layers": 3,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "vocab_size": 300, "rope_theta": 1e6,
              "rms_norm_eps": 1e-6, "sliding_window": None},
    "mistral": {"model_type": "mistral", "hidden_size": 64,
                "intermediate_size": 96, "num_hidden_layers": 3,
                "num_attention_heads": 4, "num_key_value_heads": 1,
                "vocab_size": 300, "rope_theta": 1e4, "rms_norm_eps": 1e-6,
                "sliding_window": 5},
    "mamba2": {"d_model": 64, "n_layer": 3, "vocab_size": 300,
               "d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
               "ngroups": 1, "chunk_size": 16, "norm_epsilon": 1e-6},
}


@pytest.mark.parametrize("kind", sorted(TINY))
def test_reference_matches_the_program_in_float32(kind):
    """Same weights, float32: the program's full forward and its cached
    decode against the reference, position by position."""
    from repro.configs.base import ModelConfig
    from repro.models import model as M
    hf = TINY[kind]
    arch = boot.arch_module("mamba2" if kind == "mamba2" else "gqa")
    cfg = arch.program_config(hf, ModelConfig, kind).replace(
        dtype="float32")
    params = jax.jit(lambda k: arch.init(k, hf, jnp.float32))(
        jax.random.PRNGKey(3))
    boot.check_layout(params, cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 300, (2, 12)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = arch.forward(params, hf, toks)
        full, _ = M.forward(params, cfg, {"tokens": toks})
        caches = M.init_caches(cfg, 2, 16)
        dec = []
        for t in range(toks.shape[1]):
            lg, caches = M.decode_step(params, cfg, caches, toks[:, t:t + 1],
                                       jnp.full((2,), t, jnp.int32))
            dec.append(lg)
    dec = jnp.stack(dec, 1)
    np.testing.assert_allclose(full, ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(dec, ref, rtol=2e-4, atol=2e-4)


def test_control_is_lower_precision_than_the_reference():
    hf = TINY["qwen3"]
    arch = boot.arch_module("gqa")
    params = jax.jit(lambda k: arch.init(k, hf, jnp.bfloat16))(
        jax.random.PRNGKey(4))
    toks = jnp.arange(24, dtype=jnp.int32).reshape(2, 12)
    ref = arch.forward(params, hf, toks)
    ctl = arch.forward(params, hf, toks, "control")
    err = float(jnp.max(jnp.abs(ref - ctl)))
    assert 0 < err < 0.5 * float(jnp.max(jnp.abs(ref)))


def test_router_reference_restates_the_encoder_and_support():
    """The reference's tokenizer, encoder weights and support rows are the
    program's, restated: the same embeddings and the same rows."""
    from repro.launch.serve import build_support
    from repro.serving import encoder
    texts = ["python programming question 7", "a b c", "Poetry writing " * 9]
    for t in texts:
        np.testing.assert_array_equal(route_ref.tokenize(t),
                                      encoder.hash_tokenize(t))
    with jax.default_matmul_precision("highest"):
        want = encoder.embed_texts(texts)
    got = route_ref.embed(route_ref.encoder_weights(), texts)
    np.testing.assert_allclose(got, want, atol=2e-5)
    ds = build_support(["a", "b", "c"], n=50, seed=3)
    rows, S, C = route_ref.support(50, 3, 3)
    np.testing.assert_array_equal(S, ds.scores[ds.train_idx])
    np.testing.assert_array_equal(C, ds.costs[ds.train_idx])
    assert len(rows) == len(ds.train_idx) == 35
