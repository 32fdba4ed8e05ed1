"""The control comes out as not correct: at a size a test run holds, the
reference one precision step down (fp8 weights, bf16 activations) puts
first tokens whose gap under the float32 reference is several times the
gap of the tokens the program's own bf16 forward puts first."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import boot

BENCH = Path(__file__).resolve().parents[1]

SIZES = {
    "gqa": {"model_type": "qwen3", "hidden_size": 256,
            "intermediate_size": 768, "num_hidden_layers": 8,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 64, "vocab_size": 2048, "rope_theta": 1e6,
            "rms_norm_eps": 1e-6, "sliding_window": None},
    "mamba2": {"d_model": 256, "n_layer": 8, "vocab_size": 2048,
               "d_state": 64, "d_conv": 4, "expand": 2, "headdim": 64,
               "ngroups": 1, "chunk_size": 64, "norm_epsilon": 1e-5},
}


def widest_gap(ref, pick):
    p = jnp.argmax(pick, -1)
    got = jnp.take_along_axis(ref, p[..., None], -1)[..., 0]
    return float(jnp.max(jnp.max(ref, -1) - got))


@pytest.mark.parametrize("kind", sorted(SIZES))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_gap_exceeds_program_numerics(kind, seed):
    from repro.configs.base import ModelConfig
    from repro.models import model as M
    hf = SIZES[kind]
    arch = boot.arch_module(kind)
    cfg = arch.program_config(hf, ModelConfig, kind)
    params = jax.jit(lambda k: arch.init(k, hf, jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, hf["vocab_size"], (8, 48)), jnp.int32)
    ref = arch.forward(params, hf, toks)
    program = widest_gap(ref, M.forward(params, cfg, {"tokens": toks})[0])
    control = widest_gap(ref, arch.forward(params, hf, toks, "control"))
    assert control >= 3 * program, (control, program)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_router_control_is_not_correct(seed):
    """The routing control (the reference router with the operands of
    every matmul rounded to fp8) reads an ``embed_gap`` several times the
    program's encoder's, on texts of the routing mixes."""
    from harness import check, route_ref, traffic
    from repro.serving import encoder
    mix = json.loads((BENCH / "traffic" / "route8.json").read_text())
    texts = [r["text"] for r in traffic.schedule(dict(mix, requests=64),
                                                 seed, 1)]
    w = route_ref.encoder_weights()
    ref = {"emb": route_ref.embed(w, texts), "util": np.zeros((64, 1)),
           "s_hat": np.zeros((64, 1))}
    gap = lambda e: check.route_numbers(  # noqa: E731
        ref, texts, {"choice": np.zeros(64, int), "emb": e})["embed_gap"]
    program = gap(encoder.embed_texts(texts))
    control = gap(route_ref.embed(w, texts, "float8"))
    assert control >= 3 * program, (control, program)
