"""Decoder with grouped-query attention and a SwiGLU MLP: Qwen3 (with
RMSNorm on each query and key head) and the Llama/Mistral family
(h2o-danube), from their published ``config.json`` keys.

The reference follows the Hugging Face modelling code of these families:
pre-norm residual blocks, rotary embeddings on the two halves of each head
(``rotate_half``), causal attention (within ``sliding_window`` where set),
final RMSNorm and an output head.  One departure, which the program shares:
the output head is its own matrix even where the published model ties it to
the embedding (``tie_word_embeddings``); the weights are random either way.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import Numerics, normal, rmsnorm


def dims(hf: dict) -> dict:
    D = hf["hidden_size"]
    H = hf["num_attention_heads"]
    return {"D": D, "L": hf["num_hidden_layers"], "H": H,
            "KV": hf["num_key_value_heads"],
            "hd": hf.get("head_dim") or D // H,
            "F": hf["intermediate_size"], "V": hf["vocab_size"],
            "qk_norm": hf["model_type"] == "qwen3",
            "window": hf.get("sliding_window") or 0,
            "theta": float(hf["rope_theta"]), "eps": float(hf["rms_norm_eps"])}


def _layer_matrices(d: dict) -> int:
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def param_count(hf: dict) -> int:
    """Parameters as the program holds them (untied output head)."""
    d = dims(hf)
    per_layer = _layer_matrices(d) + 2 * d["D"] + (2 * d["hd"]
                                                    if d["qk_norm"] else 0)
    return d["L"] * per_layer + 2 * d["V"] * d["D"] + d["D"]


def flops_per_token(hf: dict, context: float) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions:
    two per weight of every matrix it multiplies (the layers and the output
    head; the embedding is a gather), and the score and value products of
    attention."""
    d = dims(hf)
    ctx = min(context, d["window"]) if d["window"] else context
    matmul = d["L"] * _layer_matrices(d) + d["D"] * d["V"]
    attn = 4 * d["L"] * d["H"] * d["hd"] * ctx
    return 2.0 * matmul + attn


def init(key, hf: dict, dtype=jnp.bfloat16) -> dict:
    """Random weights in the program's layout: ``embed`` (V, D), ``lm_head``
    (D, V), ``final_norm`` and one stacked group of ``L`` layers."""
    d = dims(hf)
    D, L, H, KV, hd, F, V = (d[k] for k in ("D", "L", "H", "KV", "hd", "F",
                                             "V"))
    ks = jax.random.split(key, 9)
    ones = lambda *s: jnp.ones(s, dtype)  # noqa: E731
    attn = {"wq": normal(ks[2], (L, D, H * hd), D ** -0.5, dtype),
            "wk": normal(ks[3], (L, D, KV * hd), D ** -0.5, dtype),
            "wv": normal(ks[4], (L, D, KV * hd), D ** -0.5, dtype),
            "wo": normal(ks[5], (L, H * hd, D), (H * hd) ** -0.5, dtype)}
    if d["qk_norm"]:
        attn["q_norm"] = {"scale": ones(L, hd)}
        attn["k_norm"] = {"scale": ones(L, hd)}
    layer = {"norm1": {"scale": ones(L, D)}, "attn": attn,
             "norm2": {"scale": ones(L, D)},
             "mlp": {"w_gate": normal(ks[6], (L, D, F), D ** -0.5, dtype),
                     "w_up": normal(ks[7], (L, D, F), D ** -0.5, dtype),
                     "w_down": normal(ks[8], (L, F, D), F ** -0.5, dtype)}}
    return {"embed": normal(ks[0], (V, D), 1.0, dtype),
            "final_norm": {"scale": ones(D)},
            "lm_head": normal(ks[1], (D, V), D ** -0.5, dtype),
            "stack": {"groups": [layer]}}


def _rope(t, cos, sin):
    half = t.shape[-1] // 2
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def forward(params, hf: dict, tokens, mode: str = "reference"):
    """Logits (B, S, V) in float32 for ``tokens`` (B, S), every position
    attending causally to the ones before it."""
    nm = Numerics(mode)
    d = dims(hf)
    H, KV, hd, eps = d["H"], d["KV"], d["hd"], d["eps"]
    B, S = tokens.shape
    pos = jnp.arange(S)
    inv = 1.0 / (d["theta"] ** (jnp.arange(hd // 2, dtype=jnp.float32)
                                / (hd // 2)))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    mask = pos[:, None] >= pos[None, :]
    if d["window"]:
        mask &= (pos[:, None] - pos[None, :]) < d["window"]
    G = H // KV

    def layer(x, lp):
        a = lp["attn"]
        h = nm.cast(rmsnorm(x, lp["norm1"]["scale"], eps))
        q = nm.mm("bsd,dh->bsh", h, a["wq"]).reshape(B, S, H, hd)
        k = nm.mm("bsd,dh->bsh", h, a["wk"]).reshape(B, S, KV, hd)
        v = nm.mm("bsd,dh->bsh", h, a["wv"]).reshape(B, S, KV, hd)
        if d["qk_norm"]:
            q = rmsnorm(q, a["q_norm"]["scale"], eps)
            k = rmsnorm(k, a["k_norm"]["scale"], eps)
        q = nm.cast(_rope(q.astype(jnp.float32), cos, sin))
        k = nm.cast(_rope(k.astype(jnp.float32), cos, sin))
        qg = q.reshape(B, S, KV, G, hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = nm.cast(jax.nn.softmax(s, axis=-1))
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        o = nm.cast(o.reshape(B, S, H * hd))
        x = x + nm.mm("bsh,hd->bsd", o, a["wo"]).astype(x.dtype)
        h = nm.cast(rmsnorm(x, lp["norm2"]["scale"], eps))
        m = lp["mlp"]
        g = nm.mm("bsd,df->bsf", h, m["w_gate"])
        u = nm.mm("bsd,df->bsf", h, m["w_up"])
        act = nm.cast(jax.nn.silu(g.astype(jnp.float32))
                      * u.astype(jnp.float32))
        x = x + nm.mm("bsf,fd->bsd", act, m["w_down"]).astype(x.dtype)
        return x, None

    x = nm.cast(params["embed"][tokens])
    x, _ = jax.lax.scan(layer, x, params["stack"]["groups"][0])
    x = nm.cast(rmsnorm(x, params["final_norm"]["scale"], eps))
    return nm.mm("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)


def program_config(hf: dict, ModelConfig, name: str):
    d = dims(hf)
    return ModelConfig(
        name=name, arch_type="dense", n_layers=d["L"], d_model=d["D"],
        n_heads=d["H"], n_kv_heads=d["KV"], d_ff=d["F"], vocab_size=d["V"],
        head_dim=d["hd"], pattern=(("attn", "dense"),), n_groups=d["L"],
        qk_norm=d["qk_norm"], sliding_window=d["window"],
        rope_theta=d["theta"], dtype="bfloat16", remat=False)
