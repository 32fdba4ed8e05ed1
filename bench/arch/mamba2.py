"""Mamba-2 (state-space duality, arXiv:2405.21060), attention-free, from the
published ``config.json`` keys of ``state-spaces/mamba2-*``.

The reference is the plain recurrence of the paper, one position at a time:
per block RMSNorm, input projection to (gate z, x, B, C, dt), causal
depthwise convolution with SiLU over (x, B, C), ``h_t = exp(dt_t A) h_{t-1}
+ dt_t x_t B_t``, ``y_t = C_t h_t + D x_t``, RMSNorm of ``y * silu(z)``,
output projection and residual; final RMSNorm and output head.  As in the
program, the output head is a matrix of its own (the published model ties
it to the embedding).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import Numerics, normal, rmsnorm


def dims(hf: dict) -> dict:
    D = hf["d_model"]
    d_in = hf["expand"] * D
    P = hf["headdim"]
    return {"D": D, "L": hf["n_layer"], "V": hf["vocab_size"], "d_in": d_in,
            "H": d_in // P, "P": P, "N": hf["d_state"], "G": hf["ngroups"],
            "K": hf["d_conv"], "eps": float(hf["norm_epsilon"])}


def _conv_ch(d):
    return d["d_in"] + 2 * d["G"] * d["N"]


def _proj(d):
    return 2 * d["d_in"] + 2 * d["G"] * d["N"] + d["H"]


def param_count(hf: dict) -> int:
    d = dims(hf)
    per_layer = (d["D"] * _proj(d) + (d["K"] + 1) * _conv_ch(d)
                 + 3 * d["H"] + d["d_in"] + d["d_in"] * d["D"] + d["D"])
    return d["L"] * per_layer + 2 * d["V"] * d["D"] + d["D"]


def flops_per_token(hf: dict, context: float) -> float:
    """Forward FLOPs of one token: two per weight of the projections and
    the output head, the convolution, and the state update and read-out
    (independent of ``context``)."""
    d = dims(hf)
    matmul = d["L"] * (d["D"] * _proj(d) + d["d_in"] * d["D"]) + d["D"] * d["V"]
    conv = d["L"] * d["K"] * _conv_ch(d)
    ssm = d["L"] * 3 * d["H"] * d["P"] * d["N"]
    return 2.0 * (matmul + conv + ssm)


def init(key, hf: dict, dtype=jnp.bfloat16) -> dict:
    """Random weights in the program's layout.  ``A_log`` and ``dt_bias``
    follow the published initialisation (A in [1, 16], dt in [1e-3, 0.1])."""
    d = dims(hf)
    D, L, V, d_in, H = d["D"], d["L"], d["V"], d["d_in"], d["H"]
    ks = jax.random.split(key, 8)
    dt = jnp.exp(jax.random.uniform(ks[5], (L, H), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    ssm = {"in_proj": normal(ks[2], (L, D, _proj(d)), D ** -0.5, dtype),
           "conv_w": normal(ks[3], (L, d["K"], _conv_ch(d)), 0.5, dtype),
           "conv_b": normal(ks[4], (L, _conv_ch(d)), 0.1, dtype),
           "A_log": jnp.log(jax.random.uniform(ks[6], (L, H), jnp.float32,
                                               1.0, 16.0)),
           "D": jnp.ones((L, H), jnp.float32),
           "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
           "norm": {"scale": jnp.ones((L, d_in), dtype)},
           "out_proj": normal(ks[7], (L, d_in, D), d_in ** -0.5, dtype)}
    layer = {"norm1": {"scale": jnp.ones((L, D), dtype)}, "ssm": ssm}
    return {"embed": normal(ks[0], (V, D), 1.0, dtype),
            "final_norm": {"scale": jnp.ones((D,), dtype)},
            "lm_head": normal(ks[1], (D, V), D ** -0.5, dtype),
            "stack": {"groups": [layer]}}


def forward(params, hf: dict, tokens, mode: str = "reference"):
    """Logits (B, S, V) in float32 for ``tokens`` (B, S)."""
    nm = Numerics(mode)
    d = dims(hf)
    d_in, H, P, N, G, K, eps = (d[k] for k in ("d_in", "H", "P", "N", "G",
                                               "K", "eps"))
    B, S = tokens.shape
    f32 = jnp.float32

    def layer(x, lp):
        p = lp["ssm"]
        h = nm.cast(rmsnorm(x, lp["norm1"]["scale"], eps))
        zxbcdt = nm.mm("bsd,de->bse", h, p["in_proj"])
        z = zxbcdt[..., :d_in]
        xbc = zxbcdt[..., d_in:2 * d_in + 2 * G * N].astype(f32)
        dt_raw = zxbcdt[..., 2 * d_in + 2 * G * N:].astype(f32)
        w = p["conv_w"].astype(f32)
        xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(xp[:, i:i + S] * w[i] for i in range(K))
        xbc = nm.cast(jax.nn.silu(conv + p["conv_b"].astype(f32)))
        xs = xbc[..., :d_in].reshape(B, S, H, P).astype(f32)
        Bm = jnp.repeat(xbc[..., d_in:d_in + G * N].reshape(B, S, G, N),
                        H // G, axis=2).astype(f32)
        Cm = jnp.repeat(xbc[..., d_in + G * N:].reshape(B, S, G, N),
                        H // G, axis=2).astype(f32)
        dt = jax.nn.softplus(dt_raw + p["dt_bias"])
        A = -jnp.exp(p["A_log"])

        def step(state, inp):
            x_t, b_t, c_t, dt_t = inp
            state = (state * jnp.exp(dt_t * A)[..., None, None]
                     + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
            return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                     precision=jax.lax.Precision.HIGHEST)

        tm = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), f32),
                            (tm(xs), tm(Bm), tm(Cm), tm(dt)))
        y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xs
        y = y.reshape(B, S, d_in) * jax.nn.silu(z.astype(f32))
        y = nm.cast(rmsnorm(y, p["norm"]["scale"], eps))
        return x + nm.mm("bse,ed->bsd", y, p["out_proj"]).astype(x.dtype), None

    x = nm.cast(params["embed"][tokens])
    x, _ = jax.lax.scan(layer, x, params["stack"]["groups"][0])
    x = nm.cast(rmsnorm(x, params["final_norm"]["scale"], eps))
    return nm.mm("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)


def program_config(hf: dict, ModelConfig, name: str):
    d = dims(hf)
    return ModelConfig(
        name=name, arch_type="ssm", n_layers=d["L"], d_model=d["D"],
        n_heads=0, n_kv_heads=0, d_ff=0, vocab_size=d["V"],
        head_dim=d["P"], pattern=(("ssm", "none"),), n_groups=d["L"],
        ssm_state=d["N"], ssm_head_dim=d["P"], ssm_expand=hf["expand"],
        ssm_chunk=hf["chunk_size"], ssm_n_groups=d["G"], ssm_conv=d["K"],
        dtype="bfloat16", remat=False)
