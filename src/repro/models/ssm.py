"""Mamba-2 (SSD) mixer block: in_proj -> causal depthwise conv -> SSD -> gated
norm -> out_proj.  Full-sequence path uses the chunked SSD algorithm (Pallas
kernel on TPU, jnp reference elsewhere); decode path is the O(1) recurrence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ref import ssd_decode_step, ssd_reference
from .layers import dense_init, rmsnorm, rmsnorm_init


def _dims(cfg):
    d_in = cfg.d_inner
    H = cfg.ssm_heads
    P = cfg.ssm_head_dim
    G = cfg.ssm_n_groups
    N = cfg.ssm_state
    conv_ch = d_in + 2 * G * N
    return d_in, H, P, G, N, conv_ch


def ssm_init(key, cfg):
    d = cfg.d_model
    d_in, H, P, G, N, conv_ch = _dims(cfg)
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * d_in + 2 * G * N + H), dt),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv, conv_ch), dt, scale=0.5),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.zeros((H,), jnp.float32),      # A = -exp(A_log) = -1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": rmsnorm_init(d_in, dt),
        "out_proj": dense_init(ks[3], (d_in, d), dt),
    }


def _split_proj(cfg, zxbcdt):
    d_in, H, P, G, N, _ = _dims(cfg)
    z = zxbcdt[..., :d_in]
    xBC = zxbcdt[..., d_in: 2 * d_in + 2 * G * N]
    dt_raw = zxbcdt[..., 2 * d_in + 2 * G * N:]
    return z, xBC, dt_raw


def _causal_conv(params, xBC, state=None):
    """Depthwise causal conv, kernel K.  xBC: (B,S,C).
    Returns (out, new_state) where state: (B, K-1, C) trailing inputs."""
    K = params["conv_w"].shape[0]
    if state is None:
        pad = jnp.zeros((xBC.shape[0], K - 1, xBC.shape[2]), xBC.dtype)
    else:
        pad = state.astype(xBC.dtype)
    xp = jnp.concatenate([pad, xBC], axis=1)        # (B, S+K-1, C)
    out = sum(xp[:, i: i + xBC.shape[1], :] * params["conv_w"][i][None, None]
              for i in range(K))
    out = out + params["conv_b"]
    new_state = xp[:, -(K - 1):, :]
    return jax.nn.silu(out), new_state


def ssm_full(params, cfg, x, initial_state=None, return_state=False):
    """x: (B,S,D) -> (B,S,D).  Sequences not divisible by the SSD chunk are
    zero-padded at the tail (causal: earlier outputs unaffected); state
    handoff requires a divisible length."""
    B, S, D = x.shape
    d_in, H, P, G, N, _ = _dims(cfg)
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xBC, dt_raw = _split_proj(cfg, zxbcdt)
    xBC, _ = _causal_conv(params, xBC)
    xs = xBC[..., :d_in].reshape(B, S, H, P)
    Bm = xBC[..., d_in: d_in + G * N].reshape(B, S, G, N)
    Cm = xBC[..., d_in + G * N:].reshape(B, S, G, N)
    dt_v = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])

    chunk = min(cfg.ssm_chunk, S)
    pad = (-S) % chunk
    if pad:
        assert not return_state, "state handoff needs chunk-divisible length"
        pad_spec = ((0, 0), (0, pad), (0, 0), (0, 0))
        xs = jnp.pad(xs, pad_spec)
        Bm = jnp.pad(Bm, pad_spec)
        Cm = jnp.pad(Cm, pad_spec)
        dt_v = jnp.pad(dt_v, ((0, 0), (0, pad), (0, 0)))

    if cfg.use_pallas:
        from repro.kernels.ssd_scan.ops import ssd_scan
        y, state = ssd_scan(xs, dt_v, A, Bm, Cm, chunk=chunk,
                            initial_state=initial_state)
    else:
        y, state = ssd_reference(xs, dt_v, A, Bm, Cm, chunk=chunk,
                                 initial_state=initial_state)
    if pad:
        y = y[:, :S]
        xs = xs[:, :S]
    y = y + params["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, S, d_in).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z))
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    if return_state:
        return out, state
    return out


def ssm_state_init(cfg, batch, dtype=jnp.float32):
    d_in, H, P, G, N, conv_ch = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype),
        "ssd": jnp.zeros((batch, H, P, N), jnp.float32),
    }


def ssm_decode(params, cfg, x, state, update=None):
    """x: (B,1,D); state dict from ssm_state_init.  Returns (y, new_state).

    ``update`` (B,) bool, where given: the slots whose state this step
    advances; the others keep theirs bit for bit.  The select fuses into
    the state update, so the state is still read and written once."""
    B = x.shape[0]
    d_in, H, P, G, N, _ = _dims(cfg)
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xBC, dt_raw = _split_proj(cfg, zxbcdt)
    xBC, conv_state = _causal_conv(params, xBC, state["conv"])
    xs = xBC[:, 0, :d_in].reshape(B, H, P)
    Bm = xBC[:, 0, d_in: d_in + G * N].reshape(B, G, N)
    Cm = xBC[:, 0, d_in + G * N:].reshape(B, G, N)
    dt_v = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    y, ssd_state = ssd_decode_step(state["ssd"], xs, dt_v, A, Bm, Cm)
    y = y + params["D"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, 1, d_in).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z))
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    new_state = {"conv": conv_state, "ssd": ssd_state}
    if update is not None:
        new_state = jax.tree.map(
            lambda n, o: jnp.where(update.reshape((-1,) + (1,) * (o.ndim - 1)),
                                   n.astype(o.dtype), o), new_state, state)
    return out, new_state
