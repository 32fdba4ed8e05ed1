"""Layer-stack assembly: pattern-grouped ``lax.scan`` over stacked params.

A config's ``pattern`` (tuple of LayerSpec) is one *scan group*; params for
every group are stacked along axis 0 so the whole stack lowers to a single
small scan body (two for architectures with a tail pattern, e.g. Zamba-2's
81 = 13x(5 mamba + shared-attn) + 3 mamba).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.distributed.sharding import active_mesh, constrain
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import rmsnorm, rmsnorm_init, mlp, mlp_init


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg, spec, cross=False):
    kind, ffn = spec
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    p = {}
    if kind == "attn":
        p["norm1"] = rmsnorm_init(cfg.d_model, dt)
        p["attn"] = attn.gqa_init(ks[0], cfg)
    elif kind == "mla":
        p["norm1"] = rmsnorm_init(cfg.d_model, dt)
        p["attn"] = attn.mla_init(ks[0], cfg)
    elif kind == "ssm":
        p["norm1"] = rmsnorm_init(cfg.d_model, dt)
        p["ssm"] = ssm_mod.ssm_init(ks[1], cfg)
    elif kind == "shared_attn":
        return {}  # weights live in the shared slot
    if cross:
        p["norm_cross"] = rmsnorm_init(cfg.d_model, dt)
        p["cross"] = attn.gqa_init(ks[2], cfg)
    if ffn == "dense":
        p["norm2"] = rmsnorm_init(cfg.d_model, dt)
        p["mlp"] = mlp_init(ks[3], cfg.d_model, cfg.d_ff, dt)
    elif ffn == "moe":
        p["norm2"] = rmsnorm_init(cfg.d_model, dt)
        p["moe"] = moe_mod.moe_init(ks[4], cfg)
    return p


def shared_block_init(key, cfg):
    """Zamba-2 style shared transformer block (attention + MLP)."""
    ks = jax.random.split(key, 2)
    dt = jnp.dtype(cfg.dtype)
    return {
        "norm1": rmsnorm_init(cfg.d_model, dt),
        "attn": attn.gqa_init(ks[0], cfg),
        "norm2": rmsnorm_init(cfg.d_model, dt),
        "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, dt),
    }


def group_init(key, cfg, pattern, n_groups, cross=False):
    """Stacked params: every leaf gets a leading (n_groups,) axis."""
    def one(k):
        ks = jax.random.split(k, len(pattern))
        return [_layer_init(ki, cfg, spec, cross=cross)
                for ki, spec in zip(ks, pattern)]
    # vmapped over the group keys: one traced init per pattern slot, each
    # leaf drawn directly at its stacked shape (a python loop plus stack
    # would trace n_groups copies and hold every group twice at once)
    return jax.vmap(one)(jax.random.split(key, n_groups))


# ---------------------------------------------------------------------------
# full-sequence apply
# ---------------------------------------------------------------------------

def _apply_layer_full(lp, cfg, spec, x, positions, shared, enc_out, long_mode):
    kind, ffn = spec
    aux = jnp.zeros((), jnp.float32)
    if kind == "attn":
        h = attn.gqa_full(lp["attn"], cfg, rmsnorm(lp["norm1"], x), positions,
                          causal=True, window=cfg.sliding_window)
        x = x + h
    elif kind == "mla":
        h = attn.mla_full(lp["attn"], cfg, rmsnorm(lp["norm1"], x), positions)
        x = x + h
    elif kind == "ssm":
        x = x + ssm_mod.ssm_full(lp["ssm"], cfg, rmsnorm(lp["norm1"], x))
    elif kind == "shared_attn":
        w = cfg.shared_attn_window if long_mode else 0
        h = attn.gqa_full(shared["attn"], cfg, rmsnorm(shared["norm1"], x),
                          positions, causal=True, window=w)
        x = x + h
        x = x + mlp(shared["mlp"], rmsnorm(shared["norm2"], x))
    if enc_out is not None and "cross" in lp:
        h = attn.gqa_full(lp["cross"], cfg, rmsnorm(lp["norm_cross"], x),
                          positions, causal=False, window=0, kv_x=enc_out)
        x = x + h
    if ffn == "dense":
        x = x + mlp(lp["mlp"], rmsnorm(lp["norm2"], x))
    elif ffn == "moe":
        y, aux = moe_mod.moe_ffn(lp["moe"], cfg, rmsnorm(lp["norm2"], x),
                                 mesh=active_mesh())
        x = x + y
    x = constrain(x, ("batch", "seq", "embed"))
    return x, aux


def maybe_scan(body, init, xs, unroll_max: int = 2):
    """lax.scan, except tiny stacks are python-unrolled.  XLA cost analysis
    counts a while body ONCE regardless of trip count, so the dry-run's
    depth-extrapolation compiles (n_groups in {1,2}) must be unrolled for
    their cost to scale with depth."""
    n = jax.tree.leaves(xs)[0].shape[0]
    if n > unroll_max:
        return jax.lax.scan(body, init, xs)
    carry = init
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return carry, stacked


def _scan_stack(params, cfg, pattern, x, positions, shared, enc_out, long_mode):
    def body(carry, group_params):
        h, aux = carry
        for i, spec in enumerate(pattern):
            h, a = _apply_layer_full(group_params[i], cfg, spec, h, positions,
                                     shared, enc_out, long_mode)
            aux = aux + a
        return (h, aux), None

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    (x, aux), _ = maybe_scan(body, (x, jnp.zeros((), jnp.float32)), params)
    return x, aux


def stack_full(params, cfg, x, positions, enc_out=None, long_mode=False):
    """Apply the whole decoder/encoder stack (training / prefill).

    params: {"groups": stacked, "tail": stacked?, "shared": shared block?}
    """
    shared = params.get("shared")
    x, aux = _scan_stack(params["groups"], cfg, cfg.pattern, x, positions,
                         shared, enc_out, long_mode)
    if cfg.tail_pattern:
        x, aux2 = _scan_stack(params["tail"], cfg, cfg.tail_pattern, x,
                              positions, shared, enc_out, long_mode)
        aux = aux + aux2
    return x, aux


def stack_init(key, cfg, cross=False):
    ks = jax.random.split(key, 3)
    p = {"groups": group_init(ks[0], cfg, cfg.pattern, cfg.n_groups, cross=cross)}
    if cfg.tail_pattern:
        p["tail"] = group_init(ks[1], cfg, cfg.tail_pattern, cfg.n_tail_groups,
                               cross=cross)
    if any(k == "shared_attn" for k, _ in cfg.pattern + cfg.tail_pattern):
        p["shared"] = shared_block_init(ks[2], cfg)
    return p


# ---------------------------------------------------------------------------
# decode (single token) apply
# ---------------------------------------------------------------------------

def layer_cache_init(cfg, spec, batch, cache_len, long_mode=False,
                     enc_len=0):
    kind, _ = spec
    dt = jnp.dtype(cfg.dtype)
    if kind in ("attn", "shared_attn"):
        if kind == "attn":
            eff_w = cfg.sliding_window
        else:
            eff_w = cfg.shared_attn_window if long_mode else 0
        S = min(cache_len, eff_w) if eff_w else cache_len
        KV, hd = cfg.n_kv_heads, cfg.head_dim
        c = {"k": jnp.zeros((batch, S, KV, hd), dt),
             "v": jnp.zeros((batch, S, KV, hd), dt)}
        if enc_len and kind == "attn":
            # cached cross-attention K/V (filled by fill_cross_cache)
            c["ck"] = jnp.zeros((batch, enc_len, KV, hd), dt)
            c["cv"] = jnp.zeros((batch, enc_len, KV, hd), dt)
        return c
    if kind == "mla":
        return {"c": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dt),
                "kr": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dt)}
    if kind == "ssm":
        return ssm_mod.ssm_state_init(cfg, batch)
    raise ValueError(kind)


def caches_init(cfg, batch, cache_len, long_mode=False, enc_len=0):
    def per_pattern(pattern, n):
        per = [[layer_cache_init(cfg, spec, batch, cache_len, long_mode,
                                 enc_len=enc_len)
                for spec in pattern] for _ in range(n)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    c = {"groups": per_pattern(cfg.pattern, cfg.n_groups)}
    if cfg.tail_pattern:
        c["tail"] = per_pattern(cfg.tail_pattern, cfg.n_tail_groups)
    return c


def _apply_layer_decode(lp, cfg, spec, x, cache, pos, shared, enc_out):
    kind, ffn = spec
    if kind == "attn":
        w = cfg.sliding_window
        ring = w if (w and cache["k"].shape[1] <= w) else 0
        h, ck, cv = attn.gqa_decode(lp["attn"], cfg, rmsnorm(lp["norm1"], x),
                                    cache["k"], cache["v"], pos, window=ring)
        x = x + h
        cache = dict(cache, k=ck, v=cv)   # preserves cached cross ck/cv
    elif kind == "mla":
        h, cc, ckr = attn.mla_decode(lp["attn"], cfg, rmsnorm(lp["norm1"], x),
                                     cache["c"], cache["kr"], pos)
        x = x + h
        cache = {"c": cc, "kr": ckr}
    elif kind == "ssm":
        h, cache = ssm_mod.ssm_decode(lp["ssm"], cfg, rmsnorm(lp["norm1"], x),
                                      cache)
        x = x + h
    elif kind == "shared_attn":
        w = cfg.shared_attn_window
        ring = w if (w and cache["k"].shape[1] <= w) else 0
        h, ck, cv = attn.gqa_decode(shared["attn"], cfg,
                                    rmsnorm(shared["norm1"], x),
                                    cache["k"], cache["v"], pos, window=ring)
        x = x + h
        x = x + mlp(shared["mlp"], rmsnorm(shared["norm2"], x))
        cache = {"k": ck, "v": cv}
    if "cross" in lp and ("ck" in cache or enc_out is not None):
        xin = rmsnorm(lp["norm_cross"], x)
        if "ck" in cache:
            # cached cross K/V: one small q-projection + attend per step
            h = attn.gqa_cross_decode(lp["cross"], cfg, xin,
                                      cache["ck"], cache["cv"])
        else:
            dec_pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                       (x.shape[0],))[:, None]
            h = attn.gqa_full(lp["cross"], cfg, xin, dec_pos,
                              causal=False, window=0, kv_x=enc_out)
        x = x + h
    if ffn == "dense":
        x = x + mlp(lp["mlp"], rmsnorm(lp["norm2"], x))
    elif ffn == "moe":
        y, _ = moe_mod.moe_ffn(lp["moe"], cfg, rmsnorm(lp["norm2"], x),
                               mesh=active_mesh())
        x = x + y
    return x, cache


def stack_decode(params, cfg, caches, x, pos, enc_out=None):
    shared = params.get("shared")

    def scan_part(group_params, group_caches, pattern, h):
        def body(h, inp):
            lp, cs = inp
            new_cs = []
            for i, spec in enumerate(pattern):
                h, c = _apply_layer_decode(lp[i], cfg, spec, h, cs[i], pos,
                                           shared, enc_out)
                new_cs.append(c)
            return h, new_cs
        return maybe_scan(body, h, (group_params, group_caches))

    x, new_g = scan_part(params["groups"], caches["groups"], cfg.pattern, x)
    new_caches = {"groups": new_g}
    if cfg.tail_pattern:
        x, new_t = scan_part(params["tail"], caches["tail"], cfg.tail_pattern, x)
        new_caches["tail"] = new_t
    return x, new_caches
