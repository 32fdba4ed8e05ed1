"""Attention variants: GQA (RoPE, qk-norm, bias, sliding window), MLA.

Full-sequence paths optionally dispatch to the Pallas flash-attention kernel
(``cfg.use_pallas``); the default path is the pure-jnp reference which is what
the distributed dry-run lowers (Mosaic kernels cannot lower to the CPU
backend).  Decode paths implement ring-buffer sliding-window caches and the
MLA absorbed-matmul cache trick.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense_init, rmsnorm, rmsnorm_init, zeros_init


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, d_model=None):
    d = d_model or cfg.d_model
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dt),
        "wk": dense_init(ks[1], (d, KV * hd), dt),
        "wv": dense_init(ks[2], (d, KV * hd), dt),
        "wo": dense_init(ks[3], (H * hd, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((H * hd,), dt)
        p["bk"] = zeros_init((KV * hd,), dt)
        p["bv"] = zeros_init((KV * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dt)
        p["k_norm"] = rmsnorm_init(hd, dt)
    return p


def _project_qkv(params, cfg, x, positions, kv_x=None, rope=True):
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    src = x if kv_x is None else kv_x
    k = jnp.einsum("bsd,dh->bsh", src, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", src, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, -1, H, hd)
    k = k.reshape(B, -1, KV, hd)
    v = v.reshape(B, -1, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if rope:
        kv_pos = positions if kv_x is None else jnp.arange(src.shape[1])[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_pos, cfg.rope_theta)
    return q, k, v


def attend_ref(q, k, v, *, causal, window=0, q_offset=0):
    """Pure-jnp attention oracle.  q:(B,Sq,H,hd) k,v:(B,Sk,KV,hd)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)
    if causal or window:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Sk)
        mask = jnp.ones((Sq, Sk), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _attend(cfg, q, k, v, *, causal, window=0):
    if cfg.use_pallas:
        from repro.kernels.flash_attention.ops import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window)
    return attend_ref(q, k, v, causal=causal, window=window)


def gqa_full(params, cfg, x, positions, *, causal=True, window=None,
             kv_x=None, return_kv=False):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    w = cfg.sliding_window if window is None else window
    q, k, v = _project_qkv(params, cfg, x, positions, kv_x=kv_x,
                           rope=(kv_x is None))
    out = _attend(cfg, q, k, v, causal=causal and kv_x is None, window=w)
    B, S = x.shape[0], out.shape[1]
    y = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, -1), params["wo"])
    if return_kv:
        return y, (k, v)
    return y


def _vec_pos(pos, B):
    """Accept scalar or per-slot (B,) positions."""
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(pos, (B,))


def _row_update(cache, new, slots, update=None):
    """cache: (B, S, ...); new: (B, 1, ...); slots: (B,) — per-row insert.
    ``update`` (B,) bool, where given: a row that is False writes its old
    entry back, so its cache is left bit for bit."""
    if update is not None:
        old = jax.vmap(lambda c, s: jax.lax.dynamic_slice_in_dim(
            c, s, 1, axis=0))(cache, slots)
        new = jnp.where(update.reshape((-1,) + (1,) * (new.ndim - 1)),
                        new.astype(cache.dtype), old)

    def one(c, n, s):
        return jax.lax.dynamic_update_slice_in_dim(c, n, s, axis=0)
    return jax.vmap(one)(cache, new, slots)


def gqa_decode(params, cfg, x, cache_k, cache_v, pos, *, window=0,
               update=None):
    """Single-token decode.  x:(B,1,D); cache:(B,S,KV,hd);
    pos: scalar or per-slot (B,) int32 positions (continuous batching).

    With ``window>0`` the cache is a ring buffer of size ``window``.
    ``update`` (B,) bool, where given: the slots whose cache this step
    writes; the others keep theirs.
    Returns (y, new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = _vec_pos(pos, B)
    q, k, v = _project_qkv(params, cfg, x, pos[:, None])
    S = cache_k.shape[1]
    slot = jnp.where(window > 0, pos % jnp.maximum(S, 1), pos)
    cache_k = _row_update(cache_k, k, slot, update)
    cache_v = _row_update(cache_v, v, slot, update)

    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        cache_k.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)
    s_idx = jnp.arange(S)[None, :]                       # (1, S)
    pb = pos[:, None]
    if window > 0:
        # slot s holds absolute position p = pos - ((pos - s) mod S)
        p_s = pb - ((pb - s_idx) % S)
        valid = p_s >= 0
    else:
        valid = s_idx <= pb
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, cache_v.astype(jnp.float32))
    y = jnp.einsum("bh,hd->bd", out.reshape(B, -1).astype(x.dtype), params["wo"])
    return y[:, None, :], cache_k, cache_v


def cross_kv(params, cfg, enc_out):
    """Project encoder output to cross-attention K/V once (cached for the
    whole decode; recomputing these per step was the dominant waste in the
    enc-dec decode roofline)."""
    B, Se, _ = enc_out.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    k = jnp.einsum("bsd,dh->bsh", enc_out, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", enc_out, params["wv"])
    if cfg.qkv_bias:
        k = k + params["bk"]
        v = v + params["bv"]
    return k.reshape(B, Se, KV, hd), v.reshape(B, Se, KV, hd)


def gqa_cross_decode(params, cfg, x, ck, cv):
    """Single-token cross attention against cached encoder K/V.
    x: (B,1,D); ck/cv: (B,Se,KV,hd)."""
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"]
    q = q.reshape(B, H, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, cv.astype(jnp.float32))
    y = jnp.einsum("bh,hd->bd", out.reshape(B, -1).astype(x.dtype),
                   params["wo"])
    return y[:, None, :]


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg):
    d = cfg.d_model
    H = cfg.n_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 7)
    p = {
        "w_dkv": dense_init(ks[0], (d, r + rd), dt),
        "kv_norm": rmsnorm_init(r, dt),
        "w_uk": dense_init(ks[1], (r, H * nd), dt),
        "w_uv": dense_init(ks[2], (r, H * vd), dt),
        "wo": dense_init(ks[3], (H * vd, d), dt),
    }
    if qr:
        p["w_dq"] = dense_init(ks[4], (d, qr), dt)
        p["q_norm"] = rmsnorm_init(qr, dt)
        p["w_uq"] = dense_init(ks[5], (qr, H * (nd + rd)), dt)
    else:
        p["wq"] = dense_init(ks[6], (d, H * (nd + rd)), dt)
    return p


def _mla_q(params, cfg, x, positions):
    B, S, _ = x.shape
    H, nd, rd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        cq = rmsnorm(params["q_norm"], jnp.einsum("bsd,dr->bsr", x, params["w_dq"]))
        q = jnp.einsum("bsr,rh->bsh", cq, params["w_uq"])
    else:
        q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    q = q.reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(params, cfg, x, positions):
    ckv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"])
    r = cfg.kv_lora_rank
    c, k_rope = ckv[..., :r], ckv[..., r:]
    c = rmsnorm(params["kv_norm"], c)
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c, k_rope


def mla_full(params, cfg, x, positions, *, return_kv=False):
    B, S, _ = x.shape
    H = cfg.n_heads
    nd, rd, vd, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    c, k_rope = _mla_ckv(params, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rh->bsh", c, params["w_uk"]).reshape(B, S, H, nd)
    v = jnp.einsum("bsr,rh->bsh", c, params["w_uv"]).reshape(B, S, H, vd)
    scale = 1.0 / jnp.sqrt(jnp.float32(nd + rd))
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(jnp.float32),
                         k_nope.astype(jnp.float32))
              + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32))) * scale
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    y = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, -1).astype(x.dtype), params["wo"])
    if return_kv:
        return y, (c, k_rope)
    return y


def mla_decode_naive(params, cfg, x, cache_c, cache_krope, pos, update=None):
    """Naive MLA decode: expand the WHOLE latent cache to per-head K/V every
    step (what a direct port of full-attention decode would do).  Kept as the
    §Perf E baseline — the absorbed path below avoids the O(S·H·d) expansion."""
    B = x.shape[0]
    H, nd, rd, vd, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    pos = _vec_pos(pos, B)
    positions = pos[:, None]
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    c, k_rope = _mla_ckv(params, cfg, x, positions)
    cache_c = _row_update(cache_c, c, pos, update)
    cache_krope = _row_update(cache_krope, k_rope, pos, update)

    S = cache_c.shape[1]
    k_nope = jnp.einsum("bsr,rh->bsh", cache_c,
                        params["w_uk"]).reshape(B, S, H, nd)
    v = jnp.einsum("bsr,rh->bsh", cache_c,
                   params["w_uv"]).reshape(B, S, H, vd)
    scale = 1.0 / jnp.sqrt(jnp.float32(nd + rd))
    scores = (jnp.einsum("bhd,bshd->bhs", q_nope[:, 0].astype(jnp.float32),
                         k_nope.astype(jnp.float32))
              + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                           cache_krope.astype(jnp.float32))) * scale
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
    y = jnp.einsum("bh,hd->bd", out.reshape(B, -1).astype(x.dtype),
                   params["wo"])
    return y[:, None, :], cache_c, cache_krope


def mla_decode(params, cfg, x, cache_c, cache_krope, pos, update=None):
    """Absorbed-matmul MLA decode: attention runs in the kv_lora space so the
    cache is only (B, S, r + rope_dim) — the point of MLA.
    pos: scalar or per-slot (B,) positions; ``update`` as in `gqa_decode`."""
    if getattr(cfg, "mla_naive_decode", False):
        return mla_decode_naive(params, cfg, x, cache_c, cache_krope, pos,
                                update)
    B = x.shape[0]
    H, nd, rd, vd, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    pos = _vec_pos(pos, B)
    positions = pos[:, None]
    q_nope, q_rope = _mla_q(params, cfg, x, positions)          # (B,1,H,*)
    c, k_rope = _mla_ckv(params, cfg, x, positions)             # (B,1,r),(B,1,rd)
    cache_c = _row_update(cache_c, c, pos, update)
    cache_krope = _row_update(cache_krope, k_rope, pos, update)

    w_uk = params["w_uk"].reshape(r, H, nd)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))                # (B,H,r)
    scale = 1.0 / jnp.sqrt(jnp.float32(nd + rd))
    scores = (jnp.einsum("bhr,bsr->bhs", q_abs, cache_c.astype(jnp.float32))
              + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                           cache_krope.astype(jnp.float32))) * scale
    valid = jnp.arange(cache_c.shape[1])[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out_c = jnp.einsum("bhs,bsr->bhr", probs, cache_c.astype(jnp.float32))  # (B,H,r)
    w_uv = params["w_uv"].reshape(r, H, vd)
    out = jnp.einsum("bhr,rhv->bhv", out_c, w_uv.astype(jnp.float32))
    y = jnp.einsum("bh,hd->bd", out.reshape(B, -1).astype(x.dtype), params["wo"])
    return y[:, None, :], cache_c, cache_krope
