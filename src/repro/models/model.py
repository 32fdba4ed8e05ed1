"""Top-level language model: embeddings + frontend + stack + head.

Covers all assigned families:
  * text decoder-only (dense / MoE / MLA-MoE / SSM / hybrid)
  * VLM: patch embeddings (stubbed ViT output) projected and prepended
  * audio enc-dec: frame embeddings (stubbed codec output) -> encoder,
    text decoder with cross-attention

Public entry points used by training / serving / dry-run:
  init_params, forward, loss_fn, prefill, decode_step, init_caches
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from . import transformer as tfm
from .layers import dense_init, rmsnorm, rmsnorm_init


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=1)
def init_params(key, cfg) -> Dict[str, Any]:
    """Seeded random parameters for ``cfg``.  Jitted, so each weight is
    drawn straight into its own (cast) buffer: run op by op, every float32
    draw would sit beside its cast copy, which at published widths holds
    gigabytes of temporaries at once."""
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dt, scale=1.0),
        "final_norm": rmsnorm_init(cfg.d_model, dt),
        "lm_head": dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dt),
        "stack": tfm.stack_init(ks[2], cfg, cross=cfg.is_encoder_decoder),
    }
    if cfg.frontend in ("vision", "audio"):
        p["frontend_proj"] = dense_init(
            ks[3], (cfg.frontend_dim, cfg.d_model), dt)
    if cfg.is_encoder_decoder:
        enc_cfg = cfg.replace(pattern=(("attn", "dense"),),
                              n_groups=cfg.encoder_layers,
                              tail_pattern=(), n_tail_groups=0,
                              sliding_window=0)
        p["encoder"] = tfm.stack_init(ks[4], enc_cfg, cross=False)
        p["enc_norm"] = rmsnorm_init(cfg.d_model, dt)
    return p


def _enc_cfg(cfg):
    return cfg.replace(pattern=(("attn", "dense"),), n_groups=cfg.encoder_layers,
                       tail_pattern=(), n_tail_groups=0, sliding_window=0)


# ---------------------------------------------------------------------------
# encoder / frontend
# ---------------------------------------------------------------------------

def encode(params, cfg, frames):
    """Audio encoder: frames (B, Se, frontend_dim) -> (B, Se, D)."""
    x = jnp.einsum("bsf,fd->bsd", frames, params["frontend_proj"])
    pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    enc_cfg = _enc_cfg(cfg)

    # encoder is bidirectional: reuse stack with causal disabled via window=0
    # (we run it causal=False by calling attention directly through a tweaked
    #  pattern; simplest faithful approach: non-causal full attention)
    from . import attention as attn_mod
    from .layers import mlp

    def body(carry, gp):
        h = carry
        lp = gp[0]
        a = attn_mod.gqa_full(lp["attn"], enc_cfg,
                              rmsnorm(lp["norm1"], h), pos, causal=False)
        h = h + a
        h = h + mlp(lp["mlp"], rmsnorm(lp["norm2"], h))
        return h, None

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    x, _ = tfm.maybe_scan(body, x, params["encoder"]["groups"])
    return rmsnorm(params["enc_norm"], x)


def embed_inputs(params, cfg, batch):
    """Returns (x, positions, enc_out, label_offset).

    VLM: prepend projected patch embeddings; positions cover the full
    sequence; labels for patch slots are ignored (-1) by the loss.
    """
    tokens = batch["tokens"]
    B = tokens.shape[0]
    x = params["embed"][tokens]  # gather (B, S_text, D)
    enc_out = None
    if cfg.frontend == "vision" and "patches" in batch:
        pe = jnp.einsum("bpf,fd->bpd", batch["patches"].astype(x.dtype),
                        params["frontend_proj"])
        x = jnp.concatenate([pe, x], axis=1)
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch["frames"].astype(x.dtype))
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return x, positions, enc_out


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, cfg, batch, long_mode=False):
    x, positions, enc_out = embed_inputs(params, cfg, batch)
    x = constrain(x, ("batch", "seq", "embed"))
    x, aux = tfm.stack_full(params["stack"], cfg, x, positions,
                            enc_out=enc_out, long_mode=long_mode)
    x = rmsnorm(params["final_norm"], x)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits, aux


def loss_fn(params, cfg, batch, aux_weight=0.01):
    logits, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    if cfg.frontend == "vision" and "patches" in batch:
        pad = jnp.full(batch["patches"].shape[:2], -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    valid = labels >= 0
    lbl = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
    n = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum(jnp.where(valid, nll, 0.0)) / n
    total = loss + aux_weight * aux
    metrics = {"loss": loss, "aux_loss": aux,
               "tokens": n.astype(jnp.float32)}
    return total, metrics


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_caches(cfg, batch, cache_len, long_mode=False, enc_len=0):
    use_enc = enc_len if (cfg.is_encoder_decoder and cfg.cross_kv_cache) else 0
    return tfm.caches_init(cfg, batch, cache_len, long_mode=long_mode,
                           enc_len=use_enc)


def has_recurrent_state(cfg) -> bool:
    """Whether ``cfg``'s decode caches hold state-space layers' state."""
    return any(kind == "ssm" for kind, _ in cfg.pattern + cfg.tail_pattern)


def reset_slot(caches, slot):
    """``caches`` with slot ``slot``'s recurrent state (the ``conv`` and
    ``ssd`` leaves of the state-space layers) set to zero, as a new
    sequence starts from.  Attention caches are left as they are: a slot
    attends only to the positions it has written since it started."""
    def leaf(path, a):
        if getattr(path[-1], "key", None) in ("conv", "ssd"):
            return a.at[:, slot].set(0)
        return a
    return jax.tree_util.tree_map_with_path(leaf, caches)


def fill_cross_cache(params, cfg, caches, enc_out):
    """Project encoder output into every decoder layer's cached cross K/V
    (once per request; replaces per-step recompute)."""
    from . import attention as attn_mod
    groups = params["stack"]["groups"]

    def per_layer(cross_p):
        return attn_mod.cross_kv(cross_p, cfg, enc_out)

    new = dict(caches)
    grp = []
    for i, layer_caches in enumerate(caches["groups"]):
        lp = groups[i]
        if "cross" in lp and "ck" in layer_caches:
            # vmap over the stacked group axis of this pattern slot
            ck, cv = jax.vmap(per_layer)(lp["cross"])
            grp.append(dict(layer_caches, ck=ck, cv=cv))
        else:
            grp.append(layer_caches)
    new["groups"] = grp
    return new


def prefill(params, cfg, batch, cache_len, long_mode=False):
    """Run the full-sequence forward and materialize decode caches by
    re-projecting K/V per layer.  For simplicity (and because the dry-run
    lowers decode directly with ShapeDtypeStruct caches) prefill here runs
    the chunked full forward and then fills caches token-by-token is NOT
    done; serving uses forward() for logits and lazily-filled caches."""
    logits, _ = forward(params, cfg, batch, long_mode=long_mode)
    return logits


def decode_step(params, cfg, caches, token, pos, enc_out=None, update=None):
    """token: (B, 1) int32; pos: scalar or per-slot (B,) int32 position of
    this token.  ``update`` (B,) bool, optional: the slots this step serves;
    the caches and states of the others are left bit for bit (their logits
    mean nothing).  Without it every slot updates.
    Returns (logits (B, vocab), new_caches)."""
    x = params["embed"][token]
    x, new_caches = tfm.stack_decode(params["stack"], cfg, caches, x, pos,
                                     enc_out=enc_out, update=update)
    x = rmsnorm(params["final_norm"], x)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)
    return logits[:, 0], new_caches
