"""Public wrapper: model-layout decode attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import decode_attention_pallas
from .ref import decode_attention_reference


@functools.partial(jax.jit, static_argnames=("ring",))
def decode_attention(q, cache_k, cache_v, pos, *, ring=False):
    """q: (B, H, hd); cache_k/v: (B, S, KV, hd)."""
    B, H, hd = q.shape
    KV = cache_k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    kt = jnp.swapaxes(cache_k, 1, 2)   # (B, KV, S, hd)
    vt = jnp.swapaxes(cache_v, 1, 2)
    out = decode_attention_pallas(qg, kt, vt, pos, ring=ring)
    return out.reshape(B, H, hd)
