"""Public wrapper: model-layout (B, S, H, hd) flash attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_pallas
from .ref import flash_attention_reference


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal=True, window=0):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_pallas(qt, kt, vt, causal=causal, window=window)
    return jnp.swapaxes(out, 1, 2)
