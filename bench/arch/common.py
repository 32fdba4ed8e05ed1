"""Numerics shared by the reference architectures: no import of the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: the reference runs every matmul at full float32 precision on the TPU
HIGHEST = jax.lax.Precision.HIGHEST


def normal(key, shape, std, dtype):
    """Truncated normal (2 standard deviations), drawn in float32 and cast."""
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def fp8_weight(w):
    """Weight-only fp8 (e4m3), one scale per output column mapping the
    column's largest magnitude to 448, returned dequantized in bfloat16."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


class Numerics:
    """How a forward pass computes: ``reference`` is float32 at the highest
    matmul precision; ``control`` is fp8 (e4m3) weights and bfloat16
    activations, one step below the bfloat16 the configurations state."""

    def __init__(self, mode: str):
        if mode not in ("reference", "control"):
            raise ValueError(f"unknown numerics {mode!r}")
        self.mode = mode
        self.act = jnp.float32 if mode == "reference" else jnp.bfloat16

    def w(self, w):
        return (w.astype(jnp.float32) if self.mode == "reference"
                else fp8_weight(w))

    def mm(self, spec, x, w):
        """``einsum(spec, x, w)`` with ``w`` a weight matrix."""
        if self.mode == "reference":
            return jnp.einsum(spec, x.astype(jnp.float32), self.w(w),
                              precision=HIGHEST)
        return jnp.einsum(spec, x.astype(jnp.bfloat16), self.w(w),
                          preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16)

    def cast(self, x):
        return x.astype(self.act)


def gaps(ref_logits, served):
    """For each served token: how far its logit lies below the largest
    logit of the reference at that position.  ``ref_logits`` (T, V) f32,
    ``served`` (T,) int."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return best - got
