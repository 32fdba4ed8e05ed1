"""Pallas TPU kernel: single-token decode attention over a KV cache.

Grid (B, KV, S/BK): all G = H/KV query heads of one KV head are processed
together so the cache tile is read once per group (GQA bandwidth win — on
TPU decode attention is HBM-bound, cache bytes dominate).  The current
position arrives via scalar prefetch (SMEM) and drives both validity
masking and, for ring-buffer (sliding-window) caches, the wrap-around mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG = -3.0e38


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, bk: int, nk: int, ring: bool, scale: float):
    j = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                      # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bk)
    slot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bk
    if ring:
        S_total = nk * bk
        p_s = pos - ((pos - slot) % S_total)
        valid = p_s >= 0
    else:
        valid = slot <= pos
    s = jnp.where(valid, s, NEG)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(m_new > NEG / 2, jnp.exp(s - m_new), 0.0)
    alpha = jnp.where(m_prev > NEG / 2, jnp.exp(m_prev - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0, ...] = (acc_scr[...] / safe).astype(o_ref.dtype)


def decode_attention_pallas(q, cache_k, cache_v, pos, *, ring=False,
                            block_k=512):
    """q: (B, KV, G, hd); cache_k/v: (B, KV, S, hd); pos scalar int32."""
    B, KV, G, hd = q.shape
    S = cache_k.shape[2]
    bk = min(block_k, S)
    assert S % bk == 0
    nk = S // bk
    grid = (B, KV, nk)
    kern = functools.partial(_decode_kernel, bk=bk, nk=nk, ring=ring,
                             scale=1.0 / (hd ** 0.5))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, pos_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, pos_ref: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, pos_ref: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, pos_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=kernels.interpret_mode(),
    )(jnp.atleast_1d(pos).astype(jnp.int32), q, cache_k, cache_v)
