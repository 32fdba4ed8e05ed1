"""Pallas TPU flash attention (forward): blocked online-softmax with GQA,
causal and sliding-window masking.

Layout: q (B, H, Sq, hd); k, v (B, KV, Sk, hd).  Grid (B, H, Sq/BQ, Sk/BK);
the KV-head for a q-head h is h * KV // H, resolved in the BlockSpec index
map so GQA costs no extra bandwidth.  Running max / denominator / accumulator
live in VMEM scratch and are finalized on the last KV iteration.

Fully-masked tiles (beyond the causal frontier or outside the sliding
window) are skipped with ``pl.when`` — this is the structural win that makes
SWA sub-quadratic on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG = -3.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, nk: int, causal: bool, window: int,
                  scale: float):
    iq = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = j * bk
    # tile-level skip: strictly above the causal diagonal, or entirely
    # left of the sliding window
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window:
        # newest key in tile must still be inside the window of the
        # youngest query in the tile
        live = jnp.logical_and(live,
                               k_start + bk - 1 >= q_start - (window - 1))

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_start
        kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k_start
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        # rows with no live key yet: m_new == NEG -> p would be exp(0)=1;
        # guard by zeroing those rows
        p = jnp.where(m_new > NEG / 2, p, 0.0)
        alpha = jnp.where(m_prev > NEG / 2, jnp.exp(m_prev - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0, ...] = (acc_scr[...] / safe).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0,
                           block_q=128, block_k=128):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nk = Sk // bk
    grid = (B, H, Sq // bq, nk)
    scale = 1.0 / (hd ** 0.5)

    kern = functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk,
                             causal=causal, window=window, scale=scale)
    kv_map = lambda b, h, i, j: (b, h * KV // H, j, 0)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=kernels.interpret_mode(),
    )(q, k, v)
