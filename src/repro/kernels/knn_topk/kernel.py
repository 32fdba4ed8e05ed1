"""Pallas TPU kernel: fused cosine-similarity matmul + running top-k.

TPU adaptation of the paper's ScaNN-based CPU retrieval: brute-force blocked
matmul on the MXU with the support-row normalization fused into the score
tile, and a running (BQ, K) top-k buffer kept in VMEM that is merged with
each score tile using only max/select/iota ops (no sort / no lax.top_k —
those do not lower through Mosaic).

Grid: (Q/BQ, N/BN); the output block index map pins the out block to the
query tile so the N-dimension iterations revisit and accumulate in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels

NEG = -3.0e38  # python float: avoids captured-constant arrays in the kernel


def merge_topk(cand_s, cand_i, k: int):
    """Running top-k over a (BQ, n_cand) candidate tile using only
    max/select/iota ops (Mosaic-safe: no sort / no lax.top_k, and no gather
    or dynamic_update_slice, neither of which lowers through Mosaic).
    Returns the (BQ, k) best scores (descending) and their candidate ids;
    equal scores keep their candidate order, as `lax.top_k` does.  Shared
    by the brute-force kernel here and the IVF kernels
    (`knn_ivf/kernel.py`, `knn_ivf/pq_kernel.py`)."""
    n_cand = cand_s.shape[1]
    acc_s = jnp.full((cand_s.shape[0], k), NEG, cand_s.dtype)
    acc_i = jnp.full((cand_i.shape[0], k), -1, cand_i.dtype)
    pos_iota = jax.lax.broadcasted_iota(jnp.int32, cand_s.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, acc_s.shape, 1)

    def body(t, carry):
        cs, acc_s, acc_i = carry
        m = jnp.max(cs, axis=1, keepdims=True)                     # (BQ, 1)
        # first argmax via masked iota-min (Mosaic-safe: min/select only)
        am = jnp.min(jnp.where(cs >= m, pos_iota, n_cand), axis=1,
                     keepdims=True)                                # (BQ, 1)
        hit = pos_iota == am
        # the id at the argmax as a masked max: exactly one column hits
        chosen_i = jnp.max(jnp.where(hit, cand_i, -1), axis=1,
                           keepdims=True)                          # (BQ, 1)
        # exhausted rows (max == NEG sentinel) re-pick an already-taken
        # position whose id column still holds a real row id; emit -1 so
        # empty output slots never alias a real candidate
        chosen_i = jnp.where(m > NEG / 2, chosen_i, -1)
        # write output column t with a select on a column iota
        acc_s = jnp.where(col == t, m, acc_s)
        acc_i = jnp.where(col == t, chosen_i, acc_i)
        cs = jnp.where(hit, NEG, cs)
        return cs, acc_s, acc_i

    _, acc_s, acc_i = jax.lax.fori_loop(0, k, body, (cand_s, acc_s, acc_i))
    return acc_s, acc_i


def _knn_kernel(q_ref, s_ref, out_s_ref, out_i_ref, *, k: int, bn: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_s_ref[...] = jnp.full_like(out_s_ref, NEG)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    q = q_ref[...].astype(jnp.float32)                     # (BQ, D)
    s = s_ref[...].astype(jnp.float32)                     # (BN, D)
    inv = jax.lax.rsqrt(jnp.sum(s * s, axis=-1) + 1e-12)   # (BN,)
    sims = jax.lax.dot_general(q, s, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    sims = sims * inv[None, :]                             # (BQ, BN)

    base = j * bn
    tile_idx = jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1) + base

    cand_s = jnp.concatenate([out_s_ref[...], sims], axis=1)       # (BQ, K+BN)
    cand_i = jnp.concatenate([out_i_ref[...], tile_idx], axis=1)
    acc_s, acc_i = merge_topk(cand_s, cand_i, k)
    out_s_ref[...] = acc_s
    out_i_ref[...] = acc_i


def knn_topk_pallas(queries, support, k: int, *, block_q: int = 128,
                    block_n: int = 1024):
    Q, D = queries.shape
    N, _ = support.shape
    bq = min(block_q, Q)
    bn = min(block_n, N)
    assert Q % bq == 0 and N % bn == 0, (Q, N, bq, bn)
    grid = (Q // bq, N // bn)
    kern = functools.partial(_knn_kernel, k=k, bn=bn)
    out_s, out_i = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=kernels.interpret_mode(),
    )(queries, support)
    return out_s, out_i
