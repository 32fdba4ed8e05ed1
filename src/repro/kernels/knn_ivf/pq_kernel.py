"""Pallas TPU kernel: asymmetric-distance computation (ADC) over packed
IVF-PQ lists.

Grid (Q/BQ, S) — the same query-tile x probe-slot schedule as the raw IVF
kernel (`kernel.py`), with the same scalar-prefetched slot lists so the
BlockSpec index maps DMA exactly the probed clusters' blocks.  What changes
is WHAT gets DMA'd per slot: a CODE-MAJOR (MB, L) packed uint8 block (MB =
m*nbits/8 bytes/row) instead of an (L, D) float32 row block — the ~16-32x
cut in per-probe HBM traffic that is the whole point of the PQ tier.  The
code-major layout puts the long list axis L in the MINOR (lane) dimension:
each of the MB sublane rows is a contiguous, lane-aligned run of L bytes,
so the per-slot DMA moves MB dense lane vectors instead of L short
MB-byte rows — and the grid's slot axis keeps the standard Pallas
double-buffered pipeline (slot s+1's block streams in while slot s is
scored).

The per-query ADC lookup tables arrive precomputed (one XLA einsum ahead
of the kernel, `ops._adc_lut`) as an ``(m, BQ, K)`` block whose index map
is constant across the slot axis, so it is DMA'd once per query tile.  Each
slot's codes are widened once into an int32 VMEM scratch, then scored
subspace by subspace in a `fori_loop`: the subspace's codes expand into an
indicator block and contract against its table on the MXU::

    onehot[c, l] = 1  iff  code_jl == c            # (K, L)
    sims += lut[j] @ onehot                         # (BQ, L)
    sims  = (sims + q @ anchor_c) * inv_norm        # exact stored norms

One subspace at a time keeps the live temporaries at ``K x L`` — the
all-subspace ``m*K x L`` expansion outgrows VMEM at routing-embedding
widths (m = 192 at D = 768).  Only compares, selects, matmuls and
leading-axis ref indexing: no dynamic VMEM gathers.  Masking, the exact
stored inverse norms, and the running (BQ, K) top-k merge are identical to
the raw IVF kernel, so the shortlist contract (-1 ids / NEG scores in empty
slots) is too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from ..knn_topk.kernel import NEG, merge_topk


def _adc_kernel(probe_ref, valid_ref, lut_ref, qp_ref, aq_ref, codes_ref,
                ids_ref, inv_ref, out_s_ref, out_i_ref, codes_scr, *, k: int,
                m: int, nbits: int):
    i = pl.program_id(0)
    p = pl.program_id(1)
    kk = 2 ** nbits
    per_byte = 8 // nbits

    @pl.when(p == 0)
    def _init():
        out_s_ref[...] = jnp.full_like(out_s_ref, NEG)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    @pl.when(valid_ref[i, p] != 0)
    def _merge():
        cid = probe_ref[i, p]
        # code-major block: each packed byte row is one lane vector along L
        codes_scr[...] = codes_ref[0].astype(jnp.int32)      # (MB, L)
        ids = ids_ref[0]                                     # (1, L)
        l = codes_scr.shape[1]
        code_iota = jax.lax.broadcasted_iota(jnp.int32, (kk, l), 0)

        def subspace(j, sims):
            byte = codes_scr[pl.ds(j // per_byte, 1), :]     # (1, L)
            cj = (byte >> (nbits * (j % per_byte))) & (kk - 1)
            onehot = jnp.where(code_iota == cj, 1.0, 0.0)    # (K, L)
            return sims + jax.lax.dot_general(
                lut_ref[j], onehot, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

        sims = jax.lax.fori_loop(
            0, m, subspace, jnp.zeros((lut_ref.shape[1], l), jnp.float32))

        # the query-anchor dots of this slot's cluster: column p of the
        # tile's (BQ, S) block, picked with a select (no dynamic lane slice)
        aq_all = aq_ref[...]
        slot = jax.lax.broadcasted_iota(jnp.int32, aq_all.shape, 1)
        aq = jnp.sum(jnp.where(slot == p, aq_all, 0.0), axis=1,
                     keepdims=True)                          # (BQ, 1)
        sims = (sims + aq) * inv_ref[0]                      # (BQ, L)

        probed = jnp.any(qp_ref[...] == cid, axis=1)         # (BQ,)
        ok = probed[:, None] & (ids >= 0)                    # (BQ, L)
        sims = jnp.where(ok, sims, NEG)
        # masked candidates must not leak their row id (same contract as the
        # raw IVF kernel): empty merge picks carry -1
        ids_b = jnp.where(ok, jnp.broadcast_to(ids, sims.shape), -1)

        cand_s = jnp.concatenate([out_s_ref[...], sims], axis=1)
        cand_i = jnp.concatenate([out_i_ref[...], ids_b], axis=1)
        acc_s, acc_i = merge_topk(cand_s, cand_i, k)
        out_s_ref[...] = acc_s
        out_i_ref[...] = acc_i


def ivfpq_adc_pallas(lut, codes_cm, ids_cm, inv_cm, aq, q_probe, tile_probe,
                     tile_valid, k: int, *, m: int, nbits: int):
    """lut (m, Q, 2^nbits) per-query ADC tables, Q a multiple of the tile
    size implied by tile_probe; codes_cm (C, MB, L) CODE-MAJOR packed uint8;
    ids_cm / inv_cm (C, L); aq (Q, S) each query's dot with the anchor of
    every slot of its tile (``q @ anchors[tile_probe]``); q_probe /
    tile_probe / tile_valid as in `ivf_topk_pallas`.  Returns the ADC
    shortlist (scores (Q, k), indices (Q, k)) — original row ids, -1 / NEG
    in empty slots."""
    _, Q, KB = lut.shape
    C, MB, L = codes_cm.shape
    T, S = tile_probe.shape
    P = q_probe.shape[1]
    assert Q % T == 0, (Q, T)
    assert lut.shape == (m, Q, 2 ** nbits), (lut.shape, m, nbits)
    bq = Q // T

    kern = functools.partial(_adc_kernel, k=k, m=m, nbits=nbits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, S),
        in_specs=[
            pl.BlockSpec((m, bq, KB), lambda i, p, probe, valid: (0, i, 0)),
            pl.BlockSpec((bq, P), lambda i, p, probe, valid: (i, 0)),
            pl.BlockSpec((bq, S), lambda i, p, probe, valid: (i, 0)),
            pl.BlockSpec((1, MB, L),
                         lambda i, p, probe, valid: (probe[i, p], 0, 0)),
            pl.BlockSpec((1, 1, L),
                         lambda i, p, probe, valid: (probe[i, p], 0, 0)),
            pl.BlockSpec((1, 1, L),
                         lambda i, p, probe, valid: (probe[i, p], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, p, probe, valid: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, p, probe, valid: (i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((MB, L), jnp.int32)],
    )
    out_s, out_i = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=kernels.interpret_mode(),
    )(tile_probe, tile_valid, lut, q_probe, aq, codes_cm,
      # (C, 1, L): a per-cluster (1, L) row is then a whole trailing block
      ids_cm[:, None, :], inv_cm[:, None, :])
    return out_s, out_i
