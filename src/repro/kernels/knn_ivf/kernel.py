"""Pallas TPU kernel: IVF approximate top-k over a cluster-major support set.

Grid (Q/BQ, S): query tiles x probe SLOTS.  A slot is one coarse cluster
some query in the tile probes; the per-tile slot lists (union of the tile's
per-query probe sets, deduplicated, padded to the static width S) are
SCALAR-PREFETCHED so the BlockSpec index map can DMA exactly the probed
cluster's (L, D) list from HBM — the kernel never touches unprobed lists,
which is the sub-linear part.

Inside the kernel each query masks the slot's rows to (a) valid rows
(ids >= 0, excluding list padding) and (b) slots the QUERY itself probes
(tile mates may probe different clusters), then folds the tile into the
running (BQ, K) top-k buffer with the same Mosaic-safe max/select/iota merge
as the brute-force kernel (`knn_topk.kernel.merge_topk`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from ..knn_topk.kernel import NEG, merge_topk


def _ivf_kernel(probe_ref, valid_ref, q_ref, qp_ref, s_ref, ids_ref,
                inv_ref, out_s_ref, out_i_ref, *, k: int):
    i = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        out_s_ref[...] = jnp.full_like(out_s_ref, NEG)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    # Padded slots repeat the tile's first cluster with valid=0: the block
    # DMA stays in-bounds and the merge is skipped (no double-counting).
    @pl.when(valid_ref[i, p] != 0)
    def _merge():
        cid = probe_ref[i, p]
        q = q_ref[...].astype(jnp.float32)                   # (BQ, D)
        s = s_ref[0].astype(jnp.float32)                     # (L, D)
        ids = ids_ref[0]                                     # (1, L)
        sims = jax.lax.dot_general(q, s, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        sims = sims * inv_ref[0]                             # (BQ, L)
        probed = jnp.any(qp_ref[...] == cid, axis=1)         # (BQ,)
        ok = probed[:, None] & (ids >= 0)                    # (BQ, L)
        sims = jnp.where(ok, sims, NEG)
        # masked candidates must not leak their row id either: with no valid
        # candidate left, merge_topk picks SOME NEG-scored position, and the
        # empty-slot contract (-1 ids, later mapped to -inf) relies on those
        # positions carrying -1
        ids_b = jnp.where(ok, jnp.broadcast_to(ids, sims.shape), -1)

        cand_s = jnp.concatenate([out_s_ref[...], sims], axis=1)
        cand_i = jnp.concatenate([out_i_ref[...], ids_b], axis=1)
        acc_s, acc_i = merge_topk(cand_s, cand_i, k)
        out_s_ref[...] = acc_s
        out_i_ref[...] = acc_i


def ivf_topk_pallas(queries, sup_cm, ids_cm, inv_cm, q_probe, tile_probe,
                    tile_valid, k: int):
    """queries (Q, D) L2-normalized, Q a multiple of the tile size BQ implied
    by tile_probe (T = Q/BQ); sup_cm (C, L, D); ids_cm (C, L) i32;
    inv_cm (C, L) precomputed inverse row norms (0 on padding);
    q_probe (Q, P) per-query probe cluster ids (-1 allowed on padded query
    rows); tile_probe (T, S) / tile_valid (T, S) the deduplicated per-tile
    slot lists.  Returns (scores (Q, k), indices (Q, k)) — original row ids,
    -1 / NEG in empty slots."""
    Q, D = queries.shape
    C, L, _ = sup_cm.shape
    T, S = tile_probe.shape
    P = q_probe.shape[1]
    assert Q % T == 0, (Q, T)
    bq = Q // T

    kern = functools.partial(_ivf_kernel, k=k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, S),
        in_specs=[
            pl.BlockSpec((bq, D), lambda i, p, probe, valid: (i, 0)),
            pl.BlockSpec((bq, P), lambda i, p, probe, valid: (i, 0)),
            pl.BlockSpec((1, L, D),
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
    )
    out_s, out_i = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=kernels.interpret_mode(),
    )(tile_probe, tile_valid, queries, q_probe, sup_cm,
      # (C, 1, L): a per-cluster (1, L) row is then a whole trailing block
      ids_cm[:, None, :], inv_cm[:, None, :])
    return out_s, out_i
