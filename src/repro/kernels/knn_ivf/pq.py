"""Product quantization for IVF list storage (the IVF-PQ retrieval tier).

The raw ``(C, L, D)`` float32 cluster-major support set is the HBM ceiling of
the IVF subsystem: at deployment-scale corpora it dominates both memory and
per-probe DMA volume.  PQ replaces each list row with ``m`` one-byte (or
half-byte) codes: the row's RESIDUAL against its cluster's raw-space anchor
is split into ``m`` subvectors, each quantized against a per-subspace
codebook of ``2^nbits`` centroids trained at index-build time.  At
``m = D/8`` each row shrinks from ``4*D`` bytes to ``D/8`` (32x on the rows
themselves, ~16x on the whole hot index once the per-row ids/inverse-norms
and the small codebooks/anchors are counted in).

Scoring uses asymmetric distance computation (ADC): a query builds one
``(m, 2^nbits)`` lookup table of subvector dot products, and every code row
is scored by ``m`` table gathers instead of a ``D``-MAC dot product::

    dot(q, x_i)  ~=  q @ anchor_c  +  sum_j  LUT[j, code_ij]

which is exact when the residual quantization error is zero (the identity
``anchor + concat_j codebook[j, code_j]`` reconstructs the row).  The stored
per-row inverse norms stay EXACT, so ADC approximates only the dot product,
never the normalization — and exact re-ranking of a small ADC shortlist
against the raw rows (the cold tier) restores near-exact recall.

Everything here is numpy and runs once at build time; the jnp unpack helper
is shared by the jitted/tiles/sharded ADC paths.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp


def effective_m(d: int, m: int) -> int:
    """Largest divisor of ``d`` that is <= the requested ``m`` — PQ needs
    equal-width subspaces, and silently failing on odd embedding dims would
    make spec strings dim-dependent."""
    m = max(1, min(m, d))
    while d % m:
        m -= 1
    return m


def default_m(d: int) -> int:
    """~D/8 subspaces (8 dims per code, one byte summarizing 32 raw bytes),
    capped at 64 — past that the per-row LUT-gather count grows with no
    retrieval benefit at routing-embedding dims."""
    return effective_m(d, min(64, max(1, d // 8)))


def _kmeans_subspace(x: np.ndarray, n_centers: int, seed: int,
                     iters: int) -> np.ndarray:
    """Plain Lloyd k-means on one residual subspace (Euclidean).  Empty
    centers are reseeded from random rows; with fewer rows than centers the
    init samples with replacement (duplicate centers are harmless — argmin
    ties break to the lowest index).  The center update is one bincount
    per dimension: a per-center masked mean costs a pass over all rows for
    each of the 2^nbits centers, minutes at m = 192 subspaces."""
    rng = np.random.default_rng(seed)
    x = np.ascontiguousarray(x)
    n = len(x)
    cent = x[rng.choice(n, size=n_centers, replace=n < n_centers)].copy()
    for _ in range(iters):
        d2 = (np.square(x).sum(1, keepdims=True)
              - 2.0 * (x @ cent.T) + np.square(cent).sum(1))
        assign = np.argmin(d2, axis=1)
        counts = np.bincount(assign, minlength=n_centers)
        sums = np.stack([np.bincount(assign, weights=x[:, t],
                                     minlength=n_centers)
                         for t in range(x.shape[1])], axis=1)
        filled = counts > 0
        cent[filled] = sums[filled] / counts[filled, None]
        for c in np.flatnonzero(~filled):
            cent[c] = x[rng.integers(0, n)]
    return cent.astype(np.float32)


def train_pq(residuals: np.ndarray, m: int, nbits: int, seed: int = 0,
             iters: int = 8, max_train_rows: int = 32768) -> np.ndarray:
    """Per-subspace codebooks ``(m, 2^nbits, D/m)`` trained on the residual
    rows (subsampled to ``max_train_rows`` — codebook quality saturates well
    below full corpus size, build time does not)."""
    n, d = residuals.shape
    assert d % m == 0, (d, m)
    rng = np.random.default_rng(seed)
    if n > max_train_rows:
        residuals = residuals[rng.choice(n, size=max_train_rows,
                                         replace=False)]
    sub = residuals.reshape(len(residuals), m, d // m)
    return np.stack([_kmeans_subspace(sub[:, j], 2 ** nbits, seed + j, iters)
                     for j in range(m)])


def encode_pq(residuals: np.ndarray, codebooks: np.ndarray) -> np.ndarray:
    """Nearest-centroid code per subspace: ``(N, D)`` residuals ->
    ``(N, m)`` uint8 codes (values < 2^nbits)."""
    n, d = residuals.shape
    m, k, dsub = codebooks.shape
    sub = residuals.reshape(n, m, dsub)
    codes = np.empty((n, m), np.uint8)
    for j in range(m):
        d2 = (np.square(sub[:, j]).sum(1, keepdims=True)
              - 2.0 * (sub[:, j] @ codebooks[j].T)
              + np.square(codebooks[j]).sum(1))
        codes[:, j] = np.argmin(d2, axis=1)
    return codes


def decode_pq(codes: np.ndarray, codebooks: np.ndarray) -> np.ndarray:
    """Reconstruct residuals from codes: ``(N, m)`` -> ``(N, D)``.  The ADC
    identity (score == dot against the reconstruction) makes this the oracle
    twin of every LUT-gather scoring path."""
    n, m = codes.shape
    return np.stack([codebooks[j, codes[:, j]] for j in range(m)],
                    axis=1).reshape(n, -1)


def pack_codes(codes: np.ndarray, nbits: int) -> np.ndarray:
    """``(N, m)`` codes -> packed ``(N, m*nbits/8)`` uint8.  nbits=8 is the
    identity; nbits=4 packs code pairs as ``lo | hi<<4`` (m must be even)."""
    if nbits == 8:
        # serving hits this only via fused_state's cached delta assembly
        # repro: allow-host: encode-time packing, amortized across queries
        return np.ascontiguousarray(codes, np.uint8)
    if nbits == 4:
        assert codes.shape[-1] % 2 == 0, codes.shape
        lo = codes[..., 0::2].astype(np.uint8)
        hi = codes[..., 1::2].astype(np.uint8)
        return (lo | (hi << 4)).astype(np.uint8)
    raise ValueError(f"nbits must be 4 or 8, got {nbits}")


def unpack_codes(packed: np.ndarray, m: int, nbits: int) -> np.ndarray:
    """Inverse of ``pack_codes`` (numpy): packed bytes -> ``(..., m)`` int32."""
    p = packed.astype(np.int32)
    if nbits == 8:
        return p
    out = np.empty(p.shape[:-1] + (m,), np.int32)
    out[..., 0::2] = p & 0xF
    out[..., 1::2] = (p >> 4) & 0xF
    return out


def unpack_codes_jnp(packed, m: int, nbits: int):
    """jnp twin of ``unpack_codes`` for the jitted/tiles/sharded ADC paths."""
    p = packed.astype(jnp.int32)
    if nbits == 8:
        return p
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    return jnp.stack([lo, hi], axis=-1).reshape(*p.shape[:-1], m)


def unpack_codes_jnp_cm(packed, m: int, nbits: int):
    """Code-major twin of ``unpack_codes_jnp``: packed ``(..., MB, L)``
    blocks (the lane-efficient layout the hot lists are stored in) ->
    ``(..., m, L)`` int32 codes.  nbits=4 interleaves the nibble pairs along
    the SUBSPACE axis, matching ``pack_codes``'s lo/hi convention."""
    p = packed.astype(jnp.int32)
    if nbits == 8:
        return p
    lo = p & 0xF                                   # subspaces 0, 2, 4, ...
    hi = (p >> 4) & 0xF                            # subspaces 1, 3, 5, ...
    inter = jnp.stack([lo, hi], axis=-2)           # (..., MB, 2, L)
    return inter.reshape(*p.shape[:-2], m, p.shape[-1])


def adc_lut(queries: np.ndarray, codebooks: np.ndarray) -> np.ndarray:
    """Per-query ADC tables: ``(Q, D)`` x ``(m, K, dsub)`` ->
    ``(Q, m, K)`` of subvector dot products."""
    q_n, d = queries.shape
    m, k, dsub = codebooks.shape
    qs = queries.reshape(q_n, m, dsub)
    return np.einsum("qmd,mkd->qmk", qs, codebooks,
                     optimize=True).astype(np.float32)

