"""Public jit'd wrapper for kNN top-k retrieval.

Dispatches to the Pallas kernel (interpret-mode on CPU, compiled on TPU) or
the pure-jnp reference.  Handles padding to block multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import knn_topk_pallas
from .ref import knn_topk_reference


@functools.partial(jax.jit, static_argnames=("k", "use_pallas"))
def knn_topk(queries, support, k: int, *, use_pallas: bool = False):
    """queries (Q, D) L2-normalized; support (N, D) raw.
    Returns (scores (Q, k), indices (Q, k)); indices of padded rows never
    appear because padded support rows get -inf similarity."""
    Q, D = queries.shape
    N, _ = support.shape
    k = min(k, N)
    if not use_pallas:
        return knn_topk_reference(queries, support, k)

    bq = min(128, Q)
    bn = min(1024, N)
    pq = (-Q) % bq
    pn = (-N) % bn
    qp = jnp.pad(queries, ((0, pq), (0, 0)))
    # pad support with zero rows -> similarity 0; push them to the bottom by
    # padding with a large-negative direction instead: easier to mask after.
    sp = jnp.pad(support, ((0, pn), (0, 0)))
    scores, idx = knn_topk_pallas(qp, sp, k, block_q=bq, block_n=bn)
    scores, idx = scores[:Q], idx[:Q]
    if pn:
        valid = idx < N
        scores = jnp.where(valid, scores, -jnp.inf)
        # re-rank so padded hits (if any) fall to the end
        order = jnp.argsort(-scores, axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
        idx = jnp.take_along_axis(idx, order, axis=1)
    return scores, idx
