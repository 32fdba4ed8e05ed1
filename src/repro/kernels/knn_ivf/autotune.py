"""Measured autotuning of the retrieval kernels' tile/block constants.

The kernels ship hand-picked defaults — the platform's ``lane_pad`` list
padding in the builders (`ops.default_lane_pad`), ``block_q=32`` query
tiles in the tiles/Pallas plan, a single-chunk fused ADC scan — that were
chosen for one machine and one
shape.  This module replaces them with *measured* choices: each candidate
constant is timed on the caller's real index and query shapes, and the
compiled HLO's roofline terms (FLOPs / bytes-accessed from
`repro.launch.hlo_analysis.cost_summary`, normalized by the
`repro.launch.mesh` peak-FLOP/HBM numbers) are recorded alongside so a
reader can see WHY a candidate won (compute- vs memory-bound) without
re-running the sweep.  Wall-clock decides; the roofline terms are the
explanation, not the decider — on CPU interpret-mode shapes the analytical
model and the measured ranking can disagree, and the measurement is ground
truth.

The chosen constants ride in `DispatchPolicy.tiles` (per index kind), are
persisted with the router artifact, and are consumed by
`KNNRouter._neighbors` (``block_q``), `KNNRouter._fused_search`
(``probe_chunk``), and `KNNRouter._index_build_kw` (``lane_pad`` — so
streaming re-clusters rebuild with the tuned padding).

Tuned knobs:

  * ``block_q``     query-tile height of the tiles/Pallas staged plan
                    (`_sorted_tile_plan`): taller tiles amortize slot
                    gathers, shorter tiles keep the per-tile probe union —
                    and with it the gathered working set — small.
  * ``probe_chunk`` fused ADC scan chunking (`_adc_probe_scan`): how many
                    probed lists' codes are unpacked per fused loop nest
                    (the codes-per-block granularity bounding the
                    ``(Q, pc, L, m)`` temporary).
  * ``lane_pad``    builder list padding: 8 keeps CPU/interpret indexes
                    compact, 128 lane-aligns lists for compiled TPU runs —
                    measured on a subsampled build per candidate because a
                    full re-build per candidate would cost a k-means each.
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from repro.launch import hlo_analysis
from repro.launch.mesh import HBM_BW, PEAK_FLOPS_BF16
from . import ops


def _p50(fn, repeats: int) -> float:
    """Median wall seconds per call, jit cache warmed, result blocked on."""
    import jax
    jax.block_until_ready(fn())
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.percentile(times, 50))


def roofline_terms(jitted, *args, **kwargs) -> Dict[str, float]:
    """Compile ``jitted`` (a ``jax.jit`` object) on the given arguments and
    summarize the compiled computation against the hardware roofline:
    FLOPs / bytes-accessed from the compiled cost analysis, the peak-bound
    time each implies, and which term dominates.  Returns ``{}`` when the
    backend exposes no cost analysis (the sweep still ranks by time)."""
    try:
        cost = hlo_analysis.cost_summary(
            jitted.lower(*args, **kwargs).compile())
    except Exception:
        return {}
    t_c = cost["flops"] / PEAK_FLOPS_BF16
    t_m = cost["bytes"] / HBM_BW
    return {"flops": cost["flops"], "bytes": cost["bytes"],
            "t_compute_s": t_c, "t_memory_s": t_m,
            "bound": "memory" if t_m >= t_c else "compute"}


def _staged_candidate(index, queries, k: int, nprobe: int, rerank: int,
                      block_q: int):
    """(timed-callable, roofline-terms) for one staged ``block_q`` candidate
    — the roofline is taken from the device-side tail the plan feeds
    (`_staged_tail` / `_score_tiles`), the timing from the full public entry
    including the host tile planning the candidate changes."""
    pq = isinstance(index, ops.IVFPQIndex)
    topk = ops.ivfpq_topk if pq else ops.ivf_topk
    kw = {"rerank": rerank} if pq else {}

    def run():
        return topk(queries, index, k, nprobe=nprobe, backend="tiles",
                    block_q=block_q, **kw)

    q_probe = np.asarray(ops.ivf_probe(queries, index.centroids, nprobe))
    q_sorted, qp_sorted, tile_probe, tile_valid, inv_order, bq = \
        ops._sorted_tile_plan(queries, q_probe, block_q)
    kc = min(k, index.n_rows, nprobe * index.list_size)
    if pq:
        kk = min(max(rerank, 1) * kc, index.n_rows,
                 nprobe * index.list_size)
        terms = roofline_terms(
            ops._staged_tail, queries, q_sorted, jnp.asarray(qp_sorted),
            jnp.asarray(tile_probe), jnp.asarray(tile_valid),
            jnp.asarray(inv_order), index.codes_cm, index.ids_cm,
            index.inv_cm, index.anchors, index.codebooks, index.sup_flat,
            k=kc, kk=kk, bq=bq, m=index.m, nbits=index.nbits,
            rerank=bool(rerank), backend="tiles")
    else:
        terms = roofline_terms(
            ops._score_tiles, q_sorted, jnp.asarray(qp_sorted),
            jnp.asarray(tile_probe), jnp.asarray(tile_valid), index.sup_cm,
            index.ids_cm, index.inv_cm, k=kc, bq=bq)
    return run, terms


def _fused_candidate(index, queries, k: int, nprobe: int, rerank: int,
                     pc: int):
    """(timed-callable, roofline-terms) for one fused ``probe_chunk``
    candidate (IVF-PQ only — the raw-IVF fused scan has no code unpack to
    chunk)."""
    cand = nprobe * index.list_size
    kc = min(k, index.n_rows, cand)
    kk = min(max(rerank, 1) * kc, index.n_rows, cand) if rerank else 0

    def run():
        return ops._fused_ivfpq_topk(
            queries, index.centroids, index.codes_rm, index.ids_cm,
            index.inv_cm, index.anchors, index.codebooks, index.sup_flat,
            index.inv_flat, k=kc, kk=kk, nprobe=nprobe, m=index.m,
            nbits=index.nbits, pc=pc)

    terms = roofline_terms(
        ops._fused_ivfpq_topk, queries, index.centroids, index.codes_rm,
        index.ids_cm, index.inv_cm, index.anchors, index.codebooks,
        index.sup_flat, index.inv_flat, k=kc, kk=kk, nprobe=nprobe,
        m=index.m, nbits=index.nbits, pc=pc)
    return run, terms


def _sweep(make_candidate, candidates: Sequence[int], repeats: int) -> dict:
    detail = {}
    for c in candidates:
        run, terms = make_candidate(c)
        detail[int(c)] = {"p50_s": round(_p50(run, repeats), 6), **{
            k: (round(v, 9) if isinstance(v, float) else v)
            for k, v in terms.items()}}
    best = min(detail, key=lambda c: detail[c]["p50_s"])
    return {"chosen": int(best), "candidates": detail}


def autotune_tiles(index, queries, k: int, *,
                   nprobe: int = ops.DEFAULT_NPROBE,
                   rerank: int = ops.DEFAULT_RERANK,
                   block_qs: Sequence[int] = (8, 16, 32, 64),
                   probe_chunks: Sequence[int] = (0, 2, 4),
                   repeats: int = 5) -> dict:
    """Tune the per-index-kind kernel constants on a real (index, queries)
    pair.  Returns ``{"block_q": .., "probe_chunk": .., "sweep": {...}}`` —
    the flat chosen values feed `DispatchPolicy.tiles`, the ``sweep``
    detail (per-candidate p50 + roofline terms) goes to the bench JSON."""
    queries = jnp.asarray(queries)
    if isinstance(index, ops.DynamicIVFIndex):
        index = index.base
    out: Dict = {"sweep": {}}
    bq = _sweep(lambda c: _staged_candidate(index, queries, k, nprobe,
                                            rerank, c), block_qs, repeats)
    out["block_q"] = bq["chosen"]
    out["sweep"]["block_q"] = bq["candidates"]
    if isinstance(index, ops.IVFPQIndex):
        pcs = [p for p in probe_chunks if p == 0 or p < nprobe]
        pc = _sweep(lambda c: _fused_candidate(index, queries, k, nprobe,
                                               rerank, c), pcs, repeats)
        out["probe_chunk"] = pc["chosen"]
        out["sweep"]["probe_chunk"] = pc["candidates"]
    return out


def autotune_lane_pad(support, queries, k: int, *, pq: bool,
                      m: Optional[int] = None, nbits: int = 8,
                      nprobe: int = ops.DEFAULT_NPROBE,
                      rerank: int = ops.DEFAULT_RERANK,
                      candidates: Sequence[int] = (8, 128),
                      sample: int = 20_000, seed: int = 0,
                      repeats: int = 3) -> dict:
    """Tune the builder's list padding by building each candidate on a
    subsample (a full-corpus build per candidate would pay a k-means each)
    and timing the fused search over it.  The winner feeds
    `DispatchPolicy.tiles[index]["lane_pad"]`, which
    `KNNRouter._index_build_kw` replays into streaming re-clusters."""
    sup = np.asarray(support, np.float32)[:sample]
    queries = jnp.asarray(queries)
    detail = {}
    for lp in candidates:
        if pq:
            idx = ops.build_ivfpq_index(sup, m=m, nbits=nbits, seed=seed,
                                        lane_pad=lp)
            run = lambda: ops.ivfpq_topk(queries, idx, k, nprobe=nprobe,
                                         rerank=rerank, backend="fused")
        else:
            idx = ops.build_ivf_index(sup, seed=seed, lane_pad=lp)
            run = lambda: ops.ivf_topk(queries, idx, k, nprobe=nprobe,
                                       backend="fused")
        detail[int(lp)] = {"p50_s": round(_p50(run, repeats), 6),
                           "list_size": int(idx.list_size)}
    best = min(detail, key=lambda c: detail[c]["p50_s"])
    return {"chosen": int(best), "candidates": detail}


def autotune_router(router, queries, *, repeats: int = 5,
                    block_qs: Sequence[int] = (8, 16, 32, 64),
                    probe_chunks: Sequence[int] = (0, 2, 4)) -> dict:
    """`autotune_tiles` over a fitted `KNNRouter`'s own index and operating
    point (k / nprobe / rerank), queries L2-normalized the way the serving
    path would.  Returns ``{}`` for ``index="exact"`` (no tiled plan)."""
    if getattr(router, "index", "exact") == "exact":
        return {}
    q = np.asarray(queries, np.float32)
    q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    return autotune_tiles(router._ivf, q, router.k, nprobe=router.nprobe,
                          rerank=router.rerank, block_qs=block_qs,
                          probe_chunks=probe_chunks, repeats=repeats)
