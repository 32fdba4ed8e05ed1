"""The paper's protagonist: k-Nearest-Neighbour router (§5, C.2).

Utility prediction:  s_hat(x,m) = mean over k nearest support rows of s(xi,m)
(optionally similarity-softmax weighted); identically for costs.
Model selection:     majority vote among the neighbours' utility-optimal
models at the given lambda.

Retrieval backends (``index=``):

  * ``"exact"`` — brute-force fused Pallas kNN (`repro.kernels.knn_topk`),
    interpret-mode on CPU, compiled on TPU; O(N*D) per query.
  * ``"ivf"``  — inverted-file approximate kNN (`repro.kernels.knn_ivf`):
    a spherical k-means coarse quantizer fit once at ``fit`` time, queries
    probe only their ``nprobe`` nearest cluster lists; O(nprobe * N/C * D)
    per query, sub-linear in the support size.
  * ``"ivfpq"`` — product-quantized IVF: the probed lists store packed
    ``m``-byte PQ codes instead of raw rows (~16x less hot HBM at m=D/8),
    scored by ADC table gathers; an ADC shortlist of ``rerank * k``
    candidates is then re-scored exactly against the raw rows, restoring
    near-exact recall.  ``m=None`` auto-picks ~D/8 (clamped to a divisor
    of D at fit time).

When a mesh is supplied, all backends go through their mesh-sharded
variants in `repro.core.sharded_knn` (support rows / cluster lists sharded
across every device, per-device top-k merged with one tiny all-gather).

Execution backends (``backend=``, default per index): IVF-PQ serves through
the FUSED single-dispatch path (probe + ADC + shortlist + exact re-rank in
one jitted call), raw IVF through the host inverted traversal whose
read-each-list-once BLAS is its fastest CPU operating point; ``host`` /
``tiles`` / ``pallas`` stay addressable for debugging and TPU runs.
A fitted `DispatchPolicy` (``router.dispatch_policy``, persisted with the
artifact and fitted by ``benchmarks/serving_latency.py``) overrides the
static default PER BATCH on the serving path: `resolve_backend` looks up
the measured-fastest backend for (index kind, batch size, delta fraction),
so e.g. a batch of one can take the staged host path while a 64-wave takes
the fused one.  An explicit ``backend=`` always wins over the policy.

Streaming updates: ``partial_fit(X, scores, costs)`` appends observations to
the support arrays — for a non-parametric router that IS the whole training
step.  With an approximate backend the rows also land in a
`DynamicIVFIndex` delta tier (probed per-centroid sub-lists on the fused
backend, exact-scanned on the staged ones) that is compacted by a full
re-cluster once it exceeds ``delta_cap`` — synchronously, or on a
background thread (``recluster="background"``) with an atomic index swap;
``online=True`` (spec ``@online=1,delta_cap=..``) wraps the index at fit
time, otherwise the wrap happens lazily on the first ``partial_fit``.

``predict_utility`` / ``select`` / ``confidence`` semantics are identical
across backends: approximate retrieval can return fewer than k valid
neighbours on pathological probe sets (index -1 slots), which are excluded
from averages and votes.  ``predict_with_confidence`` fuses utility
prediction and the §8 confidence diagnostics over ONE retrieval;
``serve_fused`` goes further and runs retrieval, utility, confidence, AND
the per-request-lambda selection in ONE device dispatch — the serving
layer's hot path (`RouterService.route_fused`), bit-identical to the
staged calls because both share the same jitted kernels.
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.knn_ivf.ops import (DEFAULT_DELTA_CAP, DEFAULT_NPROBE,
                                       DEFAULT_RERANK, DynamicIVFIndex,
                                       _fused_dyn_ivf_topk_impl,
                                       _fused_dyn_ivfpq_topk_impl,
                                       _fused_ivf_topk_impl,
                                       _fused_ivfpq_topk_impl,
                                       build_ivf_index, build_ivfpq_index,
                                       ivf_topk, ivfpq_topk)
from repro.kernels.knn_topk.ops import knn_topk
from repro.spans import span
from ..dataset import RoutingDataset
from .base import Router, gold_labels, normalize_rows
from .spec import register

_INDEXES = ("exact", "ivf", "ivfpq")
_BACKENDS = (None, "fused", "host", "tiles", "pallas")


# ---------------------------------------------------------------------------
# jitted neighbour->decision kernels, shared by the legacy multi-dispatch
# path and the fused single-dispatch serving path so both produce BITWISE
# identical numbers (the fused path calls these as inner jits, which XLA
# keeps as preserved subcomputations)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("weights", "temperature"))
def _utility_jit(sims, idx, S, C, *, weights: str, temperature: float):
    """Neighbour-weighted utility/cost estimates from one retrieval's
    (sims, idx) — the jnp twin of the old numpy `_utility_from`."""
    valid = idx >= 0
    safe = jnp.maximum(idx, 0)
    s_nb = jnp.take(S, safe, axis=0)                         # (Q, k, M)
    c_nb = jnp.take(C, safe, axis=0)
    if weights == "softmax":
        fin = jnp.where(valid, sims, -jnp.inf)
        mx = jnp.max(fin, axis=1, keepdims=True)
        mx = jnp.where(jnp.isfinite(mx), mx, 0.0)            # all-invalid
        w = jnp.exp(temperature * (fin - mx))
        w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-12)
    else:
        w = valid / jnp.maximum(valid.sum(axis=1, keepdims=True), 1)
    s_hat = jnp.einsum("qk,qkm->qm", w.astype(jnp.float32), s_nb,
                       preferred_element_type=jnp.float32)
    c_hat = jnp.einsum("qk,qkm->qm", w.astype(jnp.float32), c_nb,
                       preferred_element_type=jnp.float32)
    return s_hat, c_hat


@jax.jit
def _confidence_jit(sims, idx, S):
    """(kth_sim, neighbour_agreement) from one retrieval's results — the
    jnp twin of the old numpy `_confidence_from` (agreement = mode fraction
    of the neighbours' best-model votes among valid neighbours).

    The k-th similarity is taken as a row MIN, not ``sims[:, -1]``:
    retrieval scores arrive sorted descending so the two are bit-identical,
    but when this kernel is inlined into the fused serving jit a SLICE of a
    `lax.top_k` output defeats XLA:CPU's TopK rewrite (the algebraic
    simplifier merges slice-of-slice and the pattern no longer matches),
    silently demoting the whole shortlist selection to a generic variadic
    sort — a ~20x regression on the hot path."""
    kth = jnp.min(sims, axis=1)
    valid = idx >= 0
    best = jnp.argmax(jnp.take(S, jnp.maximum(idx, 0), axis=0), axis=2)
    counts = jnp.sum((best[..., None] == jnp.arange(S.shape[1]))
                     & valid[..., None], axis=1)             # (Q, M)
    agree = (counts.max(axis=1).astype(jnp.float32)
             / jnp.maximum(valid.sum(axis=1), 1).astype(jnp.float32))
    return kth, agree


@jax.jit
def _select_jit(s_hat, c_hat, lam, avail):
    """Per-request-lambda utility argmax — the single decision kernel every
    routing path (legacy batched serving and the fused path) shares.

    ``avail`` is the per-model availability mask (bool, (M,)): models whose
    circuit breaker is open score -inf in the argmax, so routing around an
    outage happens INSIDE the fused dispatch.  With an all-ones mask the
    `where` selects ``util`` verbatim — bitwise identical to the unmasked
    kernel, which is what the parity suites pin.  The returned utilities
    are unmasked (callers report the true estimates for every model)."""
    util = s_hat - lam[:, None] * c_hat
    masked = jnp.where(avail[None, :], util, -jnp.inf)
    return jnp.argmax(masked, axis=1), util


@functools.partial(jax.jit, static_argnames=("weights", "temperature"))
def _serve_tail_jit(sims, idx, S, C, lam, avail, *, weights: str,
                    temperature: float):
    """Retrieval results -> (choice, s_hat, c_hat, kth, agree) in ONE
    dispatch: utility, confidence, and per-request-lambda availability-
    masked selection fused.  The inner calls are the same jitted kernels
    the legacy path runs separately, preserved as subcomputations —
    identical numerics, one device sync instead of three.

    The five answers leave as ONE float32 ``(Q, 3 + 2M)`` buffer, so the
    host copies it back once: column 0 is ``choice``'s int32 bits
    (bit-cast, not converted), then ``s_hat`` and ``c_hat`` (M columns
    each), then ``kth`` and ``agree``.  `_unpack_route` splits it."""
    s_hat, c_hat = _utility_jit(sims, idx, S, C, weights=weights,
                                temperature=temperature)
    kth, agree = _confidence_jit(sims, idx, S)
    choice, _ = _select_jit(s_hat, c_hat, lam, avail)
    bits = jax.lax.bitcast_convert_type(choice, jnp.float32)
    return jnp.concatenate([bits[:, None], s_hat, c_hat, kth[:, None],
                            agree[:, None]], axis=1)


def _unpack_route(buf: np.ndarray, rows: int):
    """(choice, s_hat, c_hat, kth, agree) as numpy views of the first
    ``rows`` rows of `_serve_tail_jit`'s packed buffer (the sharded branch
    pads the batch)."""
    buf = buf[:rows]
    m = (buf.shape[1] - 3) // 2
    return (buf[:, 0].view(np.int32), buf[:, 1:1 + m],
            buf[:, 1 + m:1 + 2 * m], buf[:, -2], buf[:, -1])


@functools.partial(jax.jit, static_argnames=("search", "weights",
                                             "temperature"))
def _serve_fused_jit(queries, lam, avail, S, C, *search_args, search,
                     weights: str, temperature: float):
    """The whole routed batch in ONE device dispatch: retrieval (the
    jitted single-dispatch search this router's index supports), neighbour-
    weighted utility, confidence diagnostics, and per-request-lambda
    availability-masked selection.  ``search`` is a cached
    `functools.partial` of a module-level jitted search (static by
    identity, so the jit cache is stable across calls).  Returns
    `_serve_tail_jit`'s packed buffer."""
    sims, idx = search(queries, *search_args)
    return _serve_tail_jit(sims, idx, S, C, lam, avail, weights=weights,
                           temperature=temperature)


@register("knn", k_param="k", default_ks=(10, 100), supports_ivf=True,
          paper_rank=0)
class KNNRouter(Router):
    is_parametric = False
    state_attrs = ("_X", "_S", "_C", "_ivf", "_train_best", "_sel_lam")

    def __init__(self, k: int = 100, weights: str = "uniform",
                 use_pallas: bool = False, temperature: float = 20.0,
                 mesh=None, index: str = "exact",
                 n_clusters: int | None = None,
                 nprobe: int = DEFAULT_NPROBE,
                 m: int | None = None, nbits: int = 8,
                 rerank: int = DEFAULT_RERANK,
                 online: bool = False, delta_cap: int = DEFAULT_DELTA_CAP,
                 backend: str | None = None):
        if index not in _INDEXES:
            raise ValueError(f"index must be one of {_INDEXES}, "
                             f"got {index!r}")
        if backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, "
                             f"got {backend!r}")
        self.k = k
        self.weights = weights
        self.use_pallas = use_pallas
        self.temperature = temperature
        self.mesh = mesh
        self.index = index
        self.n_clusters = n_clusters
        self.nprobe = nprobe
        self.m = m
        self.nbits = nbits
        self.rerank = rerank
        self.online = bool(online)
        self.delta_cap = int(delta_cap)
        self.backend = backend
        #: degradation state (set by the `degraded` context manager for the
        #: duration of one wave): serve from the compacted base only,
        #: giving up rows still in the streaming delta tier
        self._skip_delta = False
        #: fitted `DispatchPolicy` (or None = static defaults) — set by the
        #: serving benchmark / artifact load, not a constructor parameter,
        #: so spec strings and ``router_config`` stay policy-free
        self.dispatch_policy = None
        self._dev = {}           # device-resident (S, C) + serve-path cache
        suffix = {"exact": "", "ivf": " IVF", "ivfpq": " IVF-PQ"}[index]
        self.name = f"kNN (k={k}){suffix}"

    @property
    def exec_backend(self) -> str:
        """Execution backend of the approximate tiers.  Explicit ``backend``
        wins; ``use_pallas`` selects the kernel; otherwise IVF-PQ defaults
        to the fused single-dispatch path (its host traversal is the
        reference/debug fallback) while raw IVF keeps the host inverted
        traversal, whose read-each-list-once BLAS is the faster operating
        point for raw float lists."""
        if self.backend is not None:
            return self.backend
        if self.use_pallas:
            return "pallas"
        return "fused" if self.index == "ivfpq" else "host"

    # ---- measured dispatch policy ----
    def _policy_tiles(self) -> dict:
        """Autotuned kernel constants for this index kind from the fitted
        dispatch policy ({} when no policy / nothing tuned)."""
        pol = getattr(self, "dispatch_policy", None)
        return pol.tiles_for(self.index) if pol is not None else {}

    def _delta_frac(self) -> float:
        """Fraction of served rows currently in the streaming delta tier —
        the policy table's third axis (probed delta sub-lists shift the
        fused/staged crossover)."""
        ivf = getattr(self, "_ivf", None)
        if isinstance(ivf, DynamicIVFIndex) and ivf.n_rows:
            return ivf.delta_rows / ivf.n_rows
        return 0.0

    def resolve_backend(self, n_queries: int | None = None) -> str:
        """Effective serving backend for a batch of ``n_queries``: an
        explicit ``backend=`` always wins, then ``use_pallas``, then the
        fitted `DispatchPolicy` cell for (index, batch, delta fraction),
        then the static per-index default (`exec_backend`, with the exact
        scan defaulting to its in-jit fused search)."""
        if self.backend is not None:
            return self.backend
        if self.use_pallas:
            return "pallas"
        pol = getattr(self, "dispatch_policy", None)
        if pol is not None and n_queries:
            be = pol.exec_backend_for(self.index, int(n_queries),
                                      self._delta_frac())
            if be is not None:
                return be
        return "fused" if self.index in ("ivfpq", "exact") else "host"

    def join_recluster(self) -> None:
        """Block until any in-flight background index compaction has swapped
        in (no-op otherwise) — the teardown hook `RouterService.close` calls
        so process exit cannot race a daemon-thread rebuild."""
        ivf = getattr(self, "_ivf", None)
        if isinstance(ivf, DynamicIVFIndex):
            ivf.join_recluster()

    def set_recluster_hook(self, fn) -> None:
        """Register ``fn()`` to run after every index compaction swap (the
        durability layer's checkpoint trigger).  Attached to the live
        `DynamicIVFIndex` now and re-attached when `partial_fit` wraps a
        frozen index lazily; survives compaction swaps (the wrapper object
        is stable).  The callback contract is the index's: flag-setting
        only, it may run on the background rebuild thread."""
        self._recluster_hook = fn
        ivf = getattr(self, "_ivf", None)
        if isinstance(ivf, DynamicIVFIndex):
            ivf.on_recluster = fn

    # ---- deadline-driven graceful degradation ----
    @contextlib.contextmanager
    def degraded(self, level=None):
        """Serve the enclosed wave at a degradation level: any object with
        ``nprobe_scale`` / ``rerank`` / ``skip_delta`` attributes (see
        `repro.serving.faults.DegradationLevel`; duck-typed so the core
        router never imports the serving layer).  Overrides are restored on
        exit.  ``None`` or level 0 is a no-op — the hot path stays
        untouched.  Not re-entrant across threads: the serving loop applies
        it from the single routing thread."""
        if level is None or not (level.nprobe_scale != 1.0
                                 or level.rerank is not None
                                 or level.skip_delta):
            yield
            return
        saved = (self.nprobe, self.rerank, self._skip_delta)
        try:
            self.nprobe = max(1, int(round(self.nprobe
                                           * level.nprobe_scale)))
            if level.rerank is not None:
                self.rerank = int(level.rerank)
            self._skip_delta = bool(level.skip_delta)
            yield
        finally:
            self.nprobe, self.rerank, self._skip_delta = saved

    # ---- fit = store the support set (+ coarse quantizer / PQ codebooks) --
    def _index_build_kw(self, seed: int) -> dict:
        """Builder kwargs a `DynamicIVFIndex` re-cluster must replay so the
        compacted index equals a from-scratch build bitwise."""
        kw = {"n_clusters": self.n_clusters, "seed": seed}
        if self.index == "ivfpq":
            kw.update(m=self.m, nbits=self.nbits)
        lp = self._policy_tiles().get("lane_pad")
        if lp:
            kw["lane_pad"] = int(lp)
        return kw

    def fit(self, ds: RoutingDataset, seed: int = 0) -> "KNNRouter":
        self._record_fit(ds, seed)
        self._dev = {}
        X, S, C = ds.part("train")
        self._X = normalize_rows(X)
        self._S = S.astype(np.float32)
        self._C = C.astype(np.float32)
        # a policy-tuned lane_pad applies at build time too, so a streaming
        # re-cluster (which replays _index_build_kw) stays bitwise-equal to
        # this fresh build
        lp = self._policy_tiles().get("lane_pad")
        lane = {"lane_pad": int(lp)} if lp else {}
        if self.index == "ivf":
            self._ivf = build_ivf_index(self._X, self.n_clusters, seed=seed,
                                        **lane)
        elif self.index == "ivfpq":
            self._ivf = build_ivfpq_index(self._X, self.n_clusters,
                                          m=self.m, nbits=self.nbits,
                                          seed=seed, **lane)
        if self.online and self.index != "exact":
            self._ivf = DynamicIVFIndex(self._ivf, delta_cap=self.delta_cap,
                                        build_kw=self._index_build_kw(seed))
            self._ivf.on_recluster = getattr(self, "_recluster_hook", None)
        return self

    # ---- streaming updates: appending a row IS the whole training step ----
    def partial_fit(self, X: np.ndarray, scores: np.ndarray,
                    costs: np.ndarray | None = None,
                    recluster="auto") -> "KNNRouter":
        """Absorb new (embedding, per-model score/cost) observations without
        refitting: rows are appended to the support arrays and — for the
        approximate backends — to the index's exact-scanned delta tier, so
        the very next query can retrieve them.  ``costs`` defaults to zero
        (pure-quality feedback).

        ``recluster``: ``"auto"`` (default) compacts the index once the
        delta tier exceeds ``delta_cap`` — the amortized policy; ``False``
        never compacts (callers control timing); ``True`` forces a compaction
        now; ``"background"`` is the serving policy — same trigger as
        ``"auto"`` but the k-means rebuild runs on a daemon thread with an
        atomic index swap, so this call (and every query meanwhile) returns
        without waiting on it.  A non-online approximate index is wrapped
        into a `DynamicIVFIndex` lazily on the first call."""
        if getattr(self, "_S", None) is None:
            raise RuntimeError("KNNRouter.partial_fit() called before fit(); "
                               "the streaming step appends to a fitted "
                               "support set")
        X = np.atleast_2d(np.asarray(X, np.float32))
        S = np.atleast_2d(np.asarray(scores, np.float32))
        M = self._S.shape[1]
        if S.shape != (len(X), M):
            raise ValueError(f"scores must have shape ({len(X)}, {M}) to "
                             f"match the fitted model axis, got {S.shape}")
        if costs is None:
            C = np.zeros_like(S)
        else:
            C = np.atleast_2d(np.asarray(costs, np.float32))
            if C.shape != S.shape:
                raise ValueError(f"costs must match scores shape {S.shape}, "
                                 f"got {C.shape}")
        Xn = normalize_rows(X)
        self._X = np.concatenate([self._X, Xn])
        self._S = np.concatenate([self._S, S])
        self._C = np.concatenate([self._C, C])
        self._dev = {}
        if getattr(self, "_train_best", None) is not None:
            # keep the selection vote consistent: extend the gold labels at
            # the lambda fit_selection derived them with
            lam = self._sel_lam if self._sel_lam is not None else 0.0
            self._train_best = np.concatenate(
                [self._train_best, gold_labels(S, C, lam)])
        if self.index != "exact":
            if not isinstance(self._ivf, DynamicIVFIndex):
                self._ivf = DynamicIVFIndex(
                    self._ivf, delta_cap=self.delta_cap,
                    build_kw=self._index_build_kw(self.fit_seed or 0))
                self._ivf.on_recluster = getattr(self, "_recluster_hook",
                                                 None)
            self._ivf.append(Xn)
            if recluster is True:
                self._ivf.recluster()
            elif recluster == "auto":
                self._ivf.maybe_recluster()
            elif recluster == "background":
                self._ivf.maybe_recluster(sync=False)
        return self

    @property
    def support_size(self) -> int:
        """Rows currently backing retrieval (grows under partial_fit)."""
        return 0 if getattr(self, "_S", None) is None else len(self._S)

    def _neighbors(self, X: np.ndarray, backend: str | None = None):
        """One retrieval pass.  ``backend`` overrides the static
        `exec_backend` for this call (the serving path passes the policy-
        resolved backend through here); the tiles/pallas plans additionally
        pick up an autotuned ``block_q`` from the policy."""
        q = normalize_rows(X)
        k = min(self.k, len(self._X))
        be = backend or self.exec_backend
        kw = {}
        bq = self._policy_tiles().get("block_q")
        if bq and be in ("tiles", "pallas"):
            kw["block_q"] = int(bq)
        ivf = getattr(self, "_ivf", None)
        if self._skip_delta and isinstance(ivf, DynamicIVFIndex):
            # degraded wave: serve the compacted base only (give up delta
            # rows instead of paying the merge under deadline pressure)
            with ivf._lock:
                ivf = ivf.base
        if self.index == "ivfpq":
            if self.mesh is not None:
                from ..sharded_knn import sharded_ivfpq_topk
                sims, idx = sharded_ivfpq_topk(jnp.asarray(q), ivf, k,
                                               self.mesh, nprobe=self.nprobe,
                                               rerank=self.rerank)
            else:
                sims, idx = ivfpq_topk(jnp.asarray(q), ivf, k,
                                       nprobe=self.nprobe,
                                       rerank=self.rerank,
                                       backend=be, **kw)
        elif self.index == "ivf":
            if self.mesh is not None:
                from ..sharded_knn import sharded_ivf_topk
                sims, idx = sharded_ivf_topk(jnp.asarray(q), ivf, k,
                                             self.mesh, nprobe=self.nprobe)
            else:
                sims, idx = ivf_topk(jnp.asarray(q), ivf, k,
                                     nprobe=self.nprobe,
                                     backend=be, **kw)
        elif self.mesh is not None:
            from ..sharded_knn import sharded_knn_topk
            sims, idx = sharded_knn_topk(jnp.asarray(q), jnp.asarray(self._X),
                                         k, self.mesh)
        else:
            sims, idx = knn_topk(jnp.asarray(q), jnp.asarray(self._X), k,
                                 use_pallas=self.use_pallas)
        # repro: allow-host: _neighbors returns numpy by API contract
        return np.asarray(sims), np.asarray(idx)

    # ---- utility ----
    def _SC_dev(self):
        """Device-resident (S, C) support score/cost arrays, cached so the
        per-batch serving path never re-uploads them (invalidated by
        fit/partial_fit)."""
        sc = self._dev.get("SC")
        if sc is None or sc[0].shape != self._S.shape:
            sc = (jnp.asarray(self._S), jnp.asarray(self._C))
            self._dev["SC"] = sc
        return sc

    def _utility_from(self, sims: np.ndarray, idx: np.ndarray):
        """Neighbour-weighted utility/cost estimates from one retrieval —
        the same jitted kernel the fused serving path inlines."""
        S, C = self._SC_dev()
        s_hat, c_hat = _utility_jit(jnp.asarray(sims), jnp.asarray(idx), S, C,
                                    weights=self.weights,
                                    temperature=float(self.temperature))
        return np.asarray(s_hat), np.asarray(c_hat)

    def predict_utility(self, X: np.ndarray):
        sims, idx = self._neighbors(X)
        return self._utility_from(sims, idx)

    # ---- selection: neighbour majority vote ----
    def fit_selection(self, ds: RoutingDataset, lam: float, seed: int = 0):
        self.fit(ds, seed=seed)
        X, S, C = ds.part("train")
        self._sel_lam = float(lam)      # partial_fit extends the vote labels
        self._train_best = gold_labels(S, C, lam)
        return self

    def select(self, X: np.ndarray) -> np.ndarray:
        if getattr(self, "_train_best", None) is None:
            raise RuntimeError("KNNRouter.select() called before "
                               "fit_selection(); the neighbour vote needs the "
                               "training labels derived at a fixed lambda")
        _, idx = self._neighbors(X)
        valid = idx >= 0
        votes = self._train_best[np.maximum(idx, 0)]   # (Q, k)
        M = self._S.shape[1]
        counts = np.stack([((votes == m) & valid).sum(1) for m in range(M)],
                          axis=1)
        return np.argmax(counts, axis=1)

    # ---- practitioner diagnostics (§8): per-query confidence ----
    def _confidence_from(self, sims: np.ndarray, idx: np.ndarray):
        """(kth_sim, neighbour_agreement) from one retrieval's results —
        the same jitted kernel the fused serving path inlines."""
        S, _ = self._SC_dev()
        kth, agree = _confidence_jit(jnp.asarray(sims), jnp.asarray(idx), S)
        return np.asarray(kth), np.asarray(agree)

    def confidence(self, X: np.ndarray):
        """Returns (kth_sim, neighbour_agreement) per query: low kth-neighbour
        similarity => sparse coverage; low agreement => uncertainty.  With an
        IVF backend a -inf kth_sim flags a query whose probe set could not
        fill k neighbours — out-of-coverage by construction."""
        sims, idx = self._neighbors(X)
        return self._confidence_from(sims, idx)

    def predict_with_confidence(self, X: np.ndarray):
        """One retrieval feeding both outputs: (s_hat, c_hat, kth_sim,
        agreement).  Identical numbers to calling ``predict_utility`` and
        ``confidence`` separately — minus the second `_neighbors` search,
        which on the serving hot path is the whole cost of the call."""
        sims, idx = self._neighbors(X)
        s_hat, c_hat = self._utility_from(sims, idx)
        kth, agree = self._confidence_from(sims, idx)
        return s_hat, c_hat, kth, agree

    # ---- fused single-dispatch serving path ----
    def _fused_search(self, eff: str | None = None):
        """(search_partial, array_args) for the single-dispatch retrieval
        this router's configuration supports, or (None, None) when retrieval
        needs a host stage (raw-IVF host traversal, pallas tile planning, an
        index-sharding mesh).  ``eff`` is the resolved serving backend for
        the batch at hand (defaults to the static `exec_backend`, so
        non-serving callers see the old behaviour).  The partial is cached
        per static configuration so the jit cache is keyed by a stable
        object."""
        if eff is None:
            eff = self.exec_backend
        if self.mesh is not None:
            return None, None
        if self.index != "exact" and eff != "fused":
            return None, None
        if self.index == "exact":
            k = min(self.k, len(self._X))
            key = ("exact", k, self.use_pallas)
            if self._dev.get("search_key") != key:
                self._dev["search"] = functools.partial(
                    knn_topk.__wrapped__, k=k, use_pallas=self.use_pallas)
                self._dev["search_key"] = key
            Xd = self._dev.get("X")
            if Xd is None or Xd.shape != self._X.shape:
                Xd = jnp.asarray(self._X)
                self._dev["X"] = Xd
            return self._dev["search"], (Xd,)

        ivf = self._ivf
        dyn = isinstance(ivf, DynamicIVFIndex)
        if dyn:
            # snapshot (base, delta state) under the index lock so a
            # background re-cluster swap cannot pair the new base with a
            # stale delta tier (or vice versa) mid-assembly
            with ivf._lock:
                base = ivf.base
                delta = ivf.delta_rows
                st = ivf.fused_state() if delta else None
            if self._skip_delta:
                # degraded wave: serve the compacted base only (give up
                # delta rows instead of paying the probed merge under
                # deadline pressure)
                delta, st = 0, None
        else:
            base, delta, st = ivf, 0, None
        nprobe = max(1, min(self.nprobe, base.n_clusters))
        if self.index == "ivfpq":
            lc = st["dl_codes"].shape[1] if delta else 0
            cand = nprobe * (base.list_size + lc)
            n = base.n_rows + delta
            k = min(self.k, n, cand)
            kk = (min(max(self.rerank, 1) * k, n, cand)
                  if self.rerank else 0)
            pc = int(self._policy_tiles().get("probe_chunk", 0) or 0)
            key = ("ivfpq", delta > 0, k, kk, nprobe, base.m, base.nbits, lc,
                   pc)
            if self._dev.get("search_key") != key:
                fn = (_fused_dyn_ivfpq_topk_impl if delta
                      else _fused_ivfpq_topk_impl)
                self._dev["search"] = functools.partial(
                    fn, k=k, kk=kk, nprobe=nprobe, m=base.m,
                    nbits=base.nbits, pc=pc)
                self._dev["search_key"] = key
            args = (base.centroids, base.codes_rm, base.ids_cm, base.inv_cm,
                    base.anchors, base.codebooks)
            if delta:
                args += (st["dl_codes"], st["dl_ids"], st["dl_inv"],
                         st["sup_all"], st["inv_all"])
            else:
                args += (base.sup_flat, base.inv_flat)
            return self._dev["search"], args

        lc = st["dl_sup"].shape[1] if delta else 0
        k = min(self.k, base.n_rows + delta,
                nprobe * (base.list_size + lc))
        key = ("ivf", delta > 0, k, nprobe, lc)
        if self._dev.get("search_key") != key:
            fn = _fused_dyn_ivf_topk_impl if delta else _fused_ivf_topk_impl
            self._dev["search"] = functools.partial(fn, k=k, nprobe=nprobe)
            self._dev["search_key"] = key
        args = (base.centroids, base.sup_cm, base.ids_cm, base.inv_cm)
        if delta:
            args += (st["dl_sup"], st["dl_ids"], st["dl_inv"])
        return self._dev["search"], args

    def _avail_dev(self, avail=None):
        """Device-resident per-model availability mask (bool, (M,)) for the
        fused selection.  ``None`` means every model is up — the all-ones
        mask is cached once per model-axis width, and `_select_jit`'s
        ``where`` passes utilities through verbatim, so the default path is
        bitwise identical to the pre-mask kernel.  Explicit masks are cached
        by content so a stable outage pattern keeps a stable device array
        (no re-upload per wave, and `_serve_sharded`'s identity-keyed
        replication cache keeps hitting)."""
        M = self._S.shape[1]
        if avail is None:
            ones = self._dev.get("avail_ones")
            if ones is None or ones.shape != (M,):
                ones = jnp.ones((M,), jnp.bool_)
                self._dev["avail_ones"] = ones
            return ones
        # repro: allow-host: availability arrives as host health metadata
        a = np.asarray(avail, dtype=bool).reshape(-1)
        if a.shape != (M,):
            raise ValueError(f"availability mask must have shape ({M},) to "
                             f"match the model axis, got {a.shape}")
        if not a.any():
            raise ValueError("availability mask excludes every model; "
                             "routing has no candidate to select")
        key = a.tobytes()
        if self._dev.get("avail_key") != key:
            self._dev["avail"] = jnp.asarray(a)
            self._dev["avail_key"] = key
        return self._dev["avail"]

    def serve_fused(self, X: np.ndarray, lam: np.ndarray, qmesh=None,
                    avail=None):
        """One routed batch, ONE device dispatch: retrieval + neighbour
        utility + confidence + per-request-lambda selection inside a single
        jit (`_serve_fused_jit`).  Returns numpy
        (choice, s_hat, c_hat, kth_sim, agreement) — bitwise identical to
        running `predict_with_confidence` and the batched utility argmax
        separately, because both paths call the same jitted kernels.  The
        program hands them back packed in one buffer, copied to the host
        once and split into read-only views (`_unpack_route`).

        Backends that need a host stage (raw-IVF host traversal, pallas
        tile planning, an index-sharding mesh) keep their retrieval step
        and fuse everything after it into one dispatch (`_serve_tail_jit`).

        ``qmesh``: optional mesh to shard the BATCH axis over (replicated
        index) — bitwise-identical results, near-linear scaling for the
        gather-bound fused search.

        ``avail``: optional per-model availability mask (bool, (M,)) — open-
        circuit models are excluded from the utility argmax INSIDE the fused
        dispatch (`_select_jit` masks them to -inf).  ``None``/all-ones is
        bitwise identical to the unmasked kernel.

        The retrieval stage is chosen PER BATCH by `resolve_backend`: with
        a fitted dispatch policy a batch lands on the measured-fastest
        backend for its (index kind, size, delta fraction) cell — fused
        stays one dispatch, the host/tiles choices keep their retrieval
        stage and fuse everything after it (`_serve_tail_jit`), and on
        ``index="exact"`` a non-fused cell routes the brute-force scan as
        its own dispatch ahead of the same tail.  Decisions are identical
        across cells; only the latency profile differs.

        Traced (`repro.spans`) as ``route/dispatch``, the call of the
        fused, tail-only or sharded program until it returns, and
        ``route/fetch``, the one copy of the packed outputs to the host,
        whose ``buffers`` counts the arrays copied (1)."""
        # repro: allow-host: input embeddings arrive as host data
        X = np.atleast_2d(np.asarray(X, np.float32))
        # explicit h2d (jnp.asarray) — passing a raw np/python lambda into
        # the jitted call would be an implicit per-batch transfer, which the
        # transfer-guard sanitizer rejects
        lam_j = jnp.asarray(lam, jnp.float32)
        S, C = self._SC_dev()
        av = self._avail_dev(avail)
        eff = self.resolve_backend(len(X))
        if self.index == "exact" and eff not in ("fused", "pallas"):
            search, args = None, None
        else:
            search, args = self._fused_search(eff)
        rows = len(X)
        if search is None:
            sims, idx = self._neighbors(X, backend=eff)
            sims, idx = jnp.asarray(sims), jnp.asarray(idx)
            with span("route/dispatch", rows=rows):
                out = _serve_tail_jit(sims, idx, S, C, lam_j, av,
                                      weights=self.weights,
                                      temperature=float(self.temperature))
        else:
            q = jnp.asarray(normalize_rows(X))
            if qmesh is None:
                with span("route/dispatch", rows=rows):
                    out = _serve_fused_jit(
                        q, lam_j, av, S, C, *args, search=search,
                        weights=self.weights,
                        temperature=float(self.temperature))
            else:
                out = self._serve_sharded(qmesh, q, lam_j, av, S, C, search,
                                          args)
        with span("route/fetch", rows=rows, buffers=1):
            # repro: allow-host: the single end-of-batch materialization
            buf = np.asarray(out)
        return _unpack_route(buf, rows)

    def _serve_sharded(self, qmesh, q, lam, avail, S, C, search, args):
        """`_serve_fused_jit` with the batch sharded across ``qmesh`` —
        every per-query lane of the fused path is independent, so shard_map
        over the query axis is exact (verified bitwise in tests).  The
        wrapped callable is cached per (mesh, search), and the replicated
        index arrays are `device_put` onto the mesh ONCE per index version
        — passing host-committed arrays straight in would re-replicate tens
        of MB on every call, which is slower than not sharding at all.
        Returns the packed buffer with the batch's padding rows, which the
        host drops after its one copy."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        key = ("qmesh", qmesh, search, self.weights, self.temperature)
        cached = self._dev.get("qmesh_fn")
        if self._dev.get("qmesh_key") != key or cached is None:
            axes = tuple(qmesh.axis_names)

            def local(qs, lams, *arrs):
                sims, idx = search(qs, *arrs[:-3])
                return _serve_tail_jit(sims, idx, arrs[-3], arrs[-2], lams,
                                       arrs[-1], weights=self.weights,
                                       temperature=float(self.temperature))

            specs = (P(axes), P(axes)) + tuple(P() for _ in args) + (P(), P(),
                                                                     P())
            # repro: allow-jit-cache: cached in self._dev under `key` above
            cached = jax.jit(jax.shard_map(
                local, mesh=qmesh, in_specs=specs,
                out_specs=P(axes),
                check_vma=False))
            self._dev["qmesh_fn"] = cached
            self._dev["qmesh_key"] = key
        rep = NamedSharding(qmesh, P())
        src = (*args, S, C, avail)
        prev = self._dev.get("qmesh_args_src")
        # identity comparison against RETAINED source arrays (not bare ids:
        # a freed wrapper's address can be reused by a new array, which
        # would serve stale pre-compaction replicas)
        if (prev is None or self._dev.get("qmesh_args_mesh") is not qmesh
                or len(prev) != len(src)
                or any(a is not b for a, b in zip(prev, src))):
            self._dev["qmesh_args"] = tuple(jax.device_put(a, rep)
                                            for a in src)
            self._dev["qmesh_args_src"] = src
            self._dev["qmesh_args_mesh"] = qmesh
        rep_args = self._dev["qmesh_args"]
        n_dev = int(np.prod([qmesh.shape[a] for a in qmesh.axis_names]))
        qn = q.shape[0]
        pad = (-qn) % n_dev
        if pad:
            q = jnp.pad(q, ((0, pad), (0, 0)))
            lam = jnp.pad(lam, (0, pad))
        with qmesh, span("route/dispatch", rows=qn):
            return cached(q, lam, *rep_args)

    # ---- artifact contract: don't store the support rows twice ----
    def state_dict(self):
        """The approximate indexes already hold every support row (IVF-PQ's
        flat cold tier / IVF's cluster-major lists), so serializing ``_X``
        alongside them would double the artifact — the dominant tensor at
        the corpus scales the PQ tier targets.  Drop it and rebuild at
        load."""
        state = super().state_dict()
        if self.index != "exact":
            state.pop("_X", None)
        return state

    def load_state_dict(self, state):
        super().load_state_dict(state)
        self._dev = {}
        if (getattr(self, "_X", None) is None
                and getattr(self, "_ivf", None) is not None):
            if isinstance(self._ivf, DynamicIVFIndex):
                self._X = self._ivf.all_rows()     # base + pending delta
            else:
                self._X = self._ivf.rows()         # exact float copies
        return self
