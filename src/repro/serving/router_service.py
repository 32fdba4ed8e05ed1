"""RouterService: the paper's router as the front door of a multi-model
serving deployment.

  request text -> embed (encoder.py) -> router.predict_utility ->
  argmax_m  s_hat - lambda_r * c_hat  -> dispatch to that model's engine.

Routers are addressable three ways (see `repro.core.routers.spec`):

  * a fitted ``Router`` instance;
  * a spec string (``"knn100-ivf@lam=0.5"``) plus a dataset to fit on;
  * a saved artifact via ``RouterService.from_artifact(path, engines)`` —
    boots without ever touching the training data.

The cost/quality trade-off ``lambda`` is **per request**: every routing call
takes an optional scalar or per-request vector, falling back to the
service default and then the router's spec-level ``default_lam``
(RouteLLM-style ``router-<spec>-<threshold>`` addressing).  All entry points
share one jitted batched utility kernel (`_route_batch`).

Confidence-based fallback uses an optional protocol — any router exposing
``confidence(X) -> (kth_sim, agreement)`` (§8 diagnostics) participates; no
type checks.  Routers that additionally expose ``predict_with_confidence``
(kNN) serve utility AND confidence from ONE retrieval — without it, every
confidence-fallback route would pay for the neighbour search twice, which
on a kNN router is the entire per-request cost.  Router/engine model-count
mismatches raise at construction instead of silently aliasing choices onto
the engine list.

``observe`` closes the loop: routed-then-judged traffic is fed back into
routers exposing ``partial_fit`` (kNN), appending new support rows — and,
on the approximate backends, delta-tier index entries — in place.  Appends
never block the request path; index compaction (re-cluster) is amortized
behind the router's ``delta_cap``.  Background compactions run on a daemon
thread — ``close()`` (or using the service as a context manager) joins any
in-flight rebuild so teardown / artifact saves cannot race the swap.

A router carrying a fitted `DispatchPolicy` (``service.dispatch_policy``)
serves every ``route_fused`` batch on the measured-fastest backend for its
(index kind, batch size, delta fraction) cell, and `MicroBatcher.from_policy`
picks up the policy's wave-close constants.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dataset import RoutingDataset
from repro.core.routers import (Router, RouterSpec, load_router, make_router,
                                spec_of)
from repro.spans import span
from . import encoder
from .engine import IncompleteDrainError, Request, ServingEngine
from .faults import (CircuitOpenError, DegradationLadder,
                     EngineDeadlineExceeded, EngineHealth, ExecutionReport,
                     FeedbackValidationError)


@dataclasses.dataclass
class RoutedResult:
    uid: int
    model: str
    request: Request
    predicted_score: float
    predicted_cost: float
    lam: float = 0.0
    confidence: Optional[float] = None
    #: full per-model predicted score/cost rows — kept so a mid-execution
    #: failure can reroute to the NEXT-best-utility model deterministically
    #: (the paper's point: the kNN router already priced the whole pool)
    s_row: Optional[np.ndarray] = None
    c_row: Optional[np.ndarray] = None
    #: degradation-ladder level the wave was served at (0 = full fidelity)
    degradation: int = 0
    #: engines this request failed over from, in order
    rerouted_from: List[str] = dataclasses.field(default_factory=list)


def to_jsonable(obj):
    """Recursively convert a stats/report payload into plain JSON types.
    Numpy scalars and 0-d/1-d arrays leak easily out of routing internals
    (``support_size``, measured latencies, mask counters); everything the
    gateway serializes onto the wire goes through here so ``json.dumps``
    can never raise on a live health endpoint."""
    if isinstance(obj, dict):
        return {str(k): to_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [to_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return [to_jsonable(v) for v in obj.tolist()]
    if isinstance(obj, (np.bool_,)):
        return bool(obj)
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if isinstance(obj, (bool, int, str)) or obj is None:
        return obj
    if isinstance(obj, float):
        # json.dumps emits bare `NaN`/`Infinity`, which is not JSON and
        # breaks strict clients — clamp to null
        return obj if np.isfinite(obj) else None
    return str(obj)


def _route_batch(s_hat, c_hat, lam, avail):
    """Single batched utility path: per-request lambda, availability-masked
    argmax over models.  Delegates to the SAME jitted kernel the routers'
    fused serving path inlines (`_select_jit`), so the legacy multi-dispatch
    chain and `route_fused` make bitwise-identical decisions."""
    from repro.core.routers.knn import _select_jit
    return _select_jit(s_hat, c_hat, lam, avail)


def knn_service(ds: RoutingDataset, engines: Dict[str, "ServingEngine"],
                k: int = 100, index: str = "exact", lam: float = 0.0,
                seed: int = 0, fallback_model: Optional[str] = None,
                confidence_floor: float = 0.02,
                **router_kw) -> "RouterService":
    """Fit a kNN router on ``ds`` (building the IVF coarse quantizer — and
    the PQ codebooks when ``index='ivfpq'``) and wrap it in a RouterService
    over ``engines``.  ``router_kw`` are KNNRouter constructor kwargs
    (weights, nprobe, m, nbits, rerank, ...)."""
    spec = RouterSpec("knn", k=k, ivf=index in ("ivf", "ivfpq"),
                      kwargs=router_kw, pq=(index == "ivfpq"))
    return RouterService(spec, engines, ds=ds, lam=lam, seed=seed,
                         fallback_model=fallback_model,
                         confidence_floor=confidence_floor)


class RouterService:
    def __init__(self, router: Union[Router, RouterSpec, str],
                 engines: Dict[str, ServingEngine], *,
                 ds: Optional[RoutingDataset] = None,
                 lam: Optional[float] = None,
                 fallback_model: Optional[str] = None,
                 confidence_floor: float = 0.02, seed: int = 0,
                 breaker: Optional[Dict] = None,
                 engine_timeout_s: Optional[float] = None,
                 max_route_attempts: int = 3,
                 retry_backoff_s: float = 0.0,
                 ladder: Optional[DegradationLadder] = None,
                 durability=None):
        if isinstance(router, (str, RouterSpec)):
            router = make_router(router)
        if router.model_names is None and ds is None:
            raise ValueError(
                "router is not fitted; pass ds= to fit it here, or load "
                "a fitted artifact via RouterService.from_artifact()")
        if ds is not None:        # an explicit dataset always (re)fits, so a
            router.fit(ds, seed=seed)  # fitted router can't shadow fresh data

        self.router = router
        self.engines = engines
        self.model_names = self._validate_engines(router, engines)
        self.default_lam = router.default_lam if lam is None else float(lam)
        if fallback_model is not None and fallback_model not in engines:
            raise ValueError(
                f"fallback_model {fallback_model!r} has no serving engine "
                f"(engines: {list(engines)})")
        self.fallback_model = fallback_model
        self.confidence_floor = confidence_floor
        self._uid = 0
        self.observed = 0          # feedback rows ingested via observe()
        self.log: List[RoutedResult] = []
        #: per-engine circuit breakers (``breaker`` = EngineHealth kwargs,
        #: e.g. failure_threshold/base_backoff_s for tests with fake clocks)
        self.health: Dict[str, EngineHealth] = {
            m: EngineHealth(m, **(breaker or {})) for m in self.model_names}
        #: wall-clock budget for one engine wave (None = no deadline; a hung
        #: engine then blocks — production serving always sets one)
        self.engine_timeout_s = engine_timeout_s
        self.max_route_attempts = int(max_route_attempts)
        self.retry_backoff_s = float(retry_backoff_s)
        self.ladder = ladder if ladder is not None else DegradationLadder()
        self._mask_cache: Dict = {}
        #: `repro.serving.durability.DurabilityManager` (or None): when set,
        #: every observe() batch is WAL-logged + fsync'd BEFORE it touches
        #: the index, and checkpoints run on the batch cadence / after every
        #: re-cluster.  Duck-typed so this module never imports the
        #: durability layer.
        self.durability = durability
        #: recovery progress ({"status": "replaying"/"ready", counters...});
        #: None for a service that never recovered — /health readiness reads
        #: it through `recovery_status()`
        self._recovery: Optional[Dict] = None
        self._pending_replay: List = []
        if durability is not None:
            hook = getattr(self.router, "set_recluster_hook", None)
            if callable(hook):
                hook(durability.request_checkpoint)
            if not durability.checkpoints.list():
                # bootstrap snapshot: recovery always has a base to load +
                # replay onto, even if the process dies before the first
                # cadence checkpoint
                durability.checkpoint(self.router)

    @classmethod
    def from_artifact(cls, path, engines: Dict[str, ServingEngine],
                      **kw) -> "RouterService":
        """Boot a service from a `save_router` artifact — no training data."""
        return cls(load_router(path), engines, **kw)

    @staticmethod
    def _validate_engines(router: Router, engines: Dict) -> List[str]:
        """Router output arity and names must match the engine pool exactly —
        a mismatch would silently mis-route every request."""
        names = list(router.model_names)
        if len(names) != len(engines):
            raise ValueError(
                f"router predicts over {len(names)} models {names} but "
                f"{len(engines)} engines were supplied ({list(engines)})")
        missing = [m for m in names if m not in engines]
        if missing:
            raise ValueError(
                f"router models {missing} have no serving engine "
                f"(engines: {list(engines)})")
        return names

    @property
    def spec(self) -> str:
        """Canonical spec string of the underlying router."""
        return spec_of(self.router)

    @property
    def retrieval_backend(self) -> str:
        """'exact' / 'ivf' / 'ivfpq' for kNN routers, 'n/a' for parametric
        ones."""
        return getattr(self.router, "index", "n/a")

    @property
    def dispatch_policy(self):
        """The router's fitted `DispatchPolicy`, or None (static defaults)."""
        return getattr(self.router, "dispatch_policy", None)

    # ---- health / availability ----
    def availability_mask(self) -> Optional[np.ndarray]:
        """Per-model availability from the circuit breakers, in
        ``model_names`` order — or None when every engine is up (the common
        case: `serve_fused`'s cached all-ones default is bitwise identical
        to pre-mask serving).  Calling this IS the open -> half_open probe
        gate, so a backoff that has elapsed re-admits the engine here.
        A total outage also returns None: an all-false mask has no argmax
        candidate, so routing proceeds on utilities alone and `execute`
        sheds with typed errors instead."""
        flags = [self.health[m].available() for m in self.model_names]
        if all(flags) or not any(flags):
            return None
        # repro: allow-host: availability is host-side health metadata
        return np.asarray(flags, bool)

    def stats(self) -> Dict:
        """JSON-ready service health snapshot — the payload the gateway's
        ``/health`` and ``/stats`` endpoints serve verbatim: per-engine
        breaker state plus service counters.  Passed through `to_jsonable`
        end-to-end so no numpy scalar/array from the routing internals can
        ever make ``json.dumps`` raise on a live health check
        (regression-tested: ``json.dumps(svc.stats())`` must round-trip)."""
        support = getattr(self.router, "support_size", None)
        return to_jsonable({
            "spec": self.spec,
            "retrieval_backend": self.retrieval_backend,
            "default_lam": self.default_lam,
            "engines": {m: self.health[m].stats() for m in self.model_names},
            # side-effect-free availability view: a stats poll must not
            # perform the open -> half_open probe transition itself
            "available": {m: self.health[m].retry_after_s() == 0.0
                          for m in self.model_names},
            "observed": self.observed,
            "routed": len(self.log),
            "support_size": support,
            "durability": (None if self.durability is None
                           else self.durability.stats()),
            "recovery": self.recovery_status(),
        })

    # ---- lifecycle ----
    def close(self) -> None:
        """Join any in-flight background index compaction (daemon-thread
        re-cluster kicked off by `observe`).  Without this, process teardown
        or an artifact save can race the atomic index swap; after it, the
        router holds one consistent (base, delta) pair.  Idempotent, and
        safe to call concurrently with an in-flight compaction (or with
        other `close()` callers): every caller joins the compaction thread
        it observed, and `join_recluster` clears the thread slot with a
        compare-and-set so it never clobbers a newer compaction.  The
        service remains usable after `close()` — it is a synchronization
        point, not a teardown."""
        jr = getattr(self.router, "join_recluster", None)
        if callable(jr):
            jr()
        if self.durability is not None and self.durability.checkpoint_pending:
            # a background compaction finished since the last observe;
            # persist the compacted state before standing down
            with self.durability.mutex:
                self.durability.checkpoint(self.router)

    def __enter__(self) -> "RouterService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- routing ----
    def _resolve_lam(self, lam, n: int) -> np.ndarray:
        """None -> service default; scalar -> broadcast; (n,) vector as-is."""
        if lam is None:
            lam = self.default_lam
        # repro: allow-host: lambdas arrive as host request metadata
        arr = np.asarray(lam, np.float32)
        if arr.ndim == 0:
            return np.full((n,), float(arr), np.float32)
        if arr.shape != (n,):
            raise ValueError(f"lam must be a scalar or shape ({n},), got "
                             f"shape {arr.shape}")
        return arr

    def _check_arity(self, s_hat: np.ndarray) -> None:
        if s_hat.shape[1] != len(self.model_names):
            raise ValueError(
                f"router emitted {s_hat.shape[1]} model columns, expected "
                f"{len(self.model_names)} ({self.model_names})")

    def _avail_jnp(self, avail):
        """Device-resident availability mask for the batched utility kernel
        (all-ones when ``avail`` is None), cached by content so the legacy
        chain never re-uploads it per batch.  Routers with a fused path
        already keep this cache (`KNNRouter._avail_dev`); this reuses it so
        both paths share one device array."""
        ad = getattr(self.router, "_avail_dev", None)
        if callable(ad):
            return ad(avail)
        M = len(self.model_names)
        if avail is None:
            ones = self._mask_cache.get("ones")
            if ones is None or ones.shape != (M,):
                ones = jnp.ones((M,), jnp.bool_)
                self._mask_cache["ones"] = ones
            return ones
        # repro: allow-host: availability is host-side health metadata
        a = np.asarray(avail, bool).reshape(-1)
        key = a.tobytes()
        if self._mask_cache.get("key") != key:
            self._mask_cache["arr"] = jnp.asarray(a)
            self._mask_cache["key"] = key
        return self._mask_cache["arr"]

    def _choose(self, s_hat: np.ndarray, c_hat: np.ndarray, lam,
                n: int, avail=None) -> tuple:
        """Shared decision core: validate arity, resolve per-request lambdas,
        run the jitted batched availability-masked utility argmax."""
        self._check_arity(s_hat)
        lam_r = self._resolve_lam(lam, n)
        choice, _ = _route_batch(jnp.asarray(s_hat), jnp.asarray(c_hat),
                                 jnp.asarray(lam_r), self._avail_jnp(avail))
        # repro: allow-host: the legacy chain's end-of-batch materialization
        return np.asarray(choice), lam_r

    def _decide(self, emb: np.ndarray, lam) -> tuple:
        s_hat, c_hat = self.router.predict_utility(emb)
        choice, lam_r = self._choose(s_hat, c_hat, lam, len(emb),
                                     self.availability_mask())
        return choice, s_hat, c_hat, lam_r

    # ---- fused single-dispatch hot path ----
    def route_fused(self, emb: np.ndarray, lam=None, qmesh=None,
                    degrade: int = 0) -> tuple:
        """One routed batch, one device dispatch: retrieval -> per-model
        utility -> confidence -> per-request-lambda selection fused inside a
        single jit on routers that support it (`KNNRouter.serve_fused`),
        with one device sync for the whole batch.  Falls back to the legacy
        chain for routers without a fused path — same numbers either way,
        because both paths share the same jitted kernels.

        The circuit breakers feed an availability mask INTO the fused
        selection: open-circuit models are -inf in the utility argmax, so
        routing around an outage costs nothing on the hot path (all-up is
        a cached all-ones mask, bitwise identical to pre-mask serving).
        ``degrade`` serves the wave at that degradation-ladder level
        (shrunk nprobe / dropped re-rank / base-only retrieval) on routers
        that support it.

        Returns (choice, s_hat, c_hat, confidence-or-None, lam_r) as numpy.
        ``qmesh`` shards the batch axis across a device mesh (replicated
        index; bitwise-identical results).  Traced as the span ``route``
        (`repro.spans`), from the embeddings as a float32 matrix to the
        answers on the host."""
        # repro: allow-host: input embeddings arrive as host data
        emb = np.atleast_2d(np.asarray(emb, np.float32))
        with span("route", rows=len(emb)):
            lam_r = self._resolve_lam(lam, len(emb))
            avail = self.availability_mask()
            sf = getattr(self.router, "serve_fused", None)
            if callable(sf):
                dg = getattr(self.router, "degraded", None)
                ctx = (dg(self.ladder[degrade]) if degrade and callable(dg)
                       else contextlib.nullcontext())
                with ctx:
                    # serve_fused already returns numpy, and spans its
                    # dispatch and fetch as children of this span
                    choice, s_hat, c_hat, _, agree = sf(
                        emb, lam_r, qmesh=qmesh, avail=avail)
                self._check_arity(s_hat)
                return choice, s_hat, c_hat, agree, lam_r
            s_hat, c_hat, conf = self._predict_for_serving(emb)
            choice, lam_r = self._choose(s_hat, c_hat, lam_r, len(emb),
                                         avail)
            return choice, s_hat, c_hat, conf, lam_r

    def route_legacy(self, emb: np.ndarray, lam=None) -> tuple:
        """The pre-fusion multi-dispatch chain — retrieval dispatch, utility
        dispatch, selection dispatch, with a host sync between each — kept
        as the parity oracle and the benchmark baseline for
        `benchmarks/serving_latency.py`.  Same return shape as
        `route_fused`."""
        emb = np.atleast_2d(np.asarray(emb, np.float32))
        s_hat, c_hat, conf = self._predict_for_serving(emb)
        choice, lam_r = self._choose(s_hat, c_hat, lam, len(emb),
                                     self.availability_mask())
        return choice, s_hat, c_hat, conf, lam_r

    def route_embeddings(self, emb: np.ndarray, lam=None) -> np.ndarray:
        """Per-request lambda routing over raw embeddings -> model indices
        (served through the fused single-dispatch path)."""
        return self.route_fused(emb, lam)[0]

    def _predict_for_serving(self, emb: np.ndarray):
        """(s_hat, c_hat, agreement-or-None) with ONE retrieval pass.
        ``predict_with_confidence`` fuses utility + diagnostics over a single
        neighbour search; routers exposing only ``confidence`` pay a second
        search; routers exposing neither serve without fallback."""
        fused = getattr(self.router, "predict_with_confidence", None)
        if callable(fused):
            s_hat, c_hat, _, agree = fused(emb)
            return s_hat, c_hat, agree
        s_hat, c_hat = self.router.predict_utility(emb)
        conf_fn = getattr(self.router, "confidence", None)
        if callable(conf_fn):
            _, agree = conf_fn(emb)
            return s_hat, c_hat, agree
        return s_hat, c_hat, None

    def submit_texts(self, texts: Sequence[str], prompts_tokens=None,
                     max_new_tokens: int = 8, lam=None,
                     degrade: int = 0) -> List[RoutedResult]:
        emb = encoder.embed_texts(list(texts))
        choice, s_hat, c_hat, conf, lam_r = self.route_fused(
            emb, lam, degrade=degrade)

        results = []
        for i, text in enumerate(texts):
            mi = int(choice[i])
            if (conf is not None and self.fallback_model
                    and conf[i] < self.confidence_floor):
                # report the FALLBACK model's predicted score/cost too —
                # the log must attribute predictions to the model served
                mi = self.model_names.index(self.fallback_model)
            m = self.model_names[mi]
            toks = (prompts_tokens[i] if prompts_tokens is not None
                    else encoder.hash_tokenize(text)[:16])
            toks = np.asarray(toks, np.int32)
            vocab = self.engines[m].cfg.vocab_size
            req = Request(uid=self._uid, prompt_tokens=toks % vocab,
                          max_new_tokens=max_new_tokens)
            self._uid += 1
            res = RoutedResult(
                uid=req.uid, model=m, request=req,
                predicted_score=float(s_hat[i, mi]),
                predicted_cost=float(c_hat[i, mi]),
                lam=float(lam_r[i]),
                confidence=float(conf[i]) if conf is not None else None,
                s_row=np.asarray(s_hat[i]).copy(),
                c_row=np.asarray(c_hat[i]).copy(),
                degradation=int(degrade))
            results.append(res)
        return results

    # ---- feedback ingestion ----
    def observe(self, queries, scores, costs=None,
                recluster="background") -> int:
        """Routed-then-judged traffic becomes new support rows in place: the
        non-parametric router's whole "training step" is appending the
        observation, so the very next identical query retrieves it.

        ``queries`` — a list of texts (embedded here with the same encoder
        the routing path uses) or a pre-embedded (n, D) array; ``scores`` —
        judged per-model quality, shape (n, M) in ``model_names`` order;
        ``costs`` — optional, same shape, defaults to zero.

        The request path never blocks on an index rebuild: appends land in
        the delta tier (probed per-centroid sub-lists on the fused backend,
        exact-scanned on the staged ones), and compaction only runs once the
        tier exceeds the router's ``delta_cap`` — by default
        (``recluster="background"``) on a daemon thread with an atomic
        index swap, so even THIS call returns without waiting on k-means.
        Pass ``"auto"`` to compact synchronously in-line, ``False`` to
        defer entirely, ``True`` to force a synchronous compaction now.

        With a `DurabilityManager` attached the batch is validated, then
        serialized + fsync'd to the write-ahead log, and only THEN applied
        — so every acknowledged observe survives a crash, and garbage never
        becomes durable (validation failures are typed errors raised before
        the WAL write).  Returns the router's support size after
        ingestion."""
        pf = getattr(self.router, "partial_fit", None)
        if not callable(pf):
            raise TypeError(f"router {self.spec!r} does not support online "
                            f"updates (no partial_fit); use a kNN-family "
                            f"router, e.g. 'knn100-ivf@online=1'")
        emb, S, C = self._validate_feedback(queries, scores, costs)
        dur = self.durability
        if dur is None:
            pf(emb, S, C, recluster=recluster)
            self.observed += len(emb)
            return int(getattr(self.router, "support_size", -1))
        with dur.mutex:
            seq = dur.log(emb, S, C)       # fsync ack BEFORE any mutation
            pf(emb, S, C, recluster=recluster)
            dur.note_applied(seq)
            self.observed += len(emb)
            if dur.should_checkpoint():
                dur.checkpoint(self.router)
        return int(getattr(self.router, "support_size", -1))

    def _validate_feedback(self, queries, scores, costs):
        """Typed validation of one observe() batch — every check fires
        BEFORE the WAL write, so rejected garbage is never made durable.
        Returns the normalized (emb, scores, costs) float32 arrays."""
        if len(queries) == 0:
            raise FeedbackValidationError(
                "queries", "observe() got an empty batch — nothing to log "
                "or apply")
        if isinstance(queries[0], str):
            emb = encoder.embed_texts(list(queries))
        else:
            emb = np.atleast_2d(np.asarray(queries, np.float32))
        if emb.ndim != 2 or emb.shape[0] == 0:
            raise FeedbackValidationError(
                "queries", f"embeddings must be a non-empty (n, D) matrix, "
                           f"got shape {emb.shape}")
        dim = getattr(self.router, "embed_dim", None)
        if dim is not None and emb.shape[1] != dim:
            raise FeedbackValidationError(
                "queries", f"embedding dim {emb.shape[1]} does not match "
                           f"the router's fitted dim {dim}")
        if not np.isfinite(emb).all():
            raise FeedbackValidationError(
                "queries", "embeddings contain NaN/inf — refusing to make "
                           "non-finite support rows durable")
        M = len(self.model_names)
        S = np.atleast_2d(np.asarray(scores, np.float32))
        if S.shape != (len(emb), M):
            raise FeedbackValidationError(
                "scores", f"scores must have shape ({len(emb)}, {M}) in "
                          f"model order {self.model_names}, got {S.shape}")
        if not np.isfinite(S).all():
            raise FeedbackValidationError(
                "scores", "scores contain NaN/inf")
        if costs is None:
            C = np.zeros_like(S)
        else:
            C = np.atleast_2d(np.asarray(costs, np.float32))
            if C.shape != S.shape:
                raise FeedbackValidationError(
                    "costs", f"costs must match scores shape {S.shape}, "
                             f"got {C.shape}")
            if not np.isfinite(C).all():
                raise FeedbackValidationError("costs", "costs contain "
                                              "NaN/inf")
        return emb, S, C

    # ---- durability / crash recovery ----
    def checkpoint(self):
        """Snapshot the router through the attached `DurabilityManager`
        (atomic artifact write recording the covered WAL sequence); no-op
        returning None without one.  Joins any in-flight background
        compaction first (artifact serialization requires one consistent
        base/delta pair)."""
        if self.durability is None:
            return None
        with self.durability.mutex:
            return self.durability.checkpoint(self.router)

    @classmethod
    def open_recovery(cls, root, engines: Dict[str, ServingEngine], *,
                      durability_kw: Optional[Dict] = None,
                      **service_kw) -> "RouterService":
        """Phase 1 of crash recovery: load the newest valid checkpoint
        under ``root`` (corrupt snapshots are skipped, never loaded) and
        stage the WAL suffix it does not cover.  The returned service
        reports ``recovery_status()["status"] == "replaying"`` — a gateway
        answers readiness 503 "starting" — until `complete_recovery` has
        replayed the suffix."""
        from .durability import DurabilityManager
        dur = DurabilityManager(root, **(durability_kw or {}))
        router, covered_seq, skipped = dur.load_latest_checkpoint()
        if router is None:
            raise FileNotFoundError(
                f"no loadable checkpoint under {root!r} "
                f"(skipped corrupt: {skipped or 'none'}) — recovery needs "
                f"the bootstrap snapshot a durable service writes at "
                f"construction")
        svc = cls(router, engines, durability=dur, **service_kw)
        svc._pending_replay = dur.pending_records()
        svc._recovery = {
            "status": "replaying",
            "checkpoint_covered_seq": covered_seq,
            "corrupt_checkpoints_skipped": len(skipped),
            "skipped_detail": list(skipped),
            "wal_torn_tail_dropped": dur.wal.torn_tail_dropped,
            "pending_batches": len(svc._pending_replay),
            "replayed_batches": 0,
            "replayed_rows": 0,
        }
        return svc

    def complete_recovery(self, recluster="auto") -> int:
        """Phase 2: replay the staged WAL suffix through ``partial_fit``
        (same batch boundaries, synchronous compaction -> the recovered
        index converges to the same support and bitwise-identical retrieval
        as the uncrashed process).  Replayed batches are NOT re-logged —
        they are already durable.  Returns the number of batches replayed
        and flips recovery status to "ready"."""
        dur = self.durability
        rec = self._recovery
        if dur is None or rec is None:
            return 0
        pf = getattr(self.router, "partial_fit")
        with dur.mutex:
            for r in self._pending_replay:
                pf(r.emb, r.scores, r.costs, recluster=recluster)
                dur.note_applied(r.seq)
                self.observed += len(r.emb)
                rec["replayed_batches"] += 1
                rec["replayed_rows"] += int(len(r.emb))
            self._pending_replay = []
            rec["status"] = "ready"
        return rec["replayed_batches"]

    @classmethod
    def recover(cls, root, engines: Dict[str, ServingEngine],
                **kw) -> "RouterService":
        """Boot-time crash recovery in one call: latest valid checkpoint +
        WAL-suffix replay (see `open_recovery` / `complete_recovery`)."""
        svc = cls.open_recovery(root, engines, **kw)
        svc.complete_recovery()
        return svc

    def recovery_status(self) -> Optional[Dict]:
        """Replay progress ({"status": "replaying"/"ready", counters}) or
        None for a service that did not boot through recovery."""
        return None if self._recovery is None else dict(self._recovery)

    # ---- execution ----
    def _run_engine(self, m: str, reqs: List[Request]) -> int:
        """One wave on one engine under the service deadline.  With a
        deadline the wave runs on a daemon worker thread and a join timeout
        raises `EngineDeadlineExceeded` — a hung engine can no longer block
        the serving loop.  The hung worker keeps its slots (releasing them
        out from under a live thread would race its decode); reroutes hand
        FRESH Request objects to the next engine instead."""
        eng = self.engines[m]
        if self.engine_timeout_s is None:
            return eng.run_until_drained(reqs)
        box: Dict = {}

        def worker():
            try:
                box["steps"] = eng.run_until_drained(reqs)
            except BaseException as exc:
                box["exc"] = exc

        t = threading.Thread(target=worker, daemon=True,
                             name=f"engine-wave-{m}")
        t.start()
        t.join(self.engine_timeout_s)
        if t.is_alive():
            raise EngineDeadlineExceeded(m, self.engine_timeout_s)
        if "exc" in box:
            raise box["exc"]
        return box["steps"]

    def _next_best(self, r: RoutedResult, tried: Set[str]) -> Optional[str]:
        """Deterministic next-best model for a reroute.  The kNN router
        already priced the WHOLE pool for this request (``s_row``/
        ``c_row``), so the failover ranking is just the utility argsort of
        the request's own row — skipping engines already tried this request
        and engines whose breaker is open."""
        if r.s_row is None or r.c_row is None:
            for m in self.model_names:         # legacy result: first viable
                if m not in tried and self.health[m].available():
                    return m
            return None
        util = np.asarray(r.s_row, np.float32) - r.lam * np.asarray(
            r.c_row, np.float32)
        for mi in np.argsort(-util, kind="stable"):
            m = self.model_names[int(mi)]
            if m not in tried and self.health[m].available():
                return m
        return None

    def _reroute(self, rs: List[RoutedResult], exc: BaseException,
                 report: ExecutionReport, attempts: Dict[int, int],
                 tried: Dict[int, Set[str]]
                 ) -> List[Tuple[str, RoutedResult]]:
        """Failover a failed wave's requests: each goes to its next-best-
        utility available engine (fresh Request object — the failed engine,
        possibly still hung, may hold the old one), or lands in
        ``report.failed`` with a typed reason once its attempt budget or
        the candidate pool is exhausted.  Never a silent drop."""
        requeued = []
        for r in rs:
            tried.setdefault(r.uid, set()).add(r.model)
            attempts[r.uid] = attempts.get(r.uid, 0) + 1
            nxt = (self._next_best(r, tried[r.uid])
                   if attempts[r.uid] < self.max_route_attempts else None)
            if nxt is None:
                if not r.request.error:
                    r.request.error = type(exc).__name__
                report.failed[r.uid] = f"{type(exc).__name__}: {exc}"
                continue
            report.rerouted.append((r.uid, r.model, nxt))
            r.rerouted_from.append(r.model)
            old = r.request
            vocab = self.engines[nxt].cfg.vocab_size
            r.request = Request(
                uid=r.uid,
                prompt_tokens=np.asarray(old.prompt_tokens,
                                         np.int64) % vocab,
                max_new_tokens=old.max_new_tokens)
            r.model = nxt
            if r.s_row is not None:       # attribute predictions to the
                mi = self.model_names.index(nxt)   # model actually served
                r.predicted_score = float(r.s_row[mi])
                r.predicted_cost = float(r.c_row[mi])
            requeued.append((nxt, r))
        return requeued

    def execute(self, results: List[RoutedResult]) -> ExecutionReport:
        """Dispatch routed requests to their engines, isolating per-engine
        failures: one engine raising/hanging no longer aborts the batch or
        loses the log.  Per wave and per engine — an open breaker skips the
        engine (its requests reroute immediately), a failure/timeout records
        to that engine's breaker and reroutes the affected requests to their
        next-best-utility model (fresh Request, deterministic order), and a
        success re-closes the breaker.  Requests that exhaust
        ``max_route_attempts`` or the candidate pool land in
        ``report.failed`` with a typed reason.

        Returns an `ExecutionReport` — still the ``{model: decode_steps}``
        mapping this method always returned, now also carrying ``errors`` /
        ``rerouted`` / ``skipped`` / ``failed``."""
        report = ExecutionReport()
        queue: List[Tuple[str, RoutedResult]] = [(r.model, r)
                                                 for r in results]
        attempts: Dict[int, int] = {}
        tried: Dict[int, Set[str]] = {}
        while queue:
            by_model: Dict[str, List[RoutedResult]] = {}
            for m, r in queue:
                by_model.setdefault(m, []).append(r)
            queue = []
            for m, rs in by_model.items():
                health = self.health[m]
                if not health.available():
                    report.skipped[m] = report.skipped.get(m, 0) + 1
                    exc = CircuitOpenError(
                        m, retry_after_s=health.retry_after_s())
                    queue.extend(self._reroute(rs, exc, report, attempts,
                                               tried))
                    continue
                reqs = [r.request for r in rs]
                try:
                    steps = self._run_engine(m, reqs)
                except IncompleteDrainError as exc:
                    # partial wave: finished requests stand; only the
                    # survivors (already slot-released and error-marked by
                    # the engine) fail over
                    health.record_failure(exc)
                    report.record_error(m, exc,
                                        [q.uid for q in exc.survivors])
                    surv = {id(q) for q in exc.survivors}
                    failed_rs = [r for r in rs if id(r.request) in surv]
                    queue.extend(self._reroute(failed_rs, exc, report,
                                               attempts, tried))
                except Exception as exc:
                    health.record_failure(exc)
                    report.record_error(m, exc, [r.uid for r in rs])
                    if not isinstance(exc, EngineDeadlineExceeded):
                        # reclaim any slots the failed wave admitted; a
                        # deadline leaves them — the hung worker still owns
                        # the engine state
                        rel = getattr(self.engines[m], "release", None)
                        if callable(rel):
                            rel(reqs)
                    queue.extend(self._reroute(rs, exc, report, attempts,
                                               tried))
                else:
                    health.record_success()
                    report[m] = report.get(m, 0) + steps
            if queue and self.retry_backoff_s:
                time.sleep(self.retry_backoff_s)
        self.log.extend(results)
        return report

    def serve_texts(self, texts: Sequence[str], **kw):
        results = self.submit_texts(texts, **kw)
        self.execute(results)
        return results
