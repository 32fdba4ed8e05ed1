"""Fused single-dispatch serving hot path: route_fused bitwise parity with
the legacy multi-dispatch chain on every backend (incl. the per-request-
lambda and confidence-fallback branches), the ops-level fused backend's
contract, probed vs exact-scanned delta-tier semantics, background
re-clustering, micro-batch coalescing, and the code-major artifact
migration."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dataset import RoutingDataset
from repro.core.routers import make_router
from repro.core.routers.knn import KNNRouter
from repro.kernels.knn_ivf.ops import (DynamicIVFIndex, build_ivf_index,
                                       build_ivfpq_index, ivf_topk,
                                       ivfpq_topk)
from repro.kernels.knn_topk.ref import knn_topk_reference
from repro.serving import encoder
from repro.serving.router_service import RouterService

D = 24
MODELS = ["m-a", "m-b", "m-c"]


@pytest.fixture(scope="module")
def ds():
    texts = [f"topic {i % 5} example {i}" for i in range(220)]
    emb = encoder.embed_texts(texts)
    rng = np.random.default_rng(0)
    return RoutingDataset(
        "fused", emb,
        rng.uniform(0.2, 1.0, (220, 3)).astype(np.float32),
        rng.uniform(0.001, 0.01, (220, 3)).astype(np.float32), MODELS)


@pytest.fixture(scope="module")
def clustered():
    rng = np.random.default_rng(3)
    centers = rng.normal(size=(10, D)) * 3.0
    s = (centers[rng.integers(0, 10, 2500)]
         + rng.normal(size=(2500, D))).astype(np.float32)
    q = (centers[rng.integers(0, 10, 80)]
         + rng.normal(size=(80, D))).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    return s, jnp.asarray(q)


# ---------------------------------------------------------------------------
# ops-level fused backend contract
# ---------------------------------------------------------------------------

def test_fused_ivfpq_matches_host(clustered):
    """One jitted dispatch must reproduce the staged host traversal: the
    two-stage semantics are identical (same probe set, same global ADC
    shortlist, exact re-rank), so ids match and scores agree to fp
    tolerance (the fused re-rank multiplies by the STORED inverse norms
    instead of re-deriving them)."""
    s, q = clustered
    index = build_ivfpq_index(s, seed=0)
    sc_h, ix_h = ivfpq_topk(q, index, 20)
    sc_f, ix_f = ivfpq_topk(q, index, 20, backend="fused")
    assert np.mean(np.asarray(ix_h) == np.asarray(ix_f)) > 0.99
    np.testing.assert_allclose(np.asarray(sc_f), np.asarray(sc_h),
                               rtol=1e-5, atol=1e-6)


def test_fused_ivf_matches_host(clustered):
    s, q = clustered
    index = build_ivf_index(s, seed=0)
    sc_h, ix_h = ivf_topk(q, index, 20)
    sc_f, ix_f = ivf_topk(q, index, 20, backend="fused")
    np.testing.assert_array_equal(np.asarray(ix_h), np.asarray(ix_f))
    np.testing.assert_allclose(np.asarray(sc_f), np.asarray(sc_h),
                               rtol=1e-5, atol=1e-6)


def test_fused_short_list_padding_contract():
    """-inf / -1 tail slots when fewer valid candidates than k — the same
    contract as every staged backend."""
    rng = np.random.default_rng(5)
    s = rng.normal(size=(40, 16)).astype(np.float32)
    q = rng.normal(size=(6, 16)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    qj = jnp.asarray(q)
    for build, topk in ((build_ivfpq_index, ivfpq_topk),
                        (build_ivf_index, ivf_topk)):
        kw = {"m": 4} if build is build_ivfpq_index else {}
        index = build(s, n_clusters=6, seed=0, **kw)
        sc, ix = topk(qj, index, 32, nprobe=1, backend="fused")
        sc, ix = np.asarray(sc), np.asarray(ix)
        assert (ix >= 0).any() and (ix == -1).any()
        assert np.all(np.isneginf(sc[ix == -1]))
        assert np.all(np.isfinite(sc[ix >= 0]))


def test_fused_rerank0_matches_adc_order(clustered):
    """rerank=0 on the fused backend returns raw ADC ordering — same ids as
    the host backend's rerank=0 path."""
    s, q = clustered
    index = build_ivfpq_index(s, seed=0)
    sc_h, ix_h = ivfpq_topk(q, index, 20, rerank=0)
    sc_f, ix_f = ivfpq_topk(q, index, 20, rerank=0, backend="fused")
    assert np.mean(np.asarray(ix_h) == np.asarray(ix_f)) > 0.99
    np.testing.assert_allclose(np.asarray(sc_f), np.asarray(sc_h),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# probed delta tier vs exact-scanned delta tier
# ---------------------------------------------------------------------------

def test_probed_delta_equals_exact_scan_at_full_coverage(clustered):
    """With every cluster probed AND a re-rank budget covering every
    candidate, both delta disciplines degenerate to the brute-force result
    over base + delta — the parity point that pins the probed tier's
    semantics.  (At partial probe the two differ by construction: the
    probed tier only scans delta sub-lists of probed centroids.)"""
    s, q = clustered
    extra = s[:150] + 0.01
    dyn = DynamicIVFIndex(build_ivfpq_index(s[150:], seed=0))
    dyn.append(extra)
    C = dyn.n_clusters
    k = 15
    rr = -(-dyn.n_rows // k) + 1            # rerank * k covers everything
    sc_e, ix_e = ivfpq_topk(q, dyn, k, nprobe=C, rerank=rr)
    sc_p, ix_p = ivfpq_topk(q, dyn, k, nprobe=C, rerank=rr, backend="fused")
    full = np.concatenate([s[150:], extra])
    sc_b, ix_b = knn_topk_reference(q, jnp.asarray(full), k)
    np.testing.assert_allclose(np.asarray(sc_p), np.asarray(sc_b),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sc_p), np.asarray(sc_e),
                               rtol=1e-4, atol=1e-5)
    assert np.mean(np.asarray(ix_p) == np.asarray(ix_b)) > 0.99


def test_probed_delta_raw_ivf_full_probe_parity(clustered):
    """Raw IVF has no shortlist stage, so full probe alone already makes
    probed == exact-scanned bitwise on ids."""
    s, q = clustered
    extra = s[:100] + 0.01
    dyn = DynamicIVFIndex(build_ivf_index(s[100:], seed=0))
    dyn.append(extra)
    sc_e, ix_e = ivf_topk(q, dyn, 15, nprobe=dyn.n_clusters)
    sc_p, ix_p = ivf_topk(q, dyn, 15, nprobe=dyn.n_clusters, backend="fused")
    np.testing.assert_array_equal(np.asarray(ix_e), np.asarray(ix_p))
    np.testing.assert_allclose(np.asarray(sc_p), np.asarray(sc_e),
                               rtol=1e-5, atol=1e-6)


def test_probed_delta_recall_near_exact_scan(clustered):
    """At the default operating point the probed tier gives up only the
    delta rows whose centroid a query does not probe — recall must stay
    within a few points of the exact scan's."""
    s, q = clustered
    extra = s[:250] + 0.01
    base = s[250:]
    k = 20
    full = np.concatenate([base, extra])
    _, exact_idx = knn_topk_reference(q, jnp.asarray(full), k)
    exact_sets = [set(r) for r in np.asarray(exact_idx)]

    def recall(ix):
        got = np.asarray(ix)
        return np.mean([len(exact_sets[i] & set(got[i])) / k
                        for i in range(len(got))])

    dyn = DynamicIVFIndex(build_ivfpq_index(base, seed=0))
    dyn.append(extra)
    _, ix_e = ivfpq_topk(q, dyn, k)
    _, ix_p = ivfpq_topk(q, dyn, k, backend="fused")
    r_e, r_p = recall(ix_e), recall(ix_p)
    assert r_p >= r_e - 0.05, (r_p, r_e)
    assert r_p >= 0.9, r_p


def test_appended_rows_retrievable_through_fused(clustered):
    """A freshly appended row is its own nearest neighbour through the
    probed tier, with an (exactly re-ranked) cosine score of ~1."""
    s, _ = clustered
    rng = np.random.default_rng(11)
    extra = rng.normal(size=(30, D)).astype(np.float32)
    dyn = DynamicIVFIndex(build_ivfpq_index(s, seed=0))
    ids = dyn.append(extra)
    qe = extra[:5] / np.linalg.norm(extra[:5], axis=1, keepdims=True)
    sc, ix = ivfpq_topk(jnp.asarray(qe), dyn, 5, backend="fused")
    got = np.asarray(ix)
    for i in range(5):
        assert ids[i] in got[i], (ids[i], got[i])
    np.testing.assert_allclose(np.asarray(sc)[:, 0], 1.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# background re-cluster
# ---------------------------------------------------------------------------

def test_background_recluster_matches_sync_bitwise(clustered):
    """The background build + atomic swap must land on the identical index
    a synchronous recluster produces (same seed replay), without blocking
    the caller."""
    s, q = clustered
    rng = np.random.default_rng(1)
    extra = rng.normal(size=(60, D)).astype(np.float32)
    dyn = DynamicIVFIndex(build_ivfpq_index(s, m=4, seed=2),
                          build_kw={"m": 4, "seed": 2})
    dyn.append(extra)
    t0 = time.time()
    dyn.recluster(sync=False)
    started = time.time() - t0
    assert dyn.recluster_pending or dyn.reclusters == 1
    dyn.join_recluster()
    assert dyn.reclusters == 1 and dyn.delta_rows == 0
    fresh = build_ivfpq_index(np.concatenate([s, extra]), m=4, seed=2)
    np.testing.assert_array_equal(dyn.base.codes_h, fresh.codes_h)
    np.testing.assert_array_equal(dyn.base.ids_h, fresh.ids_h)
    # the start itself must be quick (the build runs off-thread); generous
    # bound so slow CI machines don't flake
    assert started < 5.0, started
    # queries served mid-build and post-swap both work
    sc, ix = ivfpq_topk(q, dyn, 10, backend="fused")
    assert np.all(np.isfinite(np.asarray(sc)[:, 0]))


def test_background_recluster_keeps_mid_build_appends(clustered):
    """Rows appended while the rebuild is running stay in the delta tier
    after the swap, re-assigned to the new centroids, ids stable."""
    s, _ = clustered
    rng = np.random.default_rng(2)
    dyn = DynamicIVFIndex(build_ivf_index(s, seed=0), build_kw={"seed": 0})
    dyn.append(rng.normal(size=(40, D)).astype(np.float32))
    n_before = dyn.n_rows
    dyn.recluster(sync=False)
    late = rng.normal(size=(7, D)).astype(np.float32)
    ids = dyn.append(late)                 # may land before or after swap
    dyn.join_recluster()
    assert dyn.reclusters == 1
    assert dyn.n_rows == n_before + 7
    np.testing.assert_array_equal(ids, n_before + np.arange(7))
    if dyn.delta_rows:                     # appended mid-build: still served
        assert dyn.delta_rows == 7
        assert dyn.delta_assign.min() >= 0
        assert dyn.delta_assign.max() < dyn.n_clusters
    qe = late[:2] / np.linalg.norm(late[:2], axis=1, keepdims=True)
    _, ix = ivf_topk(jnp.asarray(qe), dyn, 3, backend="fused")
    got = np.asarray(ix)
    assert ids[0] in got[0] and ids[1] in got[1]


def test_partial_fit_background_never_blocks(ds):
    """`partial_fit(recluster='background')` returns while the compaction
    builds; the router keeps answering queries and converges to the
    compacted index."""
    r = KNNRouter(k=5, index="ivf", online=True, delta_cap=10).fit(ds)
    rng = np.random.default_rng(0)
    r.partial_fit(rng.normal(size=(12, ds.dim)).astype(np.float32),
                  rng.uniform(0, 1, (12, 3)).astype(np.float32),
                  recluster="background")
    s, c = r.predict_utility(ds.part("test")[0][:4])   # serves mid-build
    assert np.all(np.isfinite(s))
    r._ivf.join_recluster()
    assert r._ivf.reclusters == 1 and r._ivf.delta_rows == 0


# ---------------------------------------------------------------------------
# route_fused: bitwise parity with the legacy multi-dispatch path
# ---------------------------------------------------------------------------

def _service(ds, index, **kw):
    r = KNNRouter(k=7, index=index, **kw).fit(ds)
    return RouterService(r, {n: None for n in MODELS}, lam=0.5)


@pytest.mark.parametrize("index", ["exact", "ivf", "ivfpq"])
def test_route_fused_bitwise_parity(ds, index):
    """route_fused == the legacy chain (predict_with_confidence -> jitted
    utility -> jitted selection) BITWISE on choices, utilities, confidence,
    and resolved lambdas — for the default lambda, a scalar override, and a
    per-request vector."""
    svc = _service(ds, index)
    X = ds.part("test")[0][:32]
    rng = np.random.default_rng(7)
    for lam in (None, 1.3, rng.uniform(0, 2, 32).astype(np.float32)):
        cf, sf, chf, conf_f, lf = svc.route_fused(X, lam)
        cl, sl, chl, conf_l, ll = svc.route_legacy(X, lam)
        np.testing.assert_array_equal(cf, cl)
        np.testing.assert_array_equal(sf, sl)
        np.testing.assert_array_equal(chf, chl)
        np.testing.assert_array_equal(conf_f, conf_l)
        np.testing.assert_array_equal(lf, ll)


@pytest.mark.parametrize("index", ["exact", "ivf", "ivfpq"])
@pytest.mark.parametrize("nq", [1, 5, 13])
def test_route_fused_odd_batches(ds, index, nq):
    """batch=1 and batch sizes that are NOT multiples of the query tile
    must route bitwise like the legacy chain on every backend — the tile
    plans pad the query axis, and the padding lanes must never leak into
    real rows."""
    svc = _service(ds, index)
    X = ds.part("test")[0][:nq]
    rng = np.random.default_rng(nq)
    lam = rng.uniform(0, 2, nq).astype(np.float32)
    cf, sf, chf, conf_f, lf = svc.route_fused(X, lam)
    cl, sl, chl, conf_l, ll = svc.route_legacy(X, lam)
    assert cf.shape == (nq,) and sf.shape[0] == nq
    np.testing.assert_array_equal(cf, cl)
    np.testing.assert_array_equal(sf, sl)
    np.testing.assert_array_equal(chf, chl)
    np.testing.assert_array_equal(conf_f, conf_l)
    np.testing.assert_array_equal(lf, ll)


def test_route_fused_bitwise_parity_softmax_weights(ds):
    svc = _service(ds, "ivfpq", weights="softmax")
    X = ds.part("test")[0][:16]
    cf, sf, *_ = svc.route_fused(X, 0.8)
    cl, sl, *_ = svc.route_legacy(X, 0.8)
    np.testing.assert_array_equal(cf, cl)
    np.testing.assert_array_equal(sf, sl)


def test_route_fused_bitwise_parity_streaming(ds):
    """Mid-stream router (non-empty probed delta): both paths share the
    same fused retrieval, so parity must survive appends."""
    svc = _service(ds, "ivfpq", online=True, delta_cap=5000)
    rng = np.random.default_rng(3)
    svc.observe(rng.normal(size=(15, ds.dim)).astype(np.float32),
                rng.uniform(0, 1, (15, 3)).astype(np.float32))
    X = ds.part("test")[0][:24]
    cf, sf, chf, conf_f, _ = svc.route_fused(X, 0.4)
    cl, sl, chl, conf_l, _ = svc.route_legacy(X, 0.4)
    np.testing.assert_array_equal(cf, cl)
    np.testing.assert_array_equal(sf, sl)
    np.testing.assert_array_equal(chf, chl)
    np.testing.assert_array_equal(conf_f, conf_l)


def test_submit_texts_fallback_branch_parity(ds):
    """The confidence-fallback branch rides on route_fused's agreement
    output: with an unattainable floor every request re-routes to the
    fallback model, exactly as the legacy path did."""
    from repro.configs import get_config, reduced
    from repro.serving.engine import ServingEngine
    names = ["qwen3-4b", "mamba2-370m"]
    engines = {n: ServingEngine(reduced(get_config(n)), max_slots=2,
                                cache_len=48, seed=i)
               for i, n in enumerate(names)}
    texts = [f"topic {i % 4} example {i}" for i in range(60)]
    emb = encoder.embed_texts(texts)
    rng = np.random.default_rng(0)
    sds = RoutingDataset("fb", emb,
                         rng.uniform(0.2, 1.0, (60, 2)).astype(np.float32),
                         rng.uniform(0.001, 0.01, (60, 2)).astype(np.float32),
                         names)
    svc = RouterService(KNNRouter(k=3, index="ivfpq").fit(sds), engines,
                        lam=1.0, fallback_model=names[1],
                        confidence_floor=1.5)
    results = svc.submit_texts([f"probe {i}" for i in range(4)],
                               max_new_tokens=2)
    assert [r.model for r in results] == [names[1]] * 4
    assert all(r.confidence is not None and r.confidence < 1.5
               for r in results)


def test_route_fused_qmesh_sharding_bitwise(ds):
    """Sharding the batch axis over a (1-device here) mesh is exact — same
    bits as the unsharded fused path, including the padded-batch case."""
    from jax.sharding import Mesh
    svc = _service(ds, "ivfpq")
    mesh = Mesh(np.array(jax.devices()[:1]), ("q",))
    X = ds.part("test")[0][:13]            # not a multiple of anything
    cf, sf, chf, conf_f, _ = svc.route_fused(X, 0.7, qmesh=mesh)
    cu, su, chu, conf_u, _ = svc.route_fused(X, 0.7)
    np.testing.assert_array_equal(cf, cu)
    np.testing.assert_array_equal(sf, su)
    np.testing.assert_array_equal(chf, chu)
    np.testing.assert_array_equal(conf_f, conf_u)


BRANCH_BACKEND = {"fused": "fused", "tail": "host", "sharded": "fused"}


@pytest.mark.parametrize("m", [2, 3])
@pytest.mark.parametrize("nq", [1, 8])
@pytest.mark.parametrize("branch", ["fused", "tail", "sharded"])
@pytest.mark.parametrize("index", ["exact", "ivf", "ivfpq"])
def test_serve_fused_packs_one_buffer(ds, monkeypatch, index, branch, nq, m):
    """Every branch's program hands back ONE float32 (Q, 3 + 2M) buffer,
    and `serve_fused` splits it into today's five answers (dtypes and
    shapes as before), bitwise equal to the legacy chain's."""
    from jax.sharding import Mesh
    import repro.core.routers.knn as knn_mod
    models = MODELS[:m]
    sub = RoutingDataset("fused-m", ds.embeddings, ds.scores[:, :m],
                         ds.costs[:, :m], models)
    r = KNNRouter(k=7, index=index, backend=BRANCH_BACKEND[branch]).fit(sub)
    svc = RouterService(r, {n: None for n in models}, lam=0.5)
    mesh = (Mesh(np.array(jax.devices()[:1]), ("q",))
            if branch == "sharded" else None)
    seen = []
    for name in ("_serve_fused_jit", "_serve_tail_jit"):
        def spy(*a, _fn=getattr(knn_mod, name), _name=name, **kw):
            out = _fn(*a, **kw)
            seen.append((_name, out))
            return out
        monkeypatch.setattr(knn_mod, name, spy)
    X = sub.part("test")[0][:nq]
    lam = np.random.default_rng(nq + m).uniform(0, 2, nq).astype(np.float32)
    got = r.serve_fused(X, lam, qmesh=mesh)

    # the sharded branch's tail runs inside its traced program
    top = "_serve_fused_jit" if branch == "fused" else "_serve_tail_jit"
    outs = [o for name, o in seen if name == top]
    assert len(outs) == 1
    assert isinstance(outs[0], jax.Array)
    assert outs[0].shape == (nq, 3 + 2 * m)
    assert outs[0].dtype == jnp.float32

    assert len(got) == 5
    for a, dtype, shape in zip(got, (np.int32,) + (np.float32,) * 4,
                               ((nq,), (nq, m), (nq, m), (nq,), (nq,))):
        assert a.dtype == dtype and a.shape == shape
    choice, s_hat, c_hat, kth, agree = got
    cl, sl, chl, agree_l, _ = svc.route_legacy(X, lam)
    np.testing.assert_array_equal(choice, cl)
    np.testing.assert_array_equal(s_hat, sl)
    np.testing.assert_array_equal(c_hat, chl)
    np.testing.assert_array_equal(kth, r.predict_with_confidence(X)[2])
    np.testing.assert_array_equal(agree, agree_l)


def test_spec_backend_key(ds):
    r = make_router("knn5-ivfpq@backend=host")
    assert r.backend == "host" and r.exec_backend == "host"
    r2 = make_router("knn5-ivfpq")
    assert r2.backend is None and r2.exec_backend == "fused"
    r3 = make_router("knn5-ivf")
    assert r3.exec_backend == "host"
    with pytest.raises(ValueError, match="backend"):
        KNNRouter(backend="warp")


# ---------------------------------------------------------------------------
# micro-batch coalescing
# ---------------------------------------------------------------------------

def test_microbatcher_coalesces_into_one_dispatch(ds):
    """N submits -> one flush -> one routing dispatch, with per-request
    lambdas preserved and results identical to routing each text alone."""
    from repro.configs import get_config, reduced
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import MicroBatcher, WaveScheduler
    names = ["qwen3-4b", "mamba2-370m"]
    engines = {n: ServingEngine(reduced(get_config(n)), max_slots=2,
                                cache_len=48, seed=i)
               for i, n in enumerate(names)}
    texts = [f"topic {i % 4} example {i}" for i in range(60)]
    emb = encoder.embed_texts(texts)
    rng = np.random.default_rng(0)
    sds = RoutingDataset("mb", emb,
                         rng.uniform(0.2, 1.0, (60, 2)).astype(np.float32),
                         rng.uniform(0.001, 0.01, (60, 2)).astype(np.float32),
                         names)
    svc = RouterService(KNNRouter(k=3, index="ivfpq").fit(sds), engines,
                        lam=1.0)
    calls = {"n": 0}
    orig = svc.route_fused
    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)
    svc.route_fused = counting

    mb = MicroBatcher(svc, max_batch=16, max_new_tokens=2)
    reqs = [(f"coalesce probe {i}", None if i % 2 else 2.0) for i in range(6)]
    for t, lam in reqs:
        mb.submit(t, lam)
    assert mb.pending() == 6
    results = mb.flush()
    assert calls["n"] == 1                 # ONE dispatch for the wave
    assert mb.flushes == 1 and mb.routed == 6 and mb.pending() == 0
    # parity with routing each request alone (lams resolved identically)
    for (t, lam), res in zip(reqs, results):
        solo = svc.submit_texts([t], max_new_tokens=2, lam=lam)[0]
        assert res.model == solo.model
        assert res.lam == solo.lam
        np.testing.assert_equal(res.predicted_score, solo.predicted_score)

    # WaveScheduler integration: submit -> tick routes + admits + decodes
    sched = WaveScheduler(engines, batcher=MicroBatcher(svc, max_new_tokens=2))
    for t, lam in reqs:
        sched.submit_text(t, lam)
    assert sched.pending() == 6
    stats = sched.drain()
    assert stats.admitted == 6
    assert sched.pending() == 0


# ---------------------------------------------------------------------------
# code-major layout migration
# ---------------------------------------------------------------------------

def test_v2_fixture_codes_transposed_to_code_major():
    """The pinned v2 artifact stores row-major (C, L, MB) codes; loading
    must hand back a live code-major index whose code_bytes axis matches
    the PQ geometry."""
    from pathlib import Path
    from repro.core.routers import load_router
    path = Path(__file__).resolve().parent / "fixtures" / "artifact_v2"
    r = load_router(path)
    idx = r._ivf
    assert idx.codes_cm.shape == (idx.n_clusters, idx.code_bytes,
                                  idx.list_size)
    assert idx.code_bytes == idx.m * idx.nbits // 8
    # ADC still produces sane neighbours after the transpose
    rng = np.random.default_rng(0)
    X = rng.normal(size=(4, 8)).astype(np.float32)
    sims, ix = r._neighbors(X)
    assert np.all(np.isfinite(sims[:, 0])) and np.all(ix[:, 0] >= 0)
