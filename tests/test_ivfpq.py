"""IVF-PQ retrieval tier: PQ pack/encode round-trips, ADC backend parity
against the decode oracle, re-rank recall properties, shortlist padding
semantics, router/serving/artifact integration, and the compiled-path
(lane_pad=128, non-interpret) smoke that auto-skips off-TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.routers.knn import KNNRouter
from repro.data.prices import ROUTERBENCH
from repro.data.synthetic import GenSpec, generate
from repro.kernels.knn_ivf import pq
from repro.kernels.knn_ivf.ops import (DEFAULT_NPROBE, build_ivf_index,
                                       build_ivfpq_index, ivf_topk,
                                       ivfpq_topk)
from repro.kernels.knn_ivf.ref import ivfpq_adc_reference
from repro.kernels.knn_topk.ref import knn_topk_reference

K = 20


@pytest.fixture(scope="module")
def clustered():
    """Synthetic clustered support + queries from the same mixture (the
    paper's locality regime), with the exact top-K ground truth."""
    rng = np.random.default_rng(7)
    centers = rng.normal(size=(12, 48)) * 3.0
    s = (centers[rng.integers(0, 12, 3000)]
         + rng.normal(size=(3000, 48))).astype(np.float32)
    q = (centers[rng.integers(0, 12, 150)]
         + rng.normal(size=(150, 48))).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    qj = jnp.asarray(q)
    index = build_ivfpq_index(s, seed=0)
    _, exact_idx = knn_topk_reference(qj, jnp.asarray(s), K)
    exact_sets = [set(row) for row in np.asarray(exact_idx)]
    return qj, s, index, exact_sets


# ---------------------------------------------------------------------------
# PQ primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbits", [4, 8])
def test_pack_unpack_round_trip(nbits):
    rng = np.random.default_rng(0)
    m = 8
    codes = rng.integers(0, 2 ** nbits, size=(64, m)).astype(np.uint8)
    packed = pq.pack_codes(codes, nbits)
    assert packed.shape == (64, m * nbits // 8)
    np.testing.assert_array_equal(pq.unpack_codes(packed, m, nbits), codes)
    np.testing.assert_array_equal(
        np.asarray(pq.unpack_codes_jnp(jnp.asarray(packed), m, nbits)), codes)


def test_effective_m_divides():
    assert pq.effective_m(48, 10) == 8       # 10 does not divide 48
    assert pq.effective_m(64, 16) == 16
    assert pq.effective_m(48, 5) == 4
    assert pq.default_m(768) == 64           # D/8 capped at 64 subspaces


def test_encode_decode_reduces_error():
    """Decoding the codes must reconstruct residuals better than the zero
    baseline (the anchor alone) — the basic PQ fidelity property."""
    rng = np.random.default_rng(1)
    r = rng.normal(size=(800, 32)).astype(np.float32)
    cb = pq.train_pq(r, m=4, nbits=8, seed=0)
    rec = pq.decode_pq(pq.encode_pq(r, cb), cb)
    assert np.mean(np.square(r - rec)) < 0.5 * np.mean(np.square(r))


# ---------------------------------------------------------------------------
# ADC backend parity + shortlist semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["host", "tiles", "pallas"])
def test_adc_backends_match_decode_oracle(clustered, backend):
    """Every ADC backend must match the decode-based oracle (which shares no
    scoring code with them): same candidate ids, same scores up to fp
    reassociation of the subspace partial sums."""
    q, _, index, _ = clustered
    os, oi = ivfpq_adc_reference(
        q, index.centroids, index.anchors, index.codebooks, index.codes_cm,
        index.ids_cm, index.inv_cm, K, DEFAULT_NPROBE, index.m, index.nbits)
    sc, ix = ivfpq_topk(q, index, K, nprobe=DEFAULT_NPROBE, rerank=0,
                        backend=backend)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(os),
                               rtol=1e-4, atol=1e-5)
    assert np.mean(np.asarray(ix) == np.asarray(oi)) > 0.99


def test_rerank_monotonically_improves_recall(clustered):
    """The re-rank shortlists are nested in ``rerank`` and stage 2 is exact,
    so recall@k can only grow with the multiplier — and must clear the
    acceptance floor at the default."""
    q, _, index, exact_sets = clustered
    recalls = []
    for rr in (0, 1, 2, 4, 8, 16):
        _, ix = ivfpq_topk(q, index, K, nprobe=DEFAULT_NPROBE, rerank=rr)
        got = np.asarray(ix)
        recalls.append(np.mean([len(exact_sets[i] & set(got[i])) / K
                                for i in range(len(got))]))
    assert all(b >= a - 1e-12 for a, b in zip(recalls, recalls[1:])), recalls
    assert recalls[-1] >= 0.95, recalls


def test_reranked_scores_are_exact(clustered):
    """Stage 2 re-scores against the raw rows with the exact-scan formula,
    so every returned score must equal the brute-force score of its row."""
    q, s, index, _ = clustered
    es, ei = knn_topk_reference(q, jnp.asarray(s), len(s))
    sc, ix = ivfpq_topk(q, index, K, nprobe=DEFAULT_NPROBE, rerank=4)
    sc, ix = np.asarray(sc), np.asarray(ix)
    full = np.zeros((len(q), len(s)), np.float32)
    np.put_along_axis(full, np.asarray(ei), np.asarray(es), axis=1)
    valid = ix >= 0
    np.testing.assert_allclose(sc[valid],
                               np.take_along_axis(full, ix, axis=1)[valid],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["host", "tiles", "pallas"])
def test_short_list_padding_matches_ivf_contract(backend):
    """With fewer valid candidates than k, the tail slots must carry
    -inf / -1 exactly like the IVF backends — and valid slots must agree
    with plain IVF on ids (both probe the same single list)."""
    rng = np.random.default_rng(3)
    s = rng.normal(size=(40, 16)).astype(np.float32)
    q = rng.normal(size=(9, 16)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    qj = jnp.asarray(q)
    kbig = 32                               # > any single list's row count
    pq_index = build_ivfpq_index(s, n_clusters=6, m=4, seed=0)
    ivf_index = build_ivf_index(s, n_clusters=6, seed=0)
    sc, ix = ivfpq_topk(qj, pq_index, kbig, nprobe=1, rerank=4,
                        backend=backend)
    sc_i, ix_i = ivf_topk(qj, ivf_index, kbig, nprobe=1)
    sc, ix = np.asarray(sc), np.asarray(ix)
    ix_i = np.asarray(ix_i)
    assert (ix >= 0).any() and (ix == -1).any()
    np.testing.assert_array_equal(ix == -1, ix_i == -1)   # same slot counts
    assert np.all(np.isneginf(sc[ix == -1]))
    # with exact re-ranking of a full single-list shortlist the surviving
    # ids are the list's rows — identical SETS to the raw-row IVF backend
    for r_pq, r_iv in zip(ix, ix_i):
        assert set(r_pq[r_pq >= 0]) == set(r_iv[r_iv >= 0])


def test_nbits4_packs_two_codes_per_byte(clustered):
    q, s, _, exact_sets = clustered
    index4 = build_ivfpq_index(s, m=8, nbits=4, seed=0)
    assert index4.code_bytes == 4           # 8 codes packed into 4 bytes
    _, ix = ivfpq_topk(q, index4, K, nprobe=DEFAULT_NPROBE, rerank=8)
    got = np.asarray(ix)
    rec = np.mean([len(exact_sets[i] & set(got[i])) / K
                   for i in range(len(got))])
    assert rec >= 0.6, rec                  # coarse codes, exact re-rank


def test_adc_pallas_nbits4_matches_decode_oracle(clustered):
    """The Pallas ADC kernel unpacks two 4-bit codes per byte row; its
    shortlist must match the decode oracle exactly like the 8-bit case."""
    q, s, _, _ = clustered
    index4 = build_ivfpq_index(s, m=8, nbits=4, seed=0)
    os, oi = ivfpq_adc_reference(
        q, index4.centroids, index4.anchors, index4.codebooks,
        index4.codes_cm, index4.ids_cm, index4.inv_cm, K, DEFAULT_NPROBE,
        index4.m, index4.nbits)
    sc, ix = ivfpq_topk(q, index4, K, nprobe=DEFAULT_NPROBE, rerank=0,
                        backend="pallas")
    np.testing.assert_allclose(np.asarray(sc), np.asarray(os),
                               rtol=1e-4, atol=1e-5)
    assert np.mean(np.asarray(ix) == np.asarray(oi)) > 0.99


def test_index_bytes_accounting(clustered):
    """The hot PQ index must be several times smaller than the raw-row IVF
    index over the same partition (the ~16x claim, reduced by the shared
    ids/inv overhead at this tiny scale)."""
    _, s, index, _ = clustered
    ivf_index = build_ivf_index(s, seed=0)
    assert ivf_index.index_bytes / index.index_bytes > 2.0
    assert index.codes_h.nbytes * 30 < ivf_index.sup_h.nbytes  # rows: 32x


# ---------------------------------------------------------------------------
# lane_pad build parameter + compiled-path smoke
# ---------------------------------------------------------------------------

def test_lane_pad_is_a_build_parameter():
    rng = np.random.default_rng(5)
    s = rng.normal(size=(600, 16)).astype(np.float32)
    for build in (build_ivf_index, build_ivfpq_index):
        idx = build(s, lane_pad=128, seed=0)
        assert idx.list_size % 128 == 0
        idx8 = build(s, seed=0)
        assert idx8.list_size % 8 == 0 and idx8.list_size < idx.list_size


@pytest.mark.skipif(jax.devices()[0].platform != "tpu",
                    reason="compiled (non-interpret) Pallas needs a TPU")
@pytest.mark.parametrize("tier", ["ivf", "ivfpq"])
def test_pallas_compiled_smoke_on_tpu(tier):
    """Non-interpret Mosaic lowering of both retrieval kernels with
    lane-aligned lists; parity against the host backend."""
    rng = np.random.default_rng(11)
    centers = rng.normal(size=(8, 128)) * 3.0
    s = (centers[rng.integers(0, 8, 4096)]
         + rng.normal(size=(4096, 128))).astype(np.float32)
    q = (centers[rng.integers(0, 8, 128)]
         + rng.normal(size=(128, 128))).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    qj = jnp.asarray(q)
    if tier == "ivf":
        index = build_ivf_index(s, lane_pad=128, seed=0)
        run = lambda be, **kw: ivf_topk(qj, index, 16, nprobe=4,
                                        backend=be, **kw)
    else:
        index = build_ivfpq_index(s, lane_pad=128, m=16, seed=0)
        run = lambda be, **kw: ivfpq_topk(qj, index, 16, nprobe=4, rerank=4,
                                          backend=be, **kw)
    sc_c, ix_c = run("pallas")
    sc_h, ix_h = run("host")
    np.testing.assert_allclose(np.asarray(sc_c), np.asarray(sc_h),
                               rtol=1e-4, atol=1e-5)
    assert np.mean(np.asarray(ix_c) == np.asarray(ix_h)) > 0.99


# ---------------------------------------------------------------------------
# router / serving / artifact integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ds():
    return generate(GenSpec(name="ivfpq", models=ROUTERBENCH["RouterBench"],
                            n_queries=900, seed=13))


def test_router_ivfpq_auc_within_tolerance(ds):
    from repro.core import eval as E
    exact = E.utility_auc(KNNRouter(k=50).fit(ds), ds)["auc"]
    pq_auc = E.utility_auc(KNNRouter(k=50, index="ivfpq").fit(ds), ds)["auc"]
    assert abs(exact - pq_auc) < 1.5, (exact, pq_auc)
    assert pq_auc > E.random_auc(ds)["auc"] + 10


def test_router_predict_with_confidence_single_retrieval(ds):
    """The fused call must return the same numbers as the two separate
    calls while running exactly ONE neighbour search."""
    r = KNNRouter(k=10, index="ivfpq").fit(ds)
    X = ds.part("test")[0]
    s1, c1 = r.predict_utility(X)
    kth1, agree1 = r.confidence(X)

    calls = {"n": 0}
    orig = r._neighbors
    r._neighbors = lambda X: (calls.__setitem__("n", calls["n"] + 1)
                              or orig(X))
    s2, c2, kth2, agree2 = r.predict_with_confidence(X)
    assert calls["n"] == 1
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(c1, c2)
    np.testing.assert_array_equal(kth1, kth2)
    np.testing.assert_array_equal(agree1, agree2)


def test_artifact_round_trip_bitwise_adc(ds, tmp_path):
    """PQ codebooks + packed codes + cold rows through save/load: ADC
    shortlist scores (rerank=0, pure table arithmetic) and the re-ranked
    utilities must both come back BITWISE identical."""
    from repro.core.routers import load_router, save_router
    r = KNNRouter(k=10, index="ivfpq", rerank=0).fit(ds)
    X = ds.part("test")[0][:32]
    sc1, ix1 = r._neighbors(X)
    s1, c1 = r.predict_utility(X)
    path = save_router(r, tmp_path / "pq")
    # the cold tier already holds every raw row — _X must not be stored twice
    assert "_X" not in np.load(path / "state.npz").files
    r2 = load_router(path)
    np.testing.assert_array_equal(r2._X, r._X)   # rebuilt from the cold tier
    assert r2._ivf.m == r._ivf.m and r2._ivf.nbits == r._ivf.nbits
    sc2, ix2 = r2._neighbors(X)
    np.testing.assert_array_equal(sc1, sc2)     # bitwise ADC scores
    np.testing.assert_array_equal(ix1, ix2)
    s2, c2 = r2.predict_utility(X)
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(c1, c2)


def test_service_ivfpq_single_pass(ds):
    """`RouterService.submit_texts` over an ivfpq router: ONE fused
    dispatch per batch feeds routing AND confidence — no separate
    `_neighbors` retrieval happens at all."""
    from repro.configs import get_config, reduced
    from repro.core.dataset import RoutingDataset
    from repro.serving import encoder
    from repro.serving.engine import ServingEngine
    from repro.serving.router_service import knn_service

    names = ["qwen3-4b", "mamba2-370m"]
    engines = {n: ServingEngine(reduced(get_config(n)), max_slots=2,
                                cache_len=48, seed=i)
               for i, n in enumerate(names)}
    texts = [f"topic {i % 4} example {i}" for i in range(80)]
    emb = encoder.embed_texts(texts)
    rng = np.random.default_rng(0)
    sds = RoutingDataset("svc", emb,
                         rng.uniform(0.2, 1.0, (80, 2)).astype(np.float32),
                         rng.uniform(0.001, 0.01, (80, 2)).astype(np.float32),
                         names)
    svc = knn_service(sds, engines, k=5, index="ivfpq", lam=1.0)
    assert svc.retrieval_backend == "ivfpq"

    calls = {"fused": 0, "neighbors": 0}
    orig_sf = svc.router.serve_fused
    svc.router.serve_fused = lambda *a, **kw: (
        calls.__setitem__("fused", calls["fused"] + 1) or orig_sf(*a, **kw))
    orig_nb = svc.router._neighbors
    svc.router._neighbors = lambda X: (
        calls.__setitem__("neighbors", calls["neighbors"] + 1) or orig_nb(X))
    results = svc.serve_texts(["topic 1 question", "topic 2 question"],
                              max_new_tokens=3)
    assert calls["fused"] == 1               # ONE dispatch for the batch
    assert calls["neighbors"] == 0           # and no staged retrieval
    assert all(r.request.done for r in results)
    assert all(r.confidence is not None for r in results)
