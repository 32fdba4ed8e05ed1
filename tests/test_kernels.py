"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp oracles, interpret mode (kernel bodies execute on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# knn_topk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Q,N,D,k", [
    (8, 64, 32, 5), (128, 1024, 768, 10), (130, 1000, 64, 100),
    (4, 50, 16, 7), (16, 256, 128, 32),
])
def test_knn_topk_matches_reference(Q, N, D, k):
    from repro.kernels.knn_topk.ops import knn_topk
    from repro.kernels.knn_topk.ref import knn_topk_reference
    kq, ks = jax.random.split(jax.random.fold_in(KEY, Q * N + k))
    q = jax.random.normal(kq, (Q, D))
    q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    s = jax.random.normal(ks, (N, D))
    rs, ri = knn_topk_reference(q, s, min(k, N))
    ps, pi = knn_topk(q, s, k, use_pallas=True)
    np.testing.assert_allclose(np.asarray(ps), np.asarray(rs),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_knn_topk_dtypes(dtype):
    from repro.kernels.knn_topk.ops import knn_topk
    from repro.kernels.knn_topk.ref import knn_topk_reference
    q = jax.random.normal(KEY, (16, 64)).astype(dtype)
    s = jax.random.normal(jax.random.fold_in(KEY, 1), (128, 64)).astype(dtype)
    rs, _ = knn_topk_reference(q, s, 8)
    ps, _ = knn_topk(q, s, 8, use_pallas=True)
    np.testing.assert_allclose(np.asarray(ps), np.asarray(rs),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("Q,N,k", [
    (130, 1100, 10),     # Q and N both off the block grid -> padded tiles
    (1, 64, 5),          # single-query tile
    (16, 100, 100),      # k == N: every support row must appear
    (200, 1030, 17),     # N pad region larger than k
])
def test_knn_topk_block_boundaries(Q, N, k):
    """Padded query rows are dropped and padded support rows never leak into
    the returned indices, even when Q/N are not block multiples."""
    from repro.kernels.knn_topk.ops import knn_topk
    from repro.kernels.knn_topk.ref import knn_topk_reference
    kq, ks = jax.random.split(jax.random.fold_in(KEY, 7 * Q + N))
    q = jax.random.normal(kq, (Q, 32))
    q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    s = jax.random.normal(ks, (N, 32))
    rs, ri = knn_topk_reference(q, s, min(k, N))
    ps, pi = knn_topk(q, s, k, use_pallas=True)
    assert ps.shape == (Q, min(k, N)) and pi.shape == (Q, min(k, N))
    np.testing.assert_allclose(np.asarray(ps), np.asarray(rs),
                               rtol=1e-5, atol=1e-5)
    pi = np.asarray(pi)
    assert pi.min() >= 0 and pi.max() < N       # no padded-row indices
    if k >= N:                                  # k == N: exact row coverage
        assert all(set(row) == set(range(N)) for row in pi)


def test_knn_topk_duplicate_rows_tied_scores():
    """Duplicated support rows create exact score ties: top-k scores must
    match the reference and tied indices must all point at copies of the
    same row."""
    from repro.kernels.knn_topk.ops import knn_topk
    from repro.kernels.knn_topk.ref import knn_topk_reference
    base = jax.random.normal(KEY, (40, 16))
    s = jnp.concatenate([base, base], axis=0)          # every row duplicated
    q = jax.random.normal(jax.random.fold_in(KEY, 9), (6, 16))
    q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    rs, _ = knn_topk_reference(q, s, 10)
    ps, pi = knn_topk(q, s, 10, use_pallas=True)
    np.testing.assert_allclose(np.asarray(ps), np.asarray(rs),
                               rtol=1e-5, atol=1e-5)
    # an index and its duplicate refer to the same underlying row
    canon = np.asarray(pi) % 40
    rcanon = np.asarray(knn_topk_reference(q, s, 10)[1]) % 40
    assert all(set(a) == set(b) for a, b in zip(canon, rcanon))


# ---------------------------------------------------------------------------
# knn_ivf
# ---------------------------------------------------------------------------

def _clustered_support(key, n, d, n_centers=8, scale=3.0):
    kc, kn, ka = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (n_centers, d)) * scale
    assign = jax.random.randint(ka, (n,), 0, n_centers)
    return centers, centers[assign] + jax.random.normal(kn, (n, d))


@pytest.mark.parametrize("Q,N,D,k,nprobe", [
    (64, 512, 32, 10, 4),
    (33, 500, 16, 7, 3),      # Q off the tile grid -> padded query rows
    (1, 200, 16, 5, 2),       # single query
    (16, 300, 32, 300, 6),    # k > valid candidates -> -1/-inf tail slots
])
def test_ivf_kernel_matches_oracle(Q, N, D, k, nprobe):
    """The Pallas IVF kernel and both jnp backends must reproduce the
    per-query probing oracle exactly (same probe sets, same masks)."""
    from repro.kernels.knn_ivf.ops import build_ivf_index, ivf_topk
    from repro.kernels.knn_ivf.ref import ivf_topk_reference
    key = jax.random.fold_in(KEY, Q * N + k)
    centers, s = _clustered_support(key, N, D)
    q = centers[jax.random.randint(jax.random.fold_in(key, 1), (Q,), 0, 8)] \
        + jax.random.normal(jax.random.fold_in(key, 2), (Q, D))
    q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    index = build_ivf_index(s, seed=0)
    os_, oi = ivf_topk_reference(q, index.centroids, index.sup_cm,
                                 index.ids_cm, k, nprobe)
    for backend in ("host", "tiles", "pallas"):
        bs, bi = ivf_topk(q, index, k, nprobe=nprobe, backend=backend)
        np.testing.assert_allclose(np.asarray(bs), np.asarray(os_),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"backend={backend}")
        bi = np.asarray(bi)
        assert ((bi >= 0) & (bi < N) | (bi == -1)).all(), backend
        # -1 exactly where the oracle has no candidate
        np.testing.assert_array_equal(bi == -1, np.asarray(oi) == -1)


def test_ivf_kernel_empty_slots_stay_minus_one():
    """Regression: when a query has fewer valid candidates than k and its
    LAST probed list is exactly full (no -1 padding rows), the kernel's
    empty tail slots must still be -1/NEG — masked candidates must not leak
    their row ids."""
    from repro.kernels.knn_ivf.kernel import ivf_topk_pallas
    L, D = 8, 16
    rng = np.random.default_rng(0)
    sup_cm = jnp.asarray(rng.normal(size=(2, L, D)).astype(np.float32))
    ids_cm = jnp.asarray(np.array(
        [[0, 1, 2] + [-1] * 5,                   # list 0: 3 rows + padding
         list(range(3, 3 + L))], np.int32))      # list 1: exactly full
    inv_cm = jnp.where(ids_cm >= 0,
                       jax.lax.rsqrt(jnp.sum(sup_cm ** 2, -1) + 1e-12), 0.0)
    q = jnp.asarray(rng.normal(size=(1, D)).astype(np.float32))
    q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    k = 12                                       # > 11 valid candidates
    scores, idx = ivf_topk_pallas(
        q, sup_cm, ids_cm, inv_cm,
        q_probe=jnp.array([[0, 1]], jnp.int32),
        tile_probe=jnp.array([[0, 1]], jnp.int32),
        tile_valid=jnp.array([[1, 1]], jnp.int32), k=k)
    idx = np.asarray(idx)[0]
    assert set(idx[:11]) == set(range(11))       # all real rows surface once
    assert (idx[11:] == -1).all()                # no leaked ids in the tail


def test_ivf_padded_lists_never_leak():
    """List padding rows (ids_cm == -1) must never surface as indices even
    when k spans whole probed lists."""
    from repro.kernels.knn_ivf.ops import build_ivf_index, ivf_topk
    key = jax.random.fold_in(KEY, 123)
    _, s = _clustered_support(key, 257, 16)      # odd N -> ragged lists
    q = jax.random.normal(jax.random.fold_in(key, 1), (9, 16))
    q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    index = build_ivf_index(s, n_clusters=5, seed=0)
    for backend in ("host", "tiles", "pallas"):
        sc, ix = ivf_topk(q, index, index.list_size, nprobe=2,
                          backend=backend)
        ix, sc = np.asarray(ix), np.asarray(sc)
        assert ix.max() < 257
        assert np.isneginf(sc[ix == -1]).all()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,causal,window", [
    (2, 128, 4, 2, 64, True, 0),
    (1, 256, 8, 8, 32, True, 0),
    (2, 128, 4, 1, 64, True, 64),
    (1, 64, 2, 2, 16, False, 0),
    (2, 256, 4, 2, 64, True, 100),
    (1, 512, 2, 1, 128, True, 128),
])
def test_flash_attention_matches_reference(B, S, H, KV, hd, causal, window):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_reference
    ks = jax.random.split(jax.random.fold_in(KEY, hash((B, S, H, window)) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    ref = flash_attention_reference(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_reference
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 64)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(jnp.bfloat16)
    ref = flash_attention_reference(q, k, v, causal=True, window=0)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,pos,ring", [
    (2, 1024, 8, 2, 64, 500, False),
    (1, 512, 4, 4, 32, 511, False),
    (2, 256, 8, 1, 64, 700, True),
    (2, 256, 8, 1, 64, 100, True),
    (1, 2048, 16, 2, 128, 0, False),
])
def test_decode_attention_matches_reference(B, S, H, KV, hd, pos, ring):
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_reference
    ks = jax.random.split(jax.random.fold_in(KEY, hash((B, S, pos)) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    ck = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    cv = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    ref = decode_attention_reference(q, ck, cv, jnp.int32(pos), ring=ring)
    out = decode_attention(q, ck, cv, jnp.int32(pos), ring=ring)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,G,N,chunk,use_init", [
    (2, 64, 4, 16, 1, 8, 16, False),
    (1, 128, 8, 32, 2, 16, 32, False),
    (2, 64, 4, 16, 1, 8, 16, True),
    (1, 256, 2, 64, 1, 128, 64, False),
])
def test_ssd_scan_matches_reference(B, S, H, P, G, N, chunk, use_init):
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_reference
    ks = jax.random.split(jax.random.fold_in(KEY, hash((B, S, H, chunk)) % 2**31), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    init = jax.random.normal(ks[5], (B, H, P, N)) if use_init else None
    yr, hr = ssd_reference(x, dt, A, Bm, Cm, chunk=chunk, initial_state=init)
    yk, hk = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, initial_state=init)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                               rtol=3e-4, atol=3e-4)


def test_ssd_reference_matches_naive_recurrence():
    from repro.kernels.ssd_scan.ref import ssd_reference

    def naive(x, dt, A, Bm, Cm):
        B_, S, H, P = x.shape
        G, N = Bm.shape[2], Bm.shape[3]
        rep = H // G
        Bh = jnp.repeat(Bm, rep, 2)
        Ch = jnp.repeat(Cm, rep, 2)
        h = jnp.zeros((B_, H, P, N))
        ys = []
        for t in range(S):
            h = (h * jnp.exp(dt[:, t] * A)[..., None, None]
                 + jnp.einsum("bh,bhn,bhp->bhpn", dt[:, t], Bh[:, t], x[:, t]))
            ys.append(jnp.einsum("bhpn,bhn->bhp", h, Ch[:, t]))
        return jnp.stack(ys, 1), h

    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (1, 32, 2, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 32, 2)))
    A = -jnp.exp(jax.random.normal(ks[2], (2,)) * 0.3)
    Bm = jax.random.normal(ks[3], (1, 32, 1, 4)) * 0.3
    Cm = jax.random.normal(ks[4], (1, 32, 1, 4)) * 0.3
    yn, hn = naive(x, dt, A, Bm, Cm)
    yr, hr = ssd_reference(x, dt, A, Bm, Cm, chunk=8)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yn),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hr), np.asarray(hn),
                               rtol=1e-4, atol=1e-4)
