"""IVF index build + public dispatcher for approximate kNN retrieval.

``build_ivf_index`` fits a spherical k-means coarse quantizer (numpy Lloyd
iterations — this runs once at ``KNNRouter.fit`` time) and lays the support
set out cluster-major: ``sup_cm (C, L, D)`` raw rows zero-padded to the list
length L, ``ids_cm (C, L)`` original row ids with -1 padding, and
``inv_cm (C, L)`` precomputed inverse row norms (so queries never re-reduce
N*D elements).  Oversized clusters are recursively halved along their top
principal direction until every list fits ``balance * N/C`` rows: L — and
with it the per-probe gather/DMA volume — is bounded by the MEAN list size,
not the worst k-means cell.

``ivf_topk`` probes each query's top-``nprobe`` centroids and scores only
those lists.  Both execution paths share one tiling strategy: queries are
SORTED by their primary cluster so that a tile of ``block_q`` queries probes
few distinct lists, the per-tile slot lists (deduplicated union, padded to a
static width S) are planned on the host, and then

  * the jnp path gathers each tile's slot lists once and scores them with a
    single batched matmul (tile-coherent inverted traversal);
  * the Pallas path scalar-prefetches the slot lists so the kernel DMAs
    exactly the probed blocks (`kernel.py`).

Per-query cost is O(nprobe * L * D) against the brute-force O(N * D);
``nprobe == n_clusters`` recovers the exact result.

``build_ivfpq_index`` / ``ivfpq_topk`` add the product-quantized tier on the
SAME coarse partition and tiling plan: hot lists hold packed uint8 codes
(`pq.py`, stored CODE-MAJOR ``(C, MB, L)`` so the long L axis sits in the
lane dimension for compiled DMA) scored by ADC table lookups (host gathers
/ jitted tiles / `pq_kernel.py`), and a shortlist of ``rerank * k`` ADC
candidates is re-scored exactly against the raw rows kept as a flat cold
tier — two-stage search that trades ~16x hot HBM for a ~rerank*k-row gather
per query.

``backend="fused"`` is the serving hot path: probe, ADC scan, shortlist
selection, AND the exact re-rank run inside ONE jitted call — no host-side
tile planning, no second host->device hop for the re-rank gather.  The
router/serving layers default to it for IVF-PQ; the ``host`` traversal
remains the CPU reference/debug fallback and stays the default of the
ops-level entry points so oracle tests keep their exact semantics.

``DynamicIVFIndex`` converts either frozen index into a STREAMING one: new
rows are assigned to their nearest coarse centroid and accumulate in a
delta tier that every ``ivf_topk`` / ``ivfpq_topk`` call merges into its
shortlist.  The host/tiles/pallas backends scan the delta exactly (appended
rows are retrieved with exact scores, so the tier can only help recall);
the fused backend instead PROBES per-centroid delta sub-lists inside the
same single dispatch — delta rows are laid out cluster-major (and, over a
PQ base, encoded with the existing codebooks) so the streaming index query
cost stays at the base index's operating point instead of adding an
O(Q * delta) exact scan.  ``recluster()`` compacts the delta into a freshly
re-trained coarse partition (and PQ codebooks) once it exceeds
``delta_cap`` — synchronously, or on a background thread with an atomic
index swap (``sync=False``) so compaction never stalls a serving query.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels, persist
from . import pq as pqmod
from .kernel import ivf_topk_pallas
from .pq_kernel import ivfpq_adc_pallas
from .ref import ivf_probe

DEFAULT_NPROBE = 8
# ADC shortlist multiplier: at corpus scale (1e5+ rows) within-cluster score
# gaps shrink while quantization error does not, so the shortlist needs
# headroom — 8x restores recall@100 > 0.95 at m=D/4 (benchmarks/ivf_recall)
DEFAULT_RERANK = 8


def default_lane_pad() -> int:
    """List-length rounding the builders use when none is given: 128
    lane-aligns every list where the Pallas kernels compile (TPU), 8 keeps
    interpreted (CPU) indexes compact."""
    return 8 if kernels.interpret_mode() else 128


@dataclasses.dataclass(frozen=True)
class IVFIndex:
    """Immutable retrieval index over one support set.  Device (jnp) arrays
    feed the Pallas / tiled-XLA / sharded paths; the host (numpy) mirrors —
    zero extra build cost, the index is assembled in numpy anyway — feed the
    CPU inverted-traversal backend without a device round-trip."""
    centroids: jnp.ndarray     # (C, D) f32, unit-norm
    sup_cm: jnp.ndarray        # (C, L, D) f32, raw rows, zero padding
    ids_cm: jnp.ndarray        # (C, L) i32, -1 padding
    inv_cm: jnp.ndarray        # (C, L) f32, 1/||row||, 0 padding
    n_rows: int                # valid support rows
    sup_h: np.ndarray          # host mirror of sup_cm
    ids_h: np.ndarray          # host mirror of ids_cm
    inv_h: np.ndarray          # host mirror of inv_cm

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def list_size(self) -> int:
        return self.sup_cm.shape[1]

    @property
    def index_bytes(self) -> int:
        """Hot (per-probe-scanned) storage: raw lists + ids + norms +
        centroids."""
        return int(self.sup_h.nbytes + self.ids_h.nbytes + self.inv_h.nbytes
                   + np.asarray(self.centroids).nbytes)

    def rows(self) -> np.ndarray:
        """Raw support rows in ORIGINAL row order — the inverse of the
        cluster-major scatter, float-exact copies.  The single source of the
        un-scatter invariant (artifact reload and the streaming tier both
        rebuild the flat support from it)."""
        X = np.empty((self.n_rows, self.sup_h.shape[2]), np.float32)
        X[self.ids_h[self.ids_h >= 0]] = self.sup_h[self.ids_h >= 0]
        return X


@dataclasses.dataclass(frozen=True)
class IVFPQIndex:
    """Product-quantized IVF index: same coarse partition as `IVFIndex`, but
    the hot lists store packed PQ codes of cluster residuals instead of raw
    rows (~16x less HBM and per-probe DMA at m=D/8).  The raw rows survive
    only as the flat cold tier ``sup_flat`` that exact re-ranking reads for
    a shortlist of ~rerank*k rows per query (see `pq.py` for the ADC math).
    Device arrays feed the Pallas/tiles/sharded paths; host mirrors feed the
    CPU traversal without a device round-trip.

    The packed code lists are stored CODE-MAJOR ``(C, MB, L)``: the long
    list axis L sits in the minor (lane) dimension, so a compiled per-probe
    block DMA moves MB lane-aligned rows of L bytes instead of L rows of MB
    bytes — the lane-efficient layout the Pallas ADC kernel is built around
    (`pq_kernel.py`)."""
    centroids: jnp.ndarray     # (C, D) f32, unit-norm coarse quantizer
    anchors: jnp.ndarray       # (C, D) f32, raw-space list means
    codes_cm: jnp.ndarray      # (C, MB, L) u8, packed PQ codes, 0 padding
    ids_cm: jnp.ndarray        # (C, L) i32, -1 padding
    inv_cm: jnp.ndarray        # (C, L) f32, EXACT 1/||row||, 0 padding
    codebooks: jnp.ndarray     # (m, 2^nbits, D/m) f32
    sup_flat: jnp.ndarray      # (N, D) f32 raw rows, original order (cold)
    n_rows: int
    m: int                     # subspaces actually used (divides D)
    nbits: int                 # 4 or 8
    codes_h: np.ndarray        # host mirrors of the hot lists
    ids_h: np.ndarray
    inv_h: np.ndarray
    anchors_h: np.ndarray
    codebooks_h: np.ndarray
    sup_flat_h: np.ndarray

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def list_size(self) -> int:
        return self.codes_cm.shape[2]

    @property
    def code_bytes(self) -> int:
        """Packed bytes per row (m*nbits/8)."""
        return self.codes_cm.shape[1]

    def rows(self) -> np.ndarray:
        """Raw support rows in ORIGINAL row order — the flat cold tier is
        already stored that way (same array, same bytes)."""
        return self.sup_flat_h

    @functools.cached_property
    def codes_rm_h(self) -> np.ndarray:
        """Row-major ``(C, L, MB)`` HOST mirror of the packed lists,
        derived once and cached — the CPU traversal reads per-row codes,
        so re-transposing each probed cluster's block on every call would
        pay an O(L*MB) copy per probe per query batch."""
        return np.ascontiguousarray(self.codes_h.transpose(0, 2, 1))

    @functools.cached_property
    def codes_rm(self) -> jnp.ndarray:
        """Row-major ``(C, L, MB)`` device mirror of the packed lists,
        derived once and cached.  The canonical storage (and the artifact)
        is code-major — the Pallas kernel's lane-aligned DMA layout — but
        the fused XLA path's flat-take ADC scan wants the m subspace codes
        of a row adjacent (gather + reduce over the MINOR axis); scanning
        the code-major blocks directly costs ~3x in strided reduces.  At
        ~m bytes/row the mirror is a rounding error next to the cold
        tier."""
        return jnp.asarray(self.codes_rm_h)

    @functools.cached_property
    def inv_flat(self) -> jnp.ndarray:
        """Exact stored inverse row norms in ORIGINAL row order (N,) — the
        fused path's re-rank multiplies by these instead of re-reducing the
        gathered rows (one (Q, kk) gather replaces a (Q, kk, D) square-sum),
        and they are float-identical to the per-list ``inv_cm`` entries."""
        inv = np.zeros(self.n_rows, np.float32)
        inv[self.ids_h[self.ids_h >= 0]] = self.inv_h[self.ids_h >= 0]
        return jnp.asarray(inv)

    @property
    def index_bytes(self) -> int:
        """Hot (per-probe-scanned) storage: packed codes + ids + norms +
        centroids + anchors + codebooks.  ``sup_flat`` is the cold re-rank
        tier and is NOT counted — it is touched only for ~rerank*k rows per
        query and can live off-device."""
        return int(self.codes_h.nbytes + self.ids_h.nbytes + self.inv_h.nbytes
                   + np.asarray(self.centroids).nbytes + self.anchors_h.nbytes
                   + self.codebooks_h.nbytes)


def default_n_clusters(n_rows: int) -> int:
    """~sqrt(N) lists — the classical IVF balance point where probe cost
    (nprobe * N/C) and quantizer cost (C) meet."""
    return int(np.clip(round(math.sqrt(max(n_rows, 1))), 1, 4096))


def _spherical_kmeans(xn: np.ndarray, n_clusters: int, seed: int,
                      iters: int) -> tuple[np.ndarray, np.ndarray]:
    """Lloyd iterations on unit-norm rows with cosine assignment.  Empty
    clusters are reseeded from the rows worst-served by their centroid."""
    rng = np.random.default_rng(seed)
    n = len(xn)
    cent = xn[rng.choice(n, size=n_clusters, replace=False)].copy()
    assign = np.zeros(n, np.int64)
    for _ in range(iters):
        cs = xn @ cent.T                        # (N, C)
        assign = np.argmax(cs, axis=1)
        best = cs[np.arange(n), assign]
        worst = np.argsort(best, kind="stable") # rows worst-served first
        w = 0
        for c in range(n_clusters):
            members = assign == c
            if not members.any():
                # reseed each empty cluster from a DISTINCT worst-served row
                # (a shared reseed row would keep the duplicates collapsed)
                cent[c] = xn[worst[w]]
                w += 1
                continue
            m = xn[members].mean(axis=0)
            cent[c] = m / max(float(np.linalg.norm(m)), 1e-12)
    assign = np.argmax(xn @ cent.T, axis=1)
    return cent.astype(np.float32), assign


def _top_pc(x: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Top principal direction of x's rows (3 power iterations)."""
    xc = x - x.mean(axis=0)
    v = rng.normal(size=x.shape[1]).astype(np.float32)
    for _ in range(3):
        v = xc.T @ (xc @ v)
        v /= max(float(np.linalg.norm(v)), 1e-12)
    return v


def _halve_by_top_pc(x: np.ndarray, rows: np.ndarray,
                     rng: np.random.Generator):
    """Split rows into two equal halves by the median projection onto the
    members' top principal direction."""
    order = np.argsort(x @ _top_pc(x, rng), kind="stable")
    half = len(rows) // 2
    return rows[order[:half]], rows[order[half:]]


def _balanced_lists(xn: np.ndarray, assign: np.ndarray, n_clusters: int,
                    cap: int, seed: int):
    """Cluster member lists with every list <= cap rows: oversized k-means
    cells are recursively halved along their top principal direction."""
    rng = np.random.default_rng(seed + 1)
    queue = [np.flatnonzero(assign == c) for c in range(n_clusters)]
    queue = [r for r in queue if len(r)]
    lists = []
    while queue:
        rows = queue.pop()
        if len(rows) <= cap:
            lists.append(rows)
        else:
            queue.extend(_halve_by_top_pc(xn[rows], rows, rng))
    return lists


def _coarse_partition(sup: np.ndarray, n_clusters: int | None, seed: int,
                      iters: int, balance: float, lane_pad: int | None):
    """Shared front half of both index builders: spherical k-means +
    principal-direction balancing/relabelling.  Returns (centroids (C, D)
    unit-norm, member-row lists ordered along the centroids' top principal
    direction, padded list length, per-row norms (N, 1))."""
    n, d = sup.shape
    c = min(n_clusters or default_n_clusters(n), n)
    lane_pad = lane_pad or default_lane_pad()
    norms = np.maximum(np.linalg.norm(sup, axis=1, keepdims=True), 1e-12)
    xn = sup / norms
    cent, assign = _spherical_kmeans(xn, c, seed, iters)

    cap = max(lane_pad, int(math.ceil(balance * n / c)))
    lists = _balanced_lists(xn, assign, c, cap, seed)
    c = len(lists)
    # relabel clusters along their top principal direction: cluster ids are
    # otherwise arbitrary, and the query sort in `ivf_topk` relies on nearby
    # ids meaning nearby clusters so query tiles share slot lists
    cents0 = np.stack([xn[r].mean(axis=0) for r in lists])
    rngv = np.random.default_rng(seed + 2)
    perm = np.argsort(cents0 @ _top_pc(cents0, rngv), kind="stable")
    lists = [lists[i] for i in perm]
    cents0 = cents0[perm]
    lsz = int(np.ceil(max(max(len(r) for r in lists), 1)
                      / lane_pad) * lane_pad)
    centroids = np.zeros((c, d), np.float32)
    for ci in range(c):
        centroids[ci] = cents0[ci] / max(float(np.linalg.norm(cents0[ci])),
                                         1e-12)
    return centroids, lists, lsz, norms


def build_ivf_index(support, n_clusters: int | None = None, seed: int = 0,
                    iters: int = 10, balance: float = 1.5,
                    lane_pad: int | None = None) -> IVFIndex:
    """support (N, D) raw rows (normalized internally for clustering only —
    scoring keeps the raw rows so results match `knn_topk` bit-for-bit).
    ``n_clusters`` is a TARGET: oversized k-means cells are split until no
    list exceeds ``balance * N/n_clusters`` rows, so the final cluster count
    can be somewhat higher.  ``lane_pad`` rounds the padded list length (and
    floors the balance cap); None takes the platform's `default_lane_pad`."""
    sup = np.asarray(support, np.float32)
    n, d = sup.shape
    centroids, lists, lsz, norms = _coarse_partition(
        sup, n_clusters, seed, iters, balance, lane_pad)
    c = len(lists)
    sup_cm = np.zeros((c, lsz, d), np.float32)
    ids_cm = np.full((c, lsz), -1, np.int32)
    inv_cm = np.zeros((c, lsz), np.float32)
    for ci, rows in enumerate(lists):
        sup_cm[ci, :len(rows)] = sup[rows]
        ids_cm[ci, :len(rows)] = rows
        inv_cm[ci, :len(rows)] = 1.0 / norms[rows, 0]
    return IVFIndex(jnp.asarray(centroids), jnp.asarray(sup_cm),
                    jnp.asarray(ids_cm), jnp.asarray(inv_cm), n,
                    sup_cm, ids_cm, inv_cm)


def assemble_ivfpq(centroids: np.ndarray, anchors: np.ndarray,
                   codes_cm: np.ndarray, ids_cm: np.ndarray,
                   inv_cm: np.ndarray, codebooks: np.ndarray,
                   sup_flat: np.ndarray, n_rows: int, m: int,
                   nbits: int) -> IVFPQIndex:
    """Wrap the serializable arrays into an `IVFPQIndex` (device views plus
    host mirrors).  ``codes_cm`` arrives CODE-MAJOR ``(C, MB, L)``.  Shared
    by `build_ivfpq_index` and the artifact loader so a reloaded index is
    byte-identical to a freshly built one."""
    return IVFPQIndex(
        jnp.asarray(centroids), jnp.asarray(anchors), jnp.asarray(codes_cm),
        jnp.asarray(ids_cm), jnp.asarray(inv_cm), jnp.asarray(codebooks),
        jnp.asarray(sup_flat), int(n_rows), int(m), int(nbits),
        codes_cm, ids_cm, inv_cm, anchors, codebooks, sup_flat)


def build_ivfpq_index(support, n_clusters: int | None = None,
                      m: int | None = None, nbits: int = 8, seed: int = 0,
                      iters: int = 10, balance: float = 1.5,
                      lane_pad: int | None = None,
                      pq_iters: int = 8) -> IVFPQIndex:
    """IVF-PQ index build: the identical coarse partition as
    `build_ivf_index` (same k-means seed path -> same lists, so recall
    differences against plain IVF isolate the quantization), then per-list
    raw-space anchors, residual PQ codebooks (`pq.train_pq`), and packed
    per-row codes.  ``m`` defaults to ~D/8 and is clamped to the largest
    divisor of D (spec strings stay valid across embedding dims); ``nbits``
    is 8 (one byte per code) or 4 (two codes per byte, m must stay even
    after clamping)."""
    sup = np.asarray(support, np.float32)
    n, d = sup.shape
    m = pqmod.default_m(d) if m is None else pqmod.effective_m(d, m)
    if nbits == 4 and m % 2:
        m = max(2, m - 1)
        m = pqmod.effective_m(d, m)
        if m % 2:
            raise ValueError(f"nbits=4 needs an even subspace count; no even "
                             f"divisor of D={d} near the requested m")
    centroids, lists, lsz, norms = _coarse_partition(
        sup, n_clusters, seed, iters, balance, lane_pad)
    c = len(lists)

    anchors = np.zeros((c, d), np.float32)
    for ci, rows in enumerate(lists):
        anchors[ci] = sup[rows].mean(axis=0)
    order = np.concatenate(lists)
    owner = np.repeat(np.arange(c), [len(r) for r in lists])
    residuals = sup[order] - anchors[owner]
    codebooks = pqmod.train_pq(residuals, m, nbits, seed=seed + 3,
                               iters=pq_iters)
    codes_all = pqmod.pack_codes(pqmod.encode_pq(residuals, codebooks), nbits)

    mb = codes_all.shape[1]
    # code-major hot lists: (C, MB, L) — the list axis is minor/lane-aligned
    codes_cm = np.zeros((c, mb, lsz), np.uint8)
    ids_cm = np.full((c, lsz), -1, np.int32)
    inv_cm = np.zeros((c, lsz), np.float32)
    at = 0
    for ci, rows in enumerate(lists):
        codes_cm[ci, :, :len(rows)] = codes_all[at:at + len(rows)].T
        ids_cm[ci, :len(rows)] = rows
        inv_cm[ci, :len(rows)] = 1.0 / norms[rows, 0]
        at += len(rows)
    return assemble_ivfpq(centroids, anchors, codes_cm, ids_cm, inv_cm,
                          codebooks, sup, n, m, nbits)


#: delta rows tolerated before ``maybe_recluster`` compacts the index; at
#: the default the rebuild cost amortizes to O(build / 4096) per append
DEFAULT_DELTA_CAP = 4096


def _pow2_pad(n: int, floor: int = 8) -> int:
    """Next power of two >= max(n, floor) — the capacity schedule that keeps
    the streaming tier's array shapes (and with them the fused path's jit
    cache) stable across appends, retracing only on doublings."""
    return max(floor, 1 << max(0, int(math.ceil(math.log2(max(n, 1))))))


class DynamicIVFIndex:
    """Streaming wrapper over a frozen `IVFIndex` / `IVFPQIndex`.

    ``append`` assigns each new row to its nearest coarse centroid — an
    O(C*D)/row observability record (``delta_occupancy``) of WHERE the
    stream is landing, persisted with the artifact so an operator can see
    whether appends concentrate in few lists (drift) before a compaction —
    and stores the row in the delta tier.  The staged backends
    (host/tiles/pallas) EXACTLY scan the flat tier and merge it into every
    shortlist — a freshly appended row is immediately retrievable with an
    exact cosine score, and the recall of the combined index is bounded
    below by the frozen base's recall on the base rows.  The fused backend
    instead PROBES per-centroid delta sub-lists (``fused_state``) inside
    its single dispatch, restoring the base index's cost model at a
    recall profile matching the base search (delta rows are found whenever
    their assigned centroid is probed — the same condition base rows
    already live under).

    ``recluster()`` folds the delta back into the base by re-training the
    coarse partition (and, for PQ, the residual codebooks) over ALL rows
    with the ORIGINAL build parameters — by k-means seed determinism the
    compacted index is bitwise identical to a from-scratch build over the
    same rows, which is what makes re-clustering a pure no-op for retrieval
    semantics.  The query path never triggers it; callers compact via
    ``maybe_recluster`` (fires once the tier exceeds ``delta_cap``) between
    batches, so serving never blocks on a rebuild mid-request.

    Row ids are stable across the whole lifecycle: delta row j carries the
    global id ``base.n_rows + j``, and a re-cluster rebuilds over the rows
    in exactly that concatenated order.
    """

    def __init__(self, base, delta_cap: int = DEFAULT_DELTA_CAP,
                 build_kw: dict | None = None):
        if not isinstance(base, (IVFIndex, IVFPQIndex)):
            raise TypeError(f"DynamicIVFIndex wraps an IVFIndex or "
                            f"IVFPQIndex, got {type(base).__name__}")
        if delta_cap < 1:
            raise ValueError(f"delta_cap must be >= 1, got {delta_cap}")
        self.base = base
        d = int(base.centroids.shape[1])
        self.delta_x = np.zeros((0, d), np.float32)
        self.delta_assign = np.zeros((0,), np.int32)
        self.delta_cap = int(delta_cap)
        self.build_kw = dict(build_kw or {})
        self.appends = 0       # rows appended over the index lifetime
        self.reclusters = 0    # compactions run
        # mutation lock: append / re-cluster swap / fused-state rebuild all
        # run under it, so a background compaction swaps the base atomically
        # while queries and appends keep flowing
        self._lock = threading.RLock()
        self._rc_thread: threading.Thread | None = None
        self._fused = None     # cached probed-delta arrays (fused backend)
        #: mutation hook: called (no args, OUTSIDE the lock, on whichever
        #: thread ran the compaction) after every re-cluster swap.  The
        #: durability layer uses it to request a checkpoint — the callback
        #: must only set a flag / enqueue, never join this thread or take
        #: long locks, since on a background compaction it runs on the
        #: daemon rebuild thread itself.
        self.on_recluster = None

    # ---- delegated shape/meta ----
    # Even single-reference reads take the (reentrant) lock: a background
    # re-cluster swaps `base` and clears the delta together, and e.g.
    # `n_rows` must never pair an old base with a new delta.
    @property
    def is_pq(self) -> bool:
        with self._lock:
            return isinstance(self.base, IVFPQIndex)

    @property
    def dim(self) -> int:
        with self._lock:
            return int(self.base.centroids.shape[1])

    @property
    def delta_rows(self) -> int:
        with self._lock:
            return len(self.delta_x)

    @property
    def n_rows(self) -> int:
        with self._lock:
            return self.base.n_rows + len(self.delta_x)

    @property
    def n_clusters(self) -> int:
        with self._lock:
            return self.base.n_clusters

    @property
    def list_size(self) -> int:
        with self._lock:
            return self.base.list_size

    @property
    def index_bytes(self) -> int:
        """Hot storage: the base index plus the exact-scanned delta tier."""
        with self._lock:
            return int(self.base.index_bytes + self.delta_x.nbytes
                       + self.delta_assign.nbytes)

    # ---- streaming append ----
    def append(self, rows) -> np.ndarray:
        """Add rows (n, D) to the delta tier.  Returns their global row ids
        (stable across any later re-cluster)."""
        rows = np.atleast_2d(np.asarray(rows, np.float32))
        if rows.ndim != 2 or rows.shape[1] != self.dim:
            raise ValueError(f"append expects rows of shape (n, {self.dim}), "
                             f"got {rows.shape}")
        rn = rows / np.maximum(np.linalg.norm(rows, axis=1, keepdims=True),
                               1e-12)
        with self._lock:
            cents = np.asarray(self.base.centroids)
            assign = np.argmax(rn @ cents.T, axis=1).astype(np.int32)
            ids = (self.base.n_rows + len(self.delta_x)
                   + np.arange(len(rows), dtype=np.int32))
            self.delta_x = np.concatenate([self.delta_x, rows])
            # kill-injection barrier: dying between the two delta mutations
            # leaves torn IN-MEMORY state only — the process is gone, and
            # recovery replays the batch from the WAL record fsync'd before
            # this append was entered
            persist.maybe_kill("index-mid-append")
            self.delta_assign = np.concatenate([self.delta_assign, assign])
            self.appends += len(rows)
            self._fused = None
        return ids

    def delta_occupancy(self) -> np.ndarray:
        """Per-centroid delta-row counts (C,) — the drift diagnostic the
        per-row assignments exist for: a tier concentrated in few lists
        means incoming traffic has moved and the next re-cluster will
        re-partition substantially."""
        with self._lock:
            return np.bincount(self.delta_assign, minlength=self.n_clusters)

    # ---- compaction ----
    @property
    def needs_recluster(self) -> bool:
        with self._lock:
            return len(self.delta_x) > self.delta_cap

    @property
    def recluster_pending(self) -> bool:
        """A background compaction is currently building."""
        t = self._rc_thread
        return t is not None and t.is_alive()

    def join_recluster(self) -> None:
        """Wait for a pending background compaction to swap in (no-op when
        none is running) — the synchronization point tests, `close()`, and
        artifact serialization use.  Safe to call concurrently: each caller
        joins the thread it observed, and only the caller that still sees
        that same thread clears the slot (a plain ``= None`` would clobber
        a newer compaction started by another thread in between)."""
        t = self._rc_thread
        if t is not None:
            t.join()
            with self._lock:
                if self._rc_thread is t:
                    self._rc_thread = None

    def maybe_recluster(self, sync: bool = True) -> bool:
        """Compact iff the delta tier exceeds ``delta_cap``.  Returns whether
        a re-cluster ran (or, with ``sync=False``, was started) — the
        amortized policy serving layers call between batches.  Pass
        ``sync=False`` to run the rebuild on a background thread with an
        atomic swap, so the call returns immediately and no serving query
        ever waits on k-means."""
        if self.needs_recluster and not self.recluster_pending:
            self.recluster(sync=sync)
            return True
        return False

    def all_rows(self) -> np.ndarray:
        """Every row the index serves, global-id order (base then delta)."""
        with self._lock:
            if not len(self.delta_x):
                return self.base.rows()
            return np.concatenate([self.base.rows(), self.delta_x])

    def _build_base(self, rows):
        """From-scratch build over ``rows`` with the ORIGINAL parameters —
        the replay that makes a compaction bitwise-equal to a fresh build.
        Runs OUTSIDE the lock (it is the slow k-means path), so it snapshots
        the base reference once instead of re-reading ``self.base``."""
        with self._lock:
            base = self.base
        kw = self.build_kw
        if isinstance(base, IVFPQIndex):
            return build_ivfpq_index(
                rows, n_clusters=kw.get("n_clusters"),
                m=kw.get("m", base.m),           # keep the base's geometry
                nbits=kw.get("nbits", base.nbits),
                seed=kw.get("seed", 0), lane_pad=kw.get("lane_pad"))
        return build_ivf_index(
            rows, n_clusters=kw.get("n_clusters"), seed=kw.get("seed", 0),
            lane_pad=kw.get("lane_pad"))

    def recluster(self, sync: bool = True) -> None:
        """Re-train the coarse partition (and PQ codebooks on residuals) over
        base + delta rows with the original build parameters, then clear the
        delta tier.  With the same seed this equals a from-scratch build over
        the concatenated rows bitwise (guarded by the seed-determinism
        regression test), so retrieval semantics are unchanged — only the
        approximation quality is restored to the fresh-build operating
        point.

        ``sync=False`` runs the k-means rebuild on a daemon thread and swaps
        the compacted base in atomically when it finishes: queries keep
        reading the old base + full delta meanwhile, and rows appended
        during the build stay in the delta (re-assigned to the new coarse
        centroids at swap time).  ``sync=True`` — the default, and the
        escape hatch determinism tests rely on — blocks until the swap."""
        if not sync:
            # start-then-publish, all under the lock: a concurrent
            # join_recluster must never observe an unstarted thread, and
            # two sync=False callers must not both spawn a job
            with self._lock:
                if self.recluster_pending:
                    return
                t = threading.Thread(target=self._recluster_job, daemon=True,
                                     name="repro-ivf-recluster")
                t.start()
                self._rc_thread = t
            return
        self.join_recluster()
        self._recluster_job()

    def _recluster_job(self) -> None:
        """Snapshot -> build (outside the lock) -> atomic swap."""
        with self._lock:
            rows = self.all_rows()
            n_delta_snap = len(self.delta_x)
        new_base = self._build_base(rows)      # slow: k-means + PQ training
        # kill-injection barrier: a SIGKILL between build and swap loses the
        # rebuilt base but NO data — recovery replays the delta rows from
        # the WAL and re-runs the (seed-deterministic) compaction
        persist.maybe_kill("recluster-pre-swap")
        with self._lock:
            tail = self.delta_x[n_delta_snap:]          # appended mid-build
            self.base = new_base
            if len(tail):
                tn = tail / np.maximum(
                    np.linalg.norm(tail, axis=1, keepdims=True), 1e-12)
                cents = np.asarray(new_base.centroids)
                self.delta_assign = np.argmax(tn @ cents.T,
                                              axis=1).astype(np.int32)
                self.delta_x = tail
            else:
                self.delta_x = np.zeros((0, self.dim), np.float32)
                self.delta_assign = np.zeros((0,), np.int32)
            self.reclusters += 1
            self._fused = None
        cb = self.on_recluster
        if cb is not None:
            # outside the lock: the hook only flags work for another thread
            cb()

    # ---- probed delta tier (fused backend) ----
    def fused_state(self) -> dict:
        """Cluster-major delta sub-list arrays for the fused single-dispatch
        backend, built lazily and cached until the next append/compaction.

        Delta rows are grouped per assigned centroid into ``(C, Lc)``-shaped
        sub-lists (Lc = the max per-centroid occupancy, padded to a power of
        two so streaming appends retrace the jitted search only on capacity
        doublings).  Over a PQ base the sub-lists hold codes ENCODED with
        the existing codebooks (ROW-major ``(C, Lc, MB)`` — the fused scan
        is their only consumer and gathers rows contiguous, unlike the
        base lists' code-major storage) so they
        join the same ADC scan, and ``sup_all`` / ``inv_all`` extend the
        flat re-rank tier with the raw delta rows at their global ids."""
        with self._lock:
            if self._fused is not None:
                return self._fused
            c = self.n_clusters
            nd = len(self.delta_x)
            d = self.dim
            counts = np.bincount(self.delta_assign, minlength=c)
            lc = _pow2_pad(int(counts.max()) if nd else 1)
            inv_d = (1.0 / np.maximum(np.linalg.norm(self.delta_x, axis=1),
                                      1e-12)).astype(np.float32)
            gids = self.base.n_rows + np.arange(nd, dtype=np.int32)
            dl_ids = np.full((c, lc), -1, np.int32)
            dl_inv = np.zeros((c, lc), np.float32)
            members = {ci: np.flatnonzero(self.delta_assign == ci)
                       for ci in np.unique(self.delta_assign)}
            for ci, rows in members.items():
                dl_ids[ci, :len(rows)] = gids[rows]
                dl_inv[ci, :len(rows)] = inv_d[rows]
            st = {"dl_ids": jnp.asarray(dl_ids),
                  "dl_inv": jnp.asarray(dl_inv)}
            if self.is_pq:
                base = self.base
                res = self.delta_x - base.anchors_h[self.delta_assign]
                codes = pqmod.pack_codes(
                    pqmod.encode_pq(res, base.codebooks_h), base.nbits)
                # row-major (C, Lc, MB): the fused scan is the only
                # consumer, and its gather wants rows contiguous
                dl_codes = np.zeros((c, lc, codes.shape[1]), np.uint8)
                for ci, rows in members.items():
                    dl_codes[ci, :len(rows)] = codes[rows]
                sup_all, inv_all = self._combined_flat(base, nd, inv_d, d)
                st.update(dl_codes=jnp.asarray(dl_codes),
                          sup_all=jnp.asarray(sup_all),
                          inv_all=jnp.asarray(inv_all))
            else:
                dl_sup = np.zeros((c, lc, d), np.float32)
                for ci, rows in members.items():
                    dl_sup[ci, :len(rows)] = self.delta_x[rows]
                st["dl_sup"] = jnp.asarray(dl_sup)
            self._fused = st
            return st

    def _combined_flat(self, base, nd: int, inv_d: np.ndarray, d: int):
        """Host buffers for the combined re-rank tier (base rows then delta
        rows at their global ids), padded to a pow2 delta capacity.  The
        O(n_base) prefix is written ONCE per (base, capacity) pair and the
        buffers are retained across appends — only the freshly appended
        delta rows are copied in per rebuild, so a feedback batch costs
        O(delta) host work, not a full 4*N*D copy."""
        with self._lock:
            cap = _pow2_pad(nd)
            buf = getattr(self, "_flat_buf", None)
            if (buf is None or buf["base"] is not base or buf["cap"] != cap):
                sup_all = np.zeros((base.n_rows + cap, d), np.float32)
                sup_all[:base.n_rows] = base.sup_flat_h
                inv_all = np.zeros(base.n_rows + cap, np.float32)
                inv_all[:base.n_rows][
                    base.ids_h[base.ids_h >= 0]] = base.inv_h[base.ids_h >= 0]
                buf = {"base": base, "cap": cap, "sup": sup_all,
                       "inv": inv_all, "nd": 0}
                self._flat_buf = buf
            lo = min(buf["nd"], nd)      # appends only grow the tier
            buf["sup"][base.n_rows + lo:base.n_rows + nd] = self.delta_x[lo:]
            buf["inv"][base.n_rows + lo:base.n_rows + nd] = inv_d[lo:]
            buf["nd"] = nd
            return buf["sup"], buf["inv"]

    # ---- delta-tier scan + merge ----
    def delta_topk(self, queries, k: int):
        """Exact cosine scan of the flat delta tier (numpy: the tier's shape
        changes every append, so a jitted scan would retrace per size — and
        the tier is delta_cap-bounded, so the scan is O(Q * delta_cap * D)).
        Output contract matches `ivf_topk`: -inf / -1 beyond the valid
        candidates; ids are global (offset by the base row count)."""
        # repro: allow-host: delta tier is a host exact scan by design
        q = np.asarray(queries, np.float32)
        with self._lock:        # coherent (delta, base-row-offset) snapshot
            delta = self.delta_x
            base_rows = self.base.n_rows
        qn, nd = len(q), len(delta)
        kk = min(k, nd)
        sc = np.full((qn, k), -np.inf, np.float32)
        ix = np.full((qn, k), -1, np.int32)
        if kk == 0:
            return sc, ix
        inv = 1.0 / np.maximum(np.linalg.norm(delta, axis=1), 1e-12)
        sims = (q @ delta.T) * inv
        if kk < nd:
            part = np.argpartition(-sims, kk - 1, axis=1)[:, :kk]
        else:
            part = np.broadcast_to(np.arange(nd), (qn, nd))
        psims = np.take_along_axis(sims, part, axis=1)
        order = np.argsort(-psims, axis=1, kind="stable")
        top = np.take_along_axis(part, order, axis=1)
        sc[:, :kk] = np.take_along_axis(sims, top, axis=1)
        ix[:, :kk] = (base_rows + top).astype(np.int32)
        return sc, ix

    def merge_delta(self, queries, base_sc, base_ix, k: int):
        """Merge the base index's top-k with the delta tier's exact scan.
        Base candidates win ties (stable sort, base first); the two id
        ranges are disjoint by construction so no dedup is needed.  With an
        EMPTY tier — the steady state between feedback batches — the base
        result passes through untouched (no device->host round trip on the
        serving hot path)."""
        with self._lock:
            n_rows = self.base.n_rows + len(self.delta_x)
            if not len(self.delta_x):
                return base_sc, base_ix
        k = min(k, n_rows)
        # repro: allow-host: staged-backend merge materializes once per batch
        bs = np.asarray(base_sc, np.float32)
        # repro: allow-host: staged-backend merge materializes once per batch
        bi = np.asarray(base_ix, np.int32)
        if bs.shape[1] < k:       # base clamped below k: pad to merge width
            padw = k - bs.shape[1]
            bs = np.pad(bs, ((0, 0), (0, padw)), constant_values=-np.inf)
            bi = np.pad(bi, ((0, 0), (0, padw)), constant_values=-1)
        ds_sc, ds_ix = self.delta_topk(queries, k)
        sc = np.concatenate([bs[:, :k], ds_sc], axis=1)
        ix = np.concatenate([bi[:, :k], ds_ix], axis=1)
        order = np.argsort(-sc, axis=1, kind="stable")[:, :k]
        out_sc = np.take_along_axis(sc, order, axis=1)
        out_ix = np.take_along_axis(ix, order, axis=1)
        out_ix[~np.isfinite(out_sc)] = -1
        return jnp.asarray(out_sc), jnp.asarray(out_ix)


def plan_tile_probes(q_probe: np.ndarray, block_q: int):
    """Deduplicate each query tile's probe set into static-width slot lists.

    Returns (tile_probe (T, S), tile_valid (T, S)) where S is the max union
    size over tiles; padded slots repeat the tile's first cluster and carry
    valid=0 so consumers skip them without double-counting.  Callers sort
    queries by primary cluster first, which keeps S near nprobe instead of
    block_q * nprobe."""
    qn = len(q_probe)
    tiles = [q_probe[t:t + block_q] for t in range(0, qn, block_q)]
    uniques = [np.unique(t[t >= 0]) for t in tiles]
    s = max(1, max(len(u) for u in uniques))
    tile_probe = np.zeros((len(tiles), s), np.int32)
    tile_valid = np.zeros((len(tiles), s), np.int32)
    for ti, u in enumerate(uniques):
        if len(u) == 0:              # all-padding tile: probe list 0, masked
            continue
        tile_probe[ti, :len(u)] = u
        tile_probe[ti, len(u):] = u[0]
        tile_valid[ti, :len(u)] = 1
    return tile_probe, tile_valid


@functools.partial(jax.jit, static_argnames=("k", "bq"))
def _score_tiles(queries, q_probe, tile_probe, tile_valid,
                 sup_cm, ids_cm, inv_cm, k: int, bq: int):
    """Tile-coherent inverted traversal (jnp twin of the Pallas kernel):
    gather each tile's slot lists ONCE, score the whole tile against them
    with one batched matmul, then mask every query down to the rows of its
    own probe set."""
    qp, d = queries.shape
    t, s = tile_probe.shape
    l = sup_cm.shape[1]
    p = q_probe.shape[1]

    lists = jnp.take(sup_cm, tile_probe, axis=0)             # (T, S, L, D)
    ids = jnp.take(ids_cm, tile_probe, axis=0)               # (T, S, L)
    inv = jnp.take(inv_cm, tile_probe, axis=0)               # (T, S, L)
    qt = queries.reshape(t, bq, d)
    sims = jax.lax.dot_general(qt, lists.reshape(t, s * l, d),
                               (((2,), (2,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)
    sims = sims * inv.reshape(t, 1, s * l)                   # (T, BQ, S*L)

    probed = jnp.any(q_probe.reshape(t, bq, p, 1)
                     == tile_probe.reshape(t, 1, 1, s), axis=2)  # (T, BQ, S)
    ok = (probed & (tile_valid != 0).reshape(t, 1, s))[..., None] \
        & (ids >= 0).reshape(t, 1, s, l)
    sims = jnp.where(ok.reshape(t, bq, s * l), sims, -jnp.inf)

    scores, pos = jax.lax.top_k(sims, k)                     # (T, BQ, k)
    cand_i = jnp.broadcast_to(ids.reshape(t, 1, s * l), sims.shape)
    idx = jnp.take_along_axis(cand_i, pos, axis=2)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores.reshape(qp, k), idx.reshape(qp, k).astype(jnp.int32)


def _pair_layout(q_probe: np.ndarray):
    """(query, probe) pairs sorted by cluster so each cluster's pairs form
    one contiguous segment.  Returns (pair_c (Q*P,), sorted query row ids,
    sort order)."""
    qn, p = q_probe.shape
    pair_c = q_probe.reshape(-1)                       # (Q*P,)
    pair_q = np.repeat(np.arange(qn), p)
    order = np.argsort(pair_c, kind="stable")
    return pair_c, pair_q[order], order


def _topk_from_pair_sims(sims_sorted: np.ndarray, order: np.ndarray,
                         pair_c: np.ndarray, ids_h: np.ndarray, qn: int,
                         k: int):
    """Shared tail of both host traversals: un-sort the per-pair similarity
    rows back to query-major, flatten each query's candidates, and take the
    top-k (argpartition + stable sort; -inf slots emit id -1)."""
    p_l = sims_sorted.shape[1]
    p = len(pair_c) // qn
    sims = np.empty_like(sims_sorted)
    sims[order] = sims_sorted                          # back to query-major
    sims = sims.reshape(qn, p * p_l)
    ids = ids_h[pair_c].reshape(qn, p * p_l)
    if k < p * p_l:
        part = np.argpartition(-sims, k - 1, axis=1)[:, :k]
    else:
        part = np.broadcast_to(np.arange(p * p_l), (qn, p * p_l))
    psims = np.take_along_axis(sims, part, axis=1)
    order2 = np.argsort(-psims, axis=1, kind="stable")[:, :k]
    top = np.take_along_axis(part, order2, axis=1)
    scores = np.take_along_axis(sims, top, axis=1)
    idx = np.take_along_axis(ids, top, axis=1).astype(np.int32)
    idx[~np.isfinite(scores)] = -1
    return jnp.asarray(scores), jnp.asarray(idx)


def _score_pairs_host(q: np.ndarray, q_probe: np.ndarray, index: IVFIndex,
                      k: int):
    """CPU inverted-list traversal: (query, probe) PAIRS are sorted by
    cluster, and each cluster's contiguous pair segment is scored with one
    BLAS matmul against the cluster's rows IN PLACE — no (Q, P, L, D)
    support gather ever materializes, no tile-union waste: exactly
    Q * nprobe * L * D MACs and each probed list is read once."""
    qn, _ = q.shape
    c, l, _ = index.sup_h.shape
    pair_c, q_rows, order = _pair_layout(q_probe)
    sorted_c = pair_c[order]
    qs = q[q_rows]                                     # (Q*P, D)

    sims_sorted = np.empty((len(pair_c), l), np.float32)
    starts = np.searchsorted(sorted_c, np.arange(c))
    ends = np.searchsorted(sorted_c, np.arange(c), side="right")
    for ci in np.unique(sorted_c):
        s0, s1 = starts[ci], ends[ci]
        sims_sorted[s0:s1] = qs[s0:s1] @ index.sup_h[ci].T
    inv_pairs = index.inv_h[sorted_c]                  # (Q*P, L)
    sims_sorted *= inv_pairs
    sims_sorted[inv_pairs == 0] = -np.inf              # list padding rows
    return _topk_from_pair_sims(sims_sorted, order, pair_c, index.ids_h,
                                qn, k)


def _adc_pairs_host(q: np.ndarray, q_probe: np.ndarray, index: IVFPQIndex,
                    k: int):
    """CPU ADC traversal — the PQ twin of `_score_pairs_host`: one (m, K)
    LUT per query built with a single batched einsum, then each cluster's
    contiguous pair segment is scored by LUT GATHERS against the cluster's
    packed codes (m byte-indexed reads per row instead of a D-MAC dot), plus
    the per-pair anchor dot and the EXACT stored inverse norms."""
    qn, _ = q.shape
    c, mb, l = index.codes_h.shape
    m, kk = index.m, 2 ** index.nbits
    pair_c, q_rows, order = _pair_layout(q_probe)
    sorted_c = pair_c[order]

    lut = pqmod.adc_lut(q, index.codebooks_h).reshape(qn, m * kk)
    offs = (np.arange(m) * kk).astype(np.int32)
    aq = np.einsum("pd,pd->p", q[q_rows],
                   index.anchors_h[sorted_c]).astype(np.float32)

    sims_sorted = np.empty((len(pair_c), l), np.float32)
    starts = np.searchsorted(sorted_c, np.arange(c))
    ends = np.searchsorted(sorted_c, np.arange(c), side="right")
    for ci in np.unique(sorted_c):
        s0, s1 = starts[ci], ends[ci]
        # cached row-major mirror -> per-row codes for the LUT gather loop
        codes = pqmod.unpack_codes(index.codes_rm_h[ci], m,
                                   index.nbits) + offs
        lseg = lut[q_rows[s0:s1]]                      # (P_c, m*K)
        acc = lseg[:, codes[:, 0]]                     # (P_c, L)
        for j in range(1, m):                          # accumulate in place:
            acc += lseg[:, codes[:, j]]                # no (P_c, L, m) temp
        sims_sorted[s0:s1] = acc
    sims_sorted += aq[:, None]
    inv_pairs = index.inv_h[sorted_c]
    sims_sorted *= inv_pairs
    sims_sorted[inv_pairs == 0] = -np.inf              # list padding rows
    return _topk_from_pair_sims(sims_sorted, order, pair_c, index.ids_h,
                                qn, k)


def _sorted_tile_plan(queries, q_probe: np.ndarray, block_q: int):
    """Shared tiling front-end of the tiles/Pallas paths: sort queries by
    primary cluster so tiles become probe-coherent (the static slot width S
    stays near nprobe instead of block_q * nprobe — the index builders order
    cluster ids along the centroids' top principal direction, so nearby ids
    are nearby clusters), pad to a tile multiple, and plan the per-tile slot
    lists.  Returns (q_sorted, qp_sorted, tile_probe, tile_valid, inv_order,
    bq)."""
    Q = len(q_probe)
    order = np.argsort(q_probe[:, 0], kind="stable")
    inv_order = np.argsort(order, kind="stable")
    bq = min(block_q, Q)
    pad = (-Q) % bq
    qp_sorted = np.pad(q_probe[order], ((0, pad), (0, 0)), constant_values=-1)
    q_sorted = jnp.pad(queries[jnp.asarray(order)], ((0, pad), (0, 0)))
    tile_probe, tile_valid = plan_tile_probes(qp_sorted, bq)
    return q_sorted, qp_sorted, tile_probe, tile_valid, inv_order, bq


@functools.partial(jax.jit, static_argnames=("k", "bq", "m", "nbits"))
def _adc_tiles(queries, q_probe, tile_probe, tile_valid, codes_cm, ids_cm,
               inv_cm, anchors, codebooks, k: int, bq: int, m: int,
               nbits: int):
    """Tile-coherent ADC traversal (jnp twin of the Pallas ADC kernel):
    build every query's (m, K) LUT with one einsum, gather each tile's
    PACKED slot lists once, score them by flat-LUT gather + anchor dot, then
    mask every query down to the rows of its own probe set — identical tile
    semantics to `_score_tiles`, with table gathers in place of the (L, D)
    matmul."""
    qp, d = queries.shape
    t, s = tile_probe.shape
    l = codes_cm.shape[2]
    p = q_probe.shape[1]
    kk = 2 ** nbits

    qf = queries.astype(jnp.float32)
    lut = _adc_lut(qf, codebooks, m, nbits).reshape(t, bq, m * kk)

    codes = pqmod.unpack_codes_jnp_cm(
        jnp.take(codes_cm, tile_probe, axis=0), m, nbits)   # (T, S, m, L)
    codes = jnp.moveaxis(codes, 2, 3).reshape(t, 1, s * l, m)
    # accumulate per subspace (static loop): peak memory stays (T, BQ, S*L)
    # instead of the m-times-larger all-subspace partials tensor
    sims = jnp.zeros((t, bq, s * l), jnp.float32)
    for j in range(m):
        cj = jnp.broadcast_to(codes[..., j] + j * kk, (t, bq, s * l))
        sims = sims + jnp.take_along_axis(lut, cj, axis=2)

    qt = qf.reshape(t, bq, d)
    anch = jnp.take(anchors, tile_probe, axis=0)            # (T, S, D)
    aq = jax.lax.dot_general(qt, anch, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)  # (T, BQ, S)
    sims = sims + jnp.repeat(aq, l, axis=2)
    ids = jnp.take(ids_cm, tile_probe, axis=0)              # (T, S, L)
    inv = jnp.take(inv_cm, tile_probe, axis=0)
    sims = sims * inv.reshape(t, 1, s * l)

    probed = jnp.any(q_probe.reshape(t, bq, p, 1)
                     == tile_probe.reshape(t, 1, 1, s), axis=2)  # (T, BQ, S)
    ok = (probed & (tile_valid != 0).reshape(t, 1, s))[..., None] \
        & (ids >= 0).reshape(t, 1, s, l)
    sims = jnp.where(ok.reshape(t, bq, s * l), sims, -jnp.inf)

    scores, pos = jax.lax.top_k(sims, k)                    # (T, BQ, k)
    cand_i = jnp.broadcast_to(ids.reshape(t, 1, s * l), sims.shape)
    idx = jnp.take_along_axis(cand_i, pos, axis=2)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores.reshape(qp, k), idx.reshape(qp, k).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def _rerank_exact(queries, sup_flat, shortlist_idx, k: int):
    """Stage 2 of the two-stage search: exact cosine re-scoring of the ADC
    shortlist against the raw rows of ONLY those candidates (a (Q, kk, D)
    gather from the cold tier), with the same on-the-fly normalization as
    `knn_topk_reference` so re-ranked scores are bit-comparable to the exact
    scan.  -1 shortlist slots stay -inf/-1."""
    rows = jnp.take(sup_flat, jnp.maximum(shortlist_idx, 0), axis=0)
    norm2 = jnp.sum(jnp.square(rows.astype(jnp.float32)), axis=-1)
    sims = jnp.einsum("qd,qkd->qk", queries.astype(jnp.float32), rows,
                      preferred_element_type=jnp.float32)
    sims = sims * jax.lax.rsqrt(norm2 + 1e-12)
    sims = jnp.where(shortlist_idx >= 0, sims, -jnp.inf)
    scores, pos = jax.lax.top_k(sims, k)
    idx = jnp.take_along_axis(shortlist_idx, pos, axis=1)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores, idx.astype(jnp.int32)


# ---------------------------------------------------------------------------
# fused single-dispatch backend: probe -> scan -> shortlist -> re-rank in
# ONE jitted call (no host-side tile planning, no second host->device hop)
# ---------------------------------------------------------------------------

def _rerank_stored_inv(qf, sup_flat, inv_flat, shortlist_idx, k: int):
    """Exact re-rank against the raw cold rows using the STORED inverse
    norms (the same float values the ADC stage multiplied by) — one (Q, kk)
    gather replaces `_rerank_exact`'s (Q, kk, D) square-sum, which on the
    serving hot path is a ~25% cut of the stage-2 cost.  Same -inf / -1
    output contract."""
    safe = jnp.maximum(shortlist_idx, 0)
    rows = jnp.take(sup_flat, safe, axis=0)
    sims = jnp.einsum("qd,qkd->qk", qf, rows,
                      preferred_element_type=jnp.float32)
    sims = sims * jnp.take(inv_flat, safe, axis=0)
    sims = jnp.where(shortlist_idx >= 0, sims, -jnp.inf)
    scores, pos = jax.lax.top_k(sims, k)
    idx = jnp.take_along_axis(shortlist_idx, pos, axis=1)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores, idx.astype(jnp.int32)


def _adc_probe_scan(qf, probe, lut_flat, codes_rm, ids_cm, inv_cm, anchors,
                    m: int, nbits: int, pc: int = 0):
    """ADC-score every row of the probed lists: gather the ROW-MAJOR packed
    blocks (``codes_rm`` — the derived gather-friendly mirror of the
    code-major storage) per query, sum LUT entries with ONE flat `jnp.take`
    (flattened (query, subspace, code) indices — ~4x faster on CPU XLA than
    a per-subspace take_along_axis loop, and the m codes of a row stay
    adjacent so the reduce runs over the minor axis), add the anchor dot,
    scale by the exact stored inverse norms.  Returns (sims (Q, P*L),
    ids (Q, P*L)) with -inf / -1 on padding rows.

    ``pc`` (codes-per-block granularity, an autotunable): process the probe
    axis in chunks of ``pc`` lists, bounding the peak ``(Q, pc, L, m)``
    unpacked-code temporary instead of materializing all ``nprobe`` lists'
    codes at once — a static python loop, so the whole scan still lowers
    into one fused computation.  ``0`` scans every probed list in one
    chunk (the widest temporary, fewest fused loop nests)."""
    qn = qf.shape[0]
    p = probe.shape[1]
    if pc and pc < p:
        parts = [_adc_probe_scan(qf, probe[:, i:i + pc], lut_flat, codes_rm,
                                 ids_cm, inv_cm, anchors, m, nbits)
                 for i in range(0, p, pc)]
        return (jnp.concatenate([s for s, _ in parts], axis=1),
                jnp.concatenate([i for _, i in parts], axis=1))
    l = codes_rm.shape[1]
    kb = 2 ** nbits
    codes = pqmod.unpack_codes_jnp(
        jnp.take(codes_rm, probe, axis=0), m, nbits)         # (Q, P, L, m)
    qoff = (jnp.arange(qn, dtype=jnp.int32) * (m * kb)).reshape(qn, 1, 1, 1)
    joff = (jnp.arange(m, dtype=jnp.int32) * kb).reshape(1, 1, 1, m)
    vals = jnp.take(lut_flat, (codes + qoff + joff).reshape(-1), axis=0)
    sims = vals.reshape(qn, p, l, m).sum(axis=3)             # (Q, P, L)
    aq = jnp.einsum("qd,qpd->qp", qf, jnp.take(anchors, probe, axis=0),
                    preferred_element_type=jnp.float32)
    inv = jnp.take(inv_cm, probe, axis=0)
    ids = jnp.take(ids_cm, probe, axis=0)
    sims = (sims + aq[:, :, None]) * inv
    sims = jnp.where(ids >= 0, sims, -jnp.inf)
    return sims.reshape(qn, p * l), ids.reshape(qn, p * l)


def _adc_lut(qf, codebooks, m: int, nbits: int):
    """Per-query ADC tables ``(Q, m * 2^nbits)`` of subvector dot products
    — one einsum, shared by every device ADC path (the Pallas kernel takes
    them as an input block)."""
    qn, d = qf.shape
    lut = jnp.einsum("qmd,mkd->qmk", qf.reshape(qn, m, d // m), codebooks,
                     preferred_element_type=jnp.float32)
    return lut.reshape(qn, m * 2 ** nbits)


def _fused_ivf_topk_impl(queries, centroids, sup_cm, ids_cm, inv_cm,
                         k: int, nprobe: int):
    """Single-dispatch raw-IVF search: in-jit probe, dense per-query list
    gather (the same formulation as the sharded path's local stage), one
    batched einsum, one top-k.  Trades the host traversal's read-each-list-
    once BLAS for zero host planning — the right trade for the serving tier
    where the per-batch dispatch chain is the bottleneck, not FLOPs."""
    qf = queries.astype(jnp.float32)
    qn = qf.shape[0]
    probe = ivf_probe(qf, centroids, nprobe)                 # (Q, P)
    lists = jnp.take(sup_cm, probe, axis=0)                  # (Q, P, L, D)
    ids = jnp.take(ids_cm, probe, axis=0)
    inv = jnp.take(inv_cm, probe, axis=0)
    sims = jnp.einsum("qd,qpld->qpl", qf, lists,
                      preferred_element_type=jnp.float32) * inv
    sims = jnp.where(ids >= 0, sims, -jnp.inf).reshape(qn, -1)
    sc, pos = jax.lax.top_k(sims, k)
    ix = jnp.take_along_axis(ids.reshape(qn, -1), pos, axis=1)
    return sc, jnp.where(jnp.isfinite(sc), ix, -1).astype(jnp.int32)


def _fused_dyn_ivf_topk_impl(queries, centroids, sup_cm, ids_cm, inv_cm,
                             dl_sup, dl_ids, dl_inv, k: int, nprobe: int):
    """`_fused_ivf_topk` plus the PROBED delta tier: the per-centroid delta
    sub-lists are gathered by the same probe set, exact-scored, and merged
    into the same single top-k — the streaming index costs one wider
    selection instead of a separate O(Q * delta) exact scan."""
    qf = queries.astype(jnp.float32)
    qn = qf.shape[0]
    probe = ivf_probe(qf, centroids, nprobe)
    lists = jnp.take(sup_cm, probe, axis=0)
    ids_b = jnp.take(ids_cm, probe, axis=0)
    inv_b = jnp.take(inv_cm, probe, axis=0)
    sims_b = jnp.einsum("qd,qpld->qpl", qf, lists,
                        preferred_element_type=jnp.float32) * inv_b
    dlists = jnp.take(dl_sup, probe, axis=0)                 # (Q, P, Lc, D)
    ids_d = jnp.take(dl_ids, probe, axis=0)
    inv_d = jnp.take(dl_inv, probe, axis=0)
    sims_d = jnp.einsum("qd,qpld->qpl", qf, dlists,
                        preferred_element_type=jnp.float32) * inv_d
    sims = jnp.concatenate([sims_b.reshape(qn, -1),
                            sims_d.reshape(qn, -1)], axis=1)
    ids = jnp.concatenate([ids_b.reshape(qn, -1),
                           ids_d.reshape(qn, -1)], axis=1)
    sims = jnp.where(ids >= 0, sims, -jnp.inf)
    sc, pos = jax.lax.top_k(sims, k)
    ix = jnp.take_along_axis(ids, pos, axis=1)
    return sc, jnp.where(jnp.isfinite(sc), ix, -1).astype(jnp.int32)


def _fused_ivfpq_topk_impl(queries, centroids, codes_cm, ids_cm, inv_cm,
                           anchors, codebooks, sup_flat, inv_flat, k: int,
                           kk: int, nprobe: int, m: int, nbits: int,
                           pc: int = 0):
    """Single-dispatch two-stage IVF-PQ search: in-jit probe, flat-take ADC
    scan of the probed code-major lists, global top-``kk`` shortlist, and
    the exact re-rank folded into the SAME dispatch (a jitted `take` of the
    cold rows + one batched matvec against the stored inverse norms).
    ``kk=0`` skips stage 2 and returns raw ADC order; ``pc`` chunks the ADC
    scan's probe axis (see `_adc_probe_scan` — an autotuned constant the
    dispatch policy records)."""
    qf = queries.astype(jnp.float32)
    probe = ivf_probe(qf, centroids, nprobe)
    lut = _adc_lut(qf, codebooks, m, nbits).reshape(-1)  # one-take gather
    sims, ids = _adc_probe_scan(qf, probe, lut, codes_cm, ids_cm, inv_cm,
                                anchors, m, nbits, pc)
    if not kk:
        sc, pos = jax.lax.top_k(sims, k)
        ix = jnp.take_along_axis(ids, pos, axis=1)
        return sc, jnp.where(jnp.isfinite(sc), ix, -1).astype(jnp.int32)
    sc, pos = jax.lax.top_k(sims, kk)
    ix = jnp.take_along_axis(ids, pos, axis=1)
    ix = jnp.where(jnp.isfinite(sc), ix, -1)
    return _rerank_stored_inv(qf, sup_flat, inv_flat, ix, k)


def _fused_dyn_ivfpq_topk_impl(queries, centroids, codes_cm, ids_cm, inv_cm,
                               anchors, codebooks, dl_codes, dl_ids, dl_inv,
                               sup_all, inv_all, k: int, kk: int, nprobe: int,
                               m: int, nbits: int, pc: int = 0):
    """`_fused_ivfpq_topk` plus the PROBED delta tier: appended rows live in
    per-centroid sub-lists ENCODED with the existing codebooks, so they join
    the same ADC scan (and the same shortlist selection), and the combined
    flat tier ``sup_all`` re-ranks base and delta candidates alike — the
    whole streaming search stays one dispatch at near the frozen-index
    cost."""
    qf = queries.astype(jnp.float32)
    probe = ivf_probe(qf, centroids, nprobe)
    lut = _adc_lut(qf, codebooks, m, nbits).reshape(-1)  # one-take gather
    sims_b, ids_b = _adc_probe_scan(qf, probe, lut, codes_cm, ids_cm, inv_cm,
                                    anchors, m, nbits, pc)
    sims_d, ids_d = _adc_probe_scan(qf, probe, lut, dl_codes, dl_ids, dl_inv,
                                    anchors, m, nbits, pc)
    sims = jnp.concatenate([sims_b, sims_d], axis=1)
    ids = jnp.concatenate([ids_b, ids_d], axis=1)
    if not kk:
        sc, pos = jax.lax.top_k(sims, k)
        ix = jnp.take_along_axis(ids, pos, axis=1)
        return sc, jnp.where(jnp.isfinite(sc), ix, -1).astype(jnp.int32)
    sc, pos = jax.lax.top_k(sims, kk)
    ix = jnp.take_along_axis(ids, pos, axis=1)
    ix = jnp.where(jnp.isfinite(sc), ix, -1)
    return _rerank_stored_inv(qf, sup_all, inv_all, ix, k)


#: standalone single-dispatch entry points (the ops-level backend="fused"
#: path).  The serving layer instead inlines the *_impl bodies into its own
#: jit: XLA CPU lowers `lax.top_k` to its fast TopK custom call only in the
#: top-level computation, so nesting these as inner pjit calls would drop
#: the shortlist selection to the generic sort (~5x slower at kk=800).
_fused_ivf_topk = functools.partial(jax.jit, static_argnames=(
    "k", "nprobe"))(_fused_ivf_topk_impl)
_fused_dyn_ivf_topk = functools.partial(jax.jit, static_argnames=(
    "k", "nprobe"))(_fused_dyn_ivf_topk_impl)
_fused_ivfpq_topk = functools.partial(jax.jit, static_argnames=(
    "k", "kk", "nprobe", "m", "nbits", "pc"))(_fused_ivfpq_topk_impl)
_fused_dyn_ivfpq_topk = functools.partial(jax.jit, static_argnames=(
    "k", "kk", "nprobe", "m", "nbits", "pc"))(_fused_dyn_ivfpq_topk_impl)


def _fused_ivf_dispatch(queries, index, k: int, nprobe: int):
    """backend='fused' entry for raw IVF — handles the streaming wrapper by
    switching to the probed-delta variant when the tier is non-empty.
    Clamps ``k`` to the candidate pool the fused scan actually covers."""
    if isinstance(index, DynamicIVFIndex):
        with index._lock:     # consistent (base, delta) under background
            base = index.base  # compaction swaps
            n = index.n_rows
            st = index.fused_state() if index.delta_rows else None
        if st is None:
            k = min(k, n, nprobe * base.list_size)
            return _fused_ivf_topk(queries, base.centroids, base.sup_cm,
                                   base.ids_cm, base.inv_cm, k=k,
                                   nprobe=nprobe)
        lc = st["dl_sup"].shape[1]
        k = min(k, n, nprobe * (base.list_size + lc))
        return _fused_dyn_ivf_topk(queries, base.centroids, base.sup_cm,
                                   base.ids_cm, base.inv_cm, st["dl_sup"],
                                   st["dl_ids"], st["dl_inv"],
                                   k=k, nprobe=nprobe)
    k = min(k, index.n_rows, nprobe * index.list_size)
    return _fused_ivf_topk(queries, index.centroids, index.sup_cm,
                           index.ids_cm, index.inv_cm, k=k, nprobe=nprobe)


def _fused_ivfpq_dispatch(queries, index, k: int, rerank: int, nprobe: int):
    """backend='fused' entry for IVF-PQ — probed-delta variant when the
    streaming tier is non-empty.  Computes the same ``k`` / shortlist
    clamps as the staged backends."""
    if isinstance(index, DynamicIVFIndex):
        with index._lock:     # consistent (base, delta) under background
            base = index.base  # compaction swaps
            n = index.n_rows
            st = index.fused_state() if index.delta_rows else None
        if st is None:
            cand = nprobe * base.list_size
            k = min(k, n, cand)
            kk = min(max(rerank, 1) * k, n, cand) if rerank else 0
            return _fused_ivfpq_topk(queries, base.centroids, base.codes_rm,
                                     base.ids_cm, base.inv_cm, base.anchors,
                                     base.codebooks, base.sup_flat,
                                     base.inv_flat, k=k, kk=kk, nprobe=nprobe,
                                     m=base.m, nbits=base.nbits)
        lc = st["dl_codes"].shape[1]
        cand = nprobe * (base.list_size + lc)
        k = min(k, n, cand)
        kk = min(max(rerank, 1) * k, n, cand) if rerank else 0
        return _fused_dyn_ivfpq_topk(queries, base.centroids, base.codes_rm,
                                     base.ids_cm, base.inv_cm, base.anchors,
                                     base.codebooks, st["dl_codes"],
                                     st["dl_ids"], st["dl_inv"],
                                     st["sup_all"], st["inv_all"],
                                     k=k, kk=kk, nprobe=nprobe,
                                     m=base.m, nbits=base.nbits)
    cand = nprobe * index.list_size
    k = min(k, index.n_rows, cand)
    kk = min(max(rerank, 1) * k, index.n_rows, cand) if rerank else 0
    return _fused_ivfpq_topk(queries, index.centroids, index.codes_rm,
                             index.ids_cm, index.inv_cm, index.anchors,
                             index.codebooks, index.sup_flat, index.inv_flat,
                             k=k, kk=kk, nprobe=nprobe, m=index.m,
                             nbits=index.nbits)


def ivf_topk(queries, index: IVFIndex, k: int,
             nprobe: int = DEFAULT_NPROBE, *, use_pallas: bool = False,
             backend: str | None = None, block_q: int = 32):
    """queries (Q, D) L2-normalized.  Returns (scores (Q, k), indices (Q, k))
    — indices into the original support row order; slots beyond the number
    of valid candidates hold -inf / -1.

    backend: 'host' (CPU BLAS inverted traversal — default), 'tiles'
    (jittable XLA twin of the kernel's tiling), 'pallas' (the kernel;
    also selected by use_pallas=True), or 'fused' (probe + scan + top-k in
    ONE jitted dispatch — the serving hot path).  All implement identical
    per-query top-nprobe semantics.

    A `DynamicIVFIndex` dispatches to its frozen base on the chosen backend
    and merges the exact-scanned delta tier into the result — except on the
    fused backend, which PROBES the per-centroid delta sub-lists inside the
    same dispatch."""
    nprobe = max(1, min(nprobe, index.n_clusters))
    backend = backend or ("pallas" if use_pallas else "host")
    if backend == "fused":
        return _fused_ivf_dispatch(jnp.asarray(queries), index, k, nprobe)
    if isinstance(index, DynamicIVFIndex):
        with index._lock:       # base swaps atomically under the lock
            base = index.base
        base_sc, base_ix = ivf_topk(
            queries, base, k, nprobe, use_pallas=use_pallas,
            backend=backend, block_q=block_q)
        return index.merge_delta(queries, base_sc, base_ix, k)
    k = min(k, index.n_rows, nprobe * index.list_size)
    queries = jnp.asarray(queries)
    # repro: allow-host: staged backends plan tile probes on the host
    q_probe = np.asarray(ivf_probe(queries, index.centroids, nprobe))

    if backend == "host":
        # repro: allow-host: the CPU inverted-traversal backend by contract
        return _score_pairs_host(np.asarray(queries, np.float32), q_probe,
                                 index, k)

    q_sorted, qp_sorted, tile_probe, tile_valid, inv_order, bq = \
        _sorted_tile_plan(queries, q_probe, block_q)

    if backend == "pallas":
        scores, idx = ivf_topk_pallas(
            q_sorted, index.sup_cm, index.ids_cm, index.inv_cm,
            jnp.asarray(qp_sorted), jnp.asarray(tile_probe),
            jnp.asarray(tile_valid), k)
        scores = jnp.where(idx >= 0, scores, -jnp.inf)
    elif backend == "tiles":
        scores, idx = _score_tiles(
            q_sorted, jnp.asarray(qp_sorted), jnp.asarray(tile_probe),
            jnp.asarray(tile_valid), index.sup_cm, index.ids_cm,
            index.inv_cm, k, bq)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    inv_order = jnp.asarray(inv_order)
    return scores[inv_order], idx[inv_order]


def ivfpq_topk(queries, index: IVFPQIndex, k: int,
               nprobe: int = DEFAULT_NPROBE, rerank: int = DEFAULT_RERANK, *,
               use_pallas: bool = False, backend: str | None = None,
               block_q: int = 32):
    """Two-stage IVF-PQ search.  queries (Q, D) L2-normalized; same output
    contract as `ivf_topk` (-inf / -1 beyond the valid candidates).

    Stage 1 scores the probed lists' PACKED codes by ADC (backend 'host' /
    'tiles' / 'pallas', mirroring `ivf_topk`) into a shortlist of
    ``rerank * k`` candidates; stage 2 re-scores exactly those rows from the
    raw cold tier and keeps the top k, which restores near-exact recall at
    a per-query cost of one small (kk, D) gather.  ``rerank=0`` skips stage
    2 and returns raw ADC scores (cheapest, recall bounded by quantization
    error); ``rerank=1`` re-scores just the top-k shortlist — exact scores
    re-sorted among themselves, so the candidate SET still comes from ADC
    but the returned ordering is exact.

    ``backend='fused'`` runs probe, ADC scan, shortlist selection AND the
    exact re-rank in one jitted dispatch (`_fused_ivfpq_topk`) — the serving
    hot path.  The staged backends fold stage 2 into the same jitted call as
    their scoring pass (`_staged_tail`), so every backend re-ranks without a
    second host->device hop; 'host' remains the CPU reference/debug
    traversal.

    A `DynamicIVFIndex` dispatches to its frozen base and merges the
    exact-scanned delta tier — except on the fused backend, which PROBES
    the per-centroid delta sub-lists inside the same dispatch.  With
    ``rerank >= 1`` both sides carry exact cosine scores, so the merge order
    is exact; at ``rerank=0`` the base side is raw ADC and the merge
    compares approximate base scores with exact delta scores (delta rows
    keep their exactness either way)."""
    nprobe = max(1, min(nprobe, index.n_clusters))
    backend = backend or ("pallas" if use_pallas else "host")
    if backend == "fused":
        return _fused_ivfpq_dispatch(jnp.asarray(queries), index, k, rerank,
                                     nprobe)
    if isinstance(index, DynamicIVFIndex):
        with index._lock:       # base swaps atomically under the lock
            base = index.base
        base_sc, base_ix = ivfpq_topk(
            queries, base, k, nprobe, rerank, use_pallas=use_pallas,
            backend=backend, block_q=block_q)
        return index.merge_delta(queries, base_sc, base_ix, k)
    k = min(k, index.n_rows, nprobe * index.list_size)
    kk = min(max(rerank, 1) * k, index.n_rows, nprobe * index.list_size)
    queries = jnp.asarray(queries)
    # repro: allow-host: staged backends plan tile probes on the host
    q_probe = np.asarray(ivf_probe(queries, index.centroids, nprobe))

    if backend == "host":
        # repro: allow-host: the CPU ADC traversal backend by contract
        scores, idx = _adc_pairs_host(np.asarray(queries, np.float32),
                                      q_probe, index, kk)
        if not rerank:
            return scores[:, :k], idx[:, :k]
        return _rerank_exact(queries, index.sup_flat, idx, k)
    if backend not in ("tiles", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    q_sorted, qp_sorted, tile_probe, tile_valid, inv_order, bq = \
        _sorted_tile_plan(queries, q_probe, block_q)
    return _staged_tail(
        queries, q_sorted, jnp.asarray(qp_sorted), jnp.asarray(tile_probe),
        jnp.asarray(tile_valid), jnp.asarray(inv_order), index.codes_cm,
        index.ids_cm, index.inv_cm, index.anchors, index.codebooks,
        index.sup_flat, k=k, kk=kk, bq=bq, m=index.m, nbits=index.nbits,
        rerank=bool(rerank), backend=backend)


@functools.partial(jax.jit, static_argnames=("k", "kk", "bq", "m", "nbits",
                                             "rerank", "backend"))
def _staged_tail(queries, q_sorted, qp_sorted, tile_probe, tile_valid,
                 inv_order, codes_cm, ids_cm, inv_cm, anchors, codebooks,
                 sup_flat, *, k: int, kk: int, bq: int, m: int, nbits: int,
                 rerank: bool, backend: str):
    """Device tail of the tiles/pallas backends: ADC scoring, un-sort, and
    the exact re-rank in ONE jitted dispatch — after the host plans the
    tile slot lists there is no further host->device hop."""
    if backend == "pallas":
        qf = q_sorted.astype(jnp.float32)
        t, s = tile_probe.shape
        # each query's dot with the anchor of every slot of its tile
        aq = jnp.einsum("tqd,tsd->tqs", qf.reshape(t, bq, -1),
                        jnp.take(anchors, tile_probe, axis=0),
                        preferred_element_type=jnp.float32)
        lut = _adc_lut(qf, codebooks, m, nbits).reshape(t * bq, m, -1)
        scores, idx = ivfpq_adc_pallas(
            jnp.moveaxis(lut, 1, 0), codes_cm, ids_cm, inv_cm,
            aq.reshape(t * bq, s), qp_sorted, tile_probe, tile_valid, kk,
            m=m, nbits=nbits)
        scores = jnp.where(idx >= 0, scores, -jnp.inf)
    else:
        scores, idx = _adc_tiles(
            q_sorted, qp_sorted, tile_probe, tile_valid, codes_cm, ids_cm,
            inv_cm, anchors, codebooks, kk, bq, m, nbits)
    scores, idx = scores[inv_order], idx[inv_order]
    if not rerank:
        return scores[:, :k], idx[:, :k]
    return _rerank_exact(queries, sup_flat, idx, k)
