"""Geometric descriptor matching + RANSAC + ICP kernels (XLA).

Role of the mvrecon matchers the reference instantiates at
SparkGeometricDescriptorMatching.java:564-621 — ``GeometricHashingPairwise``
(rotation-invariant local frames), ``(F)RGLDMPairwise`` (translation-invariant
redundant local geometric descriptors), ``IterativeClosestPointPairwise`` —
and the RANSAC consensus fit (``RANSACParameters``: 10k iterations, eps 5 px,
minInlierRatio 0.1, minInliers 12).

TPU design: descriptors for a whole point cloud build as dense (N,k) kNN +
gather ops; candidate matching is one squared-distance matmul + top-2 + ratio
test; RANSAC is hypothesis-parallel — a fixed batch of minimal samples is
fitted with the batched model fits of ``ops.models`` and scored against all
candidates at once (argmax selection, no data-dependent control flow).
"""

from __future__ import annotations

import functools
from itertools import combinations

import jax
import jax.numpy as jnp
import numpy as np

from .models import MIN_POINTS, fit_model, fit_interpolated

GEOMETRIC_HASHING = "FAST_ROTATION"        # reference method enum names
RGLDM = "PRECISE_TRANSLATION"
FRGLDM = "FAST_TRANSLATION"
ICP = "ICP"


# --------------------------------------------------------------------------
# descriptors
# --------------------------------------------------------------------------

# row-tile budget: a distance tile holds at most this many f32 entries
# (2^26 = 256 MB), so big clouds never materialize an (N,N) matrix
_TILE_ENTRIES = 1 << 26


def _row_block(n: int) -> int:
    r = max(128, _TILE_ENTRIES // max(n, 1))
    return int(min(1 << int(np.ceil(np.log2(r))), max(n, 1)))


@functools.partial(jax.jit, static_argnames=("k", "rb"))
def _knn_kernel(points: jnp.ndarray, k: int, rb: int) -> jnp.ndarray:
    """(N,k) nearest-neighbor indices, row-tiled: each lax.map step builds
    one (rb, N) distance tile — memory stays O(rb*N) instead of O(N^2), so
    1e5-point clouds (the reference handles these via KD-trees) fit HBM."""
    p = points.astype(jnp.float32)
    n = p.shape[0]
    pad_rows = (-n) % rb
    rows = jnp.pad(p, ((0, pad_rows), (0, 0)))
    row_ids = jnp.arange(n + pad_rows, dtype=jnp.int32)

    def block(args):
        rp, rid = args
        d2 = ((rp[:, None, :] - p[None, :, :]) ** 2).sum(-1)  # (rb, N)
        d2 = jnp.where(rid[:, None] == jnp.arange(n)[None, :], jnp.inf, d2)
        _, idx = jax.lax.top_k(-d2, k)
        return idx

    idx = jax.lax.map(block, (rows.reshape(-1, rb, 3),
                              row_ids.reshape(-1, rb)))
    return idx.reshape(-1, k)[:n]


def knn_indices(points, k: int):
    """Indices of the k nearest neighbors (self excluded) for each point."""
    n = int(points.shape[0])
    return _knn_kernel(jnp.asarray(points), k, _row_block(n))


def subset_combinations(n_pool: int, n_use: int) -> np.ndarray:
    """All ordered subsets (preserving distance order) of size ``n_use`` from
    the ``n_pool`` nearest neighbors — the 'redundancy' of RGLDM."""
    return np.array(list(combinations(range(n_pool), n_use)), np.int32)


@functools.partial(
    jax.jit, static_argnames=("n_neighbors", "redundancy", "rotation_invariant")
)
def build_descriptors(
    points: jnp.ndarray,
    n_neighbors: int = 3,
    redundancy: int = 1,
    rotation_invariant: bool = True,
):
    """Per-point local geometric descriptors.

    Returns (descriptors (N*S, n_neighbors*3) float32, owner (N*S,) int32)
    where S = C(n_neighbors+redundancy, n_neighbors) subsets per point.

    rotation_invariant=True expresses the neighbor offsets in a local frame
    built from the two nearest neighbors (GeometricHashing role); False keeps
    raw offsets ordered by distance (RGLDM/FRGLDM role, translation-invariant
    only).
    """
    n = points.shape[0]
    pool = n_neighbors + redundancy
    idx = knn_indices(points, pool)                       # (N, pool)
    offs = points[idx] - points[:, None, :]               # (N, pool, 3)
    subs = jnp.asarray(subset_combinations(pool, n_neighbors))  # (S, n_use)
    sel = offs[:, subs, :]                                # (N, S, n_use, 3)

    if rotation_invariant:
        # local frame from the subset's two nearest offsets:
        # x along o0; y in span(o0,o1) orthogonal to x; z = x×y (handedness
        # fixed -> reflections are NOT matched, same as the reference)
        o0 = sel[..., 0, :]
        o1 = sel[..., 1 % n_neighbors, :]
        ex = o0 / (jnp.linalg.norm(o0, axis=-1, keepdims=True) + 1e-12)
        ey = o1 - (o1 * ex).sum(-1, keepdims=True) * ex
        ey = ey / (jnp.linalg.norm(ey, axis=-1, keepdims=True) + 1e-12)
        ez = jnp.cross(ex, ey)
        frame = jnp.stack([ex, ey, ez], axis=-1)          # (N, S, 3, 3) cols=basis
        sel = jnp.einsum("nsji,nskj->nski", frame, sel)   # coords in local frame

    desc = sel.reshape(n, -1, n_neighbors * 3)            # (N, S, d)
    s = desc.shape[1]
    owner = jnp.repeat(jnp.arange(n, dtype=jnp.int32), s)
    return desc.reshape(n * s, -1).astype(jnp.float32), owner


def block_descriptors_impl(points, valid, n_neighbors: int = 3,
                           redundancy: int = 1,
                           rotation_invariant: bool = True):
    """Per-point descriptors of one detection block's FIXED-K candidate
    list (padded slots flagged by ``valid``) — the extract half of the
    fused detect+extract program (ops.dog.dog_detect_extract_impl), where
    the peaks never leave HBM between the DoG top-K and this.

    Same subset/frame math as :func:`build_descriptors`; the kNN is
    masked by VALIDITY instead of run on a dense cloud: invalid rows and
    columns (and the diagonal) get +inf DISTANCE — the coordinates are
    never poisoned, because an inf-inf arithmetic path would NaN the
    distances and break top_k ordering. Invalid offsets are zeroed before
    the frame math so padded slots produce deterministic all-zero
    descriptors. Returns (desc (K, S, n_neighbors*3) float32,
    dvalid (K,) bool); dvalid marks points with a full pool of valid
    neighbors."""
    k = int(points.shape[0])
    pool = n_neighbors + redundancy
    n_subs = len(subset_combinations(pool, n_neighbors))
    if k <= pool:  # static: fewer candidate slots than a neighbor pool
        return (jnp.zeros((k, n_subs, n_neighbors * 3), jnp.float32),
                jnp.zeros((k,), bool))
    p = points.astype(jnp.float32)
    d2 = ((p[:, None, :] - p[None, :, :]) ** 2).sum(-1)       # (K, K)
    pair_ok = valid[:, None] & valid[None, :]
    d2 = jnp.where(pair_ok & ~jnp.eye(k, dtype=bool), d2, jnp.inf)
    neg, idx = jax.lax.top_k(-d2, pool)                       # (K, pool)
    dvalid = valid & (neg[:, -1] > -jnp.inf)  # pool-th neighbor is real
    offs = p[idx] - p[:, None, :]                             # (K, pool, 3)
    offs = jnp.where(dvalid[:, None, None], offs, 0.0)
    subs = jnp.asarray(subset_combinations(pool, n_neighbors))
    sel = offs[:, subs, :]                                    # (K, S, u, 3)
    if rotation_invariant:
        o0 = sel[..., 0, :]
        o1 = sel[..., 1 % n_neighbors, :]
        ex = o0 / (jnp.linalg.norm(o0, axis=-1, keepdims=True) + 1e-12)
        ey = o1 - (o1 * ex).sum(-1, keepdims=True) * ex
        ey = ey / (jnp.linalg.norm(ey, axis=-1, keepdims=True) + 1e-12)
        ez = jnp.cross(ex, ey)
        frame = jnp.stack([ex, ey, ez], axis=-1)
        sel = jnp.einsum("nsji,nskj->nski", frame, sel)
    desc = sel.reshape(k, -1, n_neighbors * 3).astype(jnp.float32)
    return desc, dvalid


def block_descriptors_batch_impl(points, valid, n_neighbors: int = 3,
                                 redundancy: int = 1,
                                 rotation_invariant: bool = True):
    """vmapped :func:`block_descriptors_impl` over a leading batch axis.
    Un-jitted so the mesh layer can wrap it with batch-axis shardings."""
    return jax.vmap(
        lambda pp, vv: block_descriptors_impl(
            pp, vv, n_neighbors, redundancy, rotation_invariant)
    )(points, valid)


block_descriptors_batch = functools.partial(
    jax.jit,
    static_argnames=("n_neighbors", "redundancy", "rotation_invariant"),
)(block_descriptors_batch_impl)


@jax.jit
def _pairwise_sqdist(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(Na,Nb) squared euclidean distances via the matmul identity.

    The clouds are shifted to a common centroid (distance-invariant) and the
    matmul forced to full f32 — TPU matmuls default to bf16 passes, whose
    ~0.4% error would drown small distances under the a²+b²-2ab cancellation.
    """
    c = b.mean(0)
    a = a - c
    b = b - c
    a2 = (a**2).sum(-1)[:, None]
    b2 = (b**2).sum(-1)[None, :]
    ab = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(a2 + b2 - 2.0 * ab, 0.0)


@jax.jit
def _match_ratio_dense(desc_a, owner_a, desc_b, owner_b, ratio: jnp.ndarray):
    d2 = _pairwise_sqdist(desc_a, desc_b)                 # (Da, Db)
    best = jnp.argmin(d2, axis=1)
    bestd = jnp.take_along_axis(d2, best[:, None], axis=1)[:, 0]
    same_owner = owner_b[None, :] == owner_b[best][:, None]
    d2_masked = jnp.where(same_owner, jnp.inf, d2)
    second = jnp.min(d2_masked, axis=1)
    accept = jnp.sqrt(second) >= ratio * jnp.sqrt(bestd)
    return owner_b[best], accept


@functools.partial(jax.jit, static_argnames=("cb", "topk"))
def _match_ratio_row_chunk(desc_r, desc_b, owner_b, ratio, cb: int,
                           topk: int):
    """One row chunk of the tiled ratio test: scan B in ``cb``-column tiles
    keeping a running per-row top-``topk`` (distance, owner) — memory is
    O(rows*cb). topk must exceed the per-owner descriptor multiplicity so
    the best different-owner distance survives the truncation."""
    db = desc_b.shape[0]
    pad = (-db) % cb
    # pad with zeros (scale-neutral for the centered matmul — huge pad
    # values would wreck the a^2+b^2-2ab cancellation) and mask by owner
    descs = jnp.pad(desc_b, ((0, pad), (0, 0)))
    owners = jnp.pad(owner_b, (0, pad), constant_values=-1)
    r = desc_r.shape[0]
    init = (jnp.full((r, topk), jnp.inf, jnp.float32),
            jnp.full((r, topk), -1, jnp.int32))

    def step(carry, tile):
        vals, owns = carry
        dt, ot = tile
        d2 = _pairwise_sqdist(desc_r, dt)                 # (r, cb)
        d2 = jnp.where(ot[None, :] == -1, jnp.inf, d2)
        allv = jnp.concatenate([vals, d2], axis=1)
        allo = jnp.concatenate([owns, jnp.broadcast_to(ot, (r, cb))], axis=1)
        nv, ni = jax.lax.top_k(-allv, topk)
        return (-nv, jnp.take_along_axis(allo, ni, axis=1)), None

    (vals, owns), _ = jax.lax.scan(
        step, init, (descs.reshape(-1, cb, descs.shape[1]),
                     owners.reshape(-1, cb)))
    best_owner = owns[:, 0]
    bestd = vals[:, 0]
    diff = owns != best_owner[:, None]
    second = jnp.min(jnp.where(diff, vals, jnp.inf), axis=1)
    accept = jnp.sqrt(second) >= ratio * jnp.sqrt(bestd)
    return best_owner, accept


def match_ratio_test(desc_a, owner_a, desc_b, owner_b, ratio,
                     max_owner_multiplicity: int = 6):
    """Best-vs-second-best candidate matching.

    For each descriptor of A: nearest and second-nearest descriptor of B
    (second-nearest restricted to a DIFFERENT owner point, so redundant
    descriptors of one point don't veto themselves); accept if
    second/best >= ratio (mpicbg nearest-neighbor-distance-ratio test).
    Returns (match_b (Da,) int32 owner index in B, accept (Da,) bool).

    Small problems take the dense (Da,Db) kernel; large ones are tiled in
    row chunks x column tiles with a running top-k, so 1e5-point views
    (dense would need tens of GB) run in bounded memory.
    """
    da, db = int(desc_a.shape[0]), int(desc_b.shape[0])
    if da * db <= _TILE_ENTRIES:
        return _match_ratio_dense(desc_a, owner_a, desc_b, owner_b,
                                  jnp.float32(ratio))
    # one upload each, shared by every row chunk (numpy inputs used to ride
    # up the wire once per chunk before the asarray hoist; device_put makes
    # the single staging explicit and async)
    desc_a = jax.device_put(desc_a)
    desc_b = jax.device_put(desc_b)
    owner_b = jax.device_put(owner_b)
    rb = _row_block(min(db, 1 << 16))
    cb = 1 << 14
    topk = max(8, max_owner_multiplicity + 2)
    # row chunks dispatch in BYTE-BUDGETED segments instead of all at once
    # (unbounded dispatch pinned every chunk's row slice + scan workspace
    # simultaneously, so device memory scaled with da/rb): each in-flight
    # chunk pins its row slice, the (rb, cb) distance tile + top-k scan
    # carry, and its output tables; segment k+1 dispatches before segment
    # k drains — one pipelined device_get per segment, up to two segments
    # resident — so the device never idles between segments
    from ..utils.devicemem import InflightWindow, derived_budget

    dim = int(desc_a.shape[1])
    chunk_cost = (rb * dim * 4          # row slice copy
                  + 2 * rb * cb * 4     # distance tile + masked variant
                  + rb * (topk + cb) * 8)  # scan carry + top_k workspace
    # under the pair scheduler this runs pinned to a worker's device
    # (thread-local jax.default_device); size the segment window from THAT
    # device's PER-WORKER budget — N concurrent workers each claiming the
    # whole process fallback would pin N x the intended bytes, while
    # dividing by more workers than actually run shrinks the window and
    # pays avoidable sync round-trips
    own_dev = getattr(jax.config, "jax_default_device", None)
    if own_dev is not None:
        from ..parallel.pairsched import concurrent_pair_workers
        from ..utils.devicemem import pair_budget

        budget, source = pair_budget(own_dev, concurrent_pair_workers())
    else:
        budget, source = derived_budget()
    per_seg = max(1, int(budget // (2 * chunk_cost)))
    window = InflightWindow(budget, source)
    starts = list(range(0, da, rb))
    ratio32 = jnp.float32(ratio)
    owners: list[np.ndarray] = []
    accepts: list[np.ndarray] = []

    def drain(seg):
        try:
            got = jax.device_get(seg)
        finally:
            # drained or dead, the buffers leave the ledger either way
            window.release(chunk_cost * len(seg))
        for o, a in got:
            owners.append(o)
            accepts.append(a)

    prev = None
    for s0 in range(0, len(starts), per_seg):
        seg = []
        for s in starts[s0:s0 + per_seg]:
            seg.append(_match_ratio_row_chunk(desc_a[s:s + rb], desc_b,
                                              owner_b, ratio32, cb, topk))
            window.charge(chunk_cost)
        if prev is not None:
            drain(prev)
        prev = seg
    if prev is not None:
        drain(prev)
    return np.concatenate(owners), np.concatenate(accepts)


def match_candidates(
    points_a: np.ndarray,
    points_b: np.ndarray,
    method: str = GEOMETRIC_HASHING,
    n_neighbors: int = 3,
    redundancy: int = 1,
    ratio_of_distance: float = 3.0,
) -> np.ndarray:
    """Descriptor-based correspondence candidates between two clouds.

    Returns (M,2) int32 [index_a, index_b] with duplicates removed. Needs
    at least n_neighbors+redundancy+1 points per cloud.
    """
    pool = n_neighbors + redundancy
    if len(points_a) <= pool or len(points_b) <= pool:
        return np.zeros((0, 2), np.int32)
    rot = method == GEOMETRIC_HASHING
    da, oa = build_descriptors(jnp.asarray(points_a, jnp.float32),
                               n_neighbors, redundancy, rot)
    db, ob = build_descriptors(jnp.asarray(points_b, jnp.float32),
                               n_neighbors, redundancy, rot)
    # per-owner descriptor multiplicity bounds the tiled top-k truncation
    n_subsets = len(subset_combinations(pool, n_neighbors))
    mb, acc = match_ratio_test(da, oa, db, ob,
                               jnp.float32(ratio_of_distance),
                               max_owner_multiplicity=n_subsets)
    oa, mb, acc = np.asarray(oa), np.asarray(mb), np.asarray(acc)
    pairs = np.stack([oa[acc], mb[acc]], axis=1)
    return np.unique(pairs, axis=0).astype(np.int32)


# --------------------------------------------------------------------------
# RANSAC
# --------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("model_kind", "reg_kind", "iterations", "sample", "lam"),
)
def _ransac_kernel(pa, pb, valid, key, epsilon, lam,
                   model_kind, reg_kind, iterations, sample):
    m = pa.shape[0]
    keys = jax.random.split(key, iterations)
    idx = jax.vmap(
        lambda k: jax.random.choice(k, m, (sample,), replace=False,
                                    p=valid / valid.sum())
    )(keys)                                               # (I, sample)
    sp = pa[idx]                                          # (I, sample, 3)
    sq = pb[idx]
    models = fit_model(model_kind, sp, sq, xp=jnp)        # (I, 3, 4)
    pred = jnp.einsum("iab,mb->ima", models[:, :, :3], pa) + models[:, None, :, 3]
    err = jnp.linalg.norm(pred - pb[None], axis=-1)       # (I, M)
    inl = (err < epsilon) & (valid[None, :] > 0)
    counts = inl.sum(-1)
    best = jnp.argmax(counts)
    w = inl[best].astype(pa.dtype)
    final = fit_interpolated(model_kind, reg_kind, lam, pa, pb, w, xp=jnp)
    # one consensus re-fit round on the final model's inliers
    pred = pa @ final[:, :3].T + final[:, 3]
    err2 = jnp.linalg.norm(pred - pb, axis=-1)
    w2 = ((err2 < epsilon) & (valid > 0)).astype(pa.dtype)
    final = fit_interpolated(model_kind, reg_kind, lam, pa, pb, w2, xp=jnp)
    pred = pa @ final[:, :3].T + final[:, 3]
    err3 = jnp.linalg.norm(pred - pb, axis=-1)
    inliers = (err3 < epsilon) & (valid > 0)
    return final, inliers, counts[best]


@functools.partial(
    jax.jit, static_argnames=("model_kind", "iterations", "sample"),
)
def _ransac_score_chunk(pa, pb, valid, key, epsilon,
                        model_kind, iterations, sample):
    """Score one chunk of hypotheses; returns (best_count, best_model).
    Used for big candidate sets where (10k, M) error matrices would not fit;
    the (iterations, M) tile is bounded by the caller's chunking."""
    m = pa.shape[0]
    keys = jax.random.split(key, iterations)
    idx = jax.vmap(
        lambda k: jax.random.choice(k, m, (sample,), replace=False,
                                    p=valid / valid.sum())
    )(keys)
    models = fit_model(model_kind, pa[idx], pb[idx], xp=jnp)
    pred = jnp.einsum("iab,mb->ima", models[:, :, :3], pa) + models[:, None, :, 3]
    err = jnp.linalg.norm(pred - pb[None], axis=-1)
    counts = ((err < epsilon) & (valid[None, :] > 0)).sum(-1)
    best = jnp.argmax(counts)
    return counts[best], models[best]


def ransac(
    cand_a: np.ndarray,
    cand_b: np.ndarray,
    model_kind: str = "AFFINE",
    reg_kind: str = "RIGID",
    lam: float = 0.1,
    epsilon: float = 5.0,
    min_inlier_ratio: float = 0.1,
    min_inliers: int = 12,
    iterations: int = 10000,
    seed: int = 17,
) -> tuple[np.ndarray, np.ndarray] | None:
    """Hypothesis-parallel RANSAC over candidate correspondences.

    cand_a/cand_b: (M,3) matched candidate coordinates. Returns
    (model 3x4, inlier_mask (M,)) or None if consensus is too small
    (RANSAC defaults: SparkGeometricDescriptorMatching.java:180-189).
    Candidates are padded to the next power of two so compilation is shared
    across pairs of similar size. Sets too large for one (10k, M) error
    matrix are scored in iteration chunks with the consensus refits on host.
    """
    m = len(cand_a)
    sample = max(MIN_POINTS[model_kind], MIN_POINTS.get(reg_kind, 0), 1)
    if m < max(min_inliers, sample):
        return None
    padded = 1 << int(np.ceil(np.log2(max(m, 8))))
    pa = np.zeros((padded, 3), np.float32)
    pb = np.zeros((padded, 3), np.float32)
    val = np.zeros(padded, np.float32)
    pa[:m], pb[:m], val[:m] = cand_a, cand_b, 1.0

    if int(iterations) * padded <= _TILE_ENTRIES * 2:
        model, inliers, _ = _ransac_kernel(
            jnp.asarray(pa), jnp.asarray(pb), jnp.asarray(val),
            jax.random.PRNGKey(seed), jnp.float32(epsilon), float(lam),
            model_kind, reg_kind, int(iterations), int(sample),
        )
        inliers = np.asarray(inliers)[:m]
    else:
        chunk = max(64, (_TILE_ENTRIES * 2) // padded)
        ja, jb, jv = jnp.asarray(pa), jnp.asarray(pb), jnp.asarray(val)
        best_count, best_model = -1, None
        done = 0
        while done < int(iterations):
            it = int(min(chunk, int(iterations) - done))
            c, mdl = _ransac_score_chunk(
                ja, jb, jv, jax.random.PRNGKey(seed + done),
                jnp.float32(epsilon), model_kind, it, int(sample))
            if int(c) > best_count:
                best_count, best_model = int(c), np.asarray(mdl, np.float64)
            done += it
        # consensus refits on host (mirror of _ransac_kernel's tail)
        a64 = np.asarray(cand_a, np.float64)
        b64 = np.asarray(cand_b, np.float64)
        w = (np.linalg.norm(
            a64 @ best_model[:, :3].T + best_model[:, 3] - b64, axis=-1)
            < epsilon).astype(np.float64)
        mdl = fit_interpolated(model_kind, reg_kind, lam, a64, b64, w)
        w2 = (np.linalg.norm(a64 @ mdl[:, :3].T + mdl[:, 3] - b64, axis=-1)
              < epsilon).astype(np.float64)
        mdl = fit_interpolated(model_kind, reg_kind, lam, a64, b64, w2)
        inliers = np.linalg.norm(
            a64 @ mdl[:, :3].T + mdl[:, 3] - b64, axis=-1) < epsilon

    n_in = int(inliers.sum())
    if n_in < min_inliers or n_in < min_inlier_ratio * m:
        return None
    # final f64 refit on the inlier set (the device kernel runs f32)
    model = fit_interpolated(model_kind, reg_kind, lam,
                             np.asarray(cand_a, np.float64)[inliers],
                             np.asarray(cand_b, np.float64)[inliers])
    # bst-lint: off=host-sync (fit_interpolated xp=np: host f64 refit)
    return np.asarray(model, np.float64), inliers


def ransac_multi(
    cand_a: np.ndarray,
    cand_b: np.ndarray,
    model_kind: str = "AFFINE",
    reg_kind: str = "RIGID",
    lam: float = 0.1,
    epsilon: float = 5.0,
    min_inlier_ratio: float = 0.1,
    min_inliers: int = 12,
    iterations: int = 10000,
    seed: int = 17,
) -> list[tuple[np.ndarray, np.ndarray]]:
    """Multi-consensus RANSAC (RANSACParameters multiconsensus option,
    SparkGeometricDescriptorMatching.java:145-146,307): repeatedly find the
    largest consensus among the REMAINING candidates, remove its inliers,
    and continue until no consensus is left (the reference's loop
    semantics) — so a pair whose correspondences follow several distinct
    transforms (e.g. grouped tiles moving independently) yields every set.

    Returns [(model 3x4, inlier_mask over the ORIGINAL candidates), ...]
    ordered by discovery (largest consensus first in practice). Terminates:
    every accepted set removes >= min_inliers >= 1 candidates."""
    remaining = np.arange(len(cand_a))
    out: list[tuple[np.ndarray, np.ndarray]] = []
    round_i = 0
    while len(remaining) >= max(min_inliers, 1):
        res = ransac(cand_a[remaining], cand_b[remaining], model_kind,
                     reg_kind, lam, epsilon, min_inlier_ratio, min_inliers,
                     iterations, seed=seed + round_i)
        if res is None:
            break
        model, inl = res
        mask = np.zeros(len(cand_a), bool)
        mask[remaining[inl]] = True
        out.append((model, mask))
        remaining = remaining[~inl]
        round_i += 1
    return out


# --------------------------------------------------------------------------
# ICP
# --------------------------------------------------------------------------

def icp(
    points_a: np.ndarray,
    points_b: np.ndarray,
    model_kind: str = "AFFINE",
    reg_kind: str = "RIGID",
    lam: float = 0.1,
    max_distance: float = 2.5,
    max_iterations: int = 200,
    min_converged: float = 1e-4,
    use_ransac: bool = False,
    ransac_epsilon: float = 5.0,
    ransac_iterations: int = 200,
    seed: int = 17,
) -> tuple[np.ndarray, np.ndarray] | None:
    """Iterative closest point: A is progressively transformed onto B.

    Returns (model 3x4 mapping a->b, correspondences (K,2) [ia, ib]) or None.
    Defaults follow the reference (200 iterations, 2.5 px max distance).
    The NN assignment each round is one device distance matrix; the model
    refit reuses the batched fits. ``use_ransac`` filters each round's NN
    correspondences through a RANSAC consensus before the refit
    (--icpUseRANSAC, SparkGeometricDescriptorMatching.java:155-156).
    """
    a = np.asarray(points_a, np.float64)
    b = np.asarray(points_b, np.float64)
    if len(a) < MIN_POINTS[model_kind] or len(b) < MIN_POINTS[model_kind]:
        return None
    model = np.hstack([np.eye(3), np.zeros((3, 1))])
    prev_err = np.inf
    pairs = None
    for it in range(max_iterations):
        moved = a @ model[:, :3].T + model[:, 3]
        d2 = np.asarray(_pairwise_sqdist(jnp.asarray(moved, jnp.float32),
                                         jnp.asarray(b, jnp.float32)))
        nn = d2.argmin(1)
        nd = np.sqrt(d2[np.arange(len(a)), nn])
        keep = nd < max_distance
        if keep.sum() < max(MIN_POINTS[model_kind], 3):
            return None
        pairs = np.stack([np.where(keep)[0], nn[keep]], 1)
        if use_ransac:
            res = ransac(a[pairs[:, 0]], b[pairs[:, 1]], model_kind,
                         reg_kind, lam, epsilon=ransac_epsilon,
                         min_inlier_ratio=0.0,
                         min_inliers=max(MIN_POINTS[model_kind], 3),
                         iterations=ransac_iterations, seed=seed + it)
            if res is not None:
                pairs = pairs[res[1]]
        model = fit_interpolated(model_kind, reg_kind, lam,
                                 a[pairs[:, 0]], b[pairs[:, 1]])
        err = float(nd[keep].mean())
        if abs(prev_err - err) < min_converged:
            break
        prev_err = err
    return model, pairs.astype(np.int32)
