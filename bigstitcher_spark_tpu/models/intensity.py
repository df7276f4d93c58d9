"""Intensity matching + solving driver: per-pair cell sampling, RANSAC line
fits, global solve, coefficients store.

TPU redesign of SparkIntensityMatching (SparkIntensityMatching.java:137-183)
and IntensitySolver (IntensitySolver.java:100-118): every view gets a coarse
coefficient grid (default 8x8x8, --renderScale 0.25); overlapping view pairs
contribute co-located intensity samples per cell pair; pairwise linear fits
run in one batched RANSAC kernel (ops.intensity); the global solve assembles
sufficient statistics into one quadratic form. Coefficients persist to an N5
(``setup{s}/timepoint{t}/coefficients`` shape (2, cx, cy, cz)) that
affine-fusion applies per view via trilinear interpolation over cell centers
(role of mvrecon ``Coefficients`` + SparkAffineFusion.java:545-559).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .. import observe
from ..io.chunkstore import ChunkStore, StorageFormat
from ..io.dataset_io import ViewLoader, best_mipmap_level
from ..io.spimdata import SpimData, ViewId
from ..ops.dog import sample_trilinear
from ..ops.intensity import (
    match_cells_histogram,
    match_cells_ransac,
    match_stats,
    solve_intensity_coefficients,
)
from ..utils.geometry import (
    Interval,
    invert_affine,
    transformed_interval,
)


@dataclass
class IntensityParams:
    """Defaults follow the reference CLI (SparkIntensityMatching.java)."""

    coefficients: tuple[int, int, int] = (8, 8, 8)
    render_scale: float = 0.25
    method: str = "RANSAC"            # RANSAC | HISTOGRAM
    ransac_epsilon: float = 0.02      # relative to [0,1]-normalized intensity
    ransac_iterations: int = 1000
    min_samples_per_cell: int = 10
    lam: float = 0.1                  # solve regularization toward identity
    max_samples_per_cell: int = 2000
    # reference candidate/inlier filters (SparkIntensityMatching.java:51-77)
    min_threshold: float = 1.0        # --minThreshold: discard samples below
    max_threshold: float = float("nan")  # --maxThreshold: discard above
    # --minNumCandidates per cell pair (SparkIntensityMatching.java:58
    # default; programmatic callers get the same filtering as the CLI)
    min_num_candidates: int = 1000
    min_inlier_ratio: float = 0.1     # --minInlierRatio (RANSAC)
    min_num_inliers: int = 10         # --minNumInliers (RANSAC)
    max_trust: float = 3.0            # --maxTrust: drop inliers with residual
    #                                   > maxTrust * median residual


@dataclass
class CellMatch:
    view_a: ViewId
    view_b: ViewId
    cell_a: int                # flat cell index within view A's grid
    cell_b: int
    stats: tuple[float, ...]   # (n, Sx, Sy, Sxx, Syy, Sxy) of inlier samples
    fit: tuple[float, float]   # (a, b): i_b ~= a*i_a + b


def _cell_index(px: np.ndarray, view_size: np.ndarray, dims) -> np.ndarray:
    """Flat coefficient-cell index for full-res pixel coords (N,3)."""
    cell = np.floor(px / (view_size / np.asarray(dims, np.float64))).astype(int)
    cell = np.clip(cell, 0, np.asarray(dims) - 1)
    return (cell[:, 0] * dims[1] + cell[:, 1]) * dims[2] + cell[:, 2]


def _sample_view(sd, loader, view, world_pts):
    """Intensities + full-res px coords of world points inside the view
    (None-padded with NaN outside)."""
    inv = invert_affine(sd.model(view))
    px = world_pts @ inv[:, :3].T + inv[:, 3]
    size = np.array(sd.view_size(view), np.float64)
    inside = np.all((px >= 0) & (px <= size - 1), axis=1)
    vals = np.full(len(px), np.nan)
    if inside.any():
        ds_factors = loader.downsampling_factors(view.setup)
        lvl = best_mipmap_level(ds_factors, (2, 2, 2))
        f = np.asarray(ds_factors[lvl], np.float64)
        lpx = (px[inside] - (f - 1) / 2.0) / f
        lo = np.maximum(np.floor(lpx.min(axis=0)).astype(int) - 1, 0)
        hi = np.ceil(lpx.max(axis=0)).astype(int) + 2
        patch = loader.read_block(view, lvl, lo, hi - lo).astype(np.float32)
        vals[inside] = sample_trilinear(patch, lpx - lo)
    return vals, px, inside


def match_pair_intensities(
    sd: SpimData, loader: ViewLoader, va: ViewId, vb: ViewId,
    params: IntensityParams, seed: int = 5,
) -> list[CellMatch]:
    """Collect co-located samples in the pair overlap on a renderScale grid
    and fit per-cell-pair linear maps."""
    box_a = transformed_interval(sd.model(va), Interval.from_shape(sd.view_size(va)))
    box_b = transformed_interval(sd.model(vb), Interval.from_shape(sd.view_size(vb)))
    ov = box_a.intersect(box_b)
    if ov.is_empty():
        return []
    step = max(1.0 / params.render_scale, 1.0)
    axes = [np.arange(ov.min[d], ov.max[d] + 1, step) for d in range(3)]
    gx, gy, gz = np.meshgrid(*axes, indexing="ij")
    world = np.stack([gx.ravel(), gy.ravel(), gz.ravel()], axis=-1)

    ia, pa, in_a = _sample_view(sd, loader, va, world)
    ib, pb, in_b = _sample_view(sd, loader, vb, world)
    both = in_a & in_b & np.isfinite(ia) & np.isfinite(ib)
    # intensity thresholds: discard candidates outside [min, max]
    both &= (ia >= params.min_threshold) & (ib >= params.min_threshold)
    if np.isfinite(params.max_threshold):
        both &= (ia <= params.max_threshold) & (ib <= params.max_threshold)
    if not both.any():
        return []
    dims = params.coefficients
    ca = _cell_index(pa[both], np.array(sd.view_size(va), np.float64), dims)
    cb = _cell_index(pb[both], np.array(sd.view_size(vb), np.float64), dims)
    xa, xb = ia[both], ib[both]

    # normalize to [0,1] for a scale-free RANSAC epsilon
    scale = max(float(np.max(xa)), float(np.max(xb)), 1e-9)
    xa_n, xb_n = xa / scale, xb / scale

    groups: dict[tuple[int, int], np.ndarray] = {}
    order = np.lexsort((cb, ca))
    keys = np.stack([ca[order], cb[order]], axis=1)
    uniq, starts = np.unique(keys, axis=0, return_index=True)
    bounds = list(starts) + [len(order)]
    sa_list, sb_list, pairs = [], [], []
    min_cand = max(params.min_samples_per_cell, params.min_num_candidates)
    for i, (cell_a, cell_b) in enumerate(uniq):
        sel = order[bounds[i]:bounds[i + 1]]
        if len(sel) < min_cand:
            continue
        if len(sel) > params.max_samples_per_cell:
            sel = sel[:: len(sel) // params.max_samples_per_cell + 1]
        sa_list.append(xa_n[sel])
        sb_list.append(xb_n[sel])
        pairs.append((int(cell_a), int(cell_b), sel))

    if not pairs:
        return []
    if params.method.upper() == "HISTOGRAM":
        fits = match_cells_histogram(sa_list, sb_list,
                                     params.min_samples_per_cell)
    else:
        fits = match_cells_ransac(
            sa_list, sb_list, epsilon=params.ransac_epsilon,
            min_inliers=params.min_samples_per_cell,
            iterations=params.ransac_iterations, seed=seed,
        )
    out = []
    for (cell_a, cell_b, sel), fit in zip(pairs, fits):
        if fit is None:
            continue
        a, b, _ = fit
        # inlier stats in ORIGINAL intensity units for the global solve
        x, y = xa[sel], xb[sel]
        xn, yn = x / scale, y / scale
        resid = np.abs(yn - (a * xn + b))
        inl = resid < 2.0 * params.ransac_epsilon
        # --maxTrust: iterative trim + REFIT (mpicbg filterRansac: drop
        # candidates with residual > maxTrust * median, refit, repeat)
        for _ in range(10):
            if inl.sum() < 2:
                break
            A = np.stack([xn[inl], np.ones(int(inl.sum()))], axis=1)
            (a, b), *_ = np.linalg.lstsq(A, yn[inl], rcond=None)
            resid = np.abs(yn - (a * xn + b))
            med = float(np.median(resid[inl]))
            new_inl = inl & (resid <= max(params.max_trust * med,
                                          1e-12))
            if (new_inl == inl).all():
                break
            inl = new_inl
        if inl.sum() < max(params.min_samples_per_cell,
                           params.min_num_inliers):
            continue
        if inl.sum() < params.min_inlier_ratio * len(sel):
            continue
        out.append(CellMatch(
            va, vb, int(cell_a), int(cell_b),
            match_stats(x[inl], y[inl]),
            (float(a), float(b * scale)),
        ))
    return out


def _pair_sample_boxes(sd, loader, va, vb, ov):
    """``(ds, offset, shape)`` source boxes of the two level-patch reads a
    pair's ``_sample_view`` calls make — the async prefetcher feed
    (parallel.pairsched ``prefetch_boxes``). The sample grid's pixel extremes
    sit at overlap corners under an affine model, so the corner-derived box
    covers the pair's ``read_block`` (over-covering by at most one grid step,
    clipped by ``prefetch_box``)."""
    corners = np.array([[ov.min[d] if (i >> d) & 1 == 0 else ov.max[d]
                         for d in range(3)] for i in range(8)], np.float64)
    boxes = []
    for v in (va, vb):
        inv = invert_affine(sd.model(v))
        px = corners @ inv[:, :3].T + inv[:, 3]
        size = np.array(sd.view_size(v), np.float64)
        px = np.clip(px, 0, size - 1)
        ds_factors = loader.downsampling_factors(v.setup)
        lvl = best_mipmap_level(ds_factors, (2, 2, 2))
        f = np.asarray(ds_factors[lvl], np.float64)
        lpx = (px - (f - 1) / 2.0) / f
        lo = np.maximum(np.floor(lpx.min(axis=0)).astype(int) - 1, 0)
        hi = np.ceil(lpx.max(axis=0)).astype(int) + 2
        b = loader.prefetch_box(v, lvl, tuple(int(x) for x in lo),
                                tuple(int(x) for x in hi - lo))
        if b is not None:
            boxes.append(b)
    return boxes


def match_intensities(
    sd: SpimData, loader: ViewLoader, views: list[ViewId],
    params: IntensityParams | None = None, progress: bool = True,
    devices: int | None = None,
) -> list[CellMatch]:
    """All overlapping pairs (SparkIntensityMatching.java:146-166).

    Pairs spread over every local device via the pair scheduler, weighted
    by each overlap's renderScale-grid sample count; seeds are attached
    per pair so placement never changes the fits and multi-device output
    equals single-device exactly."""
    from ..parallel.pairsched import PairTask, run_pair_tasks

    params = params or IntensityParams()
    views = sorted(views)
    boxes = {
        v: transformed_interval(sd.model(v), Interval.from_shape(sd.view_size(v)))
        for v in views
    }
    step = max(1.0 / params.render_scale, 1.0)
    pairs: list[tuple[ViewId, ViewId]] = []
    tasks: list[PairTask] = []
    for i in range(len(views)):
        for j in range(i + 1, len(views)):
            va, vb = views[i], views[j]
            if va.timepoint != vb.timepoint:
                continue
            if not boxes[va].overlaps(boxes[vb]):
                continue
            ov = boxes[va].intersect(boxes[vb])
            # placement ∝ the pair's sample-grid point count
            n_samples = float(np.prod(
                [max(1.0, (ov.shape[d] - 1) / step + 1) for d in range(3)]))
            tasks.append(PairTask(index=len(tasks), cost=n_samples,
                                  tag=(len(pairs), va, vb)))
            pairs.append((va, vb))

    def run_one(task):
        k, va, vb = task.tag
        return match_pair_intensities(sd, loader, va, vb, params, seed=5 + k)

    def prefetch_boxes(task):
        k, va, vb = task.tag
        return _pair_sample_boxes(sd, loader, va, vb,
                                  boxes[va].intersect(boxes[vb]))

    outs = run_pair_tasks(tasks, run_one, n_devices=devices,
                          stage="intensity", prefetch_boxes=prefetch_boxes)
    matches: list[CellMatch] = []
    for (va, vb), m in zip(pairs, outs):
        matches.extend(m)
        observe.log(f"  {va} <-> {vb}: {len(m)} cell matches",
                    stage="match-intensities", echo=progress,
                    matches=len(m))
    return matches


# --------------------------------------------------------------------------
# persistence (matches + coefficients N5)
# --------------------------------------------------------------------------

MATCH_GROUP = "matches"
COEFF_GROUP = "coefficients"


class IntensityStore:
    """N5 store for pairwise cell matches and solved coefficients
    (ViewPairCoefficientMatchesIO + Coefficients persistence role)."""

    def __init__(self, root: str):
        import os

        self.root = str(root)
        if os.path.isdir(self.root):
            self.store = ChunkStore.open(self.root)
        else:
            self.store = ChunkStore.create(self.root, StorageFormat.N5)

    @staticmethod
    def for_project(sd: SpimData, name: str = "intensity.n5") -> "IntensityStore":
        import os

        base = os.path.dirname(sd.xml_path or ".")
        return IntensityStore(os.path.join(base, name))

    @staticmethod
    def _pair_path(va: ViewId, vb: ViewId) -> str:
        return (f"{MATCH_GROUP}/tpId_{va.timepoint}_viewSetupId_{va.setup}"
                f"__tpId_{vb.timepoint}_viewSetupId_{vb.setup}")

    def save_matches(self, matches: list[CellMatch],
                     dims: tuple[int, int, int]) -> None:
        by_pair: dict[tuple[ViewId, ViewId], list[CellMatch]] = {}
        for m in matches:
            by_pair.setdefault((m.view_a, m.view_b), []).append(m)
        if self.store.exists(MATCH_GROUP):
            self.store.remove(MATCH_GROUP)
        for (va, vb), ms in by_pair.items():
            rows = np.array(
                [[m.cell_a, m.cell_b, *m.stats, *m.fit] for m in ms],
                np.float64,
            )  # (M, 10)
            path = self._pair_path(va, vb)
            ds = self.store.create_dataset(
                f"{path}/data", rows.shape, (max(len(ms), 1), 10), "float64"
            )
            ds.write(rows, (0, 0))
        self.store.set_attribute(MATCH_GROUP, "coefficientDims", list(dims))

    def load_all_matches(self) -> list[CellMatch]:
        out = []
        if not self.store.exists(MATCH_GROUP):
            return out
        for name in self.store.list_children(MATCH_GROUP):
            a, b = name.split("__")
            va = ViewId(int(a.split("_")[1]), int(a.split("_")[3]))
            vb = ViewId(int(b.split("_")[1]), int(b.split("_")[3]))
            rows = self.store.open_dataset(
                f"{MATCH_GROUP}/{name}/data").read_full()
            for r in rows:
                out.append(CellMatch(va, vb, int(r[0]), int(r[1]),
                                     tuple(r[2:8]), (r[8], r[9])))
        return out

    def coefficient_dims(self) -> tuple[int, int, int] | None:
        d = self.store.get_attribute(MATCH_GROUP, "coefficientDims", None)
        return tuple(int(v) for v in d) if d else None

    def save_coefficients(self, view: ViewId, coeffs: np.ndarray,
                          group: str | None = None,
                          dataset: str | None = None) -> None:
        """coeffs (cx,cy,cz,2) -> dataset (2,cx,cy,cz). ``group``/``dataset``
        override the default layout (--intensityN5Group/--intensityN5Dataset,
        IntensitySolver.java)."""
        path = (f"{group or COEFF_GROUP}/setup{view.setup}"
                f"/timepoint{view.timepoint}/{dataset or 'coefficients'}")
        arr = np.moveaxis(coeffs, -1, 0).astype(np.float64)
        if self.store.exists(path):
            self.store.remove(path)
        ds = self.store.create_dataset(path, arr.shape, arr.shape, "float64")
        ds.write(arr, (0,) * arr.ndim)

    def load_coefficients(self, view: ViewId,
                          group: str | None = None,
                          dataset: str | None = None) -> np.ndarray | None:
        path = (f"{group or COEFF_GROUP}/setup{view.setup}"
                f"/timepoint{view.timepoint}/{dataset or 'coefficients'}")
        if not self.store.is_dataset(path):
            return None
        arr = self.store.open_dataset(path).read_full()
        return np.moveaxis(arr, 0, -1)


def smoothness_pairs(dims: tuple[int, int, int], n_views: int) -> np.ndarray:
    """Intra-view adjacent-cell pairs for every view's coefficient grid,
    as a (P, 2) array of GLOBAL flat cell indices.

    Pure index arithmetic (one sliced ``arange`` cube per axis broadcast
    over views) — the former per-view cx/cy/cz/axis quadruple Python loop
    walked every cell of every view and dominated ``solve_intensities``
    setup at large grids. Same pair set, axis-major order."""
    ncell = int(np.prod(dims))
    idx = np.arange(ncell).reshape(dims)
    per_axis = []
    for d in range(3):
        lo = [slice(None)] * 3
        hi = [slice(None)] * 3
        lo[d] = slice(0, dims[d] - 1)
        hi[d] = slice(1, dims[d])
        per_axis.append(np.stack(
            [idx[tuple(lo)].ravel(), idx[tuple(hi)].ravel()], axis=1))
    base = np.concatenate(per_axis, axis=0)
    offs = (np.arange(n_views) * ncell)[:, None, None]
    return (base[None, :, :] + offs).reshape(-1, 2)


def solve_intensities(
    matches: list[CellMatch],
    views: list[ViewId],
    dims: tuple[int, int, int],
    lam: float = 0.1,
    progress: bool = True,
) -> dict[ViewId, np.ndarray]:
    """Global solve -> per-view (cx,cy,cz,2) [scale, offset] grids."""
    views = sorted(views)
    ncell = int(np.prod(dims))
    base = {v: i * ncell for i, v in enumerate(views)}
    stats_rows = []
    for m in matches:
        if m.view_a not in base or m.view_b not in base:
            continue
        stats_rows.append((base[m.view_a] + m.cell_a,
                           base[m.view_b] + m.cell_b, *m.stats))
    observe.log(f"solve-intensities: {len(views)} views x {ncell} cells, "
                f"{len(stats_rows)} matches, λ={lam}",
                stage="solve-intensities", echo=progress,
                views=len(views), cells=ncell, matches=len(stats_rows))
    # intensities can be large (uint16): normalize the quadratic form by the
    # global mean intensity so lam is scale-free
    mean_i = (np.mean([r[3] / max(r[2], 1) for r in stats_rows])
              if stats_rows else 1.0)
    s = 1.0 / max(mean_i, 1e-9)
    norm = []
    for ca, cb, n, sx, sy, sxx, syy, sxy in stats_rows:
        norm.append((int(ca), int(cb), n, sx * s, sy * s,
                     sxx * s * s, syy * s * s, sxy * s * s))
    # intra-view smoothness: 6-neighborhood of each cell grid, propagating
    # corrections into cells without overlap matches
    smooth = smoothness_pairs(dims, len(views))
    dev_sol: list = []
    sol = solve_intensity_coefficients(ncell * len(views), norm, lam,
                                       smooth_pairs=smooth,
                                       on_device_solution=dev_sol.append)
    # un-normalize: f(i) = a*(i*s)/s + b/s... scale invariant: offsets scale
    out = {}
    for v in views:
        c = sol[base[v]: base[v] + ncell].copy()
        c[:, 1] /= s
        out[v] = c.reshape(*dims, 2)
    if dev_sol:
        _register_device_coefficients(dev_sol[0], out, views, base, ncell,
                                      dims, s)
    return out


def _register_device_coefficients(dev, out, views, base, ncell, dims, s):
    """Mirror the host un-normalization ON DEVICE from the CG solver's
    device output and register the per-view grids with the fusion
    coefficient-table cache (models.affine_fusion.register_coefficient_table):
    the solve→fusion coefficient path stays device-resident, so a
    following fusion's first table lookup hits without the grids ever
    making the host->device round trip. The float64 math is the same IEEE
    sequence as the host branch above, so the registered table is
    bit-identical to one rebuilt from ``out``."""
    import jax
    import jax.numpy as jnp

    from .affine_fusion import register_coefficient_table

    with jax.enable_x64(True):
        d = jnp.reshape(dev[: 2 * ncell * len(views)], (-1, 2))
        per = {}
        for v in views:
            c = d[base[v]: base[v] + ncell]
            c = jnp.concatenate([c[:, :1], c[:, 1:] / s], axis=1)
            per[v] = jnp.reshape(c, (*dims, 2)).astype(jnp.float32)
    register_coefficient_table(out, per)
