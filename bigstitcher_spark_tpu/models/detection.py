"""Interest-point detection driver: per-view block grid with halo, batched
DoG kernel, subpixel localization, brightest-N filtering, interestpoints.n5.

TPU redesign of SparkInterestPointDetection (reference call stack SURVEY.md
§3.3): the work list is (view, block) tuples at detection resolution
(strategy P3 — halo by over-read, never neighbor communication); equally
shaped blocks from ALL views batch into one compiled DoG kernel; the sparse
tail (argwhere, quadratic fit, filters) runs on host. Detections restricted
to overlap regions replace the reference's per-(view,pair) duplicate pass +
KDTree dedup (SparkInterestPointDetection.java:809-892) with a single pass
over the union of overlap boxes — same output set, no dedup needed.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np

from ..utils.threads import CtxThreadPool

from .. import observe
from ..io.dataset_io import ViewLoader, best_mipmap_level, mipmap_transform
from ..io.interestpoints import InterestPointStore, register_points_in_xml
from ..io.spimdata import SpimData, ViewId
from ..ops.dog import (
    dog_block_topk_batch,
    dog_block_topk_batch_impl,
    dog_detect_extract_batch,
    dog_detect_extract_batch_impl,
    dog_halo,
    sample_trilinear,
)
from ..ops.descriptors import (
    block_descriptors_batch,
    block_descriptors_batch_impl,
)
from ..parallel.mesh import make_mesh, run_sharded_batches, shard_jit
from ..ops.downsample import downsample_block
from ..utils.geometry import (
    Interval,
    apply_affine,
    concatenate,
    invert_affine,
    transformed_interval,
)
from ..utils.grid import create_grid
from .. import profiling
from ..observe import metrics as _metrics

_BLOCKS_WHOLE = _metrics.counter("bst_detection_blocks_whole_total")
_BLOCKS_TRUNCATED = _metrics.counter("bst_detection_blocks_truncated_total")
_POINTS = _metrics.counter("bst_detection_points_total")


@dataclass
class DetectionParams:
    """Defaults match the reference CLI (SparkInterestPointDetection.java:116-170)."""

    label: str = "beads"
    sigma: float = 1.8
    threshold: float = 0.008
    downsample_xy: int = 2
    downsample_z: int = 1
    min_intensity: float | None = None
    max_intensity: float | None = None
    find_max: bool = True
    find_min: bool = False
    overlapping_only: bool = False
    max_spots: int = 0
    max_spots_per_overlap: bool = False
    store_intensities: bool = False
    median_radius: int = 0          # 0 = off (LazyBackgroundSubtract role)
    median_exact: bool = False      # exact per-slice radius-r median
    localization: str = "QUADRATIC"  # NONE | QUADRATIC subpixel
    only_compare_overlap_tiles: bool = False  # --onlyCompareOverlapTiles
    block_size: tuple[int, int, int] = (512, 512, 128)
    batch_size: int = 8
    # device-side compaction budget: K strongest candidates per block leave
    # the device (count is returned, so truncation is detected and warned)
    max_candidates_per_block: int = 4096
    # geometric descriptor extraction riding the detection pass: when on,
    # each block's peaks get kNN-frame descriptors computed WITHOUT leaving
    # HBM (one fused program per block, gated by BST_FUSED_DETECT)
    extract_descriptors: bool = False
    descriptor_neighbors: int = 3
    descriptor_redundancy: int = 1

    @property
    def downsampling(self) -> tuple[int, int, int]:
        return (self.downsample_xy, self.downsample_xy, self.downsample_z)

    def params_string(self) -> str:
        return (f"DOG (TPU) s={self.sigma} t={self.threshold} "
                f"overlappingOnly={self.overlapping_only} min={self.min_intensity} "
                f"max={self.max_intensity} ds={','.join(map(str, self.downsampling))}")


@dataclass
class ViewDetections:
    view: ViewId
    points: np.ndarray            # (N,3) float64, full-res view-local px
    values: np.ndarray            # (N,) DoG response at the detection
    intensities: np.ndarray | None = None
    # extract_descriptors riders: per-point kNN-frame descriptors (N, S, d)
    # and their validity (points near block borders may lack a full pool)
    descriptors: np.ndarray | None = None
    descriptor_valid: np.ndarray | None = None


@dataclass
class _BlockJob:
    view_idx: int
    core: Interval                # detection-res block (core, no halo)
    result: tuple | None = None   # (subpixel pts, values) after extraction
    index: int = 0                # place in the pass's work list (spans)


class _ViewPlan:
    """Per-view read geometry: stored level + residual in-memory downsampling."""

    def __init__(self, loader: ViewLoader, view: ViewId, ds: tuple[int, int, int]):
        factors = loader.downsampling_factors(view.setup)
        lvl = best_mipmap_level(factors, ds)
        f = tuple(int(x) for x in factors[lvl])
        if any(int(ds[d]) % f[d] != 0 for d in range(3)):
            lvl, f = 0, (1, 1, 1)
        self.view = view
        self.level = lvl
        self.rel = tuple(int(ds[d]) // f[d] for d in range(3))
        lvl_dims = loader.open(view, lvl).shape
        self.det_dims = tuple(int(s) // r for s, r in zip(lvl_dims, self.rel))

    def read_raw_block(self, loader: ViewLoader, offset, shape) -> np.ndarray:
        """Read the LEVEL-resolution voxels backing a detection-res box
        (mirror-padded outside the image), native dtype: level voxels
        [o*rel, (o+s)*rel) — the shared geometry for both the host-pooled
        and device-pooled paths."""
        lvl_off = [int(o) * r for o, r in zip(offset, self.rel)]
        lvl_shape = [int(s) * r for s, r in zip(shape, self.rel)]
        return _read_mirror(loader, self.view, self.level, lvl_off, lvl_shape)

    def read_det_block(self, loader: ViewLoader, offset, shape) -> np.ndarray:
        """Read a detection-res box (mirror-padded outside the image): level
        voxels [o*rel, (o+s)*rel) average-pooled by ``rel``
        (openAndDownsample, SparkInterestPointDetection.java:998-1118)."""
        raw = self.read_raw_block(loader, offset, shape)
        if all(r == 1 for r in self.rel):
            return raw.astype(np.float32)
        import jax

        return jax.device_get(
            downsample_block(raw.astype(np.float32), self.rel))


def _read_mirror(loader: ViewLoader, view, level, offset, shape) -> np.ndarray:
    """read_block with mirror (reflect) padding outside the image — matches
    the reference's extended images so borders don't produce edge extrema.
    When a streamed producer's device-resident blocks cover the box the
    read serves straight from HBM (``Dataset.read_device``) and the
    padding runs on device — values identical to the host path."""
    ds = loader.open(view, level)
    full = ds.shape
    lo = [max(0, int(o)) for o in offset]
    hi = [min(int(f), int(o) + int(s)) for f, o, s in zip(full, offset, shape)]
    if all(h > l for l, h in zip(lo, hi)):
        size = [h - l for l, h in zip(lo, hi)]
        rd = getattr(ds, "read_device", None)
        data = rd(lo, size) if rd is not None else None
        if data is None:
            data = ds.read(lo, size)
    else:
        return np.zeros(tuple(int(s) for s in shape),
                        dtype=np.dtype(ds.dtype))
    pad = [(l - int(o), int(o) + int(s) - h)
           for l, h, o, s in zip(lo, hi, offset, shape)]
    if any(p != (0, 0) for p in pad):
        capped = [(min(p0, data.shape[d] - 1), min(p1, data.shape[d] - 1))
                  for d, (p0, p1) in enumerate(pad)]
        extra = [(p[0] - c[0], p[1] - c[1]) for p, c in zip(pad, capped)]
        if isinstance(data, np.ndarray):
            data = np.pad(data, capped, mode="reflect")
            if any(e != (0, 0) for e in extra):
                data = np.pad(data, extra, mode="edge")
        else:
            import jax.numpy as jnp

            data = jnp.pad(data, capped, mode="reflect")
            if any(e != (0, 0) for e in extra):
                data = jnp.pad(data, extra, mode="edge")
    return data


def _median_background_divide(block: np.ndarray, radius: int,
                              exact: bool = False) -> np.ndarray:
    """Per-z-slice 2-D median background divide (LazyBackgroundSubtract role,
    SparkInterestPointDetection.java:536-543; median at
    LazyBackgroundSubtract.java:74-140).

    ``exact=True`` computes the true radius-r median over a circular
    footprint per slice (ImageJ RankFilters semantics). The default is a
    4x-decimated estimate bilinearly upsampled — a cheap stand-in at equal
    purpose (flat-field normalization); tests/test_detection.py quantifies
    the detection difference between the two on structured data."""
    if exact:
        from scipy.ndimage import median_filter

        r = int(radius)
        yy, xx = np.mgrid[-r:r + 1, -r:r + 1]
        # ImageJ RankFilters circular kernel: include when d^2 <= r^2 + 1
        footprint = (yy * yy + xx * xx) <= (r * r + 1)
        out = np.empty_like(block, dtype=np.float32)
        for z in range(block.shape[2]):
            sl = block[:, :, z].astype(np.float32)
            bg = median_filter(sl, footprint=footprint, mode="nearest")
            out[:, :, z] = sl / np.maximum(bg, 1e-6)
        return out
    from numpy.lib.stride_tricks import sliding_window_view

    dec = 4
    r = max(1, radius // dec)
    out = np.empty_like(block, dtype=np.float32)
    for z in range(block.shape[2]):
        sl = block[:, :, z].astype(np.float32)
        small = sl[::dec, ::dec]
        padded = np.pad(small, r, mode="edge")
        win = sliding_window_view(padded, (2 * r + 1, 2 * r + 1))
        med = np.median(win, axis=(-2, -1))
        # bilinear upsample back to the slice grid
        yi = np.minimum(np.arange(sl.shape[0]) / dec, med.shape[0] - 1)
        xi = np.minimum(np.arange(sl.shape[1]) / dec, med.shape[1] - 1)
        y0 = np.floor(yi).astype(int)
        x0 = np.floor(xi).astype(int)
        y1 = np.minimum(y0 + 1, med.shape[0] - 1)
        x1 = np.minimum(x0 + 1, med.shape[1] - 1)
        fy = (yi - y0)[:, None]
        fx = (xi - x0)[None, :]
        bg = (med[np.ix_(y0, x0)] * (1 - fy) * (1 - fx)
              + med[np.ix_(y1, x0)] * fy * (1 - fx)
              + med[np.ix_(y0, x1)] * (1 - fy) * fx
              + med[np.ix_(y1, x1)] * fy * fx)
        out[:, :, z] = sl / np.maximum(bg, 1e-6)
    return out


def _overlap_boxes_det(
    sd: SpimData, view: ViewId, others: list[ViewId],
    det_dims, ds, expand_px: int = 2, only_tiles: bool = False,
) -> list[Interval]:
    """Overlap regions of ``view`` with each other view, in detection-res
    view-local px (the --overlappingOnly pre-pass,
    SparkInterestPointDetection.java:291-367). ``only_tiles``: compare only
    same-timepoint same-channel views, i.e. overlap across TILES only
    (--onlyCompareOverlapTiles, :263-270)."""
    model = sd.model(view)
    inv = invert_affine(model)
    my_box = transformed_interval(model, Interval.from_shape(sd.view_size(view)))
    my_channel = sd.setups[view.setup].attributes.get("channel", 0)
    out = []
    for o in others:
        if o == view:
            continue
        if only_tiles and (
                o.timepoint != view.timepoint
                or sd.setups[o.setup].attributes.get("channel", 0) != my_channel):
            continue
        ob = transformed_interval(
            sd.model(o), Interval.from_shape(sd.view_size(o)))
        if not my_box.overlaps(ob):
            continue
        world = my_box.intersect(ob)
        local = transformed_interval(inv, world).expand(expand_px)
        det = Interval(
            tuple(int(np.floor(local.min[d] / ds[d])) for d in range(3)),
            tuple(int(np.ceil(local.max[d] / ds[d])) for d in range(3)),
        ).intersect(Interval.from_shape(det_dims))
        if not det.is_empty():
            out.append(det)
    return out


def _estimate_min_max(loader: ViewLoader, view: ViewId) -> tuple[float, float]:
    """Image min/max from the coarsest stored level (the reference scans the
    downsampled image when min/maxIntensity are absent)."""
    with profiling.span("detection.minmax", item=view.setup):
        lvl = loader.num_levels(view.setup) - 1
        img = loader.open(view, lvl).read_full()
        return float(img.min()), float(img.max())


def _make_dog_kernel(n_dev: int, params: DetectionParams,
                     rel: tuple[int, int, int] = (1, 1, 1)):
    """DoG kernel over a batch of blocks (compacted top-K output: candidate
    coords + device-refined subpixel positions, ~KB/block across the host
    link instead of two dense volumes); with ``n_dev > 1`` the batch axis is
    sharded over the device mesh (one/few blocks per device). ``rel``:
    residual downsampling the kernel applies on device (blocks arrive at
    level resolution, native dtype)."""
    desc = None
    if params.extract_descriptors:
        from .. import config

        desc = (int(params.descriptor_neighbors),
                int(params.descriptor_redundancy),
                bool(config.get_bool("BST_FUSED_DETECT")))
    return _make_dog_kernel_cached(
        n_dev, float(params.sigma), bool(params.find_max),
        bool(params.find_min), int(params.max_candidates_per_block),
        dog_halo(params.sigma), tuple(int(r) for r in rel), desc)


@functools.lru_cache(maxsize=32)
def _make_dog_kernel_cached(n_dev, sigma, find_max, find_min, k, halo, rel,
                            desc=None):
    """lru_cache'd so repeated detections in one process (multi-run benches,
    detection+nonrigid pipelines) reuse the sharded jit instead of
    recompiling (same defect class as the nonrigid kernel, fixed r4).

    ``desc``: None for plain detection; (n_neighbors, redundancy, fused)
    for detect+extract. fused=True compiles ONE program per block batch —
    the peaks never leave HBM between top-K and the descriptor frame math,
    and the whole dispatch sits under the "detection.kernel" span. The
    staged fallback (fused=False, BST_FUSED_DETECT=0) runs the identical
    impl functions as two dispatches with a "detection.extract" span on the
    second, so fused-vs-staged outputs are bitwise comparable."""
    from types import SimpleNamespace

    params = SimpleNamespace(sigma=sigma, find_max=find_max,
                             find_min=find_min)
    if desc is not None:
        nn, red, fused = desc
        if fused:
            if n_dev <= 1:
                def kernel(blocks, lo, hi, thr, origins):
                    with profiling.span("detection.kernel"):
                        return dog_detect_extract_batch(
                            blocks, lo, hi, thr, origins, params.sigma,
                            params.find_max, params.find_min, k, halo, rel,
                            nn, red, True)
                return kernel
            mesh = make_mesh(n_dev)
            fn = shard_jit(
                lambda b, l, h, t, o: dog_detect_extract_batch_impl(
                    b, l, h, t, o, params.sigma, params.find_max,
                    params.find_min, k, halo, rel, nn, red, True),
                mesh, n_in=5, n_out=7,
            )

            def kernel(blocks, lo, hi, thr, origins):
                with profiling.span("detection.kernel"):
                    return fn(blocks, lo, hi, thr, origins)
            return kernel
        # staged two-pass: same impls, two compiled dispatches; the
        # (sub, valid) intermediates still stay on device between them
        detect = _make_dog_kernel_cached(n_dev, sigma, find_max, find_min,
                                         k, halo, rel, None)
        if n_dev <= 1:
            def extract(sub, valid):
                with profiling.span("detection.extract"):
                    return block_descriptors_batch(sub, valid, nn, red, True)
        else:
            efn = shard_jit(
                lambda s, v: block_descriptors_batch_impl(s, v, nn, red,
                                                          True),
                make_mesh(n_dev), n_in=2, n_out=2,
            )

            def extract(sub, valid):
                with profiling.span("detection.extract"):
                    return efn(sub, valid)

        def kernel(blocks, lo, hi, thr, origins):
            idx, sub, val, valid, count = detect(blocks, lo, hi, thr,
                                                 origins)
            dsc, dvalid = extract(sub, valid)
            return idx, sub, val, valid, count, dsc, dvalid
        return kernel
    if n_dev <= 1:
        def kernel(blocks, lo, hi, thr, origins):
            with profiling.span("detection.kernel"):
                return dog_block_topk_batch(
                    blocks, lo, hi, thr, origins, params.sigma,
                    params.find_max, params.find_min, k, halo, rel)
        return kernel

    mesh = make_mesh(n_dev)
    fn = shard_jit(
        lambda b, l, h, t, o: dog_block_topk_batch_impl(
            b, l, h, t, o, params.sigma, params.find_max, params.find_min,
            k, halo, rel),
        mesh, n_in=5, n_out=5,
    )

    def kernel(blocks, lo, hi, thr, origins):
        with profiling.span("detection.kernel"):
            return fn(blocks, lo, hi, thr, origins)
    return kernel


def _plan_blocks(sd: SpimData, views: list[ViewId],
                 plans: dict[ViewId, _ViewPlan], params: DetectionParams
                 ) -> tuple[dict[ViewId, list[Interval]], list[_BlockJob]]:
    """The pass's work list: every view's detection-resolution grid of
    ``params.block_size`` cores, with ``--overlappingOnly`` the cores that
    touch an overlap box (and the boxes, for the final crop)."""
    ds = params.downsampling
    bs = tuple(int(b) for b in params.block_size)
    overlap_boxes: dict[ViewId, list[Interval]] = {}
    jobs: list[_BlockJob] = []
    view_list = list(views)
    for vi, v in enumerate(view_list):
        plan = plans[v]
        region = Interval.from_shape(plan.det_dims)
        boxes = None
        if params.overlapping_only:
            boxes = _overlap_boxes_det(
                sd, v, view_list, plan.det_dims, ds,
                only_tiles=params.only_compare_overlap_tiles)
            overlap_boxes[v] = boxes
            if not boxes:
                continue
            region = boxes[0]
            for b in boxes[1:]:
                region = region.union(b)
        for blk in create_grid(region.shape, bs):
            core = Interval.from_shape(blk.size, blk.offset).translate(region.min)
            if boxes is not None and not any(core.overlaps(b) for b in boxes):
                continue
            jobs.append(_BlockJob(vi, core, index=len(jobs)))
    return overlap_boxes, jobs


def detect_interest_points(
    sd: SpimData,
    loader: ViewLoader,
    views: list[ViewId],
    params: DetectionParams | None = None,
    progress: bool = True,
    devices: int | None = None,
) -> list[ViewDetections]:
    """Run DoG detection over all ``views``; returns per-view detections in
    FULL-RES view-local pixel coordinates (correctForDownsampling applied,
    SparkInterestPointDetection.java:611)."""
    params = params or DetectionParams()
    ds = params.downsampling
    halo = dog_halo(params.sigma)
    bs = tuple(int(b) for b in params.block_size)

    with profiling.span("detection.plan"):
        plans = {v: _ViewPlan(loader, v, ds) for v in views}
        overlap_boxes, jobs = _plan_blocks(sd, views, plans, params)
    view_list = list(views)
    observe.log(f"detection: {len(view_list)} views, {len(jobs)} blocks "
                f"(block {bs}, halo {halo}, ds {ds})",
                stage="detection", echo=progress,
                views=len(view_list), blocks=len(jobs))
    minmax = {}
    need = [v for v in views
            if params.min_intensity is None or params.max_intensity is None]
    ests: dict[ViewId, tuple[float, float]] = {}
    if need:  # estimation reads are independent -> overlap them
        with CtxThreadPool(max_workers=min(8, len(need))) as mpool:
            ests = dict(zip(need, mpool.map(
                lambda v: _estimate_min_max(loader, v), need)))
    for v in views:
        lo, hi = ests.get(v, (0.0, 0.0))
        minmax[v] = (
            params.min_intensity if params.min_intensity is not None else lo,
            params.max_intensity if params.max_intensity is not None else hi)

    # bucket by block shape (edge blocks are smaller) -> one compiled kernel
    # per shape bucket; the bucket's block list is batched over the device
    # mesh (the reference's detection Spark map,
    # SparkInterestPointDetection.java:448-660, strategy P3)
    import jax

    n_dev = devices if devices is not None else len(jax.local_devices())
    per_dev = max(1, params.batch_size // max(n_dev, 1))

    def build(job: _BlockJob):
        v = view_list[job.view_idx]
        plan = plans[v]
        off = [m - halo for m in job.core.min]
        shape = [s + 2 * halo for s in job.core.shape]
        with profiling.span("detection.read", item=job.index):
            if params.median_radius > 0:
                raw = plan.read_det_block(loader, off, shape)
                raw = _median_background_divide(raw, params.median_radius,
                                                exact=params.median_exact)
                raw = raw.astype(np.float32)
            else:
                # ship the LEVEL-resolution block in its native dtype; the
                # kernel pools by ``rel`` + normalizes on device (half the
                # wire bytes, no separate downsample dispatch)
                raw = plan.read_raw_block(loader, off, shape)
                if raw.dtype.byteorder == ">":  # JAX rejects big-endian
                    raw = raw.astype(raw.dtype.newbyteorder("="))
        lo, hi = minmax[v]
        return (raw, np.float32(lo), np.float32(hi),
                np.float32(params.threshold),
                np.array([m - halo for m in job.core.min], np.int32))

    def consume(job: _BlockJob, *outs):
        with profiling.span("detection.consume", item=job.index):
            _consume(job, *outs)

    def _consume(job: _BlockJob, idx, sub, vals, valid, count, *extra):
        shape = job.core.shape
        k = len(idx)
        if int(count) > k:
            import warnings

            _BLOCKS_TRUNCATED.inc()
            warnings.warn(
                f"detection block {job.core.min} found {int(count)} extrema, "
                f"keeping the {k} strongest (raise max_candidates_per_block "
                "or lower the threshold noise)", stacklevel=2)
        else:
            _BLOCKS_WHOLE.inc()
        # the kernel pre-masks to the core slab; re-check as a safety net
        # (halo detections belong to the neighboring block)
        keep = valid.astype(bool)
        for d in range(3):
            keep &= (idx[:, d] >= halo) & (idx[:, d] < halo + shape[d])
        if not keep.any():
            return
        # block-local (with halo) -> view detection-res coords; lexsorted by
        # position so output order is deterministic (top-K rank order would
        # reshuffle under f32 accumulation noise between compilations)
        src = (sub if params.localization.upper() == "QUADRATIC"
               else idx)  # --localization NONE keeps integer extrema
        pts = (src[keep].astype(np.float64) - halo
               + np.array(job.core.min, np.float64))
        vv = vals[keep].astype(np.float64)
        order = np.lexsort(pts.T[::-1])
        if extra:  # (desc, dvalid) riders from detect+extract kernels
            dsc, dvalid = extra
            job.result = (pts[order], vv[order], dsc[keep][order],
                          dvalid[keep][order].astype(bool))
        else:
            job.result = (pts[order], vv[order])

    pool = CtxThreadPool(max_workers=8)
    try:
        # bucket by (det-res block shape, residual factors, input dtype):
        # one compiled kernel per bucket (median path pre-pools on host,
        # so its kernel sees rel=(1,1,1) float32 det-res blocks)
        buckets: dict[tuple, list[_BlockJob]] = {}
        for job in jobs:
            plan = plans[view_list[job.view_idx]]
            if params.median_radius > 0:
                rel, dt = (1, 1, 1), "<f4"
            else:
                rel = plan.rel
                dt = np.dtype(loader.open(plan.view, plan.level).dtype
                              ).newbyteorder("=").str
            shp = tuple(s + 2 * halo for s in job.core.shape)
            buckets.setdefault((shp, rel, dt), []).append(job)
        for (shp, rel, dt), bjobs in sorted(buckets.items()):
            kernel_fn = _make_dog_kernel(n_dev, params, rel)
            # level-res inputs are prod(rel) x larger per det-voxel than the
            # pooled float32 blocks batch_size was tuned for — scale the
            # per-device packing down so batch device memory stays bounded
            rel_vol = int(np.prod(rel))
            wmult = 8.0
            if params.extract_descriptors:
                # the (K, K) masked-distance matrix of the extract half
                # dominates its workspace; express it relative to the input
                kk = int(params.max_candidates_per_block) ** 2 * 4
                wmult += kk / max(1, int(np.prod(shp))
                                  * np.dtype(dt).itemsize)
            run_sharded_batches(bjobs, build, kernel_fn, consume, n_dev, pool,
                                label="detection batch",
                                per_dev=max(1, per_dev // rel_vol),
                                # DoG expands the native-dtype input to
                                # several pooled f32 volumes on device
                                workspace_mult=wmult)
    finally:
        pool.shutdown(wait=True)

    per_view: dict[int, list[tuple[np.ndarray, np.ndarray]]] = {
        i: [] for i in range(len(view_list))}
    for job in jobs:  # original job order => deterministic concatenation
        if job.result is not None:
            per_view[job.view_idx].append(job.result)

    want_desc = bool(params.extract_descriptors)
    out = []
    for vi, v in enumerate(view_list):
        plan = plans[v]
        res = per_view[vi]
        if res:
            pts = np.concatenate([r[0] for r in res])
            vals = np.concatenate([r[1] for r in res])
            riders = ((np.concatenate([np.asarray(r[2]) for r in res]),
                       np.concatenate([np.asarray(r[3]) for r in res]))
                      if want_desc else ())
        else:
            pts, vals = np.zeros((0, 3)), np.zeros(0)
            riders = ()
            if want_desc:
                from ..ops.descriptors import subset_combinations

                nn = int(params.descriptor_neighbors)
                s = len(subset_combinations(
                    nn + int(params.descriptor_redundancy), nn))
                riders = (np.zeros((0, s, nn * 3), np.float32),
                          np.zeros(0, bool))
        pts, vals, riders = _filter_spots(pts, vals, overlap_boxes.get(v),
                                          params, riders)
        _POINTS.inc(len(pts))
        # detection-res -> full-res: average downsampling by f maps level
        # voxel p to full-res f*p + (f-1)/2 (DownsampleTools.correctForDownsampling)
        T = mipmap_transform(ds)
        full = apply_affine(T, pts) if len(pts) else pts
        det = ViewDetections(v, full, vals)
        if want_desc:
            det.descriptors, det.descriptor_valid = riders
        if params.store_intensities and len(pts):
            det.intensities = _sample_intensities(loader, plan, pts)
        out.append(det)
        observe.log(f"  {v}: {len(full)} interest points",
                    stage="detection", echo=progress, points=len(full))
    return out


def _filter_spots(pts, vals, boxes, params: DetectionParams, riders=()):
    """overlappingOnly final crop + brightest-N filters
    (filterPoints / maxSpotsPerOverlap, SparkInterestPointDetection.java:745-806,973-995).
    ``riders``: extra per-point arrays (descriptors, validity) that must
    follow every mask/reorder applied to ``pts``/``vals``."""
    riders = tuple(riders)
    if boxes is not None and len(pts):
        keep = np.zeros(len(pts), bool)
        for b in boxes:
            inside = np.all(
                (pts >= np.array(b.min)) & (pts <= np.array(b.max)), axis=1
            )
            keep |= inside
        pts, vals = pts[keep], vals[keep]
        riders = tuple(r[keep] for r in riders)
    if params.max_spots > 0 and len(pts):
        if params.max_spots_per_overlap and boxes:
            total_vol = sum(b.num_elements for b in boxes)
            keep = np.zeros(len(pts), bool)
            assigned = np.zeros(len(pts), bool)
            for b in boxes:
                budget = max(1, int(round(params.max_spots * b.num_elements / total_vol)))
                inside = np.all(
                    (pts >= np.array(b.min)) & (pts <= np.array(b.max)), axis=1
                ) & ~assigned
                idx = np.where(inside)[0]
                assigned[idx] = True
                if len(idx) > budget:
                    order = np.argsort(-np.abs(vals[idx]))[:budget]
                    idx = idx[order]
                keep[idx] = True
            pts, vals = pts[keep], vals[keep]
            riders = tuple(r[keep] for r in riders)
        elif len(pts) > params.max_spots:
            order = np.argsort(-np.abs(vals))[: params.max_spots]
            pts, vals = pts[order], vals[order]
            riders = tuple(r[order] for r in riders)
    return pts, vals, riders


def _sample_intensities(loader, plan: _ViewPlan, det_pts: np.ndarray,
                        cell: int = 64) -> np.ndarray:
    """Sample image intensity at each detection (detection-res coords) via
    trilinear interpolation. Points are binned into ``cell``-sized spatial
    cells and each occupied cell is read once (+1 px margin), so memory is
    bounded by the cell size instead of the detections' bounding box —
    the lazy-per-point analogue of the reference's interpolation sampling
    (SparkInterestPointDetection.java:581-606)."""
    if len(det_pts) == 0:
        return np.zeros(0)
    out = np.zeros(len(det_pts))
    cells = np.floor(det_pts / cell).astype(np.int64)
    order = np.lexsort(cells.T[::-1])
    uniq, starts = np.unique(cells[order], axis=0, return_index=True)
    bounds = np.append(starts, len(order))
    for k, c in enumerate(uniq):
        idx = order[bounds[k]:bounds[k + 1]]
        lo = np.maximum(c * cell - 1, 0)
        hi = np.minimum((c + 1) * cell + 2, np.asarray(plan.det_dims))
        vol = plan.read_det_block(loader, lo, hi - lo)
        out[idx] = sample_trilinear(vol, det_pts[idx] - lo)
    return out


def save_detections(
    sd: SpimData,
    store: InterestPointStore,
    detections: list[ViewDetections],
    params: DetectionParams,
) -> None:
    """Persist to interestpoints.n5 + register in the XML
    (InterestPointTools.addInterestPoints role)."""
    for det in detections:
        grp = store.save_points(
            det.view, params.label, det.points,
            intensities=det.intensities,
        )
        register_points_in_xml(sd, det.view, params.label,
                               params.params_string(), grp)


@profiling.span("detection.stage")
def detect_project(
    xml: str,
    params: DetectionParams | None = None,
    select=None,
    dry_run: bool = False,
    devices: int | None = None,
    progress: bool = True,
    log=lambda message: None,
) -> list[ViewDetections]:
    """What ``bst detect-interestpoints`` does with a project: load it, take
    the views ``select(sd)`` names (every view where ``select`` is None),
    :func:`detect_interest_points` over them, then, unless ``dry_run``,
    store the points in ``interestpoints.n5`` beside the XML and register
    them in the saved XML. The one entry of the stage: the command calls it
    and so does the benchmark's adapter. Returns the detections."""
    params = params or DetectionParams()
    sd = SpimData.load(xml)
    views = select(sd) if select is not None else sd.view_ids()
    detections = detect_interest_points(sd, ViewLoader(sd), views, params,
                                        progress=progress, devices=devices)
    log(f"detected {sum(len(d.points) for d in detections)} interest "
        f"points over {len(detections)} views")
    if dry_run:
        log("dryRun: not saving")
        return detections
    with profiling.span("detection.save"):
        save_detections(sd, InterestPointStore.for_project(sd), detections,
                        params)
        sd.save(xml)
    log(f"saved interest points '{params.label}' + XML")
    return detections
