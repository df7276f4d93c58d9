"""Pairwise stitching driver: plan overlap pairs, extract + aggregate crops,
run the batched phase-correlation kernel, filter, store results.

TPU redesign of SparkPairwiseStitching (reference call stack SURVEY.md §3.2):
the work list is the set of overlapping grouped-view pairs (strategy P2);
pairs are bucketed by padded crop shape so one compiled kernel serves every
pair in a bucket, then results are filtered (minR/maxShift) and written into
the XML with a registration hash for solver staleness checks
(SparkPairwiseStitching.java:287-299,347-382).

Shift semantics (used by the solver): a stored result with shift S means the
per-view correction translations must satisfy ``c_A - c_B = S`` — S is the
world-space displacement by which group B's current render is offset against
group A's (derivation in ``_refine_bucket``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import jax
import numpy as np

from ..io.dataset_io import ViewLoader, best_mipmap_level
from ..io.spimdata import (
    PairwiseStitchingResult,
    SpimData,
    ViewId,
    registration_hash,
)
from ..ops.downsample import downsample_block
from ..ops.phasecorr import (
    as_uint16_lossless,
    device_sums,
    pad_to,
    pcm_peaks_batch,
    refine_peaks,
)
from ..utils.geometry import (
    Interval,
    concatenate,
    invert_affine,
    transformed_interval,
    translation_affine,
)
from .. import observe, profiling
from ..observe import metrics as _metrics

_H2D_BYTES = _metrics.counter("bst_xfer_h2d_bytes_total")
_H2D_SAVED = _metrics.counter("bst_xfer_h2d_bytes_saved_total")
_PAIRS_DONE = _metrics.counter("bst_stitching_pairs_total")
_REFINE_PAIRS = {
    scorer: _metrics.counter("bst_stitching_refine_pairs_total",
                             scorer=scorer)
    for scorer in ("device", "host")}
_REFINE_CANDIDATES = _metrics.counter("bst_stitching_refine_candidates_total")
_PACK_BUCKETS = {
    path: _metrics.counter("bst_stitching_pack_buckets_total", path=path)
    for path in ("stored", "cast", "float")}
_GROUPS = {
    combine: _metrics.counter("bst_stitching_groups_total", combine=combine)
    for combine in ("single", "average", "brightest")}


@dataclass
class StitchingParams:
    """Defaults match the reference CLI (SparkPairwiseStitching.java:76-106)."""

    downsampling: tuple[int, int, int] = (2, 2, 1)
    peaks_to_check: int = 5
    subpixel: bool = True
    min_r: float = 0.3
    max_r: float = 1.0
    max_shift: tuple[float, float, float] = (np.inf, np.inf, np.inf)
    max_shift_total: float = np.inf
    channel_combine: str = "AVERAGE"        # AVERAGE | PICK_BRIGHTEST
    illum_combine: str = "PICK_BRIGHTEST"   # AVERAGE | PICK_BRIGHTEST
    min_overlap_px: int = 32
    # candidate shifts must keep at least this fraction of the overlap crop
    # in play: a near-total shift can score a HIGHER Pearson r than the true
    # one by chance over a few thousand background voxels (observed on the
    # 2x2 fixture's corner pairs at full resolution)
    min_overlap_frac: float = 0.25
    batch_size: int = 16
    # PER-DEVICE ceiling on dispatched-but-undrained PCM bytes (padded f32
    # crop stacks x the FFT workspace multiplier below). None derives the
    # budget from the backend's memory_stats (utils.devicemem;
    # BST_PAIR_INFLIGHT_BYTES overrides per device) instead of a flat
    # constant that either starves big HBMs or overcommits small ones.
    inflight_bytes: int | None = None


@dataclass
class ViewGroup:
    """Views of one tile grouped over channel+illumination
    (reference grouping: group {Channel, Illumination}, compare {Tile},
    SparkPairwiseStitching.java:146-160)."""

    timepoint: int
    angle: int
    tile: int
    views: tuple[ViewId, ...]

    @property
    def key(self):
        return (self.timepoint, self.angle, self.tile)


def build_groups(sd: SpimData, views: list[ViewId]) -> list[ViewGroup]:
    by_key: dict[tuple, list[ViewId]] = {}
    for v in views:
        s = sd.setups[v.setup]
        key = (v.timepoint, s.attributes.get("angle", 0), s.attributes.get("tile", 0))
        by_key.setdefault(key, []).append(v)
    return [
        ViewGroup(k[0], k[1], k[2], tuple(sorted(vs)))
        for k, vs in sorted(by_key.items())
    ]


def group_bbox(sd: SpimData, g: ViewGroup) -> Interval:
    """World-space bbox of a group (union over member views)."""
    box = None
    for v in g.views:
        iv = transformed_interval(sd.model(v), Interval.from_shape(sd.view_size(v)))
        box = iv if box is None else box.union(iv)
    return box


def plan_pairs(sd: SpimData, groups: list[ViewGroup]) -> list[tuple[ViewGroup, ViewGroup, Interval]]:
    """All overlapping group pairs within one (timepoint, angle) slice
    (compare {Tile}, apply over {TimePoint, Angle};
    TransformationTools.filterNonOverlappingPairs role)."""
    out = []
    boxes = {g.key: group_bbox(sd, g) for g in groups}
    for i in range(len(groups)):
        for j in range(i + 1, len(groups)):
            a, b = groups[i], groups[j]
            if (a.timepoint, a.angle) != (b.timepoint, b.angle):
                continue
            if not boxes[a.key].overlaps(boxes[b.key]):
                continue
            ov = boxes[a.key].intersect(boxes[b.key])
            if ov.is_empty():
                continue
            out.append((a, b, ov))
    return out


@profiling.span("stitching.aggregate")
def _aggregate(sd: SpimData, crops: dict[ViewId, np.ndarray], group: ViewGroup,
               params: StitchingParams) -> np.ndarray:
    """GroupedViewAggregator: combine channels (AVERAGE default) then
    illuminations (PICK_BRIGHTEST default) of one tile
    (SparkPairwiseStitching.java:204-208).
    ``bst_stitching_groups_total{combine}`` counts the group by what came
    of it: ``single`` (one image, handed on), ``average`` (a mean was
    computed on the way: float32), ``brightest`` (one of several stored
    images picked)."""
    combined = set()

    def combine(imgs: list[np.ndarray], how: str) -> np.ndarray:
        if len(imgs) == 1:
            return imgs[0]
        combined.add(how)
        if how == "AVERAGE":
            # stored crops arrive in the container's dtype: float32 from
            # here (np.mean of uint16 would accumulate in float64)
            return np.mean(np.asarray(imgs, np.float32), axis=0)
        if how == "PICK_BRIGHTEST":
            return imgs[int(np.argmax([np.sum(i, dtype=np.float64) for i in imgs]))]
        raise ValueError(f"unknown aggregation {how}")

    by_illum: dict[int, list[np.ndarray]] = {}
    for v in group.views:
        illum = sd.setups[v.setup].attributes.get("illumination", 0)
        by_illum.setdefault(illum, []).append(crops[v])
    per_illum = [combine(imgs, params.channel_combine)
                 for _, imgs in sorted(by_illum.items())]
    out = combine(per_illum, params.illum_combine)
    _GROUPS["average" if "AVERAGE" in combined
            else "brightest" if combined else "single"].inc()
    return out


def _downsample_crop(crop: np.ndarray, ds: Sequence[int]) -> np.ndarray:
    """Average the residual factors away, in float32; a crop with none left
    is handed on as it is, in the dtype it was read in."""
    if all(int(f) == 1 for f in ds):
        return crop
    crop = np.asarray(crop, np.float32)
    pad = [(0, (-crop.shape[d]) % int(ds[d])) for d in range(3)]
    if any(p[1] for p in pad):
        crop = np.pad(crop, pad, mode="edge")
    return jax.device_get(downsample_block(crop, tuple(int(f) for f in ds)))


@dataclass
class _PairJob:
    group_a: ViewGroup
    group_b: ViewGroup
    overlap: Interval
    # downsampled; in the stored dtype (uint16 as a rule) where nothing was
    # computed on the way (one image a group or PICK_BRIGHTEST, a stored
    # level), float32 where something was (AVERAGE, a residual
    # downsample, the rendered path)
    crop_a: np.ndarray
    crop_b: np.ndarray
    # shift post-processing: S = linear @ (p0b - p0a + residual_ds*s)
    # - (t_a - t_b) with linear/t from the LEVEL model (model o mipmap), or
    # S = ds*s for the rendered (non-equal-transform) path
    linear: np.ndarray | None
    p0_delta: np.ndarray | None
    t_delta: np.ndarray | None
    models_a: list[np.ndarray] = field(default_factory=list)
    models_b: list[np.ndarray] = field(default_factory=list)
    residual_ds: tuple[int, int, int] = (1, 1, 1)


def _equal_linear(models: list[np.ndarray]) -> bool:
    return all(np.allclose(m[:, :3], models[0][:, :3], atol=1e-9) for m in models)


def _pick_common_level(loader, views, ds) -> tuple[dict, tuple[int, int, int]] | None:
    """Coarsest stored mipmap level usable by every view of the pair whose
    factors exactly divide the requested downsampling (reference
    openAndDownsample picks stored levels before computing the rest,
    SparkInterestPointDetection.java:998-1118). Views may store the same
    factors at different level indexes, so the per-view LEVEL is returned
    alongside the common factors. None -> read s0."""
    levels: dict = {}
    common_f = None
    for v in views:
        factors = loader.downsampling_factors(v.setup)
        lvl = best_mipmap_level(factors, ds)
        f = tuple(int(x) for x in factors[lvl])
        if any(int(ds[d]) % f[d] != 0 for d in range(3)):
            return None
        if common_f is None:
            common_f = f
        elif f != common_f:
            return None
        levels[v] = lvl
    return levels, common_f


def _extract_pair_job(sd, loader, ga, gb, overlap, params) -> _PairJob | None:
    models_a = [sd.model(v) for v in ga.views]
    models_b = [sd.model(v) for v in gb.views]
    ds = params.downsampling

    if _equal_linear(models_a + models_b):
        # read at the coarsest stored level that divides the requested
        # downsampling; the rest is averaged in memory
        all_views = list(ga.views) + list(gb.views)
        common = _pick_common_level(loader, all_views, ds)
        if common is None:
            levels, f = {v: 0 for v in all_views}, (1, 1, 1)
        else:
            levels, f = common
        rel = tuple(int(ds[d]) // f[d] for d in range(3))
        mip = loader.mipmap_transform(ga.views[0].setup, levels[ga.views[0]])

        # raster the overlap into each view's LEVEL pixel space; exact
        # integer offsets enter the shift formula so rounding costs no
        # accuracy (model' = model o mipmap: level px -> world)
        lvl_shape = tuple(
            int(np.ceil(overlap.shape[d] / f[d])) for d in range(3)
        )

        def crops_for(group, models):
            crops = {}
            p0 = None
            for v, m in zip(group.views, models):
                inv = invert_affine(concatenate(m, mip))
                p0v = np.round(inv[:, :3] @ np.array(overlap.min, np.float64)
                               + inv[:, 3]).astype(np.int64)
                if p0 is None:
                    p0 = p0v
                block = loader.read_block(v, levels[v], tuple(p0v), lvl_shape)
                if not np.can_cast(block.dtype, np.float32):
                    # a stored type float32 cannot hold (int32, float64)
                    # is rounded once, here, to what the PCM will see
                    block = block.astype(np.float32)
                crops[v] = block
            return crops, p0

        crops_a, p0a = crops_for(ga, models_a)
        crops_b, p0b = crops_for(gb, models_b)
        agg_a = _aggregate(sd, crops_a, ga, params)
        agg_b = _aggregate(sd, crops_b, gb, params)
        total_a = concatenate(models_a[0], mip)
        total_b = concatenate(models_b[0], mip)
        return _PairJob(
            ga, gb, overlap,
            _downsample_crop(agg_a, rel), _downsample_crop(agg_b, rel),
            linear=total_a[:, :3].copy(),
            p0_delta=(p0b - p0a).astype(np.float64),
            t_delta=(total_a[:, 3] - total_b[:, 3]).copy(),
            models_a=models_a, models_b=models_b,
            residual_ds=rel,
        )

    # non-equal transforms: render each group virtually over the overlap
    # (computeStitchingNonEqualTransformations, SparkPairwiseStitching.java:259-267)
    from .affine_fusion import fuse_grid_block
    from ..utils.grid import GridBlock

    o_ds = Interval(
        tuple(int(np.floor(overlap.min[d] / ds[d])) for d in range(3)),
        tuple(int(np.ceil((overlap.max[d] + 1) / ds[d])) - 1 for d in range(3)),
    )
    scale = np.diag([1.0 / f for f in ds])
    pre = np.hstack([scale, np.zeros((3, 1))])

    def render(group):
        block = GridBlock((0, 0, 0), o_ds.shape, (0, 0, 0))
        res = fuse_grid_block(
            sd, loader, list(group.views), block, o_ds,
            fusion_type="AVG", anisotropy=pre,
        )
        if res is None:
            return None
        return res[0]

    ra, rb = render(ga), render(gb)
    if ra is None or rb is None:
        return None
    return _PairJob(ga, gb, overlap, ra, rb,
                    linear=None, p0_delta=None, t_delta=None,
                    models_a=models_a, models_b=models_b)


def _pair_crop_boxes(sd, loader, ga, gb, overlap, params):
    """``(ds, offset, shape)`` source boxes of the equal-linear crop reads in
    ``_extract_pair_job`` — the async prefetcher feed (io/prefetch.py).
    Mirrors the level/mipmap/p0 arithmetic exactly so the prefetched chunks
    are the ones the extract loop decodes; empty for the non-equal
    (virtually rendered) path."""
    models_a = [sd.model(v) for v in ga.views]
    models_b = [sd.model(v) for v in gb.views]
    if not _equal_linear(models_a + models_b):
        return []
    all_views = list(ga.views) + list(gb.views)
    common = _pick_common_level(loader, all_views, params.downsampling)
    if common is None:
        levels, f = {v: 0 for v in all_views}, (1, 1, 1)
    else:
        levels, f = common
    mip = loader.mipmap_transform(ga.views[0].setup, levels[ga.views[0]])
    lvl_shape = tuple(
        int(np.ceil(overlap.shape[d] / f[d])) for d in range(3)
    )
    boxes = []
    for group, models in ((ga, models_a), (gb, models_b)):
        for v, m in zip(group.views, models):
            inv = invert_affine(concatenate(m, mip))
            p0v = np.round(inv[:, :3] @ np.array(overlap.min, np.float64)
                           + inv[:, 3]).astype(np.int64)
            b = loader.prefetch_box(v, levels[v],
                                    tuple(int(o) for o in p0v), lvl_shape)
            if b is not None:
                boxes.append(b)
    return boxes


def _fft_shape(shape: Sequence[int]) -> tuple[int, ...]:
    """Next power of two per axis (TPU FFTs are fastest/most accurate at
    powers of two; wrap ambiguity is resolved by the host correlation
    check, ops/phasecorr.refine_peaks)."""
    return tuple(1 << max(0, int(np.ceil(np.log2(max(int(s), 1))))) for s in shape)


@profiling.span("stitching.stage")
def stitch_all_pairs(
    sd: SpimData,
    loader: ViewLoader,
    views: list[ViewId],
    params: StitchingParams | None = None,
    progress: bool = True,
    devices: int | None = None,
) -> list[PairwiseStitchingResult]:
    """Compute pairwise shifts for every overlapping tile pair.

    Returns unfiltered results; apply ``filter_results`` + store into
    ``sd.stitching_results`` (the driver-side collect of the reference)."""
    params = params or StitchingParams()
    with profiling.span("stitching.plan"):
        groups = build_groups(sd, views)
        pairs = plan_pairs(sd, groups)
    observe.log(f"stitching: {len(groups)} groups, {len(pairs)} overlapping "
                "pairs", stage="stitching", echo=progress,
                groups=len(groups), pairs=len(pairs))

    from ..io import prefetch as _prefetch

    if _prefetch.enabled():
        # warm the chunk LRU ahead of the serial extract loop below: each
        # pair's crop reads are known now, so the read-ahead pool overlaps
        # remote fetches with the per-pair decode + aggregate work
        for ga, gb, ov in pairs:
            _prefetch.submit(
                lambda a=ga, b=gb, o=ov:
                _pair_crop_boxes(sd, loader, a, b, o, params))

    jobs: list[_PairJob] = []
    for ga, gb, ov in pairs:
        with profiling.span("stitching.extract"):
            job = _extract_pair_job(sd, loader, ga, gb, ov, params)
        if job is not None:
            jobs.append(job)

    return stitch_jobs(sd, jobs, params, devices=devices)


# resident bytes one PCM dispatch pins beyond its a+b f32 input stacks:
# windowed copies, two rfftn complex spectra, the normalized cross-power
# and the irfftn PCM — ~4x the input stacks in practice (ADVICE r5: the
# old ledger charged only the inputs and undercounted the FFT workspace)
_FFT_WORKSPACE_MULT = 4.0


def stitch_jobs(sd, jobs: list[_PairJob], params: StitchingParams,
                devices: int | None = None, multihost: bool | None = None
                ) -> list[PairwiseStitchingResult]:
    """Run the device PCM + refinement pipeline over prepared jobs.

    Chunks (shape-bucketed pair batches) become pair-scheduler tasks spread
    over every local device (parallel.pairsched): placement is weighted by
    FFT volume, each device bounds its dispatched-but-undrained bytes with
    its own window (inputs x FFT workspace multiplier against the
    device-derived budget — ``params.inflight_bytes`` overrides), and each
    device's drain is pipelined so the refinement of one bucket overlaps
    the device FFTs of the next. One local device degrades to exactly that
    pipelined loop on the caller's thread (the pre-sharding path).

    A bucket's two stacks are written once and uploaded once
    (``_pack_stacks``): crops that arrive as stored uint16 voxels are
    copied straight into a zeroed uint16 stack; crops something was
    computed on (averaged, residually downsampled, rendered) arrive
    float32, are padded and stacked as such and cross as uint16 only where
    a check finds every value whole. The data picks;
    ``bst_stitching_pack_buckets_total{path}`` counts. The upload serves
    both halves: the PCM, and — where the stacks are whole uint16 numbers,
    so that every Pearson sum is an integer — the refinement's candidate
    scorer, which runs on the device that holds them
    (ops/phasecorr.pearson_sums; the search and r stay on the host, in
    float64). A float32 bucket is scored on the host from float64
    summed-area tables; ``bst_stitching_refine_pairs_total{scorer}``
    counts.

    In a multi-process world chunks split across processes FIRST
    (cost-aware LPT over FFT volume), each process's slice over its
    local devices second, and the per-process results allgather back so
    every rank returns the full pair list — on by default when
    ``jax.process_count() > 1`` (``BST_PAIR_MULTIHOST``); pass
    ``multihost=False``/``True`` to pin it."""
    from ..parallel.pairsched import PairTask, run_pair_tasks

    buckets: dict[tuple, list[_PairJob]] = {}
    for j in jobs:
        shp = _fft_shape(np.maximum(j.crop_a.shape, j.crop_b.shape))
        buckets.setdefault(shp, []).append(j)

    # a chunk is the scheduler's unit of work. Buckets are NOT cut finer to
    # reach idle devices: every (shape, batch length, device) is a program
    # to compile, and on a 2x2 grid (three buckets, four chips) the finer
    # cut measured +20 s cold for six pairs of millisecond device work
    # (PERF.md section 6, PR 21)
    chunks = []
    for shp, bjobs in sorted(buckets.items()):
        for i in range(0, len(bjobs), params.batch_size):
            chunks.append((shp, bjobs[i:i + params.batch_size]))

    tasks = []
    for i, (shp, chunk) in enumerate(chunks):
        vol = int(np.prod(shp))
        stack_bytes = 2 * len(chunk) * vol * 4  # a+b stacks, f32 on device
        tasks.append(PairTask(
            index=i,
            cost=float(len(chunk) * vol),       # placement ∝ FFT volume
            nbytes=int(stack_bytes * _FFT_WORKSPACE_MULT),
            tag=(shp, chunk),
        ))

    def dispatch(task):
        shp, chunk = task.tag
        with profiling.span("stitching.kernel"):
            return _dispatch_bucket(chunk, shp, params)

    def drain(seg_tasks, handles):
        # one pipelined fetch for the whole segment: the round-trip
        # latency is paid per memory-bounded segment, not per shape bucket
        with profiling.span("stitching.kernel_sync"):
            peaks_list = jax.device_get([p for p, _ in handles])
        out = []
        for task, peaks, (_, stacks) in zip(seg_tasks, peaks_list, handles):
            shp, chunk = task.tag
            out.append(_refine_bucket(sd, chunk, shp, peaks, stacks, params))
        return out

    per_chunk = run_pair_tasks(tasks, dispatch, drain, n_devices=devices,
                               stage="stitching",
                               budget_bytes=params.inflight_bytes,
                               multihost=multihost)
    return [r for chunk_results in per_chunk
            if chunk_results is not None for r in chunk_results]


def _pack_stacks(jobs: list[_PairJob], shp
                 ) -> tuple[np.ndarray, np.ndarray, str]:
    """One bucket's two zero-padded stacks as they cross to the device, and
    the path that made them (``bst_stitching_pack_buckets_total{path}``).

    ``stored``: every crop arrived uint16 (stored-level voxels nothing was
    computed on), so each is copied once into the corner of its row of a
    zeroed uint16 stack: whole uint16 numbers by their type, nothing to
    check. Any other bucket is padded and stacked in float32 and asked
    whether it survives the cast (ops/phasecorr.as_uint16_lossless), decided
    once for both stacks so that the jitted kernels see only two dtype
    signatures a shape bucket (u16/u16 or f32/f32): ``cast`` where it does
    (half the bytes on the link, the device's cast back is bit-identical),
    ``float`` where it does not. ``stored`` and ``cast`` give the same
    bytes for the same values."""
    if all(c.dtype == np.uint16 for j in jobs for c in (j.crop_a, j.crop_b)):
        # a new buffer a bucket: the upload may alias it (may_alias=True),
        # so one reused across passes could still be read by a transfer
        a = np.zeros((len(jobs),) + tuple(shp), np.uint16)
        b = np.zeros_like(a)
        for k, j in enumerate(jobs):
            for stack, crop in ((a, j.crop_a), (b, j.crop_b)):
                stack[(k,) + tuple(slice(0, n) for n in crop.shape)] = crop
        return a, b, "stored"
    a = np.stack([pad_to(j.crop_a, shp) for j in jobs])
    b = np.stack([pad_to(j.crop_b, shp) for j in jobs])
    ua = as_uint16_lossless(a)
    ub = as_uint16_lossless(b) if ua is not None else None
    if ub is None:
        return a, b, "float"
    return ua, ub, "cast"


def _dispatch_bucket(jobs: list[_PairJob], shp, params):
    """Pack and upload one bucket's two stacks (``_pack_stacks``: stored
    uint16 crops are copied straight, any others padded in float32 and
    checked), start its PCM, and hand back ``(peaks, stacks)`` still on the
    device: ``stacks`` is the resident ``(a, b, ext_a, ext_b)`` the
    refinement scores on where the crops are whole uint16 numbers, None
    where they are not."""
    with profiling.span("stitching.pack"):
        a, b, path = _pack_stacks(jobs, shp)
        _PACK_BUCKETS[path].inc()
        exact = path != "float"
        if exact:
            _H2D_SAVED.inc(a.size * 4 - a.nbytes + b.size * 4 - b.nbytes)
        ext_a = np.stack([np.array(j.crop_a.shape, np.int32) for j in jobs])
        ext_b = np.stack([np.array(j.crop_b.shape, np.int32) for j in jobs])
    _H2D_BYTES.inc(a.nbytes + b.nbytes + ext_a.nbytes + ext_b.nbytes)
    # one upload serves the PCM and, for whole uint16 numbers, the scorer:
    # the stacks stay with the bucket until its pairs are refined (the
    # task's nbytes already counts them). Committed to the device they
    # landed on, so the scorer runs there from whatever thread calls it
    stacks = jax.device_put((a, b, ext_a, ext_b), may_alias=True)
    dev, = stacks[0].devices()
    stacks = jax.device_put(stacks, dev)
    peaks = pcm_peaks_batch(*stacks, params.peaks_to_check, 0.25)
    return peaks, stacks if exact else None


def _refine_bucket(sd, jobs: list[_PairJob], shp, peaks, stacks,
                   params) -> list[PairwiseStitchingResult]:
    # per-peak true-correlation scoring + subpixel on the overlap boxes
    # (ops/phasecorr.refine_peaks): the search on the host, the sums it
    # asks for on the bucket's device where the stacks are whole uint16
    # numbers (``stacks``), else in float64 on the host
    shifts = np.zeros((len(jobs), 3))
    rs = np.zeros(len(jobs))
    refined = _REFINE_PAIRS["host" if stacks is None else "device"]

    def _refine(k):
        j = jobs[k]
        min_ov = max(
            params.min_overlap_px,
            params.min_overlap_frac
            * min(int(np.prod(j.crop_a.shape)),
                  int(np.prod(j.crop_b.shape))))
        sums = None
        if stacks is not None:
            score = device_sums(*stacks, k)

            def sums(cands):
                with profiling.span("stitching.refine.score"):
                    _REFINE_CANDIDATES.inc(len(cands))
                    return score(cands)

        with profiling.span("stitching.refine.pair",
                            item=(j.group_a.views[0].setup,
                                  j.group_b.views[0].setup)):
            shifts[k], rs[k] = refine_peaks(
                j.crop_a, j.crop_b, peaks[k], shp,
                min_overlap=min_ov, subpixel=params.subpixel, sums=sums)
        refined.inc()
        _PAIRS_DONE.inc()

    with profiling.span("stitching.refine"):
        # pairs refine side by side: a device scorer's pair waits on round
        # trips, and numpy's reductions release the GIL. The host scorer's
        # pool is bounded by its footprint besides: each refine builds 4
        # float64 summed-area tables (~32 B/crop voxel), so an unbounded
        # 8-thread pool over huge crops would hold gigabytes of transient
        # tables at once. The 2e9 host budget is shared across the drains
        # actually refining concurrently (the pair scheduler's active
        # workers; 1 on the inline single-device path)
        workers = min(8, len(jobs))
        if stacks is None:
            from ..parallel.pairsched import concurrent_pair_workers

            sat_bytes = 32 * max(int(np.prod(j.crop_a.shape))
                                 + int(np.prod(j.crop_b.shape))
                                 for j in jobs)
            workers = min(workers, max(1, int(
                2e9 // max(concurrent_pair_workers(), 1)
                // max(sat_bytes, 1))))
        if workers > 1:
            from ..utils.threads import CtxThreadPool

            with CtxThreadPool(max_workers=workers) as pool:
                list(pool.map(_refine, range(len(jobs))))
        else:
            for k in range(len(jobs)):
                _refine(k)

    ds = np.array(params.downsampling, np.float64)
    out = []
    for j, s, r in zip(jobs, shifts, rs):
        if j.linear is not None:
            # S = L (p0b - p0a + rel*s) - (t_a - t_b): c_A - c_B = S
            rel = np.array(j.residual_ds, np.float64)
            S = j.linear @ (j.p0_delta + rel * s.astype(np.float64)) - j.t_delta
        else:
            S = ds * s.astype(np.float64)
        out.append(PairwiseStitchingResult(
            views_a=j.group_a.views,
            views_b=j.group_b.views,
            transform=translation_affine(S),
            correlation=float(r),
            hash=registration_hash(j.models_a, j.models_b),
            bbox=j.overlap,
        ))
    return out


def filter_results(
    results: list[PairwiseStitchingResult], params: StitchingParams,
    verbose: bool = True,
) -> list[PairwiseStitchingResult]:
    """Link filters (FilteredStitchingResults: Correlation, AbsoluteShift,
    ShiftMagnitude — SparkPairwiseStitching.java:347-382)."""
    out = []
    for res in results:
        shift = res.transform[:, 3]
        ok = (params.min_r <= res.correlation <= params.max_r
              and all(abs(shift[d]) <= params.max_shift[d] for d in range(3))
              and float(np.linalg.norm(shift)) <= params.max_shift_total)
        if ok:
            out.append(res)
        else:
            observe.log(f"  dropped pair {res.views_a[0]}<->{res.views_b[0]}: "
                        f"r={res.correlation:.3f} shift={np.round(shift, 2)}",
                        stage="stitching", echo=verbose,
                        correlation=round(float(res.correlation), 4))
    return out


@profiling.span("stitching.store")
def store_results(
    sd: SpimData,
    results: list[PairwiseStitchingResult],
    computed: list[PairwiseStitchingResult] | None = None,
) -> None:
    """Store kept results; entries for every RECOMPUTED pair (``computed``,
    default = ``results``) are cleared first so links the user just filtered
    out don't survive from a previous run."""
    for res in computed if computed is not None else results:
        sd.stitching_results.pop(res.pair_key, None)
    for res in results:
        sd.stitching_results[res.pair_key] = res
