#!/usr/bin/env python
"""Benchmark: affine-fusion voxels/sec (the BASELINE.md north-star metric),
plus pairwise phase-correlation pairs/sec and DoG detection voxels/sec.

Primary metric: fuses a 2x2-tile synthetic light-sheet project (256x256x128
per tile, uint16, AVG_BLEND) into an OME-ZARR container on the available
accelerator and reports fused output voxels per second for the steady-state
(warm compile-cache) run — best of BST_BENCH_RUNS. The span breakdown (h2d /
kernel / d2h / write) for the reported run is emitted alongside, and a
kernel-only steady-state number (tiles resident in HBM, output left on
device) separates the framework's compute from host IO.

vs_baseline: measured against REAL measurements of reference-equivalent CPU
implementations on this same host/fixture (numpy+scipy fusion; numpy FFT
phase correlation with 5-peak wrap disambiguation; scipy DoG + local maxima),
RE-MEASURED in the same run as the candidate (the shared host drifts 20-30%
day to day, so cross-day cached baselines distort the ratio); the cache in
BASELINE_MEASURED.json records provenance + the previous measurement. The
XLA output is validated against the baseline implementation before timing.

One process measures once and stamps ``platform``/``device_kind`` on the
result. The run exits non-zero when any measure raised, or when the
platform is not TPU — unless ``JAX_PLATFORMS=cpu`` was given explicitly, in
which case the numbers are a correctness and byte-count run on XLA:CPU, not
device rates.
"""

import json
import os
import shutil
import subprocess
import sys
import time

from bigstitcher_spark_tpu import config as _cfg

REPO = os.path.dirname(os.path.abspath(__file__))

FIXTURE = _cfg.get_str("BST_BENCH_DIR")
BASELINE_FILE = os.path.join(REPO, "BASELINE_MEASURED.json")
FIXTURE_SPEC = {
    "n_tiles": (2, 2, 1), "tile_size": (256, 256, 128), "overlap": 32,
    "jitter": 0.0, "seed": 11, "block_size": (128, 128, 64),
    "n_beads_per_tile": 120,
}
# optional fixture scaling for throughput-vs-volume experiments (PERF.md):
# BST_BENCH_TILE=384 runs the primary config with (384,384,192) tiles;
# the baseline cache keys on the full spec, so scales never cross-pollute
_t = _cfg.get_int("BST_BENCH_TILE")
if _t:
    FIXTURE_SPEC["tile_size"] = (_t, _t, max(64, _t // 2))
# same-process baseline memo (one measurement per bench run)
_RUN_BASELINES: dict = {}
# best-of-N: wall-clock noise on a shared host swings single runs ~30%;
# five runs stabilize the headline artifact
FUSION_RUNS = _cfg.get_int("BST_BENCH_RUNS")


def build_fixture():
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    marker = os.path.join(FIXTURE, "proj", "dataset.xml")
    if os.path.exists(marker):
        return marker
    shutil.rmtree(FIXTURE, ignore_errors=True)
    make_synthetic_project(os.path.join(FIXTURE, "proj"), **FIXTURE_SPEC)
    return marker


def run_fusion(xml_path, out_path, block_scale=(2, 2, 1)):
    from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat
    from bigstitcher_spark_tpu.io.container import create_fusion_container
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.affine_fusion import fuse_volume
    from bigstitcher_spark_tpu.utils.viewselect import maximal_bounding_box

    sd = SpimData.load(xml_path)
    loader = ViewLoader(sd)
    views = sd.view_ids()
    bbox = maximal_bounding_box(sd, views)
    shutil.rmtree(out_path, ignore_errors=True)
    create_fusion_container(
        out_path, StorageFormat.ZARR, xml_path, 1, 1, bbox,
        data_type="uint16", block_size=(128, 128, 64),
        min_intensity=0.0, max_intensity=65535.0,
    )
    store = ChunkStore.open(out_path)
    ds = store.open_dataset("0")
    stats = fuse_volume(
        sd, loader, views, ds, bbox, block_size=(128, 128, 64),
        block_scale=block_scale, fusion_type="AVG_BLEND",
        out_dtype="uint16", min_intensity=0.0, max_intensity=65535.0,
        zarr_ct=(0, 0),
    )
    return stats, ds, bbox


# ---------------------------------------------------------------------------
# Reference-equivalent CPU baselines (numpy + scipy), measured + cached
# ---------------------------------------------------------------------------


def _baseline_cache_load():
    try:
        with open(BASELINE_FILE) as f:
            return json.load(f)
    except (OSError, ValueError):
        # absent or truncated cache: every baseline is re-measured
        return {}


# Baselines are RE-MEASURED inside every bench run (BST_BENCH_FRESH_BASELINE
# defaults on): the shared host's throughput drifts 20-30% day to day, so a
# cached baseline from another day distorts vs_baseline (r4 verdict weak #7).
# The cache still records provenance + the previous measurement for
# comparison; vs_baseline always uses the same-run number.
def _fresh_baselines() -> bool:
    return _cfg.get_bool("BST_BENCH_FRESH_BASELINE")


def _baseline_cache_store(cache):
    tmp = BASELINE_FILE + ".tmp"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=1)
    os.replace(tmp, BASELINE_FILE)  # atomic: a mid-write kill can't truncate


def _fixture_key(extra=""):
    import hashlib

    return hashlib.sha256(
        json.dumps({"spec": FIXTURE_SPEC, "extra": extra}, sort_keys=True,
                   default=str).encode()).hexdigest()[:16]


_SYNC_METHODOLOGY = ("chained dispatches ended by a one-element data fetch "
                     "(_kernel_rate)")


def _tiny_fetch(out):
    """Fetch ONE element of (the first array leaf of) `out` to the host:
    a 4-byte data read cannot resolve before the producing program ran, on
    any backend. One fetch of one leaf keeps the constant identical between
    the k=1 and k=reps runs of `_kernel_rate` (profiling.device_sync syncs
    every leaf; here the stream order makes the first leaf sufficient)."""
    import jax

    from bigstitcher_spark_tpu import profiling

    leaves = [x for x in jax.tree_util.tree_leaves(out)
              if hasattr(x, "dtype") and getattr(x, "size", 0)]
    if not leaves:  # a no-op sync would silently re-open the timing bug
        raise ValueError("_tiny_fetch: no non-empty array leaf to sync on")
    return profiling.device_sync(leaves[0])


def _kernel_rate(dispatch_fn, reps=10, tries=3):
    """True steady-state seconds per execution of an async device program.

    Times `k` back-to-back dispatches (the single PJRT stream executes
    them in order) ended by one `_tiny_fetch`; the k=1 run cancels the
    round-trip + fetch constant:

        per_exec = (T(k=reps) - T(k=1)) / (reps - 1)

    `dispatch_fn()` must dispatch exactly one execution of the program
    under test and return its output. Identical on CPU/TPU backends.

    Syncing only the LAST dispatch relies on the single PJRT stream
    executing the k dispatches in order — valid on one device only. With
    multiple visible devices (multi-chip hosts) one element of EVERY
    addressable shard of EVERY rep's first leaf is fetched in one
    pipelined device_get after the dispatch loop, so reps that landed on
    other streams/devices — including sharded outputs — cannot still be
    in flight when the clock stops (ADVICE r5). Only the first array
    leaf per rep is retained (a leaf's availability implies its whole
    program ran; holding full output tuples for k reps would multiply
    device residency by the rep count), and the single batched fetch
    keeps the round-trip constant comparable to the k=1 run."""
    import jax

    single_stream = len(jax.devices()) == 1

    def _first_leaf(out):
        return next(x for x in jax.tree_util.tree_leaves(out)
                    if hasattr(x, "dtype") and getattr(x, "size", 0))

    def run(k):
        t0 = time.time()
        leaves = []
        for _ in range(k):
            out = dispatch_fn()
            if not single_stream:
                leaves.append(_first_leaf(out))
        if single_stream:
            _tiny_fetch(out)
        else:
            probes = []
            for leaf in leaves:
                shards = getattr(leaf, "addressable_shards", None) or []
                datas = [s.data for s in shards] or [leaf]
                probes.extend(d.reshape(-1)[:1] for d in datas
                              if getattr(d, "size", 0))
            jax.device_get(probes)  # one pipelined multi-shard sync
        return time.time() - t0

    run(1)  # warm any residual compile/transfer
    t1 = min(run(1) for _ in range(tries))
    tk = min(run(reps) for _ in range(tries))
    per = (tk - t1) / (reps - 1)
    if per <= 0:
        # delta within timer noise: fall back to the k=reps total, which
        # still contains one round-trip constant — a conservative UNDER-
        # estimate of the rate, never a silently absurd overestimate
        per = tk / reps
    return per


def _baseline_fuse_block(sd, loader, views, block_global, blend_range=40.0):
    """One output block fused exactly the way the reference's BlkAffineFusion
    does it, in plain host code: per view, inverse-affine coordinates,
    trilinear sample (scipy.ndimage.map_coordinates order=1), cosine-edge
    blend weight, weighted average (AVG_BLEND)."""
    import numpy as np
    from scipy.ndimage import map_coordinates

    from bigstitcher_spark_tpu.utils.geometry import (
        Interval, invert_affine, transformed_interval,
    )

    shape = block_global.shape
    acc = np.zeros(shape, np.float32)
    wsum = np.zeros(shape, np.float32)
    axes = [
        (np.arange(shape[d], dtype=np.float32) + block_global.min[d]).reshape(
            [-1 if i == d else 1 for i in range(3)])
        for d in range(3)
    ]
    for v in views:
        inv = invert_affine(sd.model(v)).astype(np.float32)
        img_dim = np.asarray(sd.view_size(v), np.float32)
        src = transformed_interval(inv, block_global).expand(1)
        img_iv = Interval.from_shape(sd.view_size(v))
        if not src.overlaps(img_iv):
            continue
        clipped = src.intersect(img_iv)
        if clipped.is_empty():
            continue
        patch = loader.read_block(v, 0, tuple(clipped.min), clipped.shape
                                  ).astype(np.float32)
        w = None
        coords = []
        for i in range(3):
            li = (inv[i, 0] * axes[0] + inv[i, 1] * axes[1]
                  + inv[i, 2] * axes[2] + inv[i, 3])  # (X,Y,Z) level coords
            coords.append(li - np.float32(clipped.min[i]))
            d = np.minimum(li, (img_dim[i] - 1.0) - li)
            ramp = 0.5 * (np.cos((1.0 - d / np.float32(blend_range)) * np.pi)
                          + 1.0)
            wi = np.where(d < 0, np.float32(0),
                          np.where(d < blend_range, ramp, np.float32(1)))
            w = wi if w is None else w * wi
        val = map_coordinates(patch, coords, order=1, mode="constant",
                              cval=0.0, output=np.float32)
        acc += val * w
        wsum += w
    fused = np.where(wsum > 0, acc / np.maximum(wsum, np.float32(1e-20)), 0.0)
    return np.clip(np.round(fused), 0, 65535).astype("uint16")


def measure_baseline(xml_path, threads=None):
    """Measure the reference-equivalent CPU fusion on the bench fixture.

    Returns voxels/sec, cached in BASELINE_MEASURED.json keyed by the fixture
    spec. ``threads`` defaults to min(8, cpu_count) — the reference's
    local[8] deployment collapses to the actual core count on small hosts."""
    if threads is None:
        threads = max(1, min(8, os.cpu_count() or 1))
    import hashlib
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    key = _fixture_key(f"fusion-threads{threads}")
    cache = _baseline_cache_load()
    ent = cache.get("fusion")
    if (ent and ent.get("key") == key and ent.get("vox_per_sec", 0) > 0
            and not _fresh_baselines()):
        return float(ent["vox_per_sec"])

    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.utils.geometry import Interval
    from bigstitcher_spark_tpu.utils.grid import create_grid
    from bigstitcher_spark_tpu.utils.viewselect import maximal_bounding_box

    sd = SpimData.load(xml_path)
    loader = ViewLoader(sd)
    views = sd.view_ids()
    bbox = maximal_bounding_box(sd, views)
    grid = create_grid(bbox.shape, (128, 128, 64), (128, 128, 64))

    def do_block(block):
        bg = Interval.from_shape(block.size, block.offset).translate(bbox.min)
        return _baseline_fuse_block(sd, loader, views, bg)

    do_block(grid[0])  # warm the OS page cache for IO parity
    t0 = time.time()
    with ThreadPoolExecutor(max_workers=threads) as pool:
        outs = list(pool.map(do_block, grid))
    dt = time.time() - t0
    vox = int(np.prod(bbox.shape))
    cache["fusion"] = {
        "previous_vox_per_sec": (ent or {}).get("vox_per_sec"),
        "previous_key": (ent or {}).get("key"),
        "key": key,
        "vox_per_sec": round(vox / dt, 1),
        "voxels": vox,
        "seconds": round(dt, 3),
        "threads": threads,
        "method": (
            "reference-equivalent CPU affine fusion: numpy + "
            "scipy.ndimage.map_coordinates trilinear resample, cosine-edge "
            "AVG_BLEND weights, uint16 convert, over the reference's "
            "(128,128,64) block grid; ThreadPoolExecutor(min(8, cores)) "
            "approximates the reference's Spark local[8] deployment "
            "(BASELINE.md) at this host's actual core count."
        ),
        "fixture": {k: list(v) if isinstance(v, tuple) else v
                    for k, v in FIXTURE_SPEC.items()},
        "cpu_count": os.cpu_count(),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "checksum_block0": hashlib.sha256(outs[0].tobytes()).hexdigest()[:16],
    }
    _baseline_cache_store(cache)
    return vox / dt


def _np_phasecorr_pair(a, b, n_peaks=5, min_overlap=32.0):
    """Reference-equivalent CPU pairwise stitching kernel: zero-padded FFT
    phase correlation, top-N peak extraction, per-peak wrap disambiguation
    (2^3 variants) scored by true Pearson cross-correlation of the shifted
    overlap (PairwiseStitching role, SparkPairwiseStitching.java:247-267)."""
    import numpy as np
    from scipy.ndimage import maximum_filter

    shp = tuple(1 << int(np.ceil(np.log2(max(sa, sb, 1))))
                for sa, sb in zip(a.shape, b.shape))
    pa = np.zeros(shp, np.float32)
    pb = np.zeros(shp, np.float32)
    pa[tuple(slice(0, s) for s in a.shape)] = a
    pb[tuple(slice(0, s) for s in b.shape)] = b
    fa = np.fft.rfftn(pa)
    fb = np.fft.rfftn(pb)
    cross = fa * np.conj(fb)
    pcm = np.fft.irfftn(cross / np.maximum(np.abs(cross), 1e-10), s=shp,
                        axes=tuple(range(len(shp))))
    loc = (pcm == maximum_filter(pcm, size=3, mode="wrap"))
    flat = np.where(loc.ravel(), pcm.ravel(), -np.inf)
    top = np.argsort(flat)[-n_peaks:][::-1]
    peaks = np.stack(np.unravel_index(top, shp), axis=-1)

    best_r, best_s = -1.0, np.zeros(3)
    for p in peaks:
        for wrap in range(8):
            s = np.array([
                p[d] - (shp[d] if (wrap >> d) & 1 else 0) for d in range(3)
            ], np.int64)
            lo = np.maximum(0, s)
            hi = np.minimum(np.array(a.shape), np.array(b.shape) + s)
            if np.any(hi - lo < 1) or np.prod(hi - lo) < min_overlap:
                continue
            av = a[tuple(slice(lo[d], hi[d]) for d in range(3))]
            bv = b[tuple(slice(lo[d] - s[d], hi[d] - s[d]) for d in range(3))]
            am, bm = av - av.mean(), bv - bv.mean()
            den = np.sqrt((am * am).sum() * (bm * bm).sum())
            r = float((am * bm).sum() / den) if den > 0 else -1.0
            if r > best_r:
                best_r, best_s = r, s.astype(np.float64)
    return best_s, best_r


def measure_phasecorr_baseline(jobs):
    """CPU pairs/sec over the fixture's overlap crops (kernel work only;
    crop extraction excluded for both sides)."""
    cache = _baseline_cache_load()
    key = _fixture_key("phasecorr")
    ent = cache.get("phasecorr")
    if (ent and ent.get("key") == key and ent.get("pairs_per_sec", 0) > 0
            and not _fresh_baselines()):
        return float(ent["pairs_per_sec"])
    _np_phasecorr_pair(jobs[0].crop_a, jobs[0].crop_b)  # warm numpy/scipy
    dt = float("inf")
    for _ in range(3):  # best-of-3 both sides: damp shared-host noise
        t0 = time.time()
        for j in jobs:
            _np_phasecorr_pair(j.crop_a, j.crop_b)
        dt = min(dt, time.time() - t0)
    cache["phasecorr"] = {
        "previous_pairs_per_sec": (ent or {}).get("pairs_per_sec"),
        "previous_key": (ent or {}).get("key"),
        "key": key,
        "pairs_per_sec": round(len(jobs) / dt, 3),
        "pairs": len(jobs),
        "seconds": round(dt, 3),
        "method": (
            "reference-equivalent CPU pairwise stitching: numpy rfftn phase "
            "correlation (power-of-two padding), scipy maximum_filter top-5 "
            "peaks, 8 wrap variants per peak scored by Pearson r of the "
            "shifted overlap. Same crops as the TPU kernel."
        ),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    _baseline_cache_store(cache)
    return len(jobs) / dt


def _spans_snapshot():
    from bigstitcher_spark_tpu import profiling

    return {k: {"count": s.count, "total_s": round(s.total_s, 3),
                "max_s": round(s.max_s, 3), "min_s": round(s.min_s, 3)}
            for k, s in profiling.get().stats().items()}


def _io_baseline():
    """Snapshot of the shared observe.metrics registry (the same registry
    the production drivers feed — chunk IO bytes by implementation path,
    h2d/d2h transfer bytes), for per-run deltas."""
    from bigstitcher_spark_tpu.observe import metrics

    return metrics.get_registry().snapshot()


def _io_snapshot(baseline):
    """This run's IO/transfer byte deltas (registry counters that moved)."""
    from bigstitcher_spark_tpu.observe import metrics

    delta = metrics.get_registry().snapshot_delta(baseline)
    return {k: (int(v) if float(v).is_integer() else round(float(v), 3))
            for k, v in delta.items()
            if k.startswith(("bst_io_", "bst_xfer_", "bst_chunk_cache_",
                             "bst_tile_cache_", "bst_inflight_",
                             "bst_pair_", "bst_trace_", "bst_epilogue_",
                             "bst_serve_", "bst_compiled_fn_", "bst_dag_"))
            and isinstance(v, (int, float)) and v}


def _best_timed(n, fn):
    """Run ``fn`` n times under span profiling; return (best_dt, result,
    spans, io) of the fastest run (same span schema as the fusion measure;
    ``io`` is the run's observe.metrics byte-counter delta). Profiling is
    always disabled on exit, even if ``fn`` raises.

    The CPU baselines run unprofiled; the asymmetry is accepted because the
    recorder costs one mutex + clock read per span and these runs have only
    a handful of spans (measured: best-of-5 stitching throughput identical
    to within noise with profiling on vs off on this host), matching the
    fusion measure's existing behavior."""
    from bigstitcher_spark_tpu import profiling

    best_dt, best_res, spans, io = float("inf"), None, {}, {}
    try:
        for _ in range(n):
            profiling.enable(True)
            profiling.get().reset()
            iob = _io_baseline()
            t0 = time.time()
            res = fn()
            dt = time.time() - t0
            if dt < best_dt:
                best_dt, best_res, spans = dt, res, _spans_snapshot()
                io = _io_snapshot(iob)
    finally:
        profiling.enable(False)
    return best_dt, best_res, spans, io


def _stitch_jobs(xml_path):
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.stitching import (
        StitchingParams, _extract_pair_job, build_groups, plan_pairs,
    )

    sd = SpimData.load(xml_path)
    loader = ViewLoader(sd)
    params = StitchingParams()
    groups = build_groups(sd, sd.view_ids())
    pairs = plan_pairs(sd, groups)
    jobs = []
    for ga, gb, ov in pairs:
        j = _extract_pair_job(sd, loader, ga, gb, ov, params)
        if j is not None:
            jobs.append(j)
    return sd, jobs, params


def measure_phasecorr(xml_path):
    """Device pairs/sec on the same crops, steady state.
    Uses the production ``stitch_jobs`` pipeline: shape buckets group into
    memory-bounded segments, each drained by ONE pipelined fetch, with
    host refinement of segment k overlapping the device FFTs of k+1."""
    from bigstitcher_spark_tpu.models.stitching import stitch_jobs

    sd, jobs, params = _stitch_jobs(xml_path)

    stitch_jobs(sd, jobs, params)  # compile
    # best-of-3, matching the baseline's treatment
    dt, results, spans, io = _best_timed(
        3, lambda: stitch_jobs(sd, jobs, params))
    cpu = measure_phasecorr_baseline(jobs)
    return {
        "metric": "phasecorr_pairs_per_sec",
        "value": round(len(results) / dt, 3),
        "unit": "pair/s",
        "pairs": len(results),
        "vs_baseline": round(len(results) / dt / cpu, 3),
        "baseline_pairs_per_sec": round(cpu, 3),
        "spans": spans,
        "io": io,
    }


def measure_phasecorr_kernel(xml_path):
    """Device-resident phase correlation: the production PCM program
    (rfftn x2, normalized cross-power, irfftn, wrapped separable local-max,
    top-P peak extraction — ops/phasecorr.pcm_peaks_batch, the same program
    ``stitch_jobs`` dispatches) timed with the padded pair stacks already
    in HBM and only the small peak tables leaving the device. End-to-end
    stitching pays the crop h2d; this
    isolates the framework's device compute rate (counterpart of
    affine_fusion_kernel_voxels_per_sec for the stitching stage). The
    baseline pairs/s is the full CPU pipeline (FFTs + Pearson refinement);
    the note records that the device program excludes the host refinement
    tail, which measure_phasecorr prices in."""
    import numpy as np

    import jax

    from bigstitcher_spark_tpu.models.stitching import _fft_shape
    from bigstitcher_spark_tpu.ops.phasecorr import pad_to, pcm_peaks_batch

    sd, jobs, params = _stitch_jobs(xml_path)
    buckets: dict[tuple, list] = {}
    for j in jobs:
        shp = tuple(_fft_shape(np.maximum(j.crop_a.shape, j.crop_b.shape)))
        buckets.setdefault(shp, []).append(j)
    shp, bjobs = max(buckets.items(), key=lambda kv: len(kv[1]))
    a = jax.device_put(np.stack([pad_to(j.crop_a, shp) for j in bjobs]))
    b = jax.device_put(np.stack([pad_to(j.crop_b, shp) for j in bjobs]))
    ea = jax.device_put(
        np.stack([np.array(j.crop_a.shape, np.int32) for j in bjobs]))
    eb = jax.device_put(
        np.stack([np.array(j.crop_b.shape, np.int32) for j in bjobs]))
    for arr in (a, b, ea, eb):  # force residency (h2d is async)
        _tiny_fetch(arr)
    per_rep = _kernel_rate(
        lambda: pcm_peaks_batch(a, b, ea, eb, params.peaks_to_check, 0.25),
        reps=20)
    # CPU baseline over the SAME pair subset (buckets have different
    # orientations/costs, so the all-pairs baseline is a different
    # workload); measured inline so the all-pairs cache entry stays clean
    _np_phasecorr_pair(bjobs[0].crop_a, bjobs[0].crop_b)  # warm
    cpu_dt = float("inf")
    for _ in range(3):
        t0 = time.time()
        for j in bjobs:
            _np_phasecorr_pair(j.crop_a, j.crop_b)
        cpu_dt = min(cpu_dt, time.time() - t0)
    cpu = len(bjobs) / cpu_dt
    value = len(bjobs) / per_rep
    return {
        "metric": "phasecorr_kernel_pairs_per_sec",
        "value": round(value, 3),
        "unit": "pair/s",
        "pairs": len(bjobs),
        "fft_shape": list(shp),
        "vs_baseline": round(value / cpu, 3),
        "baseline_pairs_per_sec": round(cpu, 3),
        "sync_methodology": _SYNC_METHODOLOGY,
        "note": ("pair stacks in HBM, dispatch+compute only, largest FFT "
                 "bucket; baseline is the full CPU pipeline incl. host "
                 "Pearson refinement over the SAME pairs (all pairs priced "
                 "end-to-end by phasecorr_pairs_per_sec)"),
    }


def measure_dog_baseline(xml_path):
    """CPU DoG detection vox/sec: scipy gaussian blurs, subtraction,
    3^3 local maxima, threshold, quadratic subpixel fit. Intensity bounds
    are explicit (0, 65535) on both sides — the reference makes
    --minIntensity/--maxIntensity REQUIRED options
    (SparkInterestPointDetection.java:140-144)."""
    import numpy as np

    # one measurement per process: measure_dog AND measure_dog_kernel both
    # need this number; re-measuring would burn ~3 full-volume CPU passes
    # and rotate the cache's previous_vox_per_sec cross-run history onto a
    # same-run intermediate
    if "dog" in _RUN_BASELINES:
        return _RUN_BASELINES["dog"]
    cache = _baseline_cache_load()
    key = _fixture_key("dog-explicit-minmax")
    ent = cache.get("dog")
    if (ent and ent.get("key") == key and ent.get("vox_per_sec", 0) > 0
            and not _fresh_baselines()):
        return float(ent["vox_per_sec"])

    from scipy.ndimage import gaussian_filter, maximum_filter

    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.detection import (
        DetectionParams, _ViewPlan,
    )
    from bigstitcher_spark_tpu.ops.dog import DOG_K

    sd = SpimData.load(xml_path)
    loader = ViewLoader(sd)
    params = DetectionParams()
    s1, s2 = params.sigma, params.sigma * DOG_K

    def one_pass():
        total_vox = 0
        t_total = 0.0
        n_spots = 0
        for v in sd.view_ids():
            plan = _ViewPlan(loader, v, params.downsampling)
            # the timed region includes the volume read: the TPU side's
            # detect_interest_points also pays its IO inside the measurement
            t0 = time.time()
            img = plan.read_det_block(loader, (0, 0, 0), plan.det_dims)
            lo, hi = 0.0, 65535.0
            norm = (img - lo) / max(hi - lo, 1e-20)
            g1 = gaussian_filter(norm, s1, mode="nearest")
            g2 = gaussian_filter(norm, s2, mode="nearest")
            dog = (g1 - g2) / (DOG_K - 1.0)
            is_max = (dog == maximum_filter(dog, size=3, mode="nearest"))
            cand = is_max & (dog > params.threshold / 2)
            pts = np.argwhere(cand)
            for p in pts:  # quadratic subpixel refinement per spot
                if np.any(p == 0) or np.any(p == np.array(dog.shape) - 1):
                    continue
                for d in range(3):
                    lo_i = tuple(p + np.eye(3, dtype=int)[d] * -1)
                    hi_i = tuple(p + np.eye(3, dtype=int)[d])
                    _ = 0.5 * (dog[lo_i] - dog[hi_i])
            n_spots += len(pts)
            t_total += time.time() - t0
            total_vox += int(np.prod(plan.det_dims))
        return total_vox, t_total, n_spots

    # untimed warm pass: the candidate side gets an explicit warm call
    # before ITS best-of-3, so the baseline must not pay the cold page
    # cache in its first timed pass (asymmetry behind a 6x cross-run
    # baseline swing flagged by baseline_drift_flags)
    for v in sd.view_ids():
        plan = _ViewPlan(loader, v, params.downsampling)
        plan.read_det_block(loader, (0, 0, 0), plan.det_dims)
    total_vox, t_total, n_spots = one_pass()
    for _ in range(2):  # best-of-3 both sides: damp shared-host noise
        tv, tt, ns = one_pass()
        if tt < t_total:
            total_vox, t_total, n_spots = tv, tt, ns
    cache["dog"] = {
        "previous_vox_per_sec": (ent or {}).get("vox_per_sec"),
        "previous_key": (ent or {}).get("key"),
        "key": key,
        "vox_per_sec": round(total_vox / t_total, 1),
        "voxels": total_vox,
        "spots": int(n_spots),
        "seconds": round(t_total, 3),
        "method": (
            "reference-equivalent CPU DoG detection: scipy gaussian_filter "
            "x2 (computeSigmas), subtraction, 3^3 maximum_filter extrema, "
            "threshold, per-spot quadratic subpixel probe. Volume read "
            "included in the timed region (the TPU side pays its IO too); "
            "same detection-resolution volumes as the TPU path; explicit "
            "minIntensity=0/maxIntensity=65535 both sides (required "
            "options in the reference)."
        ),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    _baseline_cache_store(cache)
    _RUN_BASELINES["dog"] = total_vox / t_total
    return _RUN_BASELINES["dog"]


def measure_dog(xml_path):
    import numpy as np

    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.detection import (
        DetectionParams, _ViewPlan, detect_interest_points,
    )

    sd = SpimData.load(xml_path)
    loader = ViewLoader(sd)
    views = sd.view_ids()
    params = DetectionParams(min_intensity=0.0, max_intensity=65535.0)
    total_vox = sum(
        int(np.prod(_ViewPlan(loader, v, params.downsampling).det_dims))
        for v in views)
    detect_interest_points(sd, loader, views, params, progress=False)  # warm
    # best-of-3, matching the baseline's treatment
    dt, dets, spans, io = _best_timed(
        3, lambda: detect_interest_points(sd, loader, views, params,
                                          progress=False))
    cpu = measure_dog_baseline(xml_path)
    n_spots = sum(len(d.points) for d in dets)
    return {
        "metric": "dog_detection_vox_per_sec",
        "value": round(total_vox / dt, 1),
        "unit": "voxel/s",
        "spots": int(n_spots),
        "vs_baseline": round(total_vox / dt / cpu, 3),
        "baseline_vox_per_sec": round(cpu, 1),
        "spans": spans,
        "io": io,
    }


def measure_dog_kernel(xml_path):
    """Device-resident DoG detection: the production device program
    (on-device pool-by-``rel`` + normalization, Toeplitz/FFT blurs,
    separable extrema, top-K compaction, vectorized quadratic subpixel —
    the same kernel ``detect_interest_points`` dispatches through
    ``_make_dog_kernel``) timed with its haloed level-res input blocks
    already in HBM and only the compacted (K,3)+(K,) outputs leaving the
    device. End-to-end detection pays the block h2d; this isolates the
    framework's device compute rate
    (counterpart of affine_fusion_kernel_voxels_per_sec for the detection
    stage; reference device work: SparkInterestPointDetection.java:552-568)."""
    import numpy as np

    import jax

    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.detection import (
        DetectionParams, _ViewPlan, _make_dog_kernel,
    )
    from bigstitcher_spark_tpu.ops.dog import dog_halo
    from bigstitcher_spark_tpu.utils.grid import create_grid

    sd = SpimData.load(xml_path)
    loader = ViewLoader(sd)
    views = sd.view_ids()
    params = DetectionParams(min_intensity=0.0, max_intensity=65535.0)
    halo = dog_halo(params.sigma)
    bs = tuple(int(b) for b in params.block_size)

    # bucket by geometry FIRST (mirrors detect_interest_points' shape/rel
    # bucketing), then read + stage only the winning bucket's haloed
    # level-res blocks (native dtype) — losing buckets are never read
    buckets: dict[tuple, list] = {}  # (lvl shape, rel) -> [(plan, off, core_vox)]
    for v in views:
        plan = _ViewPlan(loader, v, params.downsampling)
        for blk in create_grid(plan.det_dims, bs):
            off = [int(o) - halo for o in blk.offset]
            shape = tuple((int(s) + 2 * halo) * r
                          for s, r in zip(blk.size, plan.rel))
            buckets.setdefault((shape, plan.rel), []).append(
                (plan, off, int(np.prod(blk.size))))
    (shape, rel), picked = max(buckets.items(), key=lambda kv: len(kv[1]))
    blocks = []
    for plan, off, core in picked:
        raw = plan.read_raw_block(
            loader, off, [s // r for s, r in zip(shape, rel)])
        if raw.dtype.byteorder == ">":
            raw = raw.astype(raw.dtype.newbyteorder("="))
        blocks.append((raw[None], np.array(off, np.int32)[None], core))
    kernel = _make_dog_kernel(1, params, rel)
    # production per-device packing: run_sharded_batches groups
    # max(1, batch_size // prod(rel)) blocks per batch-axis dispatch
    # (models/detection.py per_dev scaling)
    per_dev = max(1, params.batch_size // int(np.prod(rel)))
    dev = []
    for i in range(0, len(blocks), per_dev):
        grp = blocks[i:i + per_dev]
        dev.append((jax.device_put(np.concatenate([b for b, _, _ in grp])),
                    jax.device_put(np.concatenate([o for _, o, _ in grp])),
                    np.full(len(grp), params.min_intensity, np.float32),
                    np.full(len(grp), params.max_intensity, np.float32),
                    np.full(len(grp), params.threshold, np.float32)))
    core_vox = sum(cv for _, _, cv in blocks)
    for b, o, lo, hi, thr in dev:  # warm compiles + force input residency
        _tiny_fetch(kernel(b, lo, hi, thr, o))

    def _dispatch_all():
        out = None
        for b, o, lo, hi, thr in dev:
            out = kernel(b, lo, hi, thr, o)
        return out

    per_rep = _kernel_rate(_dispatch_all, reps=10)
    cpu = measure_dog_baseline(xml_path)
    value = core_vox / per_rep
    return {
        "metric": "dog_kernel_voxels_per_sec",
        "value": round(value, 1),
        "unit": "voxel/s",
        "blocks": len(blocks),
        "blocks_per_dispatch": per_dev,
        "vs_baseline": round(value / cpu, 3),
        "baseline_vox_per_sec": round(cpu, 1),
        "sync_methodology": _SYNC_METHODOLOGY,
        "note": ("haloed level-res blocks in HBM, compacted top-K outputs "
                 "only; dispatch+compute, production per-device batch "
                 "packing; baseline includes its volume read (it prices "
                 "the full CPU stage — see dog_detection_vox_per_sec for "
                 "the like-for-like end-to-end comparison)"),
    }


def measure_kernel_only(xml_path):
    """Steady-state fusion with tiles resident in HBM and the output left on
    device: the framework's compute rate with host IO out of the picture
    (tiles are uploaded ONCE, outside the timed loop; each rep re-dispatches
    the compiled program). Also measures the wire: one timed D2H of the
    fused output."""
    import numpy as np

    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models import affine_fusion as AF
    from bigstitcher_spark_tpu.utils.viewselect import maximal_bounding_box

    sd = SpimData.load(xml_path)
    loader = ViewLoader(sd)
    views = sd.view_ids()
    bbox = maximal_bounding_box(sd, views)
    cp = AF.plan_composite_volume(sd, loader, views, bbox, None,
                                  AF.BlendParams())
    assert cp is not None, "bench fixture must take the device path"
    tiles = AF.upload_composite_tiles(loader, cp)
    for tl in tiles:  # force residency: h2d is async
        _tiny_fetch(tl)

    def _dispatch():
        return AF.dispatch_composite(cp, tiles, "AVG_BLEND", "uint16", False,
                                     0.0, 65535.0)

    t0 = time.time()
    out = _dispatch()
    _tiny_fetch(out)  # materialized: reused below for the wire timing
    first = time.time() - t0  # compile + first true execution + round-trip
    per_run = _kernel_rate(_dispatch, reps=10)
    vox = int(np.prod(bbox.shape))
    t0 = time.time()
    host = np.asarray(out)
    d2h_s = time.time() - t0
    return {
        "metric": "affine_fusion_kernel_voxels_per_sec",
        "value": round(vox / per_run, 1),
        "unit": "voxel/s",
        "sync_methodology": _SYNC_METHODOLOGY,
        "note": ("tiles in HBM, output on device, dispatch+compute only; "
                 "first(compile)={:.2f}s".format(first)),
        "wire_d2h_mb_per_sec": round(host.nbytes / d2h_s / 1e6, 1),
        "wire_d2h_bytes": int(host.nbytes),
    }


# isotropic 2x chain: the pyramid adds 1/8 + 1/64 ~= 14% extra voxels/wire
# bytes where the pre-epilogue flow re-read 100% of full res from disk
FUSION_PYRAMID_STEPS = [[1, 1, 1], [2, 2, 2], [4, 4, 4]]


def measure_fusion_pyramid(xml_path):
    """Fusion with the fused multiscale epilogue: full res + the whole
    downsample pyramid computed in HBM and shipped in ONE drain, vs the
    baseline fusion+downsample sequence (reference-equivalent numpy
    fusion, then numpy mean downsampling that re-reads the stored
    full-res container — the exact flow the epilogue eliminates).

    The headline ``value`` stays the FULL-RES-ONLY rate and the pyramid
    voxels are reported separately (``vox_per_sec_incl_pyramid``), so the
    epilogue can neither masquerade as a kernel regression (extra voxels
    hidden in the same wall clock) nor inflate the kernel rate."""
    import numpy as np

    from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat
    from bigstitcher_spark_tpu.io.container import (
        create_fusion_container, read_container_meta)
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.affine_fusion import (
        fuse_volume, pyramid_from_mr)
    from bigstitcher_spark_tpu.models.downsample_driver import (
        downsample_pyramid_level, read_padded)
    from bigstitcher_spark_tpu.utils.viewselect import maximal_bounding_box

    sd = SpimData.load(xml_path)
    loader = ViewLoader(sd)
    views = sd.view_ids()
    bbox = maximal_bounding_box(sd, views)
    out = os.path.join(FIXTURE, "fused_pyramid.ome.zarr")

    def make_container(path):
        shutil.rmtree(path, ignore_errors=True)
        create_fusion_container(
            path, StorageFormat.ZARR, xml_path, 1, 1, bbox,
            data_type="uint16", block_size=(128, 128, 64),
            downsamplings=FUSION_PYRAMID_STEPS,
            min_intensity=0.0, max_intensity=65535.0)
        store = ChunkStore.open(path)
        return store, read_container_meta(store).mr_infos[0]

    def run():
        store, mr = make_container(out)
        ds = store.open_dataset(mr[0].dataset.strip("/"))
        pyr = pyramid_from_mr(store, mr)
        stats = fuse_volume(
            sd, loader, views, ds, bbox, block_size=(128, 128, 64),
            block_scale=(2, 2, 1), fusion_type="AVG_BLEND",
            out_dtype="uint16", min_intensity=0.0, max_intensity=65535.0,
            zarr_ct=(0, 0), pyramid=pyr)
        # levels a (sharded) epilogue could not align fall back to the
        # container-reread driver, exactly like the CLI
        for lvl in range(1 + stats.pyramid_levels, len(mr)):
            downsample_pyramid_level(store, mr[lvl - 1], mr[lvl], True,
                                     (0, 0))
        return store, mr, stats

    run()  # warm compiles
    # best-of-5, the primary metric's convention: shared-host IO weather
    # swings the write-bound runs ~30% window to window
    dt, (store, mr, stats), spans, io = _best_timed(5, run)
    vox = int(np.prod(bbox.shape))
    pyr_vox = sum(int(np.prod([int(v) for v in m.dimensions[:3]]))
                  for m in mr[1:])

    # baseline downsample leg: re-read the stored full-res container,
    # numpy reshape-mean each level, round/clip, write — measured on a
    # scratch container seeded (untimed) with the fused s0
    bstore, bmr = make_container(os.path.join(FIXTURE,
                                              "baseline_pyramid.ome.zarr"))
    s0 = store.open_dataset(mr[0].dataset.strip("/")).read_full()
    prev_ds = bstore.open_dataset(bmr[0].dataset.strip("/"))
    prev_ds.write(s0, (0,) * 5)
    t0 = time.time()
    for lvl in range(1, len(bmr)):
        rel = [int(v) for v in bmr[lvl].relativeDownsampling[:3]]
        dims = [int(v) for v in bmr[lvl].dimensions[:3]]

        def read3d(off, size, _p=prev_ds):
            return _p.read((*off, 0, 0), (*size, 1, 1))[..., 0, 0]

        needed = [d * f for d, f in zip(dims, rel)]
        x = read_padded(read3d, prev_ds.shape[:3], (0, 0, 0),
                        needed).astype(np.float32)
        for ax, f in enumerate(rel):
            if int(f) == 1:
                continue
            shp = list(x.shape)
            shp[ax] //= int(f)
            shp.insert(ax + 1, int(f))
            x = x.reshape(shp).mean(axis=ax + 1)
        ds_l = bstore.open_dataset(bmr[lvl].dataset.strip("/"))
        ds_l.write(np.clip(np.round(x), 0, 65535).astype(np.uint16)
                   [..., None, None], (0,) * 5)
        prev_ds = ds_l
    base_ds_s = time.time() - t0
    if "fusion" not in _RUN_BASELINES:
        _RUN_BASELINES["fusion"] = measure_baseline(xml_path)
    base_fusion_s = vox / _RUN_BASELINES["fusion"]
    base_total_s = base_fusion_s + base_ds_s
    return {
        "metric": "affine_fusion_pyramid_vox_per_sec",
        "value": round(vox / dt, 1),
        "unit": "voxel/s",
        "note": ("fusion + full multiscale pyramid in one device drain; "
                 "value is the FULL-RES-ONLY rate, pyramid voxels "
                 "reported separately"),
        "epilogue_levels": stats.pyramid_levels,
        "pyramid_voxels": pyr_vox,
        "vox_per_sec_incl_pyramid": round((vox + pyr_vox) / dt, 1),
        "vs_baseline": round(base_total_s / dt, 3),
        "baseline_seconds": {"fusion": round(base_fusion_s, 3),
                             "downsample_reread": round(base_ds_s, 3)},
        "baseline_provenance": (
            "same-run numpy fusion rate + same-run numpy container-reread "
            "downsample chain on this host"),
        "spans": spans,
        "io": io,
    }


def measure_pipeline(xml_path):
    """Staged vs streamed stage-DAG execution of the same workload
    (resave -> create -> affine-fusion -> downsample -> detect):

    - **staged** runs the five one-shot CLI commands in sequence with
      real containers between stages, clearing the decoded-chunk cache
      between commands so the leg prices what users actually run — one
      process per stage, cold caches each (the in-process invocation
      would otherwise smuggle the chunk cache across stages and
      understate the container round-trip);
    - **streamed** runs the identical commands through `bst pipeline`
      (dag/executor.py): consumers start on block completion, blocks
      hand over through the decoded-chunk cache, and the resaved
      intermediate is elided to a memory:// root.

    Reported: both wall clocks, the staged leg's consumer-stage
    container-read bytes (the round trip the executor attacks), and the
    streamed leg's elided-vs-reread byte split from the `bst_dag_*`
    counters (ROADMAP item 2's >=90%-elision acceptance bar)."""
    from bigstitcher_spark_tpu.dag import run_pipeline
    from bigstitcher_spark_tpu.dag.executor import _invoke_tool
    from bigstitcher_spark_tpu.io.chunkcache import get_cache
    from bigstitcher_spark_tpu.observe import metrics as _om

    def run_tool(args):
        rc = _invoke_tool(args[0], args[1:])
        if rc:
            raise RuntimeError(f"bst {' '.join(args)} exited {rc}")

    def stage_cmds(root, xml):
        rexml = os.path.join(root, "bench-pipeline-resaved.xml")
        resaved = os.path.join(root, "bench-pipeline-resaved.n5")
        fused = os.path.join(root, "bench-pipeline-fused.n5")
        return rexml, resaved, fused, [
            ["resave", "-x", xml, "-xo", rexml, "-o", resaved, "--N5"],
            ["create-fusion-container", "-x", rexml, "-o", fused,
             "-s", "N5", "-d", "UINT16", "--minIntensity", "0",
             "--maxIntensity", "65535"],
            ["affine-fusion", "-o", fused],
            ["downsample", "-i", fused, "-di", "ch0tp0/s0",
             "-ds", "2,2,1"],
            ["detect-interestpoints", "-x", rexml, "-l", "beads",
             "-s", "1.8", "-t", "0.008", "-dsxy", "1", "-dsz", "1"],
        ]

    def read_bytes_snapshot():
        # real container decodes only: the path="cache" series is bytes
        # served by the in-process chunk cache, which a process-per-stage
        # run would ALSO serve from memory within one stage — counting it
        # would inflate the round trip streaming is credited with killing
        return sum(v for k, v in _om.get_registry().snapshot().items()
                   if k.startswith("bst_io_read_bytes_total")
                   and '"cache"' not in k)

    # -- staged leg: one-shot CLIs, containers between stages --------------
    staged_root = os.path.join(FIXTURE, "pipeline-staged")
    shutil.rmtree(staged_root, ignore_errors=True)
    os.makedirs(staged_root, exist_ok=True)
    _, resaved, _, cmds = stage_cmds(staged_root, xml_path)
    t0 = time.time()
    consumer_reads = 0
    for i, cmd in enumerate(cmds):
        get_cache().clear()       # process-per-stage: no cross-stage cache
        before = read_bytes_snapshot()
        run_tool(cmd)
        if i >= 2:                # fuse / downsample / detect re-read
            consumer_reads += read_bytes_snapshot() - before
    staged_s = time.time() - t0

    # -- streamed leg: the DAG executor on an identical spec ---------------
    streamed_root = os.path.join(FIXTURE, "pipeline-streamed")
    shutil.rmtree(streamed_root, ignore_errors=True)
    os.makedirs(streamed_root, exist_ok=True)
    rexml, resaved, fused, _ = stage_cmds(streamed_root, xml_path)
    spec = {
        "name": "bench-streamed",
        "datasets": {"resaved": {"path": resaved, "ephemeral": True},
                     "fused": {"path": fused}},
        "stages": [
            {"id": "resave", "tool": "resave",
             "args": ["-x", xml_path, "-xo", rexml, "-o", "@resaved",
                      "--N5"],
             "writes": ["resaved"]},
            {"id": "create", "tool": "create-fusion-container",
             "args": ["-x", rexml, "-o", "@fused", "-s", "N5",
                      "-d", "UINT16", "--minIntensity", "0",
                      "--maxIntensity", "65535"],
             "after": ["resave"]},
            {"id": "fuse", "tool": "affine-fusion",
             "args": ["-o", "@fused"],
             "after": ["create"], "reads": ["resaved"],
             "writes": ["fused"]},
            {"id": "downsample", "tool": "downsample",
             "args": ["-i", "@fused", "-di", "ch0tp0/s0", "-ds", "2,2,1"],
             "reads": ["fused"], "writes": ["fused"]},
            {"id": "detect", "tool": "detect-interestpoints",
             "args": ["-x", rexml, "-l", "beads", "-s", "1.8",
                      "-t", "0.008", "-dsxy", "1", "-dsz", "1"],
             "after": ["resave"], "reads": ["resaved"]},
        ],
    }
    get_cache().clear()
    iob = _io_baseline()
    t0 = time.time()
    res = run_pipeline(spec, workdir=streamed_root)
    streamed_s = time.time() - t0
    io = _io_snapshot(iob)
    summary = res.to_dict()
    assert summary["ok"], summary
    elided = summary["bytes_elided"]
    reread = summary["bytes_reread"]
    elision_pct = round(100.0 * elided / max(elided + reread, 1), 2)

    # -- handoff leg: the same streamed spec with the HBM handoff cache
    # enabled (BST_DAG_HANDOFF_BYTES): producer blocks reach same-mesh
    # consumers as DEVICE arrays — no drain D2H, no host-LRU hop — with
    # the identical per-rep cache clear so the legs differ by exactly the
    # one knob
    handoff_root = os.path.join(FIXTURE, "pipeline-handoff")
    shutil.rmtree(handoff_root, ignore_errors=True)
    os.makedirs(handoff_root, exist_ok=True)
    rexml_h, resaved_h, fused_h, _ = stage_cmds(handoff_root, xml_path)
    spec_h = json.loads(json.dumps(spec).replace(streamed_root,
                                                 handoff_root))
    get_cache().clear()
    iob_h = _io_baseline()
    os.environ["BST_DAG_HANDOFF_BYTES"] = str(1 << 30)
    try:
        t0 = time.time()
        res_h = run_pipeline(spec_h, workdir=handoff_root)
        handoff_s = time.time() - t0
    finally:
        os.environ.pop("BST_DAG_HANDOFF_BYTES", None)
    io_h = _io_snapshot(iob_h)
    summary_h = res_h.to_dict()
    assert summary_h["ok"], summary_h
    assert summary_h["blocks_handoff"] > 0, summary_h

    return {
        "metric": "pipeline_staged_over_streamed",
        "value": round(staged_s / max(streamed_s, 1e-9), 3),
        "unit": "x",
        "note": ("same resave->create->fuse->downsample->detect workload "
                 "as five one-shot CLIs with containers between stages "
                 "(cache cleared per stage = process-per-stage flow) vs "
                 "one streamed `bst pipeline` run with the resaved "
                 "intermediate elided to memory; the handoff leg re-runs "
                 "the streamed spec with BST_DAG_HANDOFF_BYTES=1G so "
                 "same-mesh edges hand blocks over device-resident"),
        "staged_seconds": round(staged_s, 3),
        "streamed_seconds": round(streamed_s, 3),
        "handoff_seconds": round(handoff_s, 3),
        "streamed_over_handoff": round(streamed_s / max(handoff_s, 1e-9),
                                       3),
        "staged_consumer_read_bytes": int(consumer_reads),
        "streamed_bytes_elided": int(elided),
        "streamed_bytes_reread": int(reread),
        "elision_pct": elision_pct,
        "blocks_streamed": summary["blocks_streamed"],
        "containers_elided": summary["containers_elided"],
        "handoff_blocks": summary_h["blocks_handoff"],
        "handoff_bytes_served": summary_h["bytes_handoff"],
        "handoff_bytes_spilled": summary_h["bytes_spilled"],
        "handoff_bytes_reread": summary_h["bytes_reread"],
        "edges": summary["edges"],
        "handoff_edges": summary_h["edges"],
        "io": io,
        "io_handoff": io_h,
    }


def measure_solver(xml_path):
    """numpy vs device vs sharded global-solve wall time at growing
    synthetic tile grids (ROADMAP item 4: the last driver-side O(tiles)
    stage moved onto the mesh).

    Builds truth-consistent 8-corner stitching-style link graphs (no
    image IO — the solver's cost is the iteration, not the matches),
    then times `models.solver.relax` per backend: the host numpy
    reference, the jit-compiled device while_loop, and the psum-sharded
    layout forced on via BST_SOLVE_SHARD=1. AFFINE+RIGID regularization
    with damping 0.7 keeps the sweep count meaningfully >1 so the
    per-iteration cost dominates the compile-amortized call. Reported:
    per-grid seconds + sweep rates, the device/numpy speedup at the
    largest grid (the acceptance bar: >=1x on XLA:CPU), and the
    io/solve counter deltas."""
    import numpy as _np

    from bigstitcher_spark_tpu import config as _c
    from bigstitcher_spark_tpu.io.spimdata import ViewId
    from bigstitcher_spark_tpu.models import solver as S
    from bigstitcher_spark_tpu.ops import models as M

    def graph(n):
        rng = _np.random.default_rng(17)
        tiles = [(ViewId(0, i),) for i in range(n[0] * n[1])]
        truth = {i: _np.array([(i % n[0]) * 80.0, (i // n[0]) * 80.0, 0.0])
                 for i in range(len(tiles))}
        nom = {i: truth[i] + (rng.uniform(-3, 3, 3) if i else 0.0)
               for i in truth}
        corners = _np.array([[x, y, z] for x in (0, 100) for y in (0, 100)
                             for z in (0, 50)], float)
        links = []
        for i in range(len(tiles)):
            for j in (i + 1, i + n[0]):
                if j >= len(tiles):
                    continue
                if j == i + 1 and (i % n[0]) == n[0] - 1:
                    continue
                shift = (truth[i] - nom[i]) - (truth[j] - nom[j])
                # per-corner noise keeps the fixed point away from the
                # warm start so the solve genuinely iterates
                noise = rng.normal(0, 0.5, corners.shape)
                links.append(S.MatchLink(
                    tiles[i], tiles[j], corners, corners + shift + noise,
                    _np.full(8, 0.9)))
        return tiles, links

    import jax as _jax

    n_dev = len(_jax.local_devices())
    iob = _io_baseline()
    grids = []
    speedup = 0.0
    for n in ((12, 12), (24, 24)):
        tiles, links = graph(n)
        fixed = {tiles[0]}
        row = {"tiles": len(tiles), "links": len(links),
               "local_devices": n_dev}
        legs = [("numpy", "numpy", None),
                ("device", "device", {"BST_SOLVE_SHARD": 0})]
        if n_dev > 1:
            legs.append(("sharded", "device", {"BST_SOLVE_SHARD": 1}))
        else:
            # one local device: BST_SOLVE_SHARD=1 would silently run the
            # unsharded kernel — report the absence instead of a fake row
            row["sharded_skipped"] = "1 local device (shard_map not taken)"
        for label, backend, overrides in legs:
            params = S.SolverParams(model=M.AFFINE, regularization=M.RIGID,
                                    damping=0.7, backend=backend)
            import contextlib

            scope = (_c.overrides(overrides) if overrides
                     else contextlib.nullcontext())
            with scope:
                S.relax(links, tiles, fixed, params)  # warm/compile
                best = float("inf")
                iters = 0
                for _ in range(3):
                    t0 = time.time()
                    res = S.relax(links, tiles, fixed, params)
                    best = min(best, time.time() - t0)
                    iters = res.iterations
            row[f"{label}_s"] = round(best, 4)
            row[f"{label}_sweeps_per_s"] = round(iters / max(best, 1e-9), 1)
            row[f"{label}_iterations"] = iters
        row["device_speedup_vs_numpy"] = round(
            row["numpy_s"] / max(row["device_s"], 1e-9), 2)
        if "sharded_s" in row:
            row["sharded_speedup_vs_numpy"] = round(
                row["numpy_s"] / max(row["sharded_s"], 1e-9), 2)
        speedup = row["device_speedup_vs_numpy"]
        grids.append(row)
    return {
        "metric": "solver_device_speedup_vs_numpy",
        "value": speedup,
        "unit": "x",
        "note": ("best-of-3 relax() wall per backend on synthetic "
                 "tile-grid link graphs; device = one compiled "
                 "lax.while_loop, sharded = psum collective layout "
                 "forced via BST_SOLVE_SHARD=1; speedup at the largest "
                 "grid"),
        "grids": grids,
        "io": _io_snapshot(iob),
    }


def measure_submit_latency(xml_path):
    """Cold first-submit vs warm repeat-submit wall time through a `bst
    serve` daemon (in-process, one slot): the same affine-fusion job
    submitted twice into a container whose block size no other measure
    uses, so the first submit genuinely builds its compiled-fn bucket and
    the second genuinely reuses it — the amortized-compile + warm-cache
    win a resident daemon exists for, as a measured ratio instead of a
    claim. Reported in the io columns (`bst_serve_*` /
    `bst_compiled_fn_*` counter deltas ride along)."""
    from bigstitcher_spark_tpu.io.chunkstore import StorageFormat
    from bigstitcher_spark_tpu.io.container import create_fusion_container
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.serve import client
    from bigstitcher_spark_tpu.serve.daemon import Daemon
    from bigstitcher_spark_tpu.utils.viewselect import maximal_bounding_box

    sd = SpimData.load(xml_path)
    bbox = maximal_bounding_box(sd, sd.view_ids())
    out = os.path.join(FIXTURE, "served.ome.zarr")
    shutil.rmtree(out, ignore_errors=True)
    # 96x96x48 blocks: a compiled-fn bucket nothing else in this bench
    # compiles, so submit #1 is honestly cold inside this warm process
    create_fusion_container(
        out, StorageFormat.ZARR, xml_path, 1, 1, bbox,
        data_type="uint16", block_size=(96, 96, 48),
        min_intensity=0.0, max_intensity=65535.0)
    sock = os.path.join(FIXTURE, "bench-serve.sock")
    d = Daemon(sock, slots=1,
               jobs_root=os.path.join(FIXTURE, "bench-serve-jobs")).start()
    iob = _io_baseline()
    try:
        def submit_once():
            t0 = time.time()
            res = client.submit(sock, "affine-fusion", ["-o", out])
            assert res["exit_code"] == 0, res
            return time.time() - t0, res

        cold_s, cold = submit_once()
        warm_s, warm = submit_once()
    finally:
        try:
            client.shutdown(sock)
            d.wait(60)
        except Exception:
            pass
    io = _io_snapshot(iob)
    return {
        "metric": "serve_submit_warm_seconds",
        "value": round(warm_s, 3),
        "unit": "s",
        "note": ("same fusion job submitted twice through an in-process "
                 "bst serve daemon; cold pays the compiled-fn bucket "
                 "build + cache fill, warm reuses both"),
        "cold_submit_s": round(cold_s, 3),
        "warm_submit_s": round(warm_s, 3),
        "cold_over_warm": round(cold_s / max(warm_s, 1e-9), 3),
        "warm_compile_hits": warm.get("warm_compile_hits", 0),
        "cold_compile_hits": cold.get("warm_compile_hits", 0),
        "io": io,
    }


MULTITP_SPEC = {
    "n_tiles": (2, 2, 1), "tile_size": (128, 128, 64), "overlap": 32,
    "jitter": 0.0, "seed": 23, "block_size": (64, 64, 32),
    "n_beads_per_tile": 60, "n_channels": 2, "n_timepoints": 2,
}


def _slot_views(sd, c_idx, t_idx):
    """Views of the container slot (channel index, timepoint index) —
    mrInfos[c + t*numChannels] selection (SparkAffineFusion.java:426-441)."""
    channels = sorted({s.attributes.get("channel", 0)
                      for s in sd.setups.values()})
    tps = sorted(sd.timepoints)
    ch = channels[c_idx]
    tp = tps[t_idx]
    return [v for v in sd.view_ids()
            if v.timepoint == tp
            and sd.setups[v.setup].attributes.get("channel", 0) == ch]


def measure_multitp():
    """Multi-timepoint multi-channel affine fusion -> 5-D OME-ZARR
    (BASELINE.md config), all four (c,t) slots, vs the same numpy baseline
    fusion run per slot."""
    import numpy as np

    from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat
    from bigstitcher_spark_tpu.io.container import create_fusion_container
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.affine_fusion import fuse_volume
    from bigstitcher_spark_tpu.utils.geometry import Interval
    from bigstitcher_spark_tpu.utils.grid import create_grid
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project
    from bigstitcher_spark_tpu.utils.viewselect import maximal_bounding_box

    root = os.path.join(FIXTURE, "multitp")
    xml = os.path.join(root, "proj", "dataset.xml")
    if not os.path.exists(xml):
        make_synthetic_project(os.path.join(root, "proj"), **MULTITP_SPEC)
    sd = SpimData.load(xml)
    loader = ViewLoader(sd)
    bbox = maximal_bounding_box(sd, sd.view_ids())
    out = os.path.join(root, "fused.ome.zarr")
    n_ch = MULTITP_SPEC["n_channels"]
    n_tp = MULTITP_SPEC["n_timepoints"]

    def run():
        shutil.rmtree(out, ignore_errors=True)
        create_fusion_container(
            out, StorageFormat.ZARR, xml, n_tp, n_ch, bbox,
            data_type="uint16", block_size=(64, 64, 32),
            min_intensity=0.0, max_intensity=65535.0)
        ds = ChunkStore.open(out).open_dataset("0")
        for t in range(n_tp):
            for c in range(n_ch):
                fuse_volume(
                    sd, loader, _slot_views(sd, c, t), ds, bbox,
                    block_size=(64, 64, 32), block_scale=(2, 2, 1),
                    fusion_type="AVG_BLEND", out_dtype="uint16",
                    min_intensity=0.0, max_intensity=65535.0, zarr_ct=(c, t))
        return ds

    run()  # warm compiles
    # single timed run, span-profiled
    dt, ds, spans, io = _best_timed(1, run)
    vox = int(np.prod(bbox.shape)) * n_ch * n_tp

    # baseline: the same numpy fusion per slot (cached)
    cache = _baseline_cache_load()
    key = _fixture_key(f"multitp-{MULTITP_SPEC}")
    ent = cache.get("multitp")
    if (ent and ent.get("key") == key and ent.get("vox_per_sec", 0) > 0
            and not _fresh_baselines()):
        base = float(ent["vox_per_sec"])
    else:
        grid = create_grid(bbox.shape, (64, 64, 32), (64, 64, 32))
        t0 = time.time()
        for t in range(n_tp):
            for c in range(n_ch):
                vws = _slot_views(sd, c, t)
                for block in grid:
                    bg = Interval.from_shape(block.size, block.offset
                                             ).translate(bbox.min)
                    _baseline_fuse_block(sd, loader, vws, bg)
        bdt = time.time() - t0
        base = vox / bdt
        cache["multitp"] = {
            "previous_vox_per_sec": (ent or {}).get("vox_per_sec"),
            "previous_key": (ent or {}).get("key"),
            "key": key, "vox_per_sec": round(base, 1), "voxels": vox,
            "seconds": round(bdt, 3),
            "method": ("reference-equivalent numpy fusion "
                       "(_baseline_fuse_block) over all 4 (channel,"
                       "timepoint) slots of the 5-D OME-ZARR config"),
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        _baseline_cache_store(cache)
    # sanity: every slot landed with data
    import numpy as _np
    for t in range(n_tp):
        for c in range(n_ch):
            blk = _np.asarray(ds.read((0, 0, 0, c, t), (32, 32, 32, 1, 1)))
            assert blk.std() > 0, f"slot c{c} t{t} empty"
    return {
        "metric": "multitp_omezarr_fusion_vox_per_sec",
        "value": round(vox / dt, 1),
        "unit": "voxel/s",
        "slots": n_ch * n_tp,
        "vs_baseline": round(vox / dt / base, 3),
        "baseline_vox_per_sec": round(base, 1),
        "spans": spans,
        "io": io,
    }


NONRIGID_SPEC = {
    "n_tiles": (2, 1, 1), "tile_size": (96, 96, 48), "overlap": 40,
    "jitter": 3.0, "seed": 13, "n_beads_per_tile": 40,
}


def _np_nonrigid_volume(sd, loader, views, unique, bbox, cpd=10.0):
    """Reference-equivalent CPU non-rigid fusion: per view, fit the
    control-point grid (shared host-side fit), then per voxel interpolate the
    12 model coefficients (scipy map_coordinates over the grid), deform the
    world coordinate, trilinear-sample the view, cosine-blend and average
    (NonRigidTools.fuseVirtualInterpolatedNonRigid role)."""
    import numpy as np
    from scipy.ndimage import map_coordinates

    from bigstitcher_spark_tpu.ops.nonrigid import fit_control_grid
    from bigstitcher_spark_tpu.utils.geometry import invert_affine

    shape = tuple(bbox.shape)
    origin = np.array(bbox.min, np.float64)
    gdims = tuple(int(np.ceil(shape[d] / cpd)) + 2 for d in range(3))
    gorigin = origin - cpd
    axes = np.meshgrid(*[np.arange(s, dtype=np.float64) for s in shape],
                       indexing="ij")
    world = np.stack([a + origin[d] for d, a in enumerate(axes)])  # (3,X,Y,Z)
    acc = np.zeros(shape, np.float64)
    wsum = np.zeros(shape, np.float64)
    for v in views:
        targets = unique.targets[v]
        vw = unique.view_world[v]
        grid = fit_control_grid(targets, vw, gorigin, gdims, cpd)  # (G...,12)
        gc = (world - gorigin[:, None, None, None]) / cpd
        coef = np.stack([
            map_coordinates(grid[..., k].astype(np.float64), gc, order=1,
                            mode="nearest")
            for k in range(12)
        ])  # (12,X,Y,Z)
        A = coef.reshape(3, 4, *shape)
        deformed = (np.einsum("ij...,j...->i...", A[:, :3], world)
                    + A[:, 3])
        inv = invert_affine(sd.model(v))
        local = (np.einsum("ij,j...->i...", inv[:, :3], deformed)
                 + inv[:, 3][:, None, None, None])
        img = loader.open(v, 0).read_full().astype(np.float64)
        val = map_coordinates(img, local, order=1, mode="constant", cval=0.0)
        dim = np.array(img.shape, np.float64)
        w = np.ones(shape)
        inside = np.ones(shape, bool)
        for d in range(3):
            dd = np.minimum(local[d], (dim[d] - 1.0) - local[d])
            ramp = 0.5 * (np.cos((1.0 - dd / 40.0) * np.pi) + 1.0)
            w = w * np.where(dd < 0, 0.0, np.where(dd < 40.0, ramp, 1.0))
            inside &= (local[d] >= 0) & (local[d] <= dim[d] - 1.0)
        w = w * inside
        acc += val * w
        wsum += w
    return np.where(wsum > 0, acc / np.maximum(wsum, 1e-20), 0.0)


def _nonrigid_setup():
    """Shared (memoized) staging for the nonrigid measures: synthesize the
    project, run detection + matching (untimed), build unique points."""
    if "nonrigid_setup" in _RUN_BASELINES:
        return _RUN_BASELINES["nonrigid_setup"]
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.interestpoints import InterestPointStore
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.detection import (
        DetectionParams, detect_interest_points, save_detections,
    )
    from bigstitcher_spark_tpu.models.matching import (
        MatchingParams, match_interest_points, save_matches,
    )
    from bigstitcher_spark_tpu.models.nonrigid_fusion import (
        build_unique_points,
    )
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project
    from bigstitcher_spark_tpu.utils.viewselect import maximal_bounding_box

    root = os.path.join(FIXTURE, "nonrigid")
    xml = os.path.join(root, "proj", "dataset.xml")
    if not os.path.exists(xml):
        make_synthetic_project(os.path.join(root, "proj"), **NONRIGID_SPEC)
    sd = SpimData.load(xml)
    loader = ViewLoader(sd)
    views = sorted(sd.registrations)
    store = InterestPointStore(os.path.join(root, "proj",
                                            "interestpoints.n5"))
    dets = detect_interest_points(
        sd, loader, views,
        DetectionParams(downsample_xy=1, downsample_z=1,
                        block_size=(96, 96, 48)),
        progress=False)
    save_detections(sd, store, dets, DetectionParams())
    mparams = MatchingParams(ransac_min_inliers=5, ransac_iterations=2000,
                             model="TRANSLATION", regularization="NONE")
    save_matches(sd, store,
                 match_interest_points(sd, views, mparams, store,
                                       progress=False),
                 mparams, views)
    unique = build_unique_points(sd, store, views, ["beads"])
    bbox = maximal_bounding_box(sd, views, None)
    _RUN_BASELINES["nonrigid_setup"] = (root, sd, loader, views, unique, bbox)
    return _RUN_BASELINES["nonrigid_setup"]


def measure_nonrigid():
    """Non-rigid fusion over the full volume (BASELINE.md config): detection
    + matching stage the correspondences (untimed), then time
    fuse_nonrigid_volume vs the numpy reference implementation."""
    import numpy as np

    from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat
    from bigstitcher_spark_tpu.models.nonrigid_fusion import (
        fuse_nonrigid_volume,
    )

    root, sd, loader, views, unique, bbox = _nonrigid_setup()
    out_path = os.path.join(root, "fused.n5")

    def run():
        shutil.rmtree(out_path, ignore_errors=True)
        cstore = ChunkStore.create(out_path, StorageFormat.N5)
        ds = cstore.create_dataset("fused", bbox.shape, (64, 64, 48),
                                   "float32")
        fuse_nonrigid_volume(
            sd, loader, views, unique, ds, bbox, block_size=(64, 64, 48),
            block_scale=(1, 1, 1), cpd=10.0, out_dtype="float32",
            min_intensity=0.0, max_intensity=1.0)
        return ds

    run()  # warm compiles
    # single timed run, span-profiled
    dt, ds, spans, io = _best_timed(1, run)
    vox = int(np.prod(bbox.shape))

    cache = _baseline_cache_load()
    key = _fixture_key(f"nonrigid-{NONRIGID_SPEC}")
    ent = cache.get("nonrigid")
    if (ent and ent.get("key") == key and ent.get("vox_per_sec", 0) > 0
            and not _fresh_baselines()):
        base = float(ent["vox_per_sec"])
    else:
        t0 = time.time()
        ref = _np_nonrigid_volume(sd, loader, views, unique, bbox)
        bdt = time.time() - t0
        base = vox / bdt
        # validate the XLA output against the independent implementation
        got = ds.read_full()
        diff = np.abs(got.astype(np.float64) - ref)
        assert float(np.median(diff)) < 0.02 * max(float(ref.max()), 1e-9), (
            f"nonrigid XLA disagrees with numpy baseline: "
            f"median|diff|={np.median(diff):.4f}")
        cache["nonrigid"] = {
            "previous_vox_per_sec": (ent or {}).get("vox_per_sec"),
            "previous_key": (ent or {}).get("key"),
            "key": key, "vox_per_sec": round(base, 1), "voxels": vox,
            "seconds": round(bdt, 3),
            "method": ("reference-equivalent numpy non-rigid fusion: shared "
                       "MLS control-grid fit, scipy map_coordinates "
                       "coefficient interpolation + deformation + trilinear "
                       "sampling + cosine blend (NonRigidTools role)"),
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        _baseline_cache_store(cache)
    _RUN_BASELINES["nonrigid"] = base
    return {
        "metric": "nonrigid_fusion_vox_per_sec",
        "value": round(vox / dt, 1),
        "unit": "voxel/s",
        "vs_baseline": round(vox / dt / base, 3),
        "baseline_vox_per_sec": round(base, 1),
        "spans": spans,
        "io": io,
    }


def measure_nonrigid_kernel():
    """Device-resident non-rigid fusion: the production batched kernel
    (models/nonrigid_fusion._make_nonrigid_kernel — separable control-grid
    coefficient interpolation, deformation, trilinear sampling, cosine
    blend, intensity conversion) timed with its staged block inputs
    already in HBM and the fused blocks left on device — the nonrigid
    counterpart of affine_fusion_kernel_voxels_per_sec (reference device
    work: NonRigidTools.fuseVirtualInterpolatedNonRigid, called at
    SparkNonRigidFusion.java:388-402). The CPU baseline computes in
    memory (no writes), so this is compute-vs-compute."""
    import numpy as np

    import jax

    from bigstitcher_spark_tpu.models import nonrigid_fusion as NF
    from bigstitcher_spark_tpu.utils.grid import create_grid

    root, sd, loader, views, unique, bbox = _nonrigid_setup()
    compute_block, cpd, alpha = (64, 64, 48), 10.0, 1.0
    gdims = tuple(int(np.ceil(compute_block[d] / cpd)) + 3 for d in range(3))
    aniso = NF.anisotropy_transform(float("nan"))
    blend = NF.BlendParams()
    planned = []
    for block in create_grid(bbox.shape, compute_block, compute_block):
        res = NF._plan_nonrigid_block(sd, views, unique, block, bbox,
                                      compute_block, gdims, cpd, alpha,
                                      aniso)
        if res is not None:
            planned.append((block, *res))
    # production signature bucketing; largest bucket carries the rate
    buckets: dict[tuple, list] = {}
    for item in planned:
        plans = item[3]
        vb = NF.F.bucket_views(len(plans))
        pshape = NF.F.bucket_shape(
            np.max([p[3].shape for p in plans], axis=0), 32)
        buckets.setdefault((pshape, vb), []).append(item)
    (pshape, vb), items = max(buckets.items(), key=lambda kv: len(kv[1]))
    kernel = NF._make_nonrigid_kernel(1, compute_block, "AVG_BLEND",
                                      "float32")
    stacked = []
    vox = 0
    for block, block_global, grid_origin, plans in items:
        arrs = NF._stage_nonrigid(loader, plans, pshape, vb, blend, gdims)
        stacked.append((*arrs, np.asarray(block_global.min, np.float32),
                        np.asarray(grid_origin, np.float32),
                        np.full(3, cpd, np.float32)))
        vox += int(np.prod(block.size))
    dev = tuple(jax.device_put(np.stack([s[k] for s in stacked]))
                for k in range(len(stacked[0])))
    mi, ma = np.float32(0.0), np.float32(1.0)
    _tiny_fetch(kernel(mi, ma, *dev))  # warm + force input residency
    per_rep = _kernel_rate(lambda: kernel(mi, ma, *dev), reps=10)
    base = _RUN_BASELINES.get("nonrigid")
    if base is None:  # standalone invocation: measure the numpy baseline
        t0 = time.time()
        _np_nonrigid_volume(sd, loader, views, unique, bbox)
        base = int(np.prod(bbox.shape)) / (time.time() - t0)
    value = vox / per_rep
    return {
        "metric": "nonrigid_kernel_voxels_per_sec",
        "value": round(value, 1),
        "unit": "voxel/s",
        "blocks": len(items),
        "vs_baseline": round(value / base, 3),
        "baseline_vox_per_sec": round(base, 1),
        "sync_methodology": _SYNC_METHODOLOGY,
        "note": ("staged block inputs in HBM, fused blocks left on device; "
                 "dispatch+compute of the production batched kernel over "
                 "the largest signature bucket; baseline is the in-memory "
                 "numpy nonrigid fusion (no writes either side)"),
    }


def measure_tune(xml_path):
    """The closed telemetry loop as a measured ratio: `bst tune run` over
    the built-in tiny-fusion workload (1 timed execution per config, hard
    cap 3) against a scratch history store. baseline/best is >= 1.0 by
    construction — a candidate must beat the incumbent by min-gain or the
    default configuration wins with an empty override set — so the value
    reports how much headroom the autotuner found on this host, never a
    regression."""
    from bigstitcher_spark_tpu import tune

    root = os.path.join(FIXTURE, "tune-bench")
    shutil.rmtree(root, ignore_errors=True)
    hist = os.path.join(root, "history")
    os.makedirs(hist, exist_ok=True)
    wl = tune.resolve_workload("tiny-fusion", os.path.join(root, "work"))
    res = tune.autotune(wl, force_knobs=("BST_WRITE_THREADS",),
                        trials_per_config=1, max_trials=3,
                        history_dir=hist)
    speedup = res.baseline_seconds / max(res.best_seconds, 1e-9)
    return {
        "metric": "tune_speedup_vs_default",
        "value": round(speedup, 3),
        "unit": "x",
        "baseline_s": round(res.baseline_seconds, 3),
        "best_s": round(res.best_seconds, 3),
        "trials": len(res.trials),
        "rules_fired": [d.rule for d in res.diagnoses],
        "best_overrides": res.best_overrides,
        "profile_key": res.profile_key,
        "note": ("bst tune run over the tiny-fusion workload, 1 timed "
                 "execution per config (cap 3); every trial is a "
                 "tune-trial history record in the scratch store"),
    }


_MULTIHOST_WORKER = """
import hashlib, json, os, sys, time
import numpy as np
from bigstitcher_spark_tpu.parallel.distributed import init_distributed, world
init_distributed()   # no-op for the 1-process leg
from bigstitcher_spark_tpu.dag.executor import run_pipeline
from bigstitcher_spark_tpu.io.chunkstore import ChunkStore
from bigstitcher_spark_tpu.parallel import pairsched

proj = sys.argv[1]
rank, pc = world()
xml = os.path.join(proj, "dataset.xml")
rexml = os.path.join(proj, "re.xml")
spec = {
    "name": "bench-mh",
    "datasets": {
        "resaved": {"path": os.path.join(proj, "resaved.n5"),
                    "ephemeral": True},
        "fused": {"path": os.path.join(proj, "fused.n5")},
    },
    "stages": [
        {"id": "resave", "tool": "resave",
         "args": ["-x", xml, "-xo", rexml, "-o", "@resaved", "--N5",
                  "--blockSize", "32,32,16", "-ds", "1,1,1"],
         "writes": ["resaved"]},
        {"id": "create", "tool": "create-fusion-container",
         "args": ["-x", rexml, "-o", "@fused", "-s", "N5", "-d", "UINT16",
                  "--minIntensity", "0", "--maxIntensity", "65535",
                  "--blockSize", "32,32,16"],
         "after": ["resave"], "ranks": [0]},
        {"id": "fuse", "tool": "affine-fusion", "args": ["-o", "@fused"],
         "after": ["create"], "reads": ["resaved"], "writes": ["fused"]},
    ],
}
t0 = time.time()
res = run_pipeline(spec, workdir=proj)
dt = time.time() - t0
d = res.to_dict()
assert res.ok, d
# a pair stage so the leg reports per-process scheduler utilization
tasks = [pairsched.PairTask(index=i, cost=float(1 + i % 4))
         for i in range(16)]
pairsched.run_pair_tasks(
    tasks, lambda t: (time.sleep(0.002), t.index)[1], stage="bench-mh")
util = pairsched.process_util_snapshot().get("bench-mh") or {}
ds = ChunkStore.open(os.path.join(proj, "fused.n5")).open_dataset("ch0tp0/s0")
arr = ds.read((0, 0, 0), ds.shape)
print("RESULT " + json.dumps({
    "rank": rank, "world": pc, "seconds": round(dt, 3),
    "xhost_bytes": int(d.get("bytes_xhost", 0)),
    "bytes_reread": int(d.get("bytes_reread", 0)),
    "pair_util_pct": util.get("util_pct"),
    "pair_busy_s": util.get("busy_s"),
    "s0_sha": hashlib.sha256(
        np.ascontiguousarray(arr).tobytes()).hexdigest(),
}), flush=True)
"""


def measure_multihost(runs: int = 3):
    """The multi-host execution world, measured: the same streamed
    resave -> create(rank 0) -> fuse pipeline on a tiny fixture as a
    1-process run vs a REAL 2-process jax.distributed CPU world
    (subprocess workers, gloo collectives, TCP block exchange), best of
    ``runs`` each. Reports the wall ratio, the cross-host bytes/re-read
    split of the 2-process leg, per-process pair-scheduler utilization,
    and asserts bitwise fused-output parity across ranks AND legs.

    Both legs pin JAX_PLATFORMS=cpu with 4 forced host devices — the
    extra measures the execution-world overhead (collectives, exchange,
    split), not the accelerator, and one process drives a TPU host
    anyway."""
    import socket as _socket

    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    root = os.path.join(FIXTURE, "multihost-bench")
    worker_py = os.path.join(FIXTURE, "multihost_worker.py")
    with open(worker_py, "w") as f:
        f.write(_MULTIHOST_WORKER)

    def free_port():
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    def mk_proj(path):
        shutil.rmtree(path, ignore_errors=True)
        make_synthetic_project(path, n_tiles=(2, 1, 1),
                               tile_size=(64, 64, 32), overlap=16,
                               jitter=1.0, n_beads_per_tile=20, seed=7)

    def base_env():
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                    "PYTHONPATH": REPO + os.pathsep
                    + env.get("PYTHONPATH", "")})
        for k in ("BST_COORDINATOR", "BST_NUM_PROCESSES", "BST_PROCESS_ID",
                  "BST_DAG_EXCHANGE_ADDR"):
            env.pop(k, None)
        return env

    def report(txt):
        lines = [ln for ln in txt.splitlines() if ln.startswith("RESULT ")]
        if not lines:
            raise RuntimeError(f"multihost worker printed no RESULT:\n"
                               f"{txt[-2000:]}")
        return json.loads(lines[-1][len("RESULT "):])

    def run_leg(world):
        proj = os.path.join(root, f"w{world}")
        mk_proj(proj)
        if world == 1:
            out = subprocess.run(
                [sys.executable, worker_py, proj], env=base_env(),
                capture_output=True, text=True, timeout=300, check=True)
            return [report(out.stdout)]
        coord = f"127.0.0.1:{free_port()}"
        xaddrs = f"127.0.0.1:{free_port()},127.0.0.1:{free_port()}"
        procs = []
        for r in range(world):
            env = base_env()
            env.update({"BST_COORDINATOR": coord,
                        "BST_NUM_PROCESSES": str(world),
                        "BST_PROCESS_ID": str(r),
                        "BST_DAG_EXCHANGE_ADDR": xaddrs})
            procs.append(subprocess.Popen(
                [sys.executable, worker_py, proj], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        reps = []
        for r, p in enumerate(procs):
            txt, _ = p.communicate(timeout=300)
            if p.returncode:
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                raise RuntimeError(f"multihost rank {r} exited "
                                   f"{p.returncode}:\n{txt[-2000:]}")
            reps.append(report(txt))
        return reps

    legs = {1: [], 2: []}
    for i in range(runs):
        for world in (1, 2):
            legs[world].append(run_leg(world))
            _log(f"multihost {world}p run {i + 1}/{runs}: "
                 f"{max(r['seconds'] for r in legs[world][-1]):.2f}s")

    # per-rep wall is the straggler rank (the legs barrier at dag-end)
    best1 = min(max(r["seconds"] for r in rep) for rep in legs[1])
    best2 = min(max(r["seconds"] for r in rep) for rep in legs[2])
    best2_rep = min(legs[2], key=lambda rep: max(r["seconds"] for r in rep))
    shas = {r["s0_sha"] for rep in legs[1] + legs[2] for r in rep}
    assert len(shas) == 1, f"fused output diverged across legs: {shas}"
    xhost = sum(r["xhost_bytes"] for r in best2_rep)
    assert xhost > 0, best2_rep
    assert all(r["bytes_reread"] == 0 for r in best2_rep), best2_rep
    return {
        "metric": "multihost_1p_over_2p",
        "value": round(best1 / max(best2, 1e-9), 3),
        "unit": "x",
        "seconds_1p": round(best1, 3),
        "seconds_2p": round(best2, 3),
        "best_of_runs": runs,
        "xhost_bytes_2p": int(xhost),
        "bytes_reread_2p": 0,
        "parity": "bitwise (fused s0 sha equal across ranks and legs)",
        "note": ("streamed resave->create->fuse on a tiny CPU fixture: "
                 "1 process vs a real 2-process jax.distributed world "
                 "with the TCP block exchange; >1x means the split beat "
                 "the exchange+collective overhead on this host, <1x "
                 "prices that overhead (the fixture is far below the "
                 "volumes the split targets)"),
        "io": {
            "pair_util_pct_by_process": {
                str(r["rank"]): r["pair_util_pct"] for r in best2_rep},
            "pair_busy_s_by_process": {
                str(r["rank"]): r["pair_busy_s"] for r in best2_rep},
        },
    }


def measure_cloud():
    """The tiered storage IO engine, measured: the same tiny resave->fuse
    workload against the in-repo S3-protocol fake with injected
    per-request latency (utils/s3_fake.py), three ways — cold synchronous
    reads (prefetch + disk tier + remote cache all off), async prefetch,
    and prefetch + NVMe spill tier under a deliberately undersized chunk
    LRU with a warm rerun. Reports the prefetch+tier speedup over
    cold-sync, the warm rerun's remote chunk-read bytes (must be zero:
    everything served from the memory LRU or the disk tier), and asserts
    bitwise output parity across all legs AND against the same fusion on
    a plain local root."""
    import hashlib

    import numpy as np
    from click.testing import CliRunner

    from bigstitcher_spark_tpu.cli.main import cli
    from bigstitcher_spark_tpu.io import chunkcache, prefetch, uris
    from bigstitcher_spark_tpu.io.chunkstore import (
        ChunkStore, bump_remote_pin,
    )
    from bigstitcher_spark_tpu.utils.s3_fake import S3FakeServer
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    root = os.path.join(FIXTURE, "cloud-bench")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root, exist_ok=True)
    proj = make_synthetic_project(
        os.path.join(root, "proj"), n_tiles=(2, 2, 1),
        tile_size=(96, 96, 48), overlap=24, jitter=0.0,
        n_beads_per_tile=15, seed=11)

    os.environ.setdefault("AWS_ACCESS_KEY_ID", "bench")
    os.environ.setdefault("AWS_SECRET_ACCESS_KEY", "benchsecret")
    srv = S3FakeServer().start()   # latency stays 0 through setup
    uris.set_s3_endpoint(srv.endpoint)
    uris.set_s3_region("us-east-1")
    runner = CliRunner()
    saved_env = {k: os.environ.get(k) for k in (
        "BST_PREFETCH_BYTES", "BST_PREFETCH_THREADS", "BST_REMOTE_CACHE",
        "BST_DISK_TIER_BYTES", "BST_DISK_TIER_DIR",
        "BST_CHUNK_CACHE_BYTES", "BST_TILE_CACHE_BYTES")}

    def set_env(**kv):
        for k, v in kv.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)

    def ok(args):
        r = runner.invoke(cli, args, catch_exceptions=False)
        assert r.exit_code == 0, r.output

    def fresh():
        """Every leg starts storage-cold: empty LRU + disk tier, a new
        remote coherence window, an idle prefetcher."""
        prefetch.drain(timeout_s=10)
        prefetch.reset()
        chunkcache.get_cache().clear()
        bump_remote_pin()

    def sha_of(uri, dataset):
        data = np.asarray(ChunkStore.open(uri).open_dataset(
            dataset).read_full())
        return hashlib.sha256(np.ascontiguousarray(data).tobytes()
                              ).hexdigest()

    def make_fused(uri, xml):
        # fused blocks are coarse on purpose: the cold wall should be
        # dominated by the many small SOURCE chunk reads the prefetcher
        # can hide, not by output puts
        ok(["create-fusion-container", "-x", xml, "-o", uri, "-s", "ZARR",
            "-d", "UINT16", "--blockSize", "48,48,48",
            "--minIntensity", "0", "--maxIntensity", "65535"])

    def fuse_leg(uri, env, cold=True):
        set_env(**env)
        if cold:
            fresh()
        iob = _io_baseline()
        t0 = time.time()
        ok(["affine-fusion", "-o", uri])
        dt = time.time() - t0
        io = _io_snapshot(iob)
        prefetch.drain(timeout_s=10)
        return dt, io

    try:
        # setup at zero latency: the source container on s3 AND on a
        # plain local root (the parity reference), one fused container
        # per leg
        xml_s3 = os.path.join(root, "resaved-s3.xml")
        xml_local = os.path.join(root, "resaved-local.xml")
        local_n5 = os.path.join(root, "src.n5")
        resave_args = ["--N5", "--blockSize", "16,16,16",
                       "-ds", "1,1,1; 2,2,1"]
        ok(["resave", "-x", proj.xml_path, "-xo", xml_s3,
            "-o", "s3://bench/src.n5", *resave_args])
        ok(["resave", "-x", proj.xml_path, "-xo", xml_local,
            "-o", local_n5, *resave_args])
        s0 = "setup0/timepoint0/s0"
        assert sha_of("s3://bench/src.n5", s0) == sha_of(local_n5, s0), (
            "resaved s0 over s3 differs from the local root")
        legs = {"cold_sync": "s3://bench/fused-cold.zarr",
                "prefetch": "s3://bench/fused-pf.zarr",
                "tier": "s3://bench/fused-tier.zarr"}
        for uri in legs.values():
            make_fused(uri, xml_s3)
        local_fused = os.path.join(root, "fused-local.zarr")
        make_fused(local_fused, xml_local)
        # HBM tile cache off in every leg: it would serve warm tiles
        # straight from device memory and mask the chunk-tier path under
        # measurement
        off = {"BST_PREFETCH_BYTES": 0, "BST_DISK_TIER_BYTES": 0,
               "BST_REMOTE_CACHE": "off", "BST_DISK_TIER_DIR": None,
               "BST_CHUNK_CACHE_BYTES": None,
               "BST_PREFETCH_THREADS": None, "BST_TILE_CACHE_BYTES": 0}
        dt_local, _ = fuse_leg(local_fused, off)

        srv.latency_s = 0.05   # ~one-datacenter-hop object-store RTT
        dt_cold, io_cold = fuse_leg(legs["cold_sync"], off)
        _log(f"cloud cold-sync {dt_cold:.2f}s (local {dt_local:.2f}s)")
        pf = {"BST_PREFETCH_BYTES": 256 << 20, "BST_PREFETCH_THREADS": 8,
              "BST_REMOTE_CACHE": "run", "BST_DISK_TIER_BYTES": 0,
              "BST_DISK_TIER_DIR": None, "BST_CHUNK_CACHE_BYTES": None,
              "BST_TILE_CACHE_BYTES": 0}
        dt_pf, io_pf = fuse_leg(legs["prefetch"], pf)
        _log(f"cloud prefetch {dt_pf:.2f}s")
        # the tier leg undersizes the memory LRU far below the source
        # working set, so prefetched chunks spill to (and warm reruns
        # promote from) the NVMe tier
        tier = dict(pf, BST_DISK_TIER_BYTES=256 << 20,
                    BST_DISK_TIER_DIR=os.path.join(root, "tier"),
                    BST_CHUNK_CACHE_BYTES=256 << 10)
        dt_tier, io_tier = fuse_leg(legs["tier"], tier)
        _log(f"cloud prefetch+tier cold {dt_tier:.2f}s")
        dt_warm, io_warm = fuse_leg(legs["tier"], tier, cold=False)
        _log(f"cloud prefetch+tier warm {dt_warm:.2f}s")
        warm_remote = int(io_warm.get("bst_io_remote_read_bytes_total", 0))
        assert warm_remote == 0, (
            f"warm rerun re-read {warm_remote} chunk bytes from the "
            f"remote store — the memory LRU + disk tier should have "
            f"served everything")

        srv.latency_s = 0.0    # parity readback untimed
        shas = {name: sha_of(uri, "0") for name, uri in legs.items()}
        shas["local"] = sha_of(local_fused, "0")
        assert len(set(shas.values())) == 1, (
            f"fused output diverged across legs: {shas}")
        return {
            "metric": "cloud_tiered_io_speedup",
            "value": round(dt_cold / max(dt_warm, 1e-9), 3),
            "unit": "x",
            "seconds_cold_sync": round(dt_cold, 3),
            "seconds_prefetch": round(dt_pf, 3),
            "seconds_tier_cold": round(dt_tier, 3),
            "seconds_tier_warm": round(dt_warm, 3),
            "seconds_local_root": round(dt_local, 3),
            "prefetch_speedup": round(dt_cold / max(dt_pf, 1e-9), 3),
            "tier_cold_speedup": round(dt_cold / max(dt_tier, 1e-9), 3),
            "warm_remote_read_bytes": warm_remote,
            "request_latency_s": 0.05,
            "parity": ("bitwise (fused sha equal across cold-sync, "
                       "prefetch, prefetch+tier and local-root legs; "
                       "resaved s0 equal s3 vs local)"),
            "note": ("tiny resave->fuse against the in-repo S3 fake with "
                     "50ms injected per-request latency: synchronous "
                     "per-block reads vs the byte-budgeted async "
                     "prefetcher vs prefetch + NVMe spill tier under an "
                     "undersized chunk LRU; the headline ratio is the "
                     "tier leg's warm rerun, which serves every source "
                     "chunk from the memory LRU + disk tier without "
                     "touching the remote store"),
            "io": {"cold_sync": io_cold, "prefetch": io_pf,
                   "tier_cold": io_tier, "tier_warm": io_warm},
        }
    finally:
        srv.latency_s = 0.0
        set_env(**saved_env)
        try:
            prefetch.reset()
            chunkcache.get_cache().clear()
        except Exception:
            pass
        uris.set_s3_endpoint(None)
        uris.set_s3_region(None)
        srv.stop()


def _log(msg):
    print(f"[bench:{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _validate_fusion(xml, ds):
    """The XLA output must agree with the baseline implementation
    (same math, independent code path) on the first block."""
    import numpy as np

    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.utils.geometry import Interval
    from bigstitcher_spark_tpu.utils.viewselect import maximal_bounding_box

    sd = SpimData.load(xml)
    loader = ViewLoader(sd)
    bbox = maximal_bounding_box(sd, sd.view_ids())
    blk = (128, 128, 64)
    ref_blk = _baseline_fuse_block(
        sd, loader, sd.view_ids(), Interval.from_shape(blk).translate(bbox.min))
    got_blk = np.asarray(ds.read((0, 0, 0, 0, 0), (*blk, 1, 1)))[..., 0, 0]
    diff = np.abs(got_blk.astype(np.float64) - ref_blk.astype(np.float64))
    assert float(diff.mean()) < 1.0 and float(got_blk.std()) > 0.0, (
        f"XLA fusion disagrees with baseline: mean|diff|={diff.mean():.3f}")


def _primary_result(vox_per_sec, baseline, device, spans, io=None):
    return {
        "metric": "affine_fusion_voxels_per_sec",
        "value": round(vox_per_sec, 1),
        "unit": "voxel/s",
        "vs_baseline": round(vox_per_sec / baseline, 3),
        **device,
        "baseline_vox_per_sec": round(baseline, 1),
        "baseline_provenance": (
            "measured in this run (same host, same process weather); "
            "history in BASELINE_MEASURED.json"),
        "best_of_runs": FUSION_RUNS,
        "spans": spans,
        "io": io or {},
        "extra_metrics": [],
    }


def _baseline_drift_flags():
    """Same-fixture baselines that moved >1.4x against their previous
    measurement (beyond the 20-30% host drift _fresh_baselines documents).
    vs_baseline always divides by the SAME-RUN baseline, so each artifact
    is internally consistent — but a flagged entry warns that cross-run
    comparisons of that config ride very different host weather."""
    flags = {}
    for name, ent in _baseline_cache_load().items():
        if not isinstance(ent, dict):
            continue
        if ent.get("previous_key") != ent.get("key"):
            continue  # different fixture config, not host weather
        for k, prev in ent.items():
            if (k.startswith("previous_") and isinstance(prev, (int, float))
                    and prev):
                cur = ent.get(k[len("previous_"):])
                if (isinstance(cur, (int, float)) and cur
                        and max(cur / prev, prev / cur) > 1.4):
                    flags[name] = {"previous": prev, "current": cur,
                                   "ratio": round(cur / prev, 3)}
    return flags


def _finalize_telemetry(result):
    """BST_TELEMETRY_DIR / BST_TRACE runs also leave a manifest, a metrics
    textfile and the rendered trace report; the d2h<->write overlap is
    lifted into the artifact's io columns."""
    from bigstitcher_spark_tpu import observe
    from bigstitcher_spark_tpu.observe import trace

    observe.finalize(tool="bench",
                     params={"platform": result.get("platform")})
    # BST_TRACE without a telemetry dir: flush the ring ourselves (with
    # one, observe.finalize archived it next to the manifest)
    tp = trace.finalize(dir_hint=_cfg.get_str("BST_TELEMETRY_DIR"))
    if not tp:
        return
    _log(f"trace -> {tp}")
    from bigstitcher_spark_tpu.analysis import tracereport

    evs, tmeta = tracereport.load_events(tp)
    rep = tracereport.build_report(evs, tmeta)
    rpt = os.path.join(os.path.dirname(tp), "trace-report.txt")
    with open(rpt, "w", encoding="utf-8") as f:
        f.write(tracereport.render_report(rep) + "\n")
    _log(f"trace report -> {rpt}")
    ov = (rep.get("stages", {}).get("fusion", {})
          .get("overlap", {}).get("d2h_write"))
    if ov:
        io_cols = result.setdefault("io", {})
        io_cols["trace_d2h_write_overlap_s"] = ov.get("seconds")
        io_cols["trace_d2h_write_overlap_pct_of_d2h"] = \
            ov.get("pct_of_d2h")
        io_cols["trace_d2h_write_overlap_pct_of_write"] = \
            ov.get("pct_of_write")


EXTRA_MEASURES = (
    ("kernel", lambda xml: measure_kernel_only(xml)),
    ("fusion_pyramid", lambda xml: measure_fusion_pyramid(xml)),
    ("pipeline", lambda xml: measure_pipeline(xml)),
    ("solver", lambda xml: measure_solver(xml)),
    ("submit_latency", lambda xml: measure_submit_latency(xml)),
    ("phasecorr", lambda xml: measure_phasecorr(xml)),
    ("phasecorr_kernel", lambda xml: measure_phasecorr_kernel(xml)),
    ("dog", lambda xml: measure_dog(xml)),
    ("dog_kernel", lambda xml: measure_dog_kernel(xml)),
    ("multitp", lambda xml: measure_multitp()),
    ("nonrigid", lambda xml: measure_nonrigid()),
    ("nonrigid_kernel", lambda xml: measure_nonrigid_kernel()),
    ("tune", lambda xml: measure_tune(xml)),
    ("multihost", lambda xml: measure_multihost()),
    ("cloud", lambda xml: measure_cloud()),
)


def main():
    import jax

    from bigstitcher_spark_tpu import profiling

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices())}
    if (dev.platform != "tpu"
            and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu"):
        _log(f"no TPU: jax found {device}. bench.py measures device rates; "
             "for a correctness/byte-count run on XLA:CPU say so with "
             "JAX_PLATFORMS=cpu")
        return 1
    _log(f"device {device}")
    if _cfg.get_str("BST_TELEMETRY_DIR"):
        from bigstitcher_spark_tpu import observe

        # same registry/event/manifest path as `bst ... --telemetry-dir`;
        # profiling stays under the bench's own enable/reset control
        observe.configure(_cfg.get_str("BST_TELEMETRY_DIR"), profile=False)
    if _cfg.get_bool("BST_TRACE"):
        from bigstitcher_spark_tpu.observe import trace

        # observe.finalize() archives the ring next to the run manifest
        # when BST_TELEMETRY_DIR is set; else it lands at BST_TRACE_PATH
        trace.configure()
    xml = build_fixture()
    _log("fixture ready")
    out = os.path.join(FIXTURE, "fused.ome.zarr")
    baseline = measure_baseline(xml)
    _RUN_BASELINES["fusion"] = baseline  # reused by measure_fusion_pyramid
    _log(f"baseline {baseline:.0f} vox/s")
    run_fusion(xml, out)  # warm-up: compiles all kernel variants
    _log("warmup fusion done")
    best_v = 0.0
    best_spans = {}
    best_io = {}
    try:
        for i in range(FUSION_RUNS):
            profiling.enable(True)
            profiling.get().reset()
            iob = _io_baseline()
            stats, ds, bbox = run_fusion(xml, out)
            v = stats.voxels / max(stats.seconds, 1e-9)
            _log(f"fusion run {i + 1}/{FUSION_RUNS}: {v:,.0f} vox/s "
                 f"({stats.seconds:.2f}s)")
            if v > best_v:
                best_v, best_spans = v, _spans_snapshot()
                best_io = _io_snapshot(iob)
            profiling.enable(False)
            if i == 0:
                _validate_fusion(xml, ds)
                _log("validation ok")
    finally:
        profiling.enable(False)
    result = _primary_result(best_v, baseline, device, best_spans,
                             io=best_io)
    failed = []
    for name, fn in EXTRA_MEASURES:
        try:
            m = fn(xml)
        except Exception as e:  # the other measures still report;
            # the run's exit code carries the failure
            _log(f"{name} failed: {e!r}")
            m = {"metric": name, "error": repr(e)[:200]}
            failed.append(name)
        result["extra_metrics"].append(m)
        _log(f"{name}: {json.dumps(m)[:160]}")
    _finalize_telemetry(result)
    drift = _baseline_drift_flags()
    if drift:
        result["baseline_drift_flags"] = drift
    print(json.dumps(result))
    if failed:
        _log(f"FAILED measures: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
