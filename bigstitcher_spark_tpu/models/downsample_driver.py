"""Pyramid-level writer: block-parallel 2x downsampling of an existing level
(SparkAffineFusion.java:703-782 and SparkDownsample.java:141-177 equivalent).

The block grid is the work list (strategy P1); blocks batch over the device
mesh via run_sharded_batches — the TPU replacement of the reference's
per-level Spark map (SparkDownsample.java:141-177), with double-buffered
host IO on either side of the kernel.
"""

from __future__ import annotations

import numpy as np

from ..utils.threads import CtxThreadPool

from ..io.chunkstore import ChunkStore, Dataset, StorageFormat
from ..io.container import MultiResolutionLevelInfo
from ..ops.downsample import downsample_block
from ..parallel.mesh import make_mesh, run_sharded_batches, shard_jit
from ..utils.grid import GridBlock, create_grid


def read_padded(src_read, src_shape, src_off, src_size) -> "np.ndarray":
    """Read ``src_size`` voxels at ``src_off``, edge-replicating past the
    source extent (thin axes whose level dim was clamped to 1).
    ``src_read(off, size)`` is the raw reader."""
    clamped = [min(int(s), int(e) - int(o)) for s, e, o in
               zip(src_size, src_shape, src_off)]
    data = src_read([int(o) for o in src_off], clamped)
    if clamped != [int(s) for s in src_size]:
        pad = [(0, int(s) - c) for s, c in zip(src_size, clamped)]
        if isinstance(data, np.ndarray):
            data = np.pad(data, pad, mode="edge")
        else:
            # device array (a streaming handoff read): pad on device, the
            # bytes must not round-trip through the host here
            import jax.numpy as jnp

            data = jnp.pad(data, pad, mode="edge")
    return data


def downsample_read(src_read, src_shape, src_off, src_size, factors) -> "np.ndarray":
    """read_padded + average-downsample by ``factors``."""
    import jax

    data = read_padded(src_read, src_shape, src_off, src_size)
    return jax.device_get(
        downsample_block(data, tuple(int(f) for f in factors)))


def _convert_to_dtype(out: np.ndarray, dtype) -> np.ndarray:
    if np.issubdtype(np.dtype(dtype), np.integer):
        info = np.iinfo(np.dtype(dtype))
        out = np.clip(np.round(out), info.min, info.max)
    return out.astype(dtype)


def downsample_write_block(src: Dataset, dst: Dataset, block: GridBlock,
                           factors, src_read=None, src_shape=None,
                           dst_write=None) -> None:
    """The shared per-block downsample step: read factor-scaled source box,
    average, clip/round for integer outputs, write (used by the fusion
    pyramid, resave pyramid, and the standalone downsample tool).
    ``src_read``/``src_shape``/``dst_write`` override the raw 3-D accessors
    (the 5-D OME-ZARR path supplies channel/timepoint-sliced wrappers)."""
    src_off = [o * f for o, f in zip(block.offset, factors)]
    src_size = [s * f for s, f in zip(block.size, factors)]
    out = downsample_read(src_read or src.read,
                          src_shape or src.shape, src_off, src_size, factors)
    (dst_write or dst.write)(_convert_to_dtype(out, dst.dtype), block.offset)


def make_downsample_kernel(n_dev: int, rel):
    """Batched average-downsample kernel; batch axis sharded when n_dev > 1."""
    return _make_downsample_kernel_cached(n_dev, tuple(int(f) for f in rel))


import functools


@functools.lru_cache(maxsize=32)
def _make_downsample_kernel_cached(n_dev: int, rel_t):
    """lru_cache'd: pyramid writers call this once per level — without the
    cache each level recompiled the same program."""
    import jax

    def batched(raws):
        return jax.vmap(lambda x: downsample_block(x, rel_t))(raws)

    if n_dev <= 1:
        return jax.jit(batched)
    return shard_jit(batched, make_mesh(n_dev), n_in=1)


def prefetch_src_box(ds, src_off, src_size):
    """``(ds, clipped offset, clipped shape)`` of a padded source-box read
    — what the async prefetcher feeds (io/prefetch.py) hand to
    ``Dataset.prefetch_box``. None when the clip is empty or ``ds`` is
    not a chunkstore dataset."""
    if not hasattr(ds, "prefetch_box"):
        return None
    dims = ds.shape
    lo = [max(0, int(o)) for o in src_off]
    hi = [min(int(d), int(o) + int(s))
          for d, o, s in zip(dims, src_off, src_size)]
    if any(h <= l for l, h in zip(lo, hi)):
        return None
    return ds, tuple(lo), tuple(h - l for l, h in zip(lo, hi))


def run_sharded_downsample(jobs, read_job, write_job, rel, devices=None,
                           io_threads: int = 8, per_dev: int = 4,
                           label: str = "downsample block",
                           multihost: bool = True,
                           device_drain: bool = False,
                           prefetch_job=None) -> None:
    """Downsample every (job, src-box) through the mesh. ``read_job(job)``
    returns the raw source box (size = out_block * rel, edge-padded);
    ``write_job(job, data)`` converts + writes. Jobs are bucketed by source
    shape so one compile serves each shape. ``device_drain`` routes each
    device's output shard through its own drain+write worker
    (parallel.mesh) — only safe for parallel-writer stores, never h5py.
    ``prefetch_job(job) -> [(ds, off, shape), ...]`` names the source
    boxes for the async prefetcher feed (parallel.mesh ``prefetch_boxes``;
    advisory, inert while the prefetcher is off)."""
    import jax

    n_dev = devices if devices is not None else len(jax.local_devices())
    kernel = make_downsample_kernel(n_dev, rel)
    buckets: dict[tuple, list] = {}
    for job in jobs:
        buckets.setdefault(tuple(read_shape(job, rel)), []).append(job)
    def build(job):
        # ship the source box in its stored dtype — downsample_block casts
        # to float32 ON DEVICE, so the host astype only doubled wire bytes
        # (big-endian HDF5 blocks byteswap on host: JAX rejects them)
        raw = read_job(job)
        if raw.dtype.kind in "iu" and raw.dtype.itemsize < 4:
            if raw.dtype.byteorder == ">":
                raw = raw.astype(raw.dtype.newbyteorder("="))
            return (raw,)
        return (raw.astype(np.float32),)

    pool = CtxThreadPool(max_workers=max(1, io_threads))
    try:
        for shp, items in sorted(buckets.items()):
            out_vox = int(np.prod([s // int(f) for s, f in zip(shp, rel)]))
            run_sharded_batches(
                items, build, kernel, write_job,
                n_dev, pool, label=label, per_dev=per_dev,
                multihost=multihost,
                out_bytes_per_item=out_vox * 4,  # f32 device output
                workspace_mult=3.0,              # f32 cast of the input
                device_drain=device_drain,
                prefetch_boxes=prefetch_job,
            )
    finally:
        pool.shutdown(wait=True)


def read_shape(job, rel):
    """Source-box shape of a (block,) job: out block size * relative factor."""
    block = job if isinstance(job, GridBlock) else job[1]
    return [int(s) * int(f) for s, f in zip(block.size, rel)]


def validate_pyramid(absolute: list[list[int]]) -> None:
    """Each absolute factor must be an exact multiple of the previous one,
    starting at 1,1,1 — otherwise relative steps floor-divide and levels
    would be silently corrupt."""
    if list(absolute[0]) != [1, 1, 1]:
        raise ValueError(f"pyramid must start with 1,1,1, got {absolute[0]}")
    for prev, cur in zip(absolute, absolute[1:]):
        if any(int(c) % int(p) != 0 for p, c in zip(prev, cur)):
            raise ValueError(
                f"pyramid step {cur} is not an exact multiple of {prev}"
            )


def downsample_pyramid_level(
    store: ChunkStore,
    src_info: MultiResolutionLevelInfo,
    dst_info: MultiResolutionLevelInfo,
    is_zarr5d: bool = False,
    ct: tuple[int, int] = (0, 0),
    devices: int | None = None,
    io_threads: int = 8,
    skip_existing: bool = False,
) -> None:
    """Fill ``dst_info`` from ``src_info`` by relative-factor averaging,
    block-sharded over the device mesh (SparkDownsample.java:141-177).

    ``skip_existing``: return immediately when the fusion drivers already
    materialized this level for this (channel, timepoint) slot as a fused
    multiscale epilogue (the container records that per level; epilogue
    output is bit-identical to this path, so there is nothing to redo —
    and crucially no full-res container re-read)."""
    import time

    from .. import observe
    from ..io.container import epilogue_written

    if skip_existing and epilogue_written(store, dst_info.dataset, ct):
        observe.progress.record_stage(
            f"downsample {dst_info.dataset.strip('/')}",
            done=0, total=0, blocks=0, seconds=0.0,
            skipped="fusion epilogue already materialized this level",
        )
        return

    t0 = time.time()
    src = store.open_dataset(src_info.dataset.strip("/"))
    dst = store.open_dataset(dst_info.dataset.strip("/"))
    rel = [int(v) for v in dst_info.relativeDownsampling[:3]]
    dims3 = [int(v) for v in dst_info.dimensions[:3]]
    block3 = [int(v) for v in dst_info.blockSize[:3]]
    grid = create_grid(dims3, block3)

    if is_zarr5d:
        c, t = ct

        def read3d(off, size):
            return src.read((*off, c, t), (*size, 1, 1))[..., 0, 0]

        def write3d(data, off):
            dst.write(data[..., None, None], (*off, c, t))

        src_shape = src.shape[:3]
    else:
        def read3d(off, size):
            # a streamed producer's device-resident blocks serve straight
            # from HBM (zero D2H + zero container decode); None falls back
            # to the gated host read
            dev = src.read_device(off, size)
            return dev if dev is not None else src.read(off, size)

        write3d, src_shape = dst.write, src.shape

    def read_job(block: GridBlock):
        src_off = [o * f for o, f in zip(block.offset, rel)]
        src_size = [s * f for s, f in zip(block.size, rel)]
        return read_padded(read3d, src_shape, src_off, src_size)

    def write_job(block: GridBlock, out):
        write3d(_convert_to_dtype(out, dst.dtype), block.offset)

    def prefetch_job(block: GridBlock):
        src_off = [o * f for o, f in zip(block.offset, rel)]
        src_size = [s * f for s, f in zip(block.size, rel)]
        if is_zarr5d:
            c, t = ct
            b = prefetch_src_box(src, (*src_off, c, t), (*src_size, 1, 1))
        else:
            b = prefetch_src_box(src, src_off, src_size)
        return [b] if b is not None else []

    run_sharded_downsample(grid, read_job, write_job, rel, devices=devices,
                           io_threads=io_threads,
                           # per-device direct chunk writes wherever the
                           # store allows concurrent writers
                           device_drain=getattr(store, "format", None)
                           != StorageFormat.HDF5,
                           prefetch_job=prefetch_job)
    dt = time.time() - t0
    observe.progress.record_stage(
        f"downsample {dst_info.dataset.strip('/')}",
        done=len(grid), blocks=len(grid), seconds=round(dt, 3),
        rate_per_s=round(len(grid) / max(dt, 1e-9), 3),
    )


def write_pyramid(store, mr_levels, is_zarr5d, ct, epilogue_levels=0):
    """Downsample s0 into the remaining pyramid levels
    (SparkAffineFusion.java:703-782). Each level reads chunks the previous
    stage may have written on another host -> barrier per boundary.

    ``epilogue_levels``: how many leading levels the fusion drivers already
    materialized as a fused multiscale epilogue this run. Their container
    markers are set (and stale ones from earlier runs revoked) before the
    barrier, then ``downsample_pyramid_level(skip_existing=True)`` skips
    exactly those — no full-res container re-read for levels that rode the
    fusion drain."""
    from ..io.container import set_epilogue_written
    from ..parallel.distributed import barrier, world

    if world()[0] == 0:  # one writer for the shared container attributes
        for lvl in range(1, len(mr_levels)):
            set_epilogue_written(store, mr_levels[lvl].dataset, ct,
                                 lvl <= epilogue_levels)
    barrier("fusion-s0")
    for lvl in range(1, len(mr_levels)):
        downsample_pyramid_level(store, mr_levels[lvl - 1], mr_levels[lvl],
                                 is_zarr5d, ct, skip_existing=True)
        barrier(f"fusion-s{lvl}")
