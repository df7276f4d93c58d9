"""Non-rigid fusion driver: unique interest points, per-block control grids,
deformation kernel, block writes.

TPU redesign of SparkNonRigidFusion (reference call stack SURVEY.md §3.3/§2.1:
SparkNonRigidFusion.java:313-435): per output block, the views to fuse are
those overlapping the block (+50 px margin) and the deformation of each view
comes from corresponding interest points near the block (+25 px margin),
merged into "unique points" (the average world position of each
correspondence group) — each view's control grid maps the averaged position
back to the view's own world frame, so all views agree at the control points.
"""

from __future__ import annotations

import functools
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass

import numpy as np

from ..io.chunkstore import Dataset
from ..io.dataset_io import ViewLoader
from ..io.interestpoints import InterestPointStore
from ..io.spimdata import SpimData, ViewId
from ..ops import fusion as F
from ..ops.nonrigid import fit_control_grid
from ..utils.geometry import (
    Interval,
    apply_affine,
    concatenate,
    invert_affine,
    transformed_interval,
    translation_affine,
)
from ..utils.grid import GridBlock, create_grid
from ..utils.threads import CtxThreadPool
from .. import profiling
from ..observe import metrics as _metrics
from .affine_fusion import (
    BlendParams, FusionStats, _record_fusion_stage, anisotropy_transform,
    patch_dtype,
)

_VOXELS_DONE = _metrics.counter("bst_fusion_voxels_total")
_BLOCKS_DONE = _metrics.counter("bst_fusion_blocks_total", kernel="nonrigid")
_CONTROL_POINTS = _metrics.counter("bst_nonrigid_control_points_total")
_FIT_SECONDS = _metrics.counter("bst_nonrigid_fit_seconds_total")

FUSE_MARGIN = 50.0   # px margin for view selection (SparkNonRigidFusion.java:326-371)
IP_MARGIN = 25.0     # px margin for deformation-defining points


@dataclass
class UniquePoints:
    """Per-view correspondence-averaged control points."""

    targets: dict[ViewId, np.ndarray]      # (M,3) averaged world positions
    view_world: dict[ViewId, np.ndarray]   # (M,3) the view's own world position


@profiling.span("nonrigid.unique_points")
def build_unique_points(
    sd: SpimData,
    store: InterestPointStore,
    views: list[ViewId],
    labels: list[str],
) -> UniquePoints:
    """Union-find over correspondences -> groups; target = mean world position
    of the group (NonRigidTools 'unique interest points'). Points are nodes
    in the order the correspondences name them, groups in the order of
    their first node, and a group's positions are summed in its nodes'
    order — arrays throughout: thousands of points a view."""
    vset = set(views)
    slots: dict[tuple[ViewId, str], int] = {}
    slot_view: list[ViewId] = []
    sorted_ids: list[np.ndarray] = []   # a slot's ids, ascending
    row_of: list[np.ndarray] = []       # the row each of them is stored at
    world: list[np.ndarray] = []
    first_node: list[int] = []

    def slot(view: ViewId, label: str) -> int:
        k = (view, label)
        if k not in slots:
            ids, locs = store.load_points(view, label)
            order = np.argsort(ids, kind="stable")
            slots[k] = len(slot_view)
            slot_view.append(view)
            sorted_ids.append(ids[order])
            row_of.append(order)
            world.append(apply_affine(sd.model(view), locs) if len(locs)
                         else locs)
            first_node.append(sum(len(w) for w in world[:-1]))
        return slots[k]

    def nodes(s: int, ids: np.ndarray) -> np.ndarray:
        """The node of each id in slot ``s`` (an id stored twice is its
        last row), -1 where the slot has no such point."""
        at = np.searchsorted(sorted_ids[s], ids, side="right") - 1
        found = (at >= 0) & (sorted_ids[s][np.maximum(at, 0)] == ids)
        return np.where(found, first_node[s] + row_of[s][np.maximum(at, 0)],
                        -1)

    edges = []
    for v in views:
        for label in labels:
            if label not in sd.interest_points.get(v, {}):
                continue
            mine = slot(v, label)
            rows, decode = store.load_correspondence_rows(v, label)
            b = np.full(len(rows), -1, np.int64)
            for code, (other_view, other_label) in decode.items():
                if other_view in vset:
                    of = rows[:, 2] == code
                    b[of] = nodes(slot(other_view, other_label), rows[of, 1])
            a = nodes(mine, rows[:, 0])
            keep = (a >= 0) & (b >= 0)
            edges.append(np.stack([a[keep], b[keep]], axis=1))
    edges = (np.concatenate(edges) if edges else np.zeros((0, 2), np.int64))
    if not len(edges):
        none = {v: np.zeros((0, 3)) for v in views}
        return UniquePoints(dict(none), dict(none))

    # keys: the nodes some correspondence names, in the order they do
    named, first = np.unique(edges.ravel(), return_index=True)
    keys = named[np.argsort(first, kind="stable")]
    key_of = np.full(int(named.max()) + 1, -1, np.int64)
    key_of[keys] = np.arange(len(keys))
    parent = list(range(len(keys)))

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    for ia, ib in key_of[edges].tolist():
        ra, rb = find(ia), find(ib)
        if ra != rb:
            parent[ra] = rb
    roots = np.array([find(i) for i in range(len(keys))])
    # groups in the order of their first key, a group's keys ascending
    _, first, group = np.unique(roots, return_index=True, return_inverse=True)
    group = np.argsort(np.argsort(first, kind="stable"))[group]
    by_group = np.argsort(group, kind="stable")
    sizes = np.bincount(group)
    rank = np.arange(len(keys)) - np.repeat(np.cumsum(sizes) - sizes, sizes)

    starts = np.array(first_node + [first_node[-1] + len(world[-1])])
    key_slot = np.searchsorted(starts, keys, side="right") - 1
    pos = np.concatenate(world)[keys]
    # a group's mean as numpy's own of its rows: summed one after another
    total = np.zeros((len(sizes), 3))
    for j in range(int(sizes.max())):
        nth = by_group[rank == j]
        total[group[nth]] += pos[nth]
    target = total / sizes[:, None]

    key_view = np.array([views.index(slot_view[s]) for s in range(
        len(slot_view))])[key_slot][by_group]
    targets, view_world = {}, {}
    for i, v in enumerate(views):
        of = by_group[key_view == i]
        targets[v] = target[group[of]]
        view_world[v] = pos[of]
    return UniquePoints(targets, view_world)


def fuse_nonrigid_project(
    store,
    meta,
    sd: SpimData,
    views: list[ViewId],
    labels: list[str],
    cpd: float,
    alpha: float,
    fusion_type: str,
    blend: BlendParams,
    block_scale: tuple[int, ...],
    channel_index: int | None = None,
    timepoint_index: int | None = None,
    dry_run: bool = False,
    devices: int | None = None,
    log=lambda message: None,
) -> int:
    """What ``bst nonrigid-fusion`` does with an opened fusion container
    (``store``, ``meta``): for every channel and timepoint of ``views`` the
    views to fuse, the unique points of all the timepoint's views (the
    deformation may use points of every channel), the level-0 dataset,
    :func:`fuse_nonrigid_volume`, then the pyramid's other levels. The one
    entry of the stage: the command calls it and so does the benchmark's
    adapter. Returns the voxels written."""
    from .downsample_driver import write_pyramid

    loader = ViewLoader(sd)
    ip_store = InterestPointStore.for_project(sd)
    is_zarr5d = meta.fusion_format in ("OME-ZARR", "BDV/OME-ZARR")
    channels = sorted({sd.setups[v.setup].attributes.get("channel", 0)
                       for v in views})
    tps = sorted({v.timepoint for v in views})
    c_indices = ([channel_index] if channel_index is not None
                 else list(range(len(channels))))
    t_indices = ([timepoint_index] if timepoint_index is not None
                 else list(range(len(tps))))

    total_vox = 0
    for ti in t_indices:
        t = tps[ti]
        for ci in c_indices:
            c = channels[ci]
            fused = [
                v for v in views
                if v.timepoint == t
                and sd.setups[v.setup].attributes.get("channel", 0) == c
            ]
            if not fused:
                continue
            # deformation may use IPs of ALL views of this timepoint
            # (corresponding views need not be restricted to the channel)
            ip_views = [v for v in views if v.timepoint == t]
            unique = build_unique_points(sd, ip_store, ip_views, list(labels))
            mr = meta.mr_infos[ci + ti * meta.num_channels]
            ds = store.open_dataset(mr[0].dataset.strip("/"))
            log(f"nonrigid fusing channel {c} timepoint {t}: "
                f"{len(fused)} views -> {mr[0].dataset}")
            if dry_run:
                continue
            stats = fuse_nonrigid_volume(
                sd, loader, fused, unique, ds, meta.bbox,
                block_size=tuple(meta.block_size),
                block_scale=tuple(block_scale),
                cpd=cpd, alpha=alpha,
                fusion_type=fusion_type, blend=blend,
                anisotropy_factor=(meta.anisotropy_factor
                                   if meta.preserve_anisotropy
                                   else float("nan")),
                out_dtype=meta.data_type,
                min_intensity=meta.min_intensity,
                max_intensity=meta.max_intensity,
                zarr_ct=(ci, ti) if is_zarr5d else None,
                devices=devices,
            )
            total_vox += stats.voxels
            log(f"  {stats.voxels} voxels in {stats.seconds:.2f}s")
            if len(mr) > 1:
                write_pyramid(store, mr, is_zarr5d, (ci, ti))
    return total_vox


@profiling.span("nonrigid.stage")
def fuse_nonrigid_volume(
    sd: SpimData,
    loader: ViewLoader,
    views: list[ViewId],
    unique: UniquePoints,
    out_ds: Dataset,
    bbox: Interval,
    block_size: tuple[int, ...],
    block_scale: tuple[int, ...] = (2, 2, 1),
    cpd: float = 10.0,
    alpha: float = 1.0,
    fusion_type: str = "AVG_BLEND",
    blend: BlendParams | None = None,
    anisotropy_factor: float = float("nan"),
    out_dtype: str = "float32",
    min_intensity: float | None = None,
    max_intensity: float | None = None,
    zarr_ct: tuple[int, int] | None = None,
    progress: bool = False,
    devices: int | None = None,
    io_threads: int = 4,
) -> FusionStats:
    """Fuse ``views`` non-rigidly into ``out_ds`` over ``bbox``, block-sharded
    over the local device mesh (``devices`` defaults to all).

    The work list is the blocks some view reaches, which the affines alone
    say. A block's control grids are fitted when its batch is built, one
    batch ahead of the device: the fits of batch k+1 run on the host while
    batch k's kernel runs, so the device waits for fits once, at the first
    batch, and there the views' fits run side by side. A batch is ``n_dev``
    consecutive blocks, staged at the largest patch shape and view count
    among them (the kernel is keyed by neither: jit specialises on the
    shapes it is given) — the reference's per-block Spark foreach
    (SparkNonRigidFusion.java:313-435)."""
    import jax

    from ..parallel.distributed import partition_items
    from ..parallel.mesh import run_sharded_batches

    stats = FusionStats()
    t0 = time.time()
    blend = blend or BlendParams()
    aniso = anisotropy_transform(anisotropy_factor)
    compute_block = tuple(b * s for b, s in zip(block_size, block_scale))
    grid_blocks = create_grid(bbox.shape, compute_block, block_size)
    if min_intensity is None or max_intensity is None:
        if out_dtype == "uint8":
            min_intensity, max_intensity = 0.0, 255.0
        elif out_dtype == "uint16":
            min_intensity, max_intensity = 0.0, 65535.0
        else:
            min_intensity, max_intensity = 0.0, 1.0

    # control-grid geometry is per COMPUTE block and static: origin one
    # spacing before the block, dims covering block + margins
    gdims = tuple(int(np.ceil(compute_block[d] / cpd)) + 3 for d in range(3))
    n_dev = devices if devices is not None else len(jax.local_devices())
    group = max(n_dev, 1)

    stats.blocks = len(grid_blocks)
    placed = _place_views(sd, views, unique, aniso)
    items = []
    for block in grid_blocks:
        block_global = Interval.from_shape(
            compute_block, block.offset).translate(bbox.min)
        reaching = _views_at_block(placed, block_global, cpd)
        if reaching:
            items.append((block, block_global, reaching))
        else:
            stats.skipped_empty += 1
    # this process's slice, cut here and not by the batch loop: a block's
    # batch has to be known before it is built
    items = partition_items(items)
    batch_of = {tuple(it[0].offset): i // group for i, it in enumerate(items)}
    unwritten = {bi: {o for o, b in batch_of.items() if b == bi}
                 for bi in set(batch_of.values())}

    mi, ma = np.float32(min_intensity), np.float32(max_intensity)
    kernel = _make_nonrigid_kernel(n_dev, compute_block, fusion_type,
                                   out_dtype)
    pool = CtxThreadPool(max_workers=max(1, io_threads))
    # a view's fit or read is a task of its own; these never wait for
    # another task, so a build that waits for them on ``pool`` cannot lock
    view_pool = CtxThreadPool(max_workers=max(1, io_threads),
                              thread_name_prefix="bst-nonrigid-view")
    lock = threading.Lock()
    planned: dict[int, Future] = {}
    written: dict[tuple, int] = {}

    def plan_batch(bi: int):
        """The plans of batch ``bi``'s blocks with the patch shape and view
        count they are staged at; made once, by the first build that asks."""
        with lock:
            fut = planned.get(bi)
            mine = fut is None
            if mine:
                fut = planned[bi] = Future()
        if mine:
            try:
                plans = {
                    tuple(block.offset): _plan_nonrigid_block(
                        sd, block, block_global, reaching, gdims, cpd, alpha,
                        view_pool)
                    for block, block_global, reaching
                    in items[bi * group:(bi + 1) * group]}
                boxes = [p[3].shape for ps in plans.values() for p in ps]
                fut.set_result((
                    plans,
                    F.bucket_shape(np.max(boxes, axis=0), 32) if boxes
                    else (32, 32, 32),
                    F.bucket_views(max(len(ps) for ps in plans.values()))))
            except BaseException as e:
                with lock:
                    planned.pop(bi, None)   # a retry plans again
                fut.set_exception(e)
        return fut.result()

    def build(item):
        block, block_global, reaching = item
        plans, pshape, vb = plan_batch(batch_of[tuple(block.offset)])
        arrs = _stage_nonrigid(
            loader, plans[tuple(block.offset)], pshape, vb, blend, gdims,
            patch_dtype(loader, [(r[0], 0) for r in reaching]), view_pool)
        origin = np.asarray(block_global.min, np.float64)
        return (*arrs, origin.astype(np.float32),
                (origin - cpd).astype(np.float32),
                np.full(3, cpd, np.float32))

    if n_dev > 1:
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import BLOCK_AXIS, make_mesh

        placement = NamedSharding(make_mesh(n_dev), PartitionSpec(BLOCK_AXIS))
    else:
        placement = jax.local_devices()[0]

    def kernel_call(*stacked):
        stats.compile_keys.add(
            (tuple(compute_block), stacked[0].shape[2:], stacked[0].shape[1],
             fusion_type, "nonrigid", n_dev > 1))
        with profiling.span("nonrigid.h2d",
                            nbytes=sum(int(a.nbytes) for a in stacked)):
            # may_alias: the transfer reads the stacked host buffers in
            # place, as the call's implicit upload would
            stacked = jax.block_until_ready(
                jax.device_put(list(stacked), placement, may_alias=True))
        with profiling.span("nonrigid.kernel"):
            return kernel(mi, ma, *stacked)

    def fetch(outs):
        with profiling.span("nonrigid.kernel"):
            # the fetch queued behind the kernel: its host buffers are
            # allocated and faulted in while the device works
            for o in outs:
                o.copy_to_host_async()
            jax.block_until_ready(outs)
        with profiling.span("nonrigid.d2h",
                            nbytes=sum(int(o.nbytes) for o in outs)):
            return jax.device_get(list(outs))

    def consume(item, data):
        block = item[0]
        offset = tuple(block.offset)
        bi = batch_of[offset]
        # a block none of whose views' deformed boxes meets its image went
        # through the kernel with nothing valid: it is not written
        if plan_batch(bi)[0][offset]:
            sl = tuple(slice(0, s) for s in block.size)
            with profiling.span("nonrigid.write", item=list(offset)):
                if zarr_ct is not None:
                    c, t = zarr_ct
                    out_ds.write(data[sl][..., None, None],
                                 (*block.offset, c, t))
                else:
                    out_ds.write(data[sl], block.offset)
            voxels = int(np.prod(block.size))
            _VOXELS_DONE.inc(voxels)
            _BLOCKS_DONE.inc()
        else:
            voxels = None
        with lock:
            written[offset] = voxels
            unwritten[bi].discard(offset)
            if not unwritten[bi]:
                planned.pop(bi, None)   # the batch's grids are done with

    try:
        run_sharded_batches(
            items, build, kernel_call, consume, n_dev, pool,
            label="nonrigid-fusion", progress=progress, fetch=fetch,
            out_bytes_per_item=int(np.prod(compute_block))
            * np.dtype(out_dtype or "float32").itemsize,
            workspace_mult=4.0)
    finally:
        pool.shutdown(wait=True)
        view_pool.shutdown(wait=True)
    stats.voxels = sum(v for v in written.values() if v is not None)
    stats.skipped_empty += sum(v is None for v in written.values())
    stats.seconds = time.time() - t0
    _record_fusion_stage("nonrigid-fusion", stats, "sharded")
    return stats


@functools.lru_cache(maxsize=32)
def _make_nonrigid_kernel(n_dev, compute_block, fusion_type, out_dtype):
    """Batch-of-blocks nonrigid fusion kernel with on-device intensity
    conversion; batch axis sharded over the mesh when n_dev > 1.
    lru_cache'd: a fresh jax.jit per call would recompile every run."""
    import jax

    from ..ops.nonrigid import nonrigid_fuse_block_impl
    from ..parallel.mesh import make_mesh, shard_jit

    def one(mi, ma, *args):
        fused, _ = nonrigid_fuse_block_impl(
            *args, block_shape=tuple(compute_block), fusion_type=fusion_type)
        return F._convert_intensity_expr(fused, mi, ma, out_dtype)

    # the device module is named after this function (jit_nonrigid_batched):
    # what a trace tells from the affine sharded driver's jit_batched
    def nonrigid_batched(mi, ma, *arrays):
        return jax.vmap(lambda *a: one(mi, ma, *a))(*arrays)

    if n_dev <= 1:
        return jax.jit(nonrigid_batched)
    return shard_jit(nonrigid_batched, make_mesh(n_dev), n_in=11, n_repl=2)


def _place_views(sd, views, unique: UniquePoints, aniso) -> list[tuple]:
    """Each view with its model, its image's bounding box in the world and
    its unique points: what no block changes."""
    placed = []
    for v in views:
        model = sd.model(v)
        if aniso is not None:
            model = concatenate(aniso, model)
        vbox = transformed_interval(
            model, Interval.from_shape(sd.view_size(v)))
        placed.append((v, model, vbox,
                       unique.targets.get(v, np.zeros((0, 3))),
                       unique.view_world.get(v, np.zeros((0, 3)))))
    return placed


def _views_at_block(placed, block_global: Interval, cpd) -> list[tuple]:
    """The views whose image reaches the block (+50 px), each with its
    model and the unique points that define its deformation there (+25 px
    and two spacings): geometry alone, nothing fitted."""
    sel_box = block_global.expand(int(FUSE_MARGIN))
    ip_box = block_global.expand(int(IP_MARGIN + 2 * cpd))
    ip_lo, ip_hi = np.array(ip_box.min), np.array(ip_box.max)
    reaching = []
    for v, model, vbox, tgt, vw in placed:
        if not vbox.overlaps(sel_box):
            continue
        if len(tgt):
            keep = np.all((tgt >= ip_lo) & (tgt <= ip_hi), axis=1)
            tgt, vw = tgt[keep], vw[keep]
        reaching.append((v, model, tgt, vw))
    return reaching


def _plan_nonrigid_block(sd, block: GridBlock, block_global: Interval,
                         reaching, gdims, cpd, alpha, view_pool) -> list:
    """Fit the grids of the views that reach one block, side by side, and
    keep those whose deformed block meets their image: the block's plans
    (view, grid, world -> view px, source box, view size)."""
    grid_origin = np.asarray(block_global.min, np.float64) - cpd
    with profiling.span("nonrigid.plan", item=list(block.offset)):
        futs = [view_pool.submit(_plan_nonrigid_view, sd, v, model, tgt, vw,
                                 block_global, grid_origin, gdims, cpd, alpha)
                for v, model, tgt, vw in reaching]
        return [p for p in (f.result() for f in futs) if p is not None]


def _plan_nonrigid_view(sd, v, model, tgt, vw, block_global: Interval,
                        grid_origin, gdims, cpd, alpha):
    """One view's control grid for one block and the source box that covers
    the block deformed by it; None when that box misses the image."""
    with profiling.span("nonrigid.fit", item=[v.timepoint, v.setup]):
        t0 = time.perf_counter()
        grid = fit_control_grid(tgt, vw, grid_origin, gdims, cpd, alpha)
        _FIT_SECONDS.inc(time.perf_counter() - t0)
    _CONTROL_POINTS.inc(len(tgt))

    # source patch must cover the DEFORMED block under every vertex model
    corners = np.array(
        [[(block_global.min[d], block_global.max[d] + 1)[(i >> d) & 1]
          for d in range(3)] for i in range(8)], np.float64,
    )
    A = grid.reshape(-1, 3, 4).astype(np.float64)
    warped = np.einsum("gij,cj->gci", A[:, :, :3], corners) + A[:, None, :, 3]
    inv_total = invert_affine(model)  # world -> full-res view px (level 0)
    lo = warped.reshape(-1, 3) @ inv_total[:, :3].T + inv_total[:, 3]
    src = Interval(
        tuple(np.floor(lo.min(axis=0)).astype(np.int64) - 1),
        tuple(np.ceil(lo.max(axis=0)).astype(np.int64) + 1),
    )
    clipped = src.intersect(Interval.from_shape(sd.view_size(v)))
    if clipped.is_empty():
        return None
    return (v, grid, inv_total, clipped,
            np.array(sd.view_size(v), np.float64))


def _stage_nonrigid(loader, plans, pshape, vb, blend: BlendParams, gdims,
                    dtype, view_pool):
    """Host-side input staging for one block's nonrigid kernel inputs; the
    views' source boxes are read side by side. ``dtype`` is the stored
    integer dtype when every view shares one (<=16-bit): patches ship at
    native width, the kernel casts to float32 on device (lossless — same
    memoized transport decision as the affine paths)."""
    patches = np.zeros((vb, *pshape), dtype)
    grids = np.zeros((vb, *gdims, 12), np.float32)
    grids[..., 0] = 1.0
    grids[..., 5] = 1.0
    grids[..., 10] = 1.0
    vaffines = np.zeros((vb, 3, 4), np.float32)
    offsets = np.zeros((vb, 3), np.float32)
    img_dims = np.ones((vb, 3), np.float32)
    borders = np.zeros((vb, 3), np.float32)
    ranges = np.ones((vb, 3), np.float32)
    valid = np.zeros((vb,), np.float32)

    def read(i, v, clipped):
        with profiling.span("nonrigid.prefetch",
                            item=[v.timepoint, v.setup]):
            patches[i] = loader.read_block(v, 0, tuple(clipped.min), pshape)

    reads = [view_pool.submit(read, i, p[0], p[3])
             for i, p in enumerate(plans)]
    for i, (v, grid, inv_total, clipped, dim) in enumerate(plans):
        grids[i] = grid
        vaffines[i] = concatenate(
            translation_affine(-np.asarray(clipped.min, np.float64)), inv_total
        )
        offsets[i] = clipped.min
        img_dims[i] = dim
        borders[i] = blend.border
        ranges[i] = blend.range
        valid[i] = 1.0
    for r in reads:
        r.result()
    return (patches, grids, vaffines, offsets, img_dims, borders, ranges,
            valid)
