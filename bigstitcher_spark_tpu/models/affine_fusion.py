"""Affine fusion driver: plan blocks, prefetch patches, run the XLA kernel.

The TPU redesign of SparkAffineFusion's per-block map (reference call stack
SURVEY.md §3.1): the work list is the output block grid (strategy P1); per
block the host finds overlapping views (OverlappingViews.java:28-47),
prefetches the exact source boxes the inverse affine needs
(ViewUtil.findOverlappingBlocks role), buckets shapes, and launches one fused
XLA computation. Writers own disjoint storage chunks; halos are over-read —
both reference invariants preserved.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

from ..io.chunkstore import Dataset
from ..io.dataset_io import ViewLoader, best_mipmap_level
from ..io.spimdata import SpimData, ViewId
from ..ops import fusion as F
from ..utils.geometry import (
    Interval,
    concatenate,
    invert_affine,
    scale_affine,
    translation_affine,
    transformed_interval,
)
from ..utils.grid import GridBlock, create_grid
from .. import config, observe, profiling
from ..observe import metrics as _metrics

_H2D_BYTES = _metrics.counter("bst_xfer_h2d_bytes_total")
_D2H_BYTES = _metrics.counter("bst_xfer_d2h_bytes_total")
_H2D_SAVED = _metrics.counter("bst_xfer_h2d_bytes_saved_total")
_D2H_SAVED = _metrics.counter("bst_xfer_d2h_bytes_saved_total")
_VOXELS_DONE = _metrics.counter("bst_fusion_voxels_total")
_BLOCKS_BY_KERNEL = {k: _metrics.counter("bst_fusion_blocks_total", kernel=k)
                     for k in ("shift", "sep", "gather")}
_TILE_HITS = _metrics.counter("bst_tile_cache_hits_total")
_TILE_MISSES = _metrics.counter("bst_tile_cache_misses_total")
_TILE_HIT_BYTES = _metrics.counter("bst_tile_cache_hit_bytes_total")
_TILE_EVICT_BYTES = _metrics.counter("bst_tile_cache_evict_bytes_total")
_EPI_D2H_BYTES = _metrics.counter("bst_epilogue_d2h_bytes_total")
_EPI_WRITE_BYTES = _metrics.counter("bst_epilogue_write_bytes_total")


@dataclass
class BlendParams:
    """Cosine blending configuration (mvrecon FusionTools defaults)."""

    border: tuple[float, float, float] = (0.0, 0.0, 0.0)
    range: tuple[float, float, float] = (40.0, 40.0, 40.0)


@dataclass
class FusionStats:
    voxels: int = 0
    blocks: int = 0
    skipped_empty: int = 0
    seconds: float = 0.0
    compile_keys: set = field(default_factory=set)
    # multiscale-epilogue output, kept SEPARATE from ``voxels`` so
    # full-res-only and pyramid-inclusive rates stay distinguishable
    # (the epilogue must not masquerade as a kernel slowdown — or win)
    pyramid_voxels: int = 0
    pyramid_levels: int = 0


@dataclass(frozen=True)
class PyramidLevel:
    """One downsample pyramid level the fusion drivers may materialize as a
    kernel epilogue while the fused data is still device-resident
    (ROADMAP item 3a), instead of the downsample stage re-reading the
    full-res container. ``rel`` is the factor from the PREVIOUS level,
    ``abs_factor`` from full resolution, ``dims`` the 3-D level extent —
    all straight off the container's ``MultiResolutionLevelInfo``."""

    ds: Dataset
    rel: tuple[int, int, int]
    abs_factor: tuple[int, int, int]
    dims: tuple[int, int, int]


def pyramid_from_mr(store, mr_levels) -> list["PyramidLevel"]:
    """Epilogue spec for a container slot's ``MultiResolutionLevelInfo``
    list (levels 1..n; level 0 is the fusion target itself) — the one
    place the rel/abs/dims unpacking rules live, shared by the CLI
    ``--pyramid`` path and the bench measure that validates it."""
    return [PyramidLevel(
        ds=store.open_dataset(m.dataset.strip("/")),
        rel=tuple(int(v) for v in m.relativeDownsampling[:3]),
        abs_factor=tuple(int(v) for v in m.absoluteDownsampling[:3]),
        dims=tuple(int(v) for v in m.dimensions[:3]),
    ) for m in mr_levels[1:]]


def anisotropy_transform(factor: float) -> np.ndarray:
    """Concatenate (1,1,1/f) scaling into all view models
    (TransformVirtual.adjustAllTransforms, SparkAffineFusion.java:487-491)."""
    if not np.isfinite(factor) or factor == 1.0:
        return None
    return scale_affine((1.0, 1.0, 1.0 / factor))


@dataclass
class _ViewPlan:
    patch_offset: np.ndarray  # (3,) int, level coords
    patch_interval: Interval
    affine: np.ndarray        # (3,4) block idx -> patch coords
    inv_total: np.ndarray     # (3,4) world -> level coords
    img_dim: np.ndarray       # (3,) level image dims
    level: int
    view: ViewId

    @property
    def is_translation(self) -> bool:
        """True when sampling is a pure (sub-pixel) shift — the no-gather
        fast path applies (ops.fusion.fuse_block_shift)."""
        return bool(np.allclose(self.inv_total[:, :3], np.eye(3), atol=1e-7))

    @property
    def is_diagonal(self) -> bool:
        """True when the linear part is axis-aligned (diagonal) — e.g.
        translation-registered tiles under --preserveAnisotropy z-scaling:
        sampling factorizes into three 1-D interpolation GEMMs, no gathers
        (ops.fusion.fuse_block_sep)."""
        lin = self.inv_total[:, :3]
        return bool(np.allclose(lin, np.diag(np.diagonal(lin)), atol=1e-7))


def plan_block(
    sd: SpimData,
    loader: ViewLoader,
    views: list[ViewId],
    block_global: Interval,
    anisotropy: np.ndarray | None,
) -> list[_ViewPlan]:
    """Find views overlapping this output block and their needed source boxes."""
    plans: list[_ViewPlan] = []
    for v in views:
        model = sd.model(v)
        if anisotropy is not None:
            model = concatenate(anisotropy, model)
        factors = loader.downsampling_factors(v.setup)
        level = best_mipmap_level(factors, (1.0, 1.0, 1.0))
        mip = loader.mipmap_transform(v.setup, level)
        total = concatenate(model, mip)  # level coords -> world
        inv_total = invert_affine(total)
        src = transformed_interval(inv_total, block_global).expand(1)
        img_shape = loader.open(v, level).shape
        img_iv = Interval.from_shape(img_shape)
        # +2 px tolerance like OverlappingViews (fusion/OverlappingViews.java:28-47)
        if not src.overlaps(img_iv.expand(2)):
            continue
        clipped = src.intersect(img_iv)
        if clipped.is_empty():
            continue
        patch_offset = np.array(clipped.min, dtype=np.float64)
        aff = concatenate(
            translation_affine(-patch_offset),
            concatenate(inv_total, translation_affine(block_global.min)),
        )
        plans.append(
            _ViewPlan(
                patch_offset=np.array(clipped.min, dtype=np.int64),
                patch_interval=clipped,
                affine=aff,
                inv_total=inv_total,
                img_dim=np.array(img_shape, dtype=np.float64),
                level=level,
                view=v,
            )
        )
    return plans


def fuse_grid_block(
    sd: SpimData,
    loader: ViewLoader,
    views: list[ViewId],
    block: GridBlock,
    bbox: Interval,
    fusion_type: str = "AVG_BLEND",
    blend: BlendParams | None = None,
    anisotropy: np.ndarray | None = None,
    patch_quantum: int = 32,
    compute_block_shape: tuple[int, ...] | None = None,
    stats: FusionStats | None = None,
    inside_offset: tuple[float, float, float] = (0.0, 0.0, 0.0),
    coefficients: dict[ViewId, np.ndarray] | None = None,
) -> tuple[np.ndarray, np.ndarray] | None:
    """Fuse one grid block. Returns (fused f32, weight f32) arrays of
    ``block.size``, or None when no view overlaps (block left empty —
    reference skips saving empty blocks).

    ``coefficients``: optional per-view (cx,cy,cz,2) intensity-correction
    grids (BlkAffineFusion.initWithIntensityCoefficients role); forces the
    general gather kernel."""
    dev_out = _fuse_grid_block_device(
        sd, loader, views, block, bbox, fusion_type, blend, anisotropy,
        patch_quantum, compute_block_shape, stats, inside_offset,
        coefficients)
    if dev_out is None:
        return None
    with profiling.span("fusion.d2h", item=tuple(map(int, block.offset))):
        return _fetch_block(dev_out, block)


def _fuse_grid_block_device(sd, loader, views, block, bbox,
                            fusion_type="AVG_BLEND", blend=None,
                            anisotropy=None, patch_quantum=32,
                            compute_block_shape=None, stats=None,
                            inside_offset=(0.0, 0.0, 0.0),
                            coefficients=None, pool=None):
    """:func:`fuse_grid_block` up to the kernel's end: the DEVICE (fused,
    wsum) of the static compute shape, or None when no view overlaps.
    ``pool``: where the block's views are read side by side."""
    bkey = tuple(map(int, block.offset))
    blend = blend or BlendParams()
    bshape = tuple(compute_block_shape or block.size)
    with profiling.span("fusion.plan", item=bkey):
        block_global = Interval.from_shape(
            bshape, block.offset).translate(bbox.min)
        plans = plan_block(sd, loader, views, block_global, anisotropy)
    if not plans:
        return None

    if coefficients is None and all(p.is_translation for p in plans):
        _BLOCKS_BY_KERNEL["shift"].inc()
        return _fuse_shift_path(
            loader, plans, block, block_global, bshape, fusion_type, blend,
            stats, inside_offset, pool,
        )

    if coefficients is None and all(p.is_diagonal for p in plans):
        _BLOCKS_BY_KERNEL["sep"].inc()
        return _fuse_sep_path(
            sd, loader, plans, block, bshape, fusion_type, blend, stats,
            inside_offset, patch_quantum, pool,
        )

    _BLOCKS_BY_KERNEL["gather"].inc()
    vb = F.bucket_views(len(plans))
    pshape = F.bucket_shape(
        np.max([p.patch_interval.shape for p in plans], axis=0), patch_quantum
    )
    (patches, affines, offsets, img_dims, borders, ranges, valid, ioffs,
     coeffs, coeff_affs) = _gather_inputs(
        sd, loader, plans, pshape, vb, blend, inside_offset, coefficients,
        pool)

    if stats is not None:
        stats.compile_keys.add((bshape, pshape, vb, fusion_type,
                                coefficients is not None))
    return _run_block_kernel(
        F.fuse_block, bkey,
        (patches, affines, offsets, img_dims, borders, ranges, valid),
        dict(inside_offs=ioffs, coeffs=coeffs, coeff_affines=coeff_affs),
        block_shape=bshape, fusion_type=fusion_type)


def _run_block_kernel(kernel, bkey, args: tuple, kwargs: dict, **static):
    """One block through its kernel with each leg bracketed: the explicit
    upload of the staged inputs until it is done, then the call until its
    outputs are ready. The per-block chain is serial anyway (a fetch
    follows every call), so neither wait is new. Returns the DEVICE
    (fused, wsum), their fetch already queued."""
    staged = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(
        (args, kwargs)) if isinstance(x, np.ndarray))
    with profiling.span("fusion.h2d", item=bkey, nbytes=staged):
        # may_alias: the transfer reads the staged host buffers in place,
        # as a jitted call's implicit upload does; device_put's default
        # copies them first (a host memcpy of the whole patch stack)
        args, kwargs = jax.block_until_ready(
            jax.device_put((args, kwargs), may_alias=True))
    _H2D_BYTES.inc(staged)
    with profiling.span("fusion.kernel", item=bkey):
        out = kernel(*args, **kwargs, **static)
        # queue the fetch behind the kernel, as the device_get that used
        # to follow the call did: its host buffers are then allocated and
        # faulted in while the device works, not after it
        for leaf in out:
            leaf.copy_to_host_async()
        return jax.block_until_ready(out)


def _fetch_block(dev_out, block):
    """Fetch one block's device (fused, wsum) and crop the static compute
    shape back to the (possibly clipped) block."""
    fused, wsum = jax.device_get(dev_out)
    _D2H_BYTES.inc(int(fused.nbytes) + int(wsum.nbytes))
    sl = tuple(slice(0, s) for s in block.size)
    return fused[sl], wsum[sl]


def _coeff_grid_affine(sd, loader, p, cdims):
    """(3, 4) lpos->grid affine for one view plan: level coords -> grid
    coords with full-res px = f*l + (f-1)/2 and cell centers at
    (k+0.5)*cs - 0.5, cs = view_size/dims (BlkAffineFusion coefficients
    semantics). The one place the convention lives, shared by the
    composite and per-block gather paths so it cannot diverge."""
    f = np.asarray(loader.downsampling_factors(p.view.setup)[p.level],
                   np.float64)
    cs = np.array(sd.view_size(p.view), np.float64) / np.array(cdims)
    aff = np.zeros((3, 4), np.float32)
    aff[:, :3] = np.diag(f / cs)
    aff[:, 3] = ((f - 1) / 2.0 + 0.5) / cs - 0.5
    return aff


def _coeff_digest(coefficients) -> bytes:
    """Content signature of a coefficient set: view identity + grid bytes.
    Any regenerated/reloaded grid (a new solve, a store round-trip after a
    rewrite) hashes differently, so a stale device table can never serve a
    changed solve — the in-memory equivalent of the tile cache's
    (signature, write-generation) key."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    for v in sorted(coefficients, key=lambda v: (v.timepoint, v.setup)):
        g = np.ascontiguousarray(coefficients[v], np.float32)
        h.update(np.asarray([v.timepoint, v.setup, *g.shape],
                            np.int64).tobytes())
        h.update(g.tobytes())
    return h.digest()


def _coeff_rows(coefficients) -> dict:
    """Canonical {view: table row} assignment (row 0 is the identity)."""
    views = sorted(coefficients, key=lambda v: (v.timepoint, v.setup))
    return {v: i + 1 for i, v in enumerate(views)}


# One-time device residency for intensity-correction grids: the old
# per-block path re-staged the FULL (vb, Cx,Cy,Cz, 2) grid stack into
# every block's kernel inputs, so identical coefficient bytes re-crossed
# H2D with every fused block. The table uploads once per coefficient-set
# content digest; per-block inputs become a device-side jnp.take.
_COEFF_TABLE_KEEP = 4


def coefficient_table(coefficients):
    """(table, rows): ``table`` a DEVICE (n_views+1, Cx,Cy,Cz, 2) stack
    whose row 0 is the identity map (gain 1, offset 0) for padded/missing
    slots, ``rows`` the {view: row} map. Uploaded at most once per content
    digest (LRU of ``_COEFF_TABLE_KEEP`` sets)."""
    import jax

    dig = _coeff_digest(coefficients)
    with _TILE_CACHE_LOCK:
        ent = _COEFF_TABLE_CACHE.get(dig)
        if ent is not None:
            _COEFF_TABLE_CACHE.move_to_end(dig)
            return ent
    rows = _coeff_rows(coefficients)
    cdims = next(iter(coefficients.values())).shape[:3]
    host = np.zeros((len(rows) + 1, *cdims, 2), np.float32)
    host[..., 0] = 1.0
    for v, r in rows.items():
        host[r] = coefficients[v]
    table = jax.device_put(host)
    _H2D_BYTES.inc(int(table.nbytes))
    with _TILE_CACHE_LOCK:
        _COEFF_TABLE_CACHE[dig] = (table, rows)
        while len(_COEFF_TABLE_CACHE) > _COEFF_TABLE_KEEP:
            _COEFF_TABLE_CACHE.popitem(last=False)
    return table, rows


def register_coefficient_table(coefficients, per_view_dev) -> None:
    """Adopt ALREADY-DEVICE-RESIDENT per-view grids for ``coefficients``
    (the solve→fusion handoff: models.intensity registers the CG solver's
    device output here, reshaped on device, so fusion's first
    :func:`coefficient_table` lookup hits without the grids ever making a
    host->device round trip). ``per_view_dev``: {view: device
    (Cx,Cy,Cz,2)} matching ``coefficients`` bit-for-bit."""
    import jax.numpy as jnp

    rows = _coeff_rows(coefficients)
    if set(rows) != set(per_view_dev):
        return
    cdims = next(iter(coefficients.values())).shape[:3]
    ident = jnp.concatenate(
        [jnp.ones((1, *cdims, 1), jnp.float32),
         jnp.zeros((1, *cdims, 1), jnp.float32)], axis=-1)
    order = sorted(rows, key=rows.get)
    table = jnp.concatenate(
        [ident] + [jnp.asarray(per_view_dev[v],
                               jnp.float32)[None] for v in order], axis=0)
    _H2D_SAVED.inc(int(table.nbytes))  # the upload that never happens
    dig = _coeff_digest(coefficients)
    with _TILE_CACHE_LOCK:
        _COEFF_TABLE_CACHE[dig] = (table, rows)
        while len(_COEFF_TABLE_CACHE) > _COEFF_TABLE_KEEP:
            _COEFF_TABLE_CACHE.popitem(last=False)


def gather_coefficient_inputs(sd, loader, plans, coefficients, nb):
    """Per-block coefficient kernel inputs off the device-resident table:
    a DEVICE (nb, Cx,Cy,Cz, 2) row gather — it rides the work loop's
    device-side batch stacking, so zero grid bytes cross H2D per block —
    plus the tiny host (nb, 3, 4) lpos->grid affines (48 B/view)."""
    import jax.numpy as jnp

    table, rows = coefficient_table(coefficients)
    cdims = tuple(int(s) for s in table.shape[1:4])
    idx = np.zeros((nb,), np.int32)
    coeff_affs = np.zeros((nb, 3, 4), np.float32)
    coeff_affs[:, :, :3] = np.eye(3)
    for i, p in enumerate(plans):
        r = rows.get(p.view)
        if r is None or coefficients.get(p.view) is None:
            continue
        idx[i] = r
        coeff_affs[i] = _coeff_grid_affine(sd, loader, p, cdims)
    coeffs = jnp.take(table, jnp.asarray(idx), axis=0)
    # grid bytes the per-block re-staging path would have re-shipped
    _H2D_SAVED.inc(int(coeffs.nbytes))
    return coeffs, coeff_affs


def patch_dtype(loader, view_levels) -> np.dtype:
    """The staged patch stack's dtype for ``(view, level)`` pairs: the
    stored dtype when every view shares a <=16-bit integer type — patches
    then ship to the device at native width and the kernels cast to
    float32 on device (lossless, halves h2d bytes on wire-limited links)
    — float32 otherwise. Probes are memoized per (view, level) on the
    loader for the whole run."""
    memo = loader.__dict__.setdefault("_patch_dtype_memo", {})
    dts = set()
    for key in view_levels:
        d = memo.get(key)
        if d is None:  # probe once per (view, level) for the whole run
            d = np.dtype(loader.open(*key).dtype).newbyteorder("=")
            memo[key] = d
        dts.add(d)
    if len(dts) == 1:
        d = dts.pop()
        if d.kind in "ui" and d.itemsize <= 2:
            return d
    return np.dtype(np.float32)


def _read_boxes(loader, plans, offsets, pshape, patches, pool=None):
    """Each plan's source box at ``offsets[i]`` into ``patches[i]``: one
    after another, or side by side on ``pool`` (the per-block driver's,
    whose device would otherwise sit through four reads a block)."""
    def read(i, p):
        with profiling.span("fusion.prefetch"):
            patches[i] = loader.read_block(
                p.view, p.level, tuple(offsets[i]), pshape)

    if pool is None or len(plans) < 2:
        for i, p in enumerate(plans):
            read(i, p)
    else:
        list(pool.map(read, range(len(plans)), plans))


def _gather_inputs(sd, loader, plans, pshape, vb, blend, inside_offset,
                   coefficients, pool=None):
    """Host-side input staging for the general gather kernel: prefetch the
    clipped source boxes and assemble the per-view parameter arrays."""
    patches = np.zeros((vb, *pshape), dtype=patch_dtype(
        loader, [(p.view, p.level) for p in plans]))
    affines = np.zeros((vb, 3, 4), dtype=np.float32)
    offsets = np.zeros((vb, 3), dtype=np.float32)
    img_dims = np.ones((vb, 3), dtype=np.float32)
    borders = np.zeros((vb, 3), dtype=np.float32)
    ranges = np.ones((vb, 3), dtype=np.float32)
    valid = np.zeros((vb,), dtype=np.float32)
    _read_boxes(loader, plans, [p.patch_offset for p in plans], pshape,
                patches, pool)
    for i, p in enumerate(plans):
        affines[i] = p.affine
        offsets[i] = p.patch_offset
        img_dims[i] = p.img_dim
        factors = loader.downsampling_factors(p.view.setup)[p.level]
        borders[i] = np.asarray(blend.border) / np.asarray(factors, dtype=np.float64)
        ranges[i] = np.asarray(blend.range) / np.asarray(factors, dtype=np.float64)
        valid[i] = 1.0

    coeffs = coeff_affs = None
    if coefficients is not None:
        coeffs, coeff_affs = gather_coefficient_inputs(
            sd, loader, plans, coefficients, vb)
    ioffs = np.tile(np.asarray(inside_offset, np.float32), (vb, 1))
    return (patches, affines, offsets, img_dims, borders, ranges, valid,
            ioffs, coeffs, coeff_affs)


def _shift_inputs(loader, plans, block_global, bshape, vb, blend,
                  inside_offset, pool=None):
    """Host-side input staging for the translation shifted-slice kernel."""
    pshape = tuple(s + 1 for s in bshape)
    patches = np.zeros((vb, *pshape), dtype=patch_dtype(
        loader, [(p.view, p.level) for p in plans]))
    fracs = np.zeros((vb, 3), dtype=np.float32)
    lpos0 = np.zeros((vb, 3), dtype=np.float32)
    img_dims = np.ones((vb, 3), dtype=np.float32)
    borders = np.zeros((vb, 3), dtype=np.float32)
    ranges = np.ones((vb, 3), dtype=np.float32)
    valid = np.zeros((vb,), dtype=np.float32)
    bg_min = np.asarray(block_global.min, dtype=np.float64)
    tlevels = [p.inv_total[:, :3] @ bg_min + p.inv_total[:, 3]
               for p in plans]
    floor_offs = [np.floor(t).astype(np.int64) for t in tlevels]
    _read_boxes(loader, plans, floor_offs, pshape, patches, pool)
    for i, p in enumerate(plans):
        fracs[i] = tlevels[i] - floor_offs[i]
        lpos0[i] = tlevels[i]
        img_dims[i] = p.img_dim
        factors = loader.downsampling_factors(p.view.setup)[p.level]
        borders[i] = np.asarray(blend.border) / np.asarray(factors, dtype=np.float64)
        ranges[i] = np.asarray(blend.range) / np.asarray(factors, dtype=np.float64)
        valid[i] = 1.0
    ioffs = np.tile(np.asarray(inside_offset, np.float32), (vb, 1))
    return patches, fracs, lpos0, img_dims, borders, ranges, valid, ioffs


def _sep_inputs(sd, loader, plans, pshape, vb, blend, inside_offset,
                pool=None):
    """Host-side staging for the diagonal separable kernel: same clipped
    patch prefetch as the gather path, plus the per-view (diag, t) of the
    block-index -> patch-coordinate affine."""
    (patches, affines, offsets, img_dims, borders, ranges, valid, ioffs,
     _c, _ca) = _gather_inputs(sd, loader, plans, pshape, vb, blend,
                               inside_offset, None, pool)
    diags = np.ascontiguousarray(
        np.stack([np.diagonal(affines[i, :, :3]) for i in range(vb)]))
    ts = np.ascontiguousarray(affines[:, :, 3])
    return patches, diags, ts, offsets, img_dims, borders, ranges, valid, ioffs


def _fuse_sep_path(sd, loader, plans, block, bshape, fusion_type, blend,
                   stats, inside_offset=(0.0, 0.0, 0.0), patch_quantum=32,
                   pool=None):
    """Diagonal-affine blocks (e.g. --preserveAnisotropy over
    translation-registered views): separable interpolation GEMMs, no
    gathers."""
    vb = F.bucket_views(len(plans))
    pshape = F.bucket_shape(
        np.max([p.patch_interval.shape for p in plans], axis=0), patch_quantum)
    (patches, diags, ts, offsets, img_dims, borders, ranges, valid, ioffs
     ) = _sep_inputs(sd, loader, plans, pshape, vb, blend, inside_offset,
                     pool)
    if stats is not None:
        stats.compile_keys.add((bshape, pshape, "sep", vb, fusion_type))
    return _run_block_kernel(
        F.fuse_block_sep, tuple(map(int, block.offset)),
        (patches, diags, ts, offsets, img_dims, borders, ranges, valid),
        dict(inside_offs=ioffs), block_shape=bshape, fusion_type=fusion_type)


def _fuse_shift_path(loader, plans, block, block_global, bshape, fusion_type,
                     blend, stats, inside_offset=(0.0, 0.0, 0.0), pool=None):
    """Translation-only blocks: 8-shifted-slice kernel, no gather, one compile
    per (block shape, view bucket)."""
    vb = F.bucket_views(len(plans))
    (patches, fracs, lpos0, img_dims, borders, ranges, valid, ioffs
     ) = _shift_inputs(loader, plans, block_global, bshape, vb, blend,
                       inside_offset, pool)
    if stats is not None:
        stats.compile_keys.add((bshape, "shift", vb, fusion_type))
    return _run_block_kernel(
        F.fuse_block_shift, tuple(map(int, block.offset)),
        (patches, fracs, lpos0, img_dims, borders, ranges, valid),
        dict(inside_offs=ioffs), block_shape=bshape, fusion_type=fusion_type)


def device_tile_budget_bytes() -> int:
    """Composite-path device residency budget, read at call time (the old
    import-time snapshot ignored BST_DEVICE_TILE_BUDGET set after import)."""
    return config.get_bytes("BST_DEVICE_TILE_BUDGET")


@dataclass
class CompositePlan:
    """Host-side plan for the whole-volume composite fusion path: static
    per-view windows/offsets (baked into the compiled program) plus the
    traced per-view parameter arrays."""

    plans: list
    out_shape: tuple
    windows: tuple
    n_offs: tuple
    pad: tuple
    fracs: np.ndarray
    img_dims: np.ndarray
    borders: np.ndarray
    ranges: np.ndarray
    inside_offs: np.ndarray
    coeffs: np.ndarray | None = None       # (V, Cx,Cy,Cz, 2) intensity maps
    coeff_affs: np.ndarray | None = None   # (V, 3, 4) diagonal lpos->grid
    kinds: tuple = ()                      # per-view "shift" | "sep"
    diags: np.ndarray | None = None        # (V, 3) sampling step per axis
    offs: np.ndarray | None = None         # (V, 3) tile coord of output idx 0


def plan_composite_volume(
    sd, loader, views, bbox, anisotropy, blend, masks=False,
    mask_offset=(0.0, 0.0, 0.0), coefficients=None,
) -> CompositePlan | None:
    """Plan the composite device path. None when a view is not a pure
    translation at stored level 0 or the tile stack exceeds the budget."""
    vol_iv = Interval.from_shape(bbox.shape).translate(bbox.min)
    plans = plan_block(sd, loader, views, vol_iv, anisotropy)
    if not plans:
        return None
    if any(not (p.is_translation or p.is_diagonal) or p.level != 0
           for p in plans):
        return None
    if coefficients is not None and any(not p.is_translation for p in plans):
        return None  # coeffs + diagonal views -> per-block path
    if any(not p.is_translation
           and np.any(np.diagonal(p.inv_total[:, :3]) <= 0) for p in plans):
        return None  # mirrored axes: keep the general gather path
    shapes = [tuple(int(s) for s in p.img_dim) for p in plans]
    itemsizes = [np.dtype(loader.open(p.view, 0).dtype).itemsize
                 for p in plans]
    nbytes = sum(int(np.prod(s)) * isz for s, isz in zip(shapes, itemsizes))
    # device residency: tiles + the kernel's full-volume f32 accumulators
    # (acc + wsum + converted output ~= 3x) must fit the budget, or the
    # caller falls back to the per-block path (fuse_grid_block loop).
    # Cached tiles of OTHER datasets/generations also occupy HBM — but a
    # cache must never lock a fitting plan out of the fast path, so
    # foreign residents are EVICTED (LRU-first) to make room rather than
    # counted against the plan (this plan's own cached tiles are the very
    # buffers `nbytes` already prices).
    nbytes += 3 * int(np.prod(bbox.shape)) * 4
    budget = device_tile_budget_bytes()
    if nbytes > budget:
        return None
    own_keys = {k for k in (_tile_cache_key(loader.open(p.view, 0))
                            for p in plans) if k is not None}
    with _TILE_CACHE_LOCK:
        for k in [k for k in _TILE_CACHE if k not in own_keys]:
            if nbytes + _TILE_CACHE_BYTES[0] <= budget:
                break
            _tile_cache_drop_locked(k)

    out_shape = tuple(bbox.shape)
    io_ceil = tuple(int(np.ceil(max(0.0, o))) for o in
                    (mask_offset if masks else (0.0, 0.0, 0.0)))
    # tile pad must cover the window widening from --maskOffset inside-test
    # expansion, or the static corner slices run out of bounds
    pad = tuple(1 + io_ceil[d] for d in range(3))
    windows, n_offs, kinds = [], [], []
    fracs = np.zeros((len(plans), 3), np.float32)
    diags = np.ones((len(plans), 3), np.float32)
    offs = np.zeros((len(plans), 3), np.float32)
    img_dims = np.ones((len(plans), 3), np.float32)
    borders = np.zeros((len(plans), 3), np.float32)
    ranges = np.ones((len(plans), 3), np.float32)
    inside_offs = np.zeros((len(plans), 3), np.float32)
    if masks:
        inside_offs[:] = np.asarray(mask_offset, np.float32)
    bb_min = np.asarray(bbox.min, np.float64)
    for i, p in enumerate(plans):
        # tile coord of output voxel (0,0,0): g = inv_total @ bbox.min
        g = p.inv_total[:, :3] @ bb_min + p.inv_total[:, 3]
        S = shapes[i]
        if p.is_translation:
            kinds.append("shift")
            n = np.floor(g).astype(np.int64)
            f = g - n
            a = tuple(int(max(0, -n[d] - 1 - io_ceil[d])) for d in range(3))
            b = tuple(int(min(out_shape[d], S[d] - n[d] + io_ceil[d]))
                      for d in range(3))
            n_offs.append(tuple(int(v) for v in n))
            fracs[i] = f
        else:
            # diagonal: tile coord at output idx = diag*idx + g; window from
            # the inverse map of the tile extent [-1, S] (+maskOffset slack)
            kinds.append("sep")
            dg = np.diagonal(p.inv_total[:, :3]).astype(np.float64)
            a = tuple(int(max(0, np.floor((-1.0 - io_ceil[d] - g[d]) / dg[d])))
                      for d in range(3))
            b = tuple(int(min(out_shape[d],
                              np.ceil((S[d] + io_ceil[d] - g[d]) / dg[d]) + 1))
                      for d in range(3))
            n_offs.append((0, 0, 0))
            diags[i] = dg
            offs[i] = g
        windows.append((a, b))
        img_dims[i] = p.img_dim
        factors = loader.downsampling_factors(p.view.setup)[p.level]
        borders[i] = np.asarray(blend.border) / np.asarray(factors)
        ranges[i] = np.asarray(blend.range) / np.asarray(factors)
    coeffs = coeff_affs = None
    if coefficients is not None:
        coeffs, coeff_affs = gather_coefficient_inputs(
            sd, loader, plans, coefficients, len(plans))
    return CompositePlan(plans, out_shape, tuple(windows), tuple(n_offs),
                         pad, fracs, img_dims, borders, ranges, inside_offs,
                         coeffs, coeff_affs, tuple(kinds), diags, offs)


# Cross-call device residency for composite-path tiles: repeated fusions
# over the same stored views (best-of bench reps, the --masks double pass,
# parameter sweeps) re-shipped identical tiles up a 70 MB/s wire every
# call. Keys fold the dataset's chunk-cache identity, metadata signature
# AND write-generation (io.chunkcache bumps it on every Dataset.write /
# remove / recreate), so any host-visible mutation orphans the HBM copy;
# orphaned generations of a dataset are purged eagerly when its current
# generation uploads, not just under LRU pressure.
import threading as _threading
from collections import OrderedDict as _OrderedDict

_TILE_CACHE: "_OrderedDict[tuple, object]" = _OrderedDict()
_TILE_CACHE_LOCK = _threading.Lock()
_TILE_CACHE_BYTES = [0]
# device-resident coefficient tables (coefficient_table above); shares the
# tile-cache lock — both are tiny critical sections on the same call paths
_COEFF_TABLE_CACHE: "_OrderedDict[bytes, tuple]" = _OrderedDict()


def _tile_cache_budget() -> int:
    return config.get_bytes("BST_TILE_CACHE_BYTES")


def _tile_cache_key(ds) -> tuple | None:
    """Stable content identity of a stored tile, or None when the dataset
    has no cacheable identity (wrapper datasets, remote stores)."""
    from ..io import chunkcache

    if not (hasattr(ds, "_cache_key") and hasattr(ds, "_cacheable")):
        return None
    if not ds._cacheable():
        return None
    dkey = ds._cache_key()
    return (*dkey, ds._cache_sig(), chunkcache.get_cache().generation(dkey))


def _tile_cache_drop_locked(key) -> None:
    v = _TILE_CACHE.pop(key, None)
    if v is not None:
        _TILE_CACHE_BYTES[0] -= int(v.nbytes)
        _TILE_EVICT_BYTES.inc(int(v.nbytes))


def upload_composite_tiles(loader, cp: CompositePlan) -> list:
    """Stage the plan's tiles in HBM (async device_put per tile), serving
    unchanged tiles from the device-resident cache
    (``BST_TILE_CACHE_BYTES`` budget, 0 disables)."""
    import jax

    budget = _tile_cache_budget()
    from ..io import prefetch as _prefetch

    if _prefetch.enabled():
        # announce every tile read below to the async prefetcher: the
        # upload loop is serial per view, so later views' chunks fetch
        # (and decode into the chunk LRU) while earlier tiles upload
        boxes = []
        for p in cp.plans:
            ds = loader.open(p.view, 0)
            if hasattr(ds, "prefetch_box"):
                boxes.append((ds, (0,) * len(ds.shape),
                              tuple(int(s) for s in ds.shape)))
        _prefetch.submit_boxes(boxes)
    tiles = []
    with profiling.span("fusion.h2d_tiles"):
        h2d = saved = 0
        for p in cp.plans:
            ds = loader.open(p.view, 0)
            key = _tile_cache_key(ds) if budget > 0 else None
            if key is not None:
                with _TILE_CACHE_LOCK:
                    ent = _TILE_CACHE.get(key)
                    if ent is not None:
                        _TILE_CACHE.move_to_end(key)
                if ent is not None:
                    _TILE_HITS.inc()
                    _TILE_HIT_BYTES.inc(int(ent.nbytes))
                    tiles.append(ent)
                    continue
            arr = ds.read_full()
            t = jax.device_put(arr)
            h2d += int(t.nbytes)
            if arr.dtype.kind in "iu" and arr.dtype.itemsize < 4:
                saved += arr.size * 4 - arr.nbytes  # vs a float32 upload
            if key is not None:
                _TILE_MISSES.inc()
                with _TILE_CACHE_LOCK:
                    # purge write-orphaned generations of this dataset NOW
                    # (they could otherwise pin dead HBM until LRU pressure)
                    for stale in [k for k in _TILE_CACHE
                                  if k[:2] == key[:2] and k != key]:
                        _tile_cache_drop_locked(stale)
                    if int(t.nbytes) <= budget:  # oversize: never resident
                        _TILE_CACHE[key] = t
                        _TILE_CACHE_BYTES[0] += int(t.nbytes)
                        while _TILE_CACHE_BYTES[0] > budget and len(_TILE_CACHE) > 1:
                            _tile_cache_drop_locked(next(iter(_TILE_CACHE)))
            tiles.append(t)
        _H2D_BYTES.inc(h2d)
        _H2D_SAVED.inc(saved)
        return tiles


def dispatch_composite(cp: CompositePlan, tiles, fusion_type, out_dtype,
                       masks, min_intensity, max_intensity):
    """Run the compiled composite program; returns the device-resident
    converted output (does not block)."""
    with_coeffs = cp.coeffs is not None
    from ..parallel.mesh import record_compile_bucket

    record_compile_bucket(("composite", cp.out_shape, cp.windows, cp.n_offs,
                           cp.pad, fusion_type, out_dtype, masks,
                           with_coeffs, cp.kinds))
    fuser = F.make_translation_composite(
        cp.out_shape, cp.windows, cp.n_offs, pad=cp.pad,
        fusion_type=fusion_type, out_dtype=out_dtype, masks=masks,
        with_coeffs=with_coeffs, kinds=cp.kinds)
    extra = (cp.coeffs, cp.coeff_affs) if with_coeffs else ()
    return fuser(tiles, cp.fracs, cp.img_dims, cp.borders, cp.ranges,
                 cp.inside_offs, np.float32(min_intensity),
                 np.float32(max_intensity), cp.diags, cp.offs, *extra)


def _try_fuse_volume_device(
    sd, loader, views, bbox, fusion_type, blend,
    anisotropy, out_dtype, min_intensity, max_intensity, masks, stats,
    mask_offset=(0.0, 0.0, 0.0), coefficients=None,
):
    """Whole-volume device-resident fusion via the static composite kernel
    (ops.fusion.make_translation_composite): per-view static output windows,
    8 statically-shifted slices, separable blend — no dynamic slices, so the
    XLA program is pure fused elementwise work at HBM speed.

    Applies when every view is translation-registered at stored level 0 and
    the tile stack fits the device budget; returns the fused volume as a
    DEVICE array (converted to out_dtype) ready for pipelined D2H via
    _drain_device_volume, or None to fall back to the per-block path."""
    cp = plan_composite_volume(sd, loader, views, bbox, anisotropy, blend,
                               masks, mask_offset, coefficients)
    if cp is None:
        return None
    tiles = upload_composite_tiles(loader, cp)
    if stats is not None:
        stats.compile_keys.add((cp.out_shape, cp.windows, fusion_type,
                                out_dtype, masks, "composite"))
    with profiling.span("fusion.kernel"):
        out = dispatch_composite(cp, tiles, fusion_type, out_dtype, masks,
                                 min_intensity, max_intensity)
        if profiling.get().enabled:
            # span attribution only: costs one round-trip, so skip it when
            # nobody reads the spans (the drain's D2H is the real sync)
            profiling.device_sync(out)
    return out


def _epilogue_pyramid_device(vol, pyramid, out_dtype):
    """Chain the downsample pyramid ON DEVICE from the converted full-res
    volume (the fused multiscale epilogue, ROADMAP item 3a): each level is
    a strided float32 mean of the previous one, quantized back to the
    storage dtype between steps — exactly what the container-reread path
    sees when it reads the stored previous level, so levels are
    bit-identical to ``downsample_pyramid_level`` output. Dispatch only
    (the drain's D2H is the real sync). Returns [(PyramidLevel, device
    array), ...]."""
    from ..ops.downsample import downsample_level

    levels = []
    prev = vol
    with profiling.span("fusion.epilogue.kernel"):
        for lv in pyramid:
            prev = downsample_level(prev, tuple(int(v) for v in lv.rel),
                                    tuple(int(v) for v in lv.dims),
                                    str(out_dtype))
            levels.append((lv, prev))
    return levels


def _drain_device_volume(out, out_ds, zarr_ct, pyramid=(),
                         out_dtype="float32"):
    """Pipelined D2H + write of a device-resident fused volume and its
    epilogue ``pyramid`` levels: slab every level along x in storage-chunk
    multiples (each slab write touches its chunks exactly once), start all
    transfers asynchronously, and let a thread pool overlap the remaining
    transfers with compression + disk writes. Every fused voxel crosses
    the wire exactly once; the pyramid rides the same drain instead of a
    second read-modify-write pass over the container.

    Dispatch order matters: the full-res slab transfers are primed FIRST,
    then the epilogue levels are computed (they queue behind the slab
    slices on the device stream) — s0 lands earliest and the pyramid
    reductions overlap the full-res compression + writes instead of
    stalling them. Returns the [(PyramidLevel, device array), ...] it
    materialized."""
    from ..io.chunkstore import StorageFormat
    from ..utils.threads import CtxThreadPool

    # ~8 MB slabs over ~8 streams measured best on the wire-limited link
    # (the knob's default); --prefetch/io_threads does not reach this
    # drain — BST_WRITE_THREADS is its one width control
    io_threads = config.get_int("BST_WRITE_THREADS") or 1
    if getattr(out_ds.store, "format", None) == StorageFormat.HDF5:
        io_threads = 1  # h5py writers must not run concurrently

    def slab_plan(vol, ds):
        bs = ds.block_size
        step = max(int(bs[0]), 1)
        target = 8 << 20
        row_bytes = int(np.prod(vol.shape[1:])) * vol.dtype.itemsize
        if row_bytes * step < target:
            step = int(np.ceil(target / max(row_bytes * step, 1))) * step
        return [(x0, vol[x0:min(x0 + step, vol.shape[0])])
                for x0 in range(0, vol.shape[0], step)]

    from ..dag.stream import handoff_active

    handoff = handoff_active() and zarr_ct is None

    def prime(jobs):
        if handoff:
            return  # slabs are offered to the HBM handoff tier first —
            # pre-starting their D2H would burn wire for claimed slabs
        for _, _, slab, _ in jobs:
            try:
                slab.copy_to_host_async()
            except AttributeError:
                pass

    jobs = [(out_ds, x0, slab, False) for x0, slab in slab_plan(out, out_ds)]
    prime(jobs)
    levels = _epilogue_pyramid_device(out, pyramid, out_dtype)
    for lv, lvol in levels:
        lvl_jobs = [(lv.ds, x0, slab, True)
                    for x0, slab in slab_plan(lvol, lv.ds)]
        prime(lvl_jobs)
        jobs += lvl_jobs

    def drain(job):
        from ..utils import cancel as _cancel

        # per-slab safe point: a cancelled composite-path job stops
        # fetching/writing between slabs (writes are chunk-atomic)
        _cancel.check("fusion drain")
        ds, x0, slab, epi = job
        # device-resident handoff: a streamed same-mesh consumer takes the
        # slab as device chunks straight out of HBM — no D2H, no write, no
        # container decode on its side (dag.stream publishes + accounts)
        if handoff and ds.write_device(slab, (x0, 0, 0)):
            return
        nb = int(slab.nbytes)   # known pre-fetch: device arrays size freely
        d2h_span = (profiling.span("fusion.epilogue.d2h", item=int(x0),
                                   nbytes=nb) if epi else
                    profiling.span("fusion.d2h", item=int(x0), nbytes=nb))
        with d2h_span:
            data = np.asarray(slab)
            _D2H_BYTES.inc(data.nbytes)
            if epi:
                _EPI_D2H_BYTES.inc(data.nbytes)
            if data.dtype.kind in "iu" and data.dtype.itemsize < 4:
                # output converted to storage dtype ON DEVICE: the wire
                # carries uint16/uint8, not the kernel's float32
                _D2H_SAVED.inc(data.size * 4 - data.nbytes)
        write_span = (profiling.span("fusion.epilogue.write", item=int(x0),
                                     nbytes=nb) if epi else
                      profiling.span("fusion.write", item=int(x0), nbytes=nb))
        with write_span:
            if zarr_ct is not None:
                c, t = zarr_ct
                ds.write(data[..., None, None], (x0, 0, 0, c, t))
            else:
                ds.write(data, (x0, 0, 0))
            if epi:
                _EPI_WRITE_BYTES.inc(data.nbytes)
            else:
                _VOXELS_DONE.inc(int(data.size))

    with CtxThreadPool(max_workers=max(1, io_threads)) as pool:
        list(pool.map(drain, jobs))
    return levels

def _write_block(out_ds, data, block, zarr_ct):
    from ..parallel.mesh import drain_device

    with profiling.span("fusion.write", item=tuple(map(int, block.offset)),
                        nbytes=int(data.nbytes), device=drain_device()):
        if zarr_ct is not None:
            c, t = zarr_ct
            out_ds.write(data[..., None, None], (*block.offset, c, t))
        else:
            out_ds.write(data, block.offset)
    _VOXELS_DONE.inc(int(data.size))


def _write_epilogue_block(ds, data, offset, zarr_ct):
    """One pyramid sub-block produced by the sharded per-block epilogue,
    written by the device worker that drained it (its bytes crossed the
    wire inside the batch shard fetch — counted as epilogue traffic
    here)."""
    from ..parallel.mesh import drain_device

    with profiling.span("fusion.epilogue.write",
                        item=tuple(map(int, offset)),
                        nbytes=int(data.nbytes), device=drain_device()):
        if zarr_ct is not None:
            c, t = zarr_ct
            ds.write(data[..., None, None], (*offset, c, t))
        else:
            ds.write(data, offset)
    _EPI_D2H_BYTES.inc(int(data.nbytes))
    _EPI_WRITE_BYTES.inc(int(data.nbytes))


def eligible_epilogue_levels(pyramid, compute_block, full_dims):
    """The PREFIX of pyramid levels the per-block sharded epilogue can
    materialize. Per axis, a level's absolute factor must (1) divide the
    compute block exactly, so block boundaries align with reduction
    windows; (2) be no wider than the axis, so no window needs the
    edge-replication only the whole-volume composite path can do; and
    (3) leave the per-block level piece a whole multiple of the level
    dataset's storage chunk, so concurrent per-device writers never
    read-modify-write a shared chunk. Later levels chain off earlier
    ones, so the first ineligible level stops the prefix; the remaining
    levels fall back to the container-reread downsample stage (which then
    reads the much smaller last materialized level, not full res)."""
    out = []
    for lv in (pyramid or ()):
        ok = all(int(cb) % int(a) == 0 and int(dim) >= int(a)
                 for cb, a, dim in zip(compute_block, lv.abs_factor,
                                       full_dims))
        if ok:
            chunk = lv.ds.block_size[:3]
            ok = all((int(cb) // int(a)) % max(int(c), 1) == 0
                     for cb, a, c in zip(compute_block, lv.abs_factor,
                                         chunk))
        if not ok:
            break
        out.append(lv)
    return out


def _fuse_volume_sharded(
    sd, loader, views, out_ds, bbox, compute_block, fusion_type, blend,
    aniso, out_dtype, min_intensity, max_intensity, masks, mask_offset,
    zarr_ct, stats, coefficients, n_dev, io_threads, progress,
    patch_quantum=32, pyramid=None,
):
    """Multi-device per-block fusion: the block work list is bucketed by
    kernel signature, batched ``n_dev`` at a time, sharded over the local
    device mesh — the TPU replacement of the reference's Spark map over
    grid blocks (SparkAffineFusion.java:480-482).

    Host prefetch for batch k+1 overlaps device compute for batch k
    (double buffering); writers own disjoint chunks so no write needs a
    lock (the reference's no-shuffle invariant). Each device's worker
    drains and WRITES its own shard directly (``device_drain`` in
    parallel.mesh) — the driver thread performs no D2H and no writes —
    except into h5py containers, whose single-writer rule keeps the
    driver-drained path. ``pyramid`` levels whose factors divide
    ``compute_block`` are produced per block as a kernel epilogue and
    written by the same per-device workers."""
    from ..io.chunkstore import StorageFormat
    from ..parallel.mesh import make_mesh, make_sharded_fuser, run_sharded_batches
    from ..utils.threads import CtxThreadPool

    grid = create_grid(bbox.shape, compute_block, compute_block)
    inside_offset = mask_offset if masks else (0.0, 0.0, 0.0)
    epi = eligible_epilogue_levels(pyramid, compute_block, bbox.shape)
    epi_rels = tuple(tuple(int(v) for v in lv.rel) for lv in epi)
    direct = getattr(out_ds.store, "format", None) != StorageFormat.HDF5

    # multi-host: slice the grid BEFORE bucketing so batching heuristics
    # (per_dev) see this process's actual work list
    from ..parallel.distributed import partition_items

    grid = partition_items(grid)
    planned = []
    for block in grid:
        bg = Interval.from_shape(compute_block, block.offset).translate(bbox.min)
        plans = plan_block(sd, loader, views, bg, aniso)
        stats.blocks += 1
        if not plans:
            stats.skipped_empty += 1
            continue
        planned.append((block, bg, plans))

    # bucket by compiled-kernel signature
    buckets: dict[tuple, list] = {}
    for item in planned:
        _, _, plans = item
        vb = F.bucket_views(len(plans))
        if coefficients is None and all(p.is_translation for p in plans):
            key = ("shift", vb)
        else:
            pshape = F.bucket_shape(
                np.max([p.patch_interval.shape for p in plans], axis=0),
                patch_quantum)
            if coefficients is None and all(p.is_diagonal for p in plans):
                key = ("sep", pshape, vb)
            else:
                key = ("gather", pshape, vb)
        buckets.setdefault(key, []).append(item)

    mesh = make_mesh(n_dev)
    mi = np.float32(min_intensity)
    ma = np.float32(max_intensity)
    pwritten: dict[tuple, int] = {}
    pool = CtxThreadPool(max_workers=max(1, io_threads))
    try:
        for key, items in sorted(buckets.items(), key=lambda kv: str(kv[0])):
            kernel, vb = key[0], key[-1]
            fuser = make_sharded_fuser(
                mesh, compute_block, fusion_type, kernel=kernel,
                with_coeffs=coefficients is not None and kernel == "gather",
                out_dtype=out_dtype, masks=masks, pyramid=epi_rels,
            )
            stats.compile_keys.add((compute_block, key, fusion_type,
                                    out_dtype, masks, "sharded"))

            def build(item, _key=key, _kernel=kernel, _vb=vb):
                block, bg, plans = item
                if _kernel == "shift":
                    arrs = _shift_inputs(loader, plans, bg, compute_block,
                                         _vb, blend, inside_offset)
                elif _kernel == "sep":
                    arrs = _sep_inputs(sd, loader, plans, _key[1], _vb,
                                       blend, inside_offset)
                else:
                    arrs = _gather_inputs(sd, loader, plans, _key[1], _vb,
                                          blend, inside_offset, coefficients)
                    if coefficients is None:
                        arrs = arrs[:8]
                return arrs

            def prefetch_boxes(item, _key=key, _kernel=kernel):
                # the same source boxes build() will read (io/prefetch.py
                # feed: batch k+2's crops fetch while batch k computes)
                block, bg, plans = item
                boxes = []
                for p in plans:
                    if _kernel == "shift":
                        tlevel = (p.inv_total[:, :3]
                                  @ np.asarray(bg.min, np.float64)
                                  + p.inv_total[:, 3])
                        off = np.floor(tlevel).astype(np.int64)
                        shp = tuple(int(s) + 1 for s in compute_block)
                    else:
                        off, shp = p.patch_offset, _key[1]
                    b = loader.prefetch_box(
                        p.view, p.level, tuple(int(o) for o in off), shp)
                    if b is not None:
                        boxes.append(b)
                return boxes

            def kernel_call(*stacked):
                # dispatch only — return the DEVICE arrays and let the work
                # loop's per-device drains fetch them, so the early-dispatch
                # window actually overlaps compute with this batch's D2H
                # (a blocking np.asarray here serialized the pipeline,
                # ADVICE r5); wsum is dropped on device, never fetched.
                # Epilogue pyramid levels ride the same dispatch.
                with profiling.span("fusion.kernel"):
                    out, _wsum, *lvls = fuser(mi, ma, *stacked)
                    return (out, *lvls)

            written: dict[tuple, int] = {}

            def epi_pieces(block, lvls):
                for lv, ldata in zip(epi, lvls):
                    a = lv.abs_factor
                    off = tuple(int(o) // int(f)
                                for o, f in zip(block.offset, a))
                    end = tuple(min(int(d), (int(o) + int(s)) // int(f))
                                for d, o, s, f in zip(lv.dims, block.offset,
                                                      block.size, a))
                    size = tuple(e - o for e, o in zip(end, off))
                    if any(s <= 0 for s in size):
                        continue
                    yield lv, ldata, off, size

            def consume(item, data, *lvls):
                block, bg, plans = item
                sl = tuple(slice(0, s) for s in block.size)
                _write_block(out_ds, data[sl], block, zarr_ct)
                written[tuple(block.offset)] = int(np.prod(block.size))
                for lv, ldata, off, size in epi_pieces(block, lvls):
                    _write_epilogue_block(
                        lv.ds, ldata[tuple(slice(0, s) for s in size)],
                        off, zarr_ct)
                    pwritten[(lv.abs_factor, off)] = int(np.prod(size))

            def device_consume(item, data, *lvls):
                # offer the block to the HBM handoff tier BEFORE any D2H:
                # a claimed block stays device-resident for the streamed
                # consumer stage and its rows never cross the wire. All or
                # nothing per item — a partial claim host-writes everything
                # (on_write supersedes the device copies, so no stale read)
                block, bg, plans = item
                sl = tuple(slice(0, s) for s in block.size)
                if not out_ds.write_device(data[sl], block.offset):
                    return False
                for lv, ldata, off, size in epi_pieces(block, lvls):
                    piece = ldata[tuple(slice(0, s) for s in size)]
                    if not lv.ds.write_device(piece, off):
                        return False
                    pwritten[(lv.abs_factor, off)] = int(np.prod(size))
                written[tuple(block.offset)] = int(np.prod(block.size))
                return True

            # pack several blocks per device per batch: fusion dispatches
            # are compute-light, so fewer+bigger launches amortize dispatch
            # and keep the host IO pipeline ahead (VERDICT r3 item 1b) — but
            # bounded by a per-device staging budget so configurations that
            # fit at per_dev=1 cannot OOM
            if kernel == "shift":
                item_bytes = vb * int(np.prod(
                    [c + 1 for c in compute_block])) * 4
            else:
                item_bytes = vb * int(np.prod(key[1])) * 4
            budget = config.get_bytes("BST_PER_DEV_BUDGET")
            per_dev = max(1, min(4, len(items) // max(n_dev, 1),
                                 budget // max(item_bytes, 1)))
            # device-resident per item: converted block + f32 wsum + the
            # epilogue levels
            item_out = int(np.prod(compute_block)) \
                * (np.dtype(out_dtype or "float32").itemsize + 4)
            for lv in epi:
                item_out += int(np.prod(
                    [int(c) // int(a) for c, a in zip(compute_block,
                                                      lv.abs_factor)])) \
                    * np.dtype(out_dtype or "float32").itemsize
            from ..dag.stream import handoff_active

            run_sharded_batches(
                items, build, kernel_call, consume, n_dev, pool,
                label=f"fusion batch {key}", progress=progress,
                per_dev=per_dev,
                out_bytes_per_item=item_out,
                workspace_mult=3.0,
                device_drain=direct,
                device_consume=(device_consume
                                if handoff_active() and zarr_ct is None
                                else None),
                prefetch_boxes=prefetch_boxes,
            )
            stats.voxels += sum(written.values())
    finally:
        pool.shutdown(wait=True)
    stats.pyramid_levels = len(epi)
    stats.pyramid_voxels += sum(pwritten.values())


def _record_fusion_stage(stage: str, stats: "FusionStats",
                         path_kind: str) -> None:
    """File the driver's end-of-stage summary with the telemetry layer
    (block/voxel totals the reference reads off the Spark UI)."""
    observe.progress.record_stage(
        stage,
        done=stats.blocks - stats.skipped_empty,
        total=stats.blocks,
        blocks=stats.blocks,
        skipped_empty=stats.skipped_empty,
        voxels=stats.voxels,
        seconds=round(stats.seconds, 3),
        rate_per_s=round((stats.blocks - stats.skipped_empty)
                         / max(stats.seconds, 1e-9), 3),
        voxels_per_s=round(stats.voxels / max(stats.seconds, 1e-9), 1),
        compile_keys=len(stats.compile_keys),
        path=path_kind,
        # epilogue output reported SEPARATELY from the full-res rate so
        # pyramid voxels can never masquerade as (or hide) a kernel change
        pyramid_levels=stats.pyramid_levels,
        pyramid_voxels=stats.pyramid_voxels,
        voxels_per_s_incl_pyramid=round(
            (stats.voxels + stats.pyramid_voxels)
            / max(stats.seconds, 1e-9), 1),
    )


@profiling.span("fusion.stage")
def fuse_volume(
    sd: SpimData,
    loader: ViewLoader,
    views: list[ViewId],
    out_ds: Dataset,
    bbox: Interval,
    block_size: tuple[int, ...],
    block_scale: tuple[int, ...] = (2, 2, 1),
    fusion_type: str = "AVG_BLEND",
    blend: BlendParams | None = None,
    anisotropy_factor: float = float("nan"),
    out_dtype: str = "float32",
    min_intensity: float | None = None,
    max_intensity: float | None = None,
    masks: bool = False,
    mask_offset: tuple[float, float, float] = (0.0, 0.0, 0.0),
    zarr_ct: tuple[int, int] | None = None,
    progress: bool = False,
    coefficients: dict[ViewId, np.ndarray] | None = None,
    devices: int | None = None,
    io_threads: int = 4,
    device_resident: bool | None = None,
    pyramid: list[PyramidLevel] | None = None,
) -> FusionStats:
    """Fuse ``views`` into ``out_ds`` over ``bbox``.

    ``zarr_ct``: (channel, timepoint) indices when out_ds is a 5-D OME-ZARR
    dataset (3-D block embedded at [...,c,t], SparkAffineFusion.java:630-651).
    ``coefficients``: per-view intensity-correction grids (models.intensity).
    ``devices``: number of local devices to shard the block grid over
    (default: all); with one device the whole-volume device-resident scan
    path is tried first (``device_resident=False`` disables it).
    ``pyramid``: downsample levels to materialize as a fused multiscale
    epilogue while the data is device-resident — shipped in the same
    drain, bit-identical to the container-reread downsample. The composite
    path produces every level; the sharded path the
    :func:`eligible_epilogue_levels` prefix; the per-block fallback none
    (``stats.pyramid_levels`` says how many were done — the rest is the
    downsample stage's job).
    """
    stats = FusionStats()
    t0 = time.time()
    aniso = anisotropy_transform(anisotropy_factor)
    compute_block = tuple(b * s for b, s in zip(block_size, block_scale))
    grid = create_grid(bbox.shape, compute_block, block_size)
    if min_intensity is None or max_intensity is None:
        if out_dtype == "uint8":
            min_intensity, max_intensity = 0.0, 255.0
        elif out_dtype == "uint16":
            min_intensity, max_intensity = 0.0, 65535.0
        else:
            min_intensity, max_intensity = 0.0, 1.0

    import jax

    n_dev = devices if devices is not None else len(jax.local_devices())
    if n_dev > 1:
        _fuse_volume_sharded(
            sd, loader, views, out_ds, bbox, compute_block, fusion_type,
            blend or BlendParams(), aniso, out_dtype, min_intensity,
            max_intensity, masks, mask_offset, zarr_ct, stats, coefficients,
            n_dev, io_threads, progress, pyramid=pyramid,
        )
        stats.seconds = time.time() - t0
        _record_fusion_stage("affine-fusion", stats, "sharded")
        return stats

    # multi-host with one local device: each process takes its slice of the
    # block grid (strided partition); the whole-volume composite path is
    # skipped — it would compute and write the full volume on every host
    from ..parallel.distributed import partition_items, world

    multi_process = world()[1] > 1
    if multi_process:
        grid = partition_items(grid)

    use_composite = device_resident is not False and not multi_process
    vol = None if not use_composite else (
        _try_fuse_volume_device(
            sd, loader, views, bbox, fusion_type,
            blend or BlendParams(), aniso, out_dtype, min_intensity,
            max_intensity, masks, stats, mask_offset=mask_offset,
            coefficients=coefficients,
        ))
    if vol is not None:
        levels = _drain_device_volume(vol, out_ds, zarr_ct,
                                      pyramid=pyramid or (),
                                      out_dtype=out_dtype)
        stats.blocks = len(grid)
        stats.voxels = bbox.num_elements
        stats.pyramid_levels = len(levels)
        stats.pyramid_voxels = sum(int(np.prod(lv.dims))
                                   for lv, _ in levels)
        stats.seconds = time.time() - t0
        _record_fusion_stage("affine-fusion", stats, "composite")
        return stats

    def process(block: GridBlock) -> None:
        dev_out = _fuse_grid_block_device(
            sd, loader, views, block, bbox, fusion_type, blend, aniso,
            compute_block_shape=compute_block, stats=stats,
            inside_offset=mask_offset if masks else (0.0, 0.0, 0.0),
            coefficients=coefficients, pool=pool,
        )
        stats.blocks += 1
        if dev_out is None:
            stats.skipped_empty += 1
            return
        bkey = tuple(map(int, block.offset))
        nvox = int(np.prod(block.size))
        out_nbytes = nvox * np.dtype(out_dtype).itemsize
        # every fetch of the block is one span: the float32 block and its
        # weights come to the host, and the block goes up again to be
        # converted to the output type and comes back a second time
        with profiling.span("fusion.d2h", item=bkey, nbytes=out_nbytes):
            fused, wsum = _fetch_block(dev_out, block)
            if not masks:
                data = jax.device_get(
                    F.convert_intensity(
                        fused, np.float32(min_intensity),
                        np.float32(max_intensity), out_dtype=out_dtype,
                    )
                )
                _H2D_BYTES.inc(int(fused.nbytes))
                _D2H_BYTES.inc(int(data.nbytes))
        if masks:
            out = (wsum > 0).astype(np.float32)
            if out_dtype != "float32":
                out *= float(np.iinfo(np.dtype(out_dtype)).max)
            data = out.astype(out_dtype)
        with profiling.span("fusion.write", item=bkey,
                            nbytes=int(data.nbytes)):
            if zarr_ct is not None:
                c, t = zarr_ct
                out5 = data[..., None, None]
                out_ds.write(out5, (*block.offset, c, t))
            else:
                out_ds.write(data, block.offset)
        stats.voxels += nvox
        _VOXELS_DONE.inc(nvox)
        if progress:
            observe.log(f"  block {block.offset} done ({len(grid)} total)",
                        stage="affine-fusion")

    from ..parallel.retry import run_with_retry
    from ..utils.threads import CtxThreadPool

    with CtxThreadPool(max_workers=max(1, io_threads)) as pool:
        run_with_retry(grid, process, label="fusion block")
    stats.seconds = time.time() - t0
    _record_fusion_stage("affine-fusion", stats, "per-block")
    return stats
