"""Affine-fusion XLA kernel: resample + blend all views into an output block.

TPU-native re-design of the reference's core fusion pipeline
(``BlkAffineFusion.initWithIntensityCoefficients``, SparkAffineFusion.java:602-615):
for each output block, every overlapping view is inverse-affine resampled
(tri-linear) out of a host-prefetched source patch, weighted with a cosine
ramp at the image borders (FusionType AVG_BLEND), accumulated, and normalized.
One fused XLA computation per (block shape, patch bucket, view bucket) — all
shapes static; views are a leading axis and invalid/padded views are masked,
so a single compile serves every block with the same bucket.

Three kernels, picked per block by the host planner
(models/affine_fusion.py): ``fuse_block_shift`` (translations: eight shifted
slices), ``fuse_block_sep`` (diagonal affines: three GEMMs) and
``fuse_block`` (general affines). The general kernel fetches no tap on its
own: it walks the block in small output tiles, fetches each tile's window
of source rows once (one index a tile) and selects the eight taps of every
voxel with dense hat weights, the z taps as a float32 contraction on the
MXU (``_tile_sample``). A scalar gather a tap — what ``_trilinear_sample``
does, kept for the displacement fields of ops/nonrigid.py — runs at
16 ns an index on XLA:TPU, thirty times slower at a compute block.

Fusion types (reference enum use at SparkAffineFusion.java:124-125):
AVG, AVG_BLEND, MAX_INTENSITY, FIRST_WINS (lowest view wins),
LAST_WINS (highest view wins).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

FUSION_TYPES = ("AVG", "AVG_BLEND", "MAX_INTENSITY", "FIRST_WINS", "LAST_WINS")


def _trilinear_sample(patch: jnp.ndarray, pts: jnp.ndarray) -> jnp.ndarray:
    """Sample one (Px,Py,Pz) patch at (N,3) float coords; clamped at edges.

    One scalar gather a tap: right for coordinates with no structure (the
    displaced ones of ops/nonrigid.py); ``_tile_sample`` is the fetch for
    coordinates that come in compact tiles."""
    px, py, pz = patch.shape
    p0 = jnp.floor(pts)
    f = pts - p0
    p0 = p0.astype(jnp.int32)
    x0 = jnp.clip(p0[:, 0], 0, px - 1)
    y0 = jnp.clip(p0[:, 1], 0, py - 1)
    z0 = jnp.clip(p0[:, 2], 0, pz - 1)
    x1 = jnp.clip(p0[:, 0] + 1, 0, px - 1)
    y1 = jnp.clip(p0[:, 1] + 1, 0, py - 1)
    z1 = jnp.clip(p0[:, 2] + 1, 0, pz - 1)
    flat = patch.ravel()
    syz = py * pz

    def g(xi, yi, zi):
        return jnp.take(flat, xi * syz + yi * pz + zi)

    fx, fy, fz = f[:, 0], f[:, 1], f[:, 2]
    c000 = g(x0, y0, z0) * (1 - fx) * (1 - fy) * (1 - fz)
    c100 = g(x1, y0, z0) * fx * (1 - fy) * (1 - fz)
    c010 = g(x0, y1, z0) * (1 - fx) * fy * (1 - fz)
    c110 = g(x1, y1, z0) * fx * fy * (1 - fz)
    c001 = g(x0, y0, z1) * (1 - fx) * (1 - fy) * fz
    c101 = g(x1, y0, z1) * fx * (1 - fy) * fz
    c011 = g(x0, y1, z1) * (1 - fx) * fy * fz
    c111 = g(x1, y1, z1) * fx * fy * fz
    return c000 + c100 + c010 + c110 + c001 + c101 + c011 + c111


# ---------------------------------------------------------------------------
# General affines: the tap fetch by tile.
#
# An affine maps a small output tile into a small source box, so the eight
# taps of a tile's voxels share a few source rows. The block is walked in
# x slabs of tiles; a tile's (x, y) window of whole z rows is fetched with
# ONE index, and the taps are selected by the hat weight
# max(0, 1 - |coordinate - voxel|), which is trilinear interpolation written
# without indices: along z as a float32 contraction over the row (MXU,
# precision HIGHEST: stored values reach 65535), along x and y as a weighted
# sum over the window. The window's size is static, a guess from the shapes
# alone (``_tile_window``); when an affine needs more (a patch clipped at the
# image edge) a loop with a run-time count fetches further windows, so the
# result never depends on the guess.
# ---------------------------------------------------------------------------

_TILE_VOXELS = 256   # output voxels a tile: the rows of one MXU contraction


def _tile_grid(block_shape: Sequence[int]):
    """Static (tile shape, tiles per axis): the block cut n times an axis,
    so a tile keeps the block's proportions and its source box the
    patch's."""
    n = max(1, round((float(np.prod(block_shape)) / _TILE_VOXELS) ** (1 / 3)))
    tile = tuple(-(-int(b) // n) for b in block_shape)
    grid = tuple(-(-int(b) // t) for b, t in zip(block_shape, tile))
    return tile, grid


def _tile_window(px: int, py: int, grid) -> tuple[int, int]:
    """Static (x, y) extent of the source window fetched a tile: the patch
    is the block's image, so a tile's image is the patch over the tiles an
    axis, and one voxel more on either side for the taps."""
    n = max(grid)
    return -(-px // n) + 2, -(-py // n) + 2


def _slab_coords(ix, tile, grid) -> jnp.ndarray:
    """(N,3) float32 block voxel indices of x slab ``ix`` in tile order:
    (tile y, tile z, x, y, z in the tile)."""
    shape = (grid[1], grid[2]) + tuple(tile)

    def iota(axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    xyz = (ix * tile[0] + iota(2),
           iota(0) * tile[1] + iota(3),
           iota(1) * tile[2] + iota(4))
    return jnp.stack([c.astype(jnp.float32).ravel() for c in xyz], axis=-1)


def _hat(d):
    return jnp.maximum(0.0, 1.0 - jnp.abs(d))


def _pad_for_windows(patches: jnp.ndarray, box: tuple[int, int]):
    """(V,Px,Py,Pz) patches with room past x and y for a window that starts
    at the last voxel: rows past the patch weigh nought."""
    return jnp.pad(patches, ((0, 0), (0, box[0]), (0, box[1]), (0, 0)))


def _tile_sample(padded: jnp.ndarray, pts: jnp.ndarray,
                 box: tuple[int, int]) -> jnp.ndarray:
    """Trilinear samples of (V,Px,Py,Pz) patches, handed over as
    ``_pad_for_windows`` leaves them, at (V,T,n,3) float coords that come
    in T compact tiles of n; clamped at edges. ``box`` is the (x, y) window
    fetched at once."""
    cx, cy = box
    V, px, py, pz = padded.shape
    px, py = px - cx, py - cy
    # a coordinate clamped to the patch gives the clamped taps' weights
    q = jnp.clip(pts, 0.0, jnp.array([px - 1, py - 1, pz - 1], jnp.float32))
    lo = jnp.floor(q.min(axis=2)).astype(jnp.int32)          # (V,T,3)
    top = jnp.floor(q.max(axis=2)).astype(jnp.int32) + 1
    span = jnp.max(top - lo + 1, axis=(0, 1))
    nbx, nby = -(-span[0] // cx), -(-span[1] // cy)
    view = jnp.broadcast_to(jnp.arange(V)[:, None], lo.shape[:2])
    xs = jnp.arange(cx, dtype=jnp.int32)
    ys = jnp.arange(cy, dtype=jnp.int32)
    hz = _hat(q[..., 2, None] - jnp.arange(pz, dtype=jnp.float32))

    def window(v, x, y):
        return jax.lax.dynamic_slice(padded, (v, x, y, 0), (1, cx, cy, pz))

    def fetch(k, acc):
        ox = lo[..., 0] + (k // nby) * cx                     # (V,T)
        oy = lo[..., 1] + (k % nby) * cy
        rows = jax.vmap(jax.vmap(window))(
            view, jnp.minimum(ox, px), jnp.minimum(oy, py))
        rows = rows.reshape(lo.shape[:2] + (cx * cy, pz)).astype(jnp.float32)
        wx = _hat(q[..., 0, None] - (ox[..., None, None] + xs))
        wy = _hat(q[..., 1, None] - (oy[..., None, None] + ys))
        wxy = (wx[..., :, None] * wy[..., None, :]).reshape(
            q.shape[:3] + (cx * cy,))
        along_z = jnp.einsum("vtnz,vtrz->vtnr", hz, rows,
                             precision=jax.lax.Precision.HIGHEST)
        return acc + jnp.sum(along_z * wxy, axis=-1)

    return jax.lax.fori_loop(0, nbx * nby, fetch,
                             jnp.zeros(q.shape[:3], jnp.float32))


def _blend_weight(
    lpos: jnp.ndarray, img_dim: jnp.ndarray, border: jnp.ndarray,
    blend_range: jnp.ndarray,
) -> jnp.ndarray:
    """Cosine border-ramp blending weight at level-image coords lpos (N,3).

    Per dim: distance to the (border-offset) image edge; 0 outside, cosine
    ramp over ``blend_range`` px, 1 in the interior; total = product
    (mvrecon BlendingRealRandomAccess semantics)."""
    lo = border  # (3,)
    hi = img_dim - 1.0 - border
    d = jnp.minimum(lpos - lo, hi - lpos)  # (N,3) distance to nearest edge
    r = jnp.maximum(blend_range, 1e-6)
    ramp = 0.5 * (jnp.cos((1.0 - d / r) * jnp.pi) + 1.0)
    w = jnp.where(d < 0, 0.0, jnp.where(d < r, ramp, 1.0))
    return jnp.prod(w, axis=-1)


def _patch_coords(affine, coords):
    """Block voxel indices (N,3) -> patch coords (N,3), in float32 whatever
    the backend's matmul default."""
    return (coords[:, 0:1] * affine[:, 0] + coords[:, 1:2] * affine[:, 1]
            + coords[:, 2:3] * affine[:, 2] + affine[:, 3])


def _weigh_one_view(val, p, patch_offset, img_dim, border, blend_range,
                    inside_off, coeff=None, coeff_affine=None):
    """Per-view: correct and weight the samples ``val`` taken at patch
    coords ``p``. Returns (val, inside, w_blend).

    ``inside_off`` expands (+) or shrinks (-) the image box used for the
    inside test — the reference's ``--maskOffset`` for masks mode
    (GenerateComputeBlockMasks, fusion/GenerateComputeBlockMasks.java:84-177).
    ``coeff`` (Cx,Cy,Cz,2): per-view intensity-correction grid [scale,offset]
    sampled at ``coeff_affine @ lpos`` — mvrecon Coefficients applied inside
    the fusion kernel (SparkAffineFusion.java:545-559)."""
    lpos = p + patch_offset  # level-image coords
    if coeff is not None:
        from .nonrigid import _trilinear_vec

        g = lpos @ coeff_affine[:, :3].T + coeff_affine[:, 3]
        so = _trilinear_vec(coeff, g)
        val = so[:, 0] * val + so[:, 1]
    # named scopes are metadata in the HLO: they name the kernel's phases
    # in a device trace and cost nothing at run time
    with jax.named_scope("blend_weights"):
        inside = jnp.all(
            (lpos >= -inside_off) & (lpos <= img_dim - 1.0 + inside_off),
            axis=-1).astype(jnp.float32)
        w_blend = _blend_weight(lpos, img_dim, border, blend_range)
    return val, inside, w_blend


def fuse_block_impl(
    patches: jnp.ndarray,        # (V, Px, Py, Pz) float32 or stored integer
    affines: jnp.ndarray,        # (V, 3, 4) float32: block idx -> patch coords
    patch_offsets: jnp.ndarray,  # (V, 3) float32: patch origin in level coords
    img_dims: jnp.ndarray,       # (V, 3) float32
    borders: jnp.ndarray,        # (V, 3) float32
    blend_ranges: jnp.ndarray,   # (V, 3) float32
    valid: jnp.ndarray,          # (V,) float32 1/0 (padding mask)
    block_shape: tuple[int, int, int],
    fusion_type: str = "AVG_BLEND",
    inside_offs: jnp.ndarray | None = None,  # (V, 3) mask-offset expansion
    coeffs: jnp.ndarray | None = None,       # (V, Cx,Cy,Cz, 2) intensity maps
    coeff_affines: jnp.ndarray | None = None,  # (V, 3, 4) lpos -> grid coords
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fuse one output block. Returns (fused float32 block, weight-sum block).

    Weight-sum doubles as the coverage mask for ``--masks`` mode
    (GenerateComputeBlockMasks equivalent)."""
    # patches stay in their stored dtype (lossless transport downcast —
    # halves h2d bytes and the fetched rows); math is float32
    if inside_offs is None:
        inside_offs = jnp.zeros_like(borders)
    V, px, py, _ = patches.shape
    tile, grid = _tile_grid(block_shape)
    box = _tile_window(px, py, grid)
    padded = _pad_for_windows(patches, box)   # once, not a slab
    per_view = (patch_offsets, img_dims, borders, blend_ranges, inside_offs)
    if coeffs is not None:
        per_view += (coeffs, coeff_affines)

    def slab(ix):
        with jax.named_scope("coords"):
            coords = _slab_coords(ix, tile, grid)
            p = jax.vmap(_patch_coords, in_axes=(0, None))(affines, coords)
        with jax.named_scope("tile_fetch"):
            vals = _tile_sample(
                padded, p.reshape(V, grid[1] * grid[2], -1, 3), box)
        vals, insides, wblends = jax.vmap(_weigh_one_view)(
            vals.reshape(V, -1), p, *per_view)
        with jax.named_scope("accumulate"):
            return _combine_views(vals, insides, wblends, valid, fusion_type)

    def untile(x):
        # (tile x, tile y, tile z, x, y, z) -> the block, its padding cut
        x = x.reshape(grid + tile).transpose(0, 3, 1, 4, 2, 5)
        x = x.reshape(tuple(g * t for g, t in zip(grid, tile)))
        return x[tuple(slice(0, b) for b in block_shape)]

    fused, wsum = jax.lax.map(slab, jnp.arange(grid[0]))
    return untile(fused), untile(wsum)


fuse_block = jax.jit(
    fuse_block_impl, static_argnames=("block_shape", "fusion_type")
)


# ---------------------------------------------------------------------------
# Translation fast path: no gather at all.
#
# When a view's inverse affine has an identity linear part (the common case:
# translation-registered tiles, which is everything before/after a
# translation-model solve), sampling degenerates to EIGHT STATICALLY-SHIFTED
# SLICES of the patch with constant trilinear corner weights, and the blend
# weight is separable per axis. That is pure elementwise arithmetic — the
# shape XLA/TPU wants — with no fetch by index at all, not even the general
# kernel's one window a tile. The host planner picks this kernel per block
# (models/affine_fusion.py).
# ---------------------------------------------------------------------------


def _axis_blend(lp0, n: int, dim, border, blend_range, inside_off=0.0):
    """1-D blend weight + inside mask along one axis, positions lp0+[0..n)."""
    pos = lp0 + jnp.arange(n, dtype=jnp.float32)
    lo = border
    hi = dim - 1.0 - border
    d = jnp.minimum(pos - lo, hi - pos)
    r = jnp.maximum(blend_range, 1e-6)
    ramp = 0.5 * (jnp.cos((1.0 - d / r) * jnp.pi) + 1.0)
    w = jnp.where(d < 0, 0.0, jnp.where(d < r, ramp, 1.0))
    inside = ((pos >= -inside_off) & (pos <= dim - 1.0 + inside_off)).astype(
        jnp.float32)
    return w, inside


def _one_view_shift(patch, frac, lpos0, img_dim, border, blend_range,
                    inside_off, block_shape):
    bx, by, bz = block_shape
    fx, fy, fz = frac[0], frac[1], frac[2]
    val = jnp.zeros(block_shape, jnp.float32)
    with jax.named_scope("shift8"):
        for cx in (0, 1):
            wxc = fx if cx else 1.0 - fx
            for cy in (0, 1):
                wyc = fy if cy else 1.0 - fy
                for cz in (0, 1):
                    wzc = fz if cz else 1.0 - fz
                    sl = jax.lax.slice(
                        patch, (cx, cy, cz), (cx + bx, cy + by, cz + bz)
                    )
                    val = val + (wxc * wyc * wzc) * sl
    with jax.named_scope("blend_weights"):
        wx, ix = _axis_blend(lpos0[0], bx, img_dim[0], border[0],
                             blend_range[0], inside_off[0])
        wy, iy = _axis_blend(lpos0[1], by, img_dim[1], border[1],
                             blend_range[1], inside_off[1])
        wz, iz = _axis_blend(lpos0[2], bz, img_dim[2], border[2],
                             blend_range[2], inside_off[2])
        blend = wx[:, None, None] * wy[None, :, None] * wz[None, None, :]
        inside = ix[:, None, None] * iy[None, :, None] * iz[None, None, :]
    return val, inside, blend


def _axis_blend_at(pos, dim, border, blend_range, inside_off=0.0):
    """1-D blend weight + inside mask at arbitrary float positions (the
    non-unit-step generalization of ``_axis_blend``)."""
    lo = border
    hi = dim - 1.0 - border
    d = jnp.minimum(pos - lo, hi - pos)
    r = jnp.maximum(blend_range, 1e-6)
    ramp = 0.5 * (jnp.cos((1.0 - d / r) * jnp.pi) + 1.0)
    w = jnp.where(d < 0, 0.0, jnp.where(d < r, ramp, 1.0))
    inside = ((pos >= -inside_off) & (pos <= dim - 1.0 + inside_off)).astype(
        jnp.float32)
    return w, inside


def _one_view_sep(patch, diag, t, patch_offset, img_dim, border, blend_range,
                  inside_off, block_shape):
    """One view with a DIAGONAL block->patch affine (axis-aligned scale +
    translation — e.g. translation-registered tiles under --preserveAnisotropy
    z-scaling): trilinear sampling factorizes into three 1-D interpolation
    matrix contractions (GEMMs), no gathers; blending stays separable."""
    L = block_shape
    so = patch
    ws, ins = [], []
    for d in range(3):
        pos = diag[d] * jnp.arange(L[d], dtype=jnp.float32) + t[d]
        m = _separable_interp_matrix(pos, patch.shape[d])
        so = jnp.tensordot(so, m, axes=[[0], [1]])
        lpos = pos + patch_offset[d]
        w, i = _axis_blend_at(lpos, img_dim[d], border[d], blend_range[d],
                              inside_off[d])
        ws.append(w)
        ins.append(i)
    blend = ws[0][:, None, None] * ws[1][None, :, None] * ws[2][None, None, :]
    inside = ins[0][:, None, None] * ins[1][None, :, None] * ins[2][None, None, :]
    return so, inside, blend


def fuse_block_sep_impl(
    patches: jnp.ndarray,       # (V, Px, Py, Pz) float32
    diags: jnp.ndarray,         # (V, 3) diagonal of the block->patch affine
    ts: jnp.ndarray,            # (V, 3) its translation
    patch_offsets: jnp.ndarray,  # (V, 3) patch origin in level coords
    img_dims: jnp.ndarray,      # (V, 3)
    borders: jnp.ndarray,       # (V, 3)
    blend_ranges: jnp.ndarray,  # (V, 3)
    valid: jnp.ndarray,         # (V,)
    block_shape: tuple[int, int, int],
    fusion_type: str = "AVG_BLEND",
    inside_offs: jnp.ndarray | None = None,
):
    if inside_offs is None:
        inside_offs = jnp.zeros_like(borders)
    patches = patches.astype(jnp.float32)  # lossless transport downcast

    def one(*args):
        return _one_view_sep(*args, block_shape=block_shape)

    vals, insides, wblends = jax.vmap(
        one, in_axes=(0, 0, 0, 0, 0, 0, 0, 0),
    )(patches, diags, ts, patch_offsets, img_dims, borders, blend_ranges,
      inside_offs)
    return _combine_views(vals, insides, wblends, valid, fusion_type)


fuse_block_sep = jax.jit(
    fuse_block_sep_impl, static_argnames=("block_shape", "fusion_type")
)


def _combine_views(vals, insides, wblends, valid, fusion_type: str):
    """Combine per-view samples (V, ...) by fusion type -> (fused, wsum)."""
    extra = (1,) * (vals.ndim - 1)
    vmask = valid.reshape(valid.shape + extra)
    if fusion_type == "AVG":
        w = insides * vmask
    elif fusion_type == "AVG_BLEND":
        w = insides * wblends * vmask
    elif fusion_type == "MAX_INTENSITY":
        w = insides * vmask
        fused = jnp.max(jnp.where(w > 0, vals, -jnp.inf), axis=0)
        wsum = jnp.sum(w, axis=0)
        return jnp.where(wsum > 0, fused, 0.0), wsum
    elif fusion_type in ("FIRST_WINS", "LAST_WINS"):
        inside = insides * vmask
        V = vals.shape[0]
        order = jnp.arange(V, dtype=jnp.float32).reshape((V,) + extra)
        if fusion_type == "FIRST_WINS":
            pick = jnp.where(inside > 0, order, jnp.inf)
            sel = jnp.argmin(pick, axis=0)
        else:
            pick = jnp.where(inside > 0, order, -jnp.inf)
            sel = jnp.argmax(pick, axis=0)
        fused = jnp.take_along_axis(vals, sel[None], axis=0)[0]
        wsum = jnp.sum(inside, axis=0)
        return jnp.where(wsum > 0, fused, 0.0), wsum
    else:
        raise ValueError(f"unknown fusion type {fusion_type}")
    wsum = jnp.sum(w, axis=0)
    acc = jnp.sum(vals * w, axis=0)
    fused = jnp.where(wsum > 0, acc / jnp.maximum(wsum, 1e-20), 0.0)
    return fused, wsum


def fuse_block_shift_impl(
    patches: jnp.ndarray,       # (V, bx+1, by+1, bz+1) float32
    fracs: jnp.ndarray,         # (V, 3) in [0,1)
    lpos0: jnp.ndarray,         # (V, 3) level coords of output voxel (0,0,0)
    img_dims: jnp.ndarray,      # (V, 3)
    borders: jnp.ndarray,       # (V, 3)
    blend_ranges: jnp.ndarray,  # (V, 3)
    valid: jnp.ndarray,         # (V,)
    block_shape: tuple[int, int, int],
    fusion_type: str = "AVG_BLEND",
    inside_offs: jnp.ndarray | None = None,  # (V, 3)
):
    if inside_offs is None:
        inside_offs = jnp.zeros_like(borders)
    patches = patches.astype(jnp.float32)  # lossless transport downcast
    vals, insides, wblends = jax.vmap(
        _one_view_shift, in_axes=(0, 0, 0, 0, 0, 0, 0, None)
    )(patches, fracs, lpos0, img_dims, borders, blend_ranges, inside_offs,
      block_shape)
    with jax.named_scope("accumulate"):
        return _combine_views(vals, insides, wblends, valid, fusion_type)


fuse_block_shift = jax.jit(
    fuse_block_shift_impl, static_argnames=("block_shape", "fusion_type")
)


# ---------------------------------------------------------------------------
# Device-resident volume fusion: one dispatch per (channel, timepoint) volume.
#
# Host<->device transfers are the scarce resource (PCIe);
# the per-block path moves every patch across it. Here the source tiles are
# uploaded ONCE as a uint16 stack living in HBM, a lax.scan walks the output
# block grid — per block: gather the K relevant tiles, dynamic-slice the
# needed window out of each, realign (roll) for out-of-range clamping, fuse
# with the shifted-slice kernel — and dynamic-update-slices into the output
# volume, which leaves the device exactly once, already converted to the
# output dtype. The scan carry is donated, so XLA updates in place.
# ---------------------------------------------------------------------------


def _realign(S: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """patch[i] = S[(i + delta) mod n] per axis (wrapped entries are later
    masked by the inside test, so wrap garbage never contributes)."""
    for ax in range(3):
        n = S.shape[ax]
        shift = jnp.mod(delta[ax], n)
        S2 = jnp.concatenate([S, S], axis=ax)
        S = jax.lax.dynamic_slice_in_dim(S2, shift, n, axis=ax)
    return S


def _one_view_device(tile, floor_off, frac, lp0, img_dim, border, blend_range,
                     inside_off, block_shape):
    ps = tuple(s + 1 for s in block_shape)
    tshape = jnp.array(tile.shape, jnp.int32)
    lim = tshape - jnp.array(ps, jnp.int32)
    clamp = jnp.clip(floor_off, 0, lim)
    S = jax.lax.dynamic_slice(tile, tuple(clamp[d] for d in range(3)), ps)
    S = _realign(S, floor_off - clamp).astype(jnp.float32)
    return _one_view_shift(S, frac, lp0, img_dim, border, blend_range,
                           inside_off, block_shape)


def fuse_volume_scan_impl(
    tiles: jnp.ndarray,          # (V, tx, ty, tz) uint16/float32, HBM-resident
    view_idx: jnp.ndarray,       # (B, K) int32 into tiles
    floor_offs: jnp.ndarray,     # (B, K, 3) int32
    fracs: jnp.ndarray,          # (B, K, 3) float32
    lpos0: jnp.ndarray,          # (B, K, 3) float32
    img_dims: jnp.ndarray,       # (B, K, 3) float32 (true dims, pre-padding)
    borders: jnp.ndarray,        # (B, K, 3) float32
    blend_ranges: jnp.ndarray,   # (B, K, 3) float32
    valid: jnp.ndarray,          # (B, K) float32
    block_offsets: jnp.ndarray,  # (B, 3) int32 into the padded output volume
    min_i: jnp.ndarray,
    max_i: jnp.ndarray,
    out_shape: tuple[int, int, int],   # padded to block multiples
    block_shape: tuple[int, int, int],
    fusion_type: str = "AVG_BLEND",
    out_dtype: str = "float32",
    masks: bool = False,
    inside_offs: jnp.ndarray | None = None,  # (B, K, 3)
):
    if inside_offs is None:
        inside_offs = jnp.zeros_like(borders)

    def body(out, p):
        vidx, fo, fr, lp, dim, bo, rg, va, io, boff = p
        tiles_sel = jnp.take(tiles, vidx, axis=0)
        vals, insides, wblends = jax.vmap(
            _one_view_device, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None)
        )(tiles_sel, fo, fr, lp, dim, bo, rg, io, block_shape)
        fused, wsum = _combine_views(vals, insides, wblends, va, fusion_type)
        res = (wsum > 0).astype(jnp.float32) if masks else fused
        out = jax.lax.dynamic_update_slice(out, res, tuple(boff[d] for d in range(3)))
        return out, None

    out0 = jnp.zeros(out_shape, jnp.float32)
    out, _ = jax.lax.scan(
        body, out0,
        (view_idx, floor_offs, fracs, lpos0, img_dims, borders, blend_ranges,
         valid, inside_offs, block_offsets),
    )
    if masks:
        info_max = (1.0 if out_dtype == "float32"
                    else float(np.iinfo(np.dtype(out_dtype)).max))
        return (out * info_max).astype(np.dtype(out_dtype))
    return _convert_intensity_expr(out, min_i, max_i, out_dtype)


fuse_volume_scan = jax.jit(
    fuse_volume_scan_impl,
    static_argnames=("out_shape", "block_shape", "fusion_type", "out_dtype",
                     "masks"),
)


# ---------------------------------------------------------------------------
# Static composite translation fusion: the whole-volume device path, redesigned.
#
# The lax.scan device path (above) walks the block grid with dynamic slices —
# on TPU those force relayouts of unaligned windows and run two orders of
# magnitude below HBM speed. For translation-registered views the right XLA
# program has NO dynamic control flow at all: each view's tile occupies a
# statically-known output window (floor of its world offset), its sub-pixel
# fraction is a constant trilinear mix of EIGHT STATICALLY-SHIFTED tile
# slices, and its blend weight is a separable outer product of 1-D vectors.
# So the volume fuse compiles to a handful of pads, slices, and fused
# elementwise ops — pure bandwidth. One compile per (volume layout) key,
# cached; offsets are baked in as constants.
# ---------------------------------------------------------------------------


def _composite_one_view(P, frac, img_dim, border, blend_range, inside_off,
                        a, L, n, pad):
    """One view's contribution over its static output window.

    ``P``: tile padded by ``pad`` voxels on every side (so the 8 corner
    slices are always in-bounds, including windows widened by --maskOffset).
    ``a``/``L``/``n``: static window start, window length, and integer tile
    offset. Returns (val, inside, blend) of shape L."""
    fx, fy, fz = frac[0], frac[1], frac[2]
    val = jnp.zeros(L, jnp.float32)
    for cx in (0, 1):
        wxc = fx if cx else 1.0 - fx
        for cy in (0, 1):
            wyc = fy if cy else 1.0 - fy
            for cz in (0, 1):
                wzc = fz if cz else 1.0 - fz
                start = (a[0] + n[0] + pad[0] + cx, a[1] + n[1] + pad[1] + cy,
                         a[2] + n[2] + pad[2] + cz)
                sl = jax.lax.slice(
                    P, start, tuple(start[d] + L[d] for d in range(3)))
                val = val + (wxc * wyc * wzc) * sl
    ws, ins = [], []
    for d in range(3):
        pos = (a[d] + n[d]) + jnp.arange(L[d], dtype=jnp.float32) + frac[d]
        lo = border[d]
        hi = img_dim[d] - 1.0 - border[d]
        dd = jnp.minimum(pos - lo, hi - pos)
        r = jnp.maximum(blend_range[d], 1e-6)
        ramp = 0.5 * (jnp.cos((1.0 - dd / r) * jnp.pi) + 1.0)
        ws.append(jnp.where(dd < 0, 0.0, jnp.where(dd < r, ramp, 1.0)))
        ins.append(((pos >= -inside_off[d])
                    & (pos <= img_dim[d] - 1.0 + inside_off[d])
                    ).astype(jnp.float32))
    blend = ws[0][:, None, None] * ws[1][None, :, None] * ws[2][None, None, :]
    inside = ins[0][:, None, None] * ins[1][None, :, None] * ins[2][None, None, :]
    return val, inside, blend


def _composite_one_view_sep(P, diag, off, img_dim, border, blend_range,
                            inside_off, a, L, pad):
    """Diagonal-affine sibling of ``_composite_one_view``: sampling positions
    step by ``diag`` per output voxel, so the tile contribution is three 1-D
    interpolation matrix contractions (GEMMs) over the padded tile — no
    gathers, still a static window."""
    so = P
    ws, ins = [], []
    for d in range(3):
        pos = (diag[d] * (a[d] + jnp.arange(L[d], dtype=jnp.float32))
               + off[d])
        m = _separable_interp_matrix(pos + pad[d], P.shape[d])
        so = jnp.tensordot(so, m, axes=[[0], [1]])
        w, i = _axis_blend_at(pos, img_dim[d], border[d], blend_range[d],
                              inside_off[d])
        ws.append(w)
        ins.append(i)
    blend = ws[0][:, None, None] * ws[1][None, :, None] * ws[2][None, None, :]
    inside = ins[0][:, None, None] * ins[1][None, :, None] * ins[2][None, None, :]
    return so, inside, blend


def _separable_interp_matrix(pos, c: int):
    """(L, c) linear-interpolation matrix for 1-D grid coords ``pos`` (L,),
    edge-clamped: row i holds weights (1-f) at floor(pos_i), f at floor+1.
    Trilinear interpolation of a regular grid at separable coordinates is
    the tensor product of three of these (exact, no gathers)."""
    p = jnp.clip(pos, 0.0, float(c - 1))
    lo = jnp.clip(jnp.floor(p), 0, max(c - 2, 0)).astype(jnp.int32)
    f = p - lo
    cols = jnp.arange(c, dtype=jnp.int32)[None, :]
    return (jnp.where(cols == lo[:, None], 1.0 - f[:, None], 0.0)
            + jnp.where(cols == jnp.minimum(lo + 1, c - 1)[:, None],
                        f[:, None], 0.0))


@functools.lru_cache(maxsize=32)
def make_translation_composite(
    out_shape: tuple[int, int, int],
    windows: tuple,      # per-view ((a0,a1,a2), (b0,b1,b2)) static ints
    n_offs: tuple,       # per-view (3,) static int tile offsets (floor)
    pad: tuple = (1, 1, 1),  # per-axis tile pad (1 + ceil(maskOffset))
    fusion_type: str = "AVG_BLEND",
    out_dtype: str = "float32",
    masks: bool = False,
    with_coeffs: bool = False,
    kinds: tuple = (),   # per-view "shift" | "sep" ("" -> all shift)
):
    """Build + jit the composite fusion program for one volume layout.

    Returned fn(tiles, fracs, img_dims, borders, ranges, inside_offs,
    min_i, max_i[, diags, offs][, coeffs, coeff_affs]) -> converted output
    of ``out_shape``. ``tiles`` is a list of raw (unpadded) per-view tiles
    (any integer/float dtype). Views may mix two sampling kinds: "shift"
    (translation: 8 statically-shifted slices) and "sep" (diagonal affine:
    separable interpolation GEMMs) — ``diags``/``offs`` are consumed by the
    "sep" views. With ``with_coeffs``, per-view (Cx,Cy,Cz,2) intensity grids
    [scale, offset] are applied inside the kernel — trilinear over the
    window via separable interpolation matrices
    (BlkAffineFusion.initWithIntensityCoefficients role)."""
    V = len(windows)
    if not kinds:
        kinds = ("shift",) * V
    any_sep = any(k == "sep" for k in kinds)
    if with_coeffs and any_sep:
        # the in-kernel coefficient interpolation assumes unit-step lpos;
        # the planner routes coeffs+diagonal volumes to the per-block path
        raise ValueError("intensity coefficients with diagonal views are "
                         "handled by the per-block kernels")

    def impl(tiles, fracs, img_dims, borders, ranges, inside_offs, min_i,
             max_i, diags=None, offs=None, coeffs=None, coeff_affs=None):
        if fusion_type == "MAX_INTENSITY":
            acc = jnp.full(out_shape, -jnp.inf, jnp.float32)
        else:
            acc = jnp.zeros(out_shape, jnp.float32)
        wsum = jnp.zeros(out_shape, jnp.float32)
        order = range(V - 1, -1, -1) if fusion_type == "FIRST_WINS" else range(V)
        for v in order:
            (a, b), n = windows[v], n_offs[v]
            L = tuple(b[d] - a[d] for d in range(3))
            if any(s <= 0 for s in L):
                continue
            P = jnp.pad(tiles[v].astype(jnp.float32),
                        tuple((p, p) for p in pad))
            if kinds[v] == "sep":
                val, inside, blend = _composite_one_view_sep(
                    P, diags[v], offs[v], img_dims[v], borders[v], ranges[v],
                    inside_offs[v], a, L, pad)
            else:
                val, inside, blend = _composite_one_view(
                    P, fracs[v], img_dims[v], borders[v], ranges[v],
                    inside_offs[v], a, L, n, pad)
            if with_coeffs:
                # lpos over the window is separable; grid coords through the
                # diagonal coeff affine stay separable -> trilinear of the
                # (Cx,Cy,Cz,2) grid = 3 small tensordots, no gathers.
                # Each step contracts the leading C axis and appends L_d;
                # after 3 steps the layout is (2, L0, L1, L2).
                so = coeffs[v]
                for d in range(3):
                    lpos_d = ((a[d] + n[d])
                              + jnp.arange(L[d], dtype=jnp.float32)
                              + fracs[v][d])
                    gc = lpos_d * coeff_affs[v][d, d] + coeff_affs[v][d, 3]
                    m = _separable_interp_matrix(gc, so.shape[0])
                    so = jnp.tensordot(so, m, axes=[[0], [1]])
                val = so[0] * val + so[1]
            win = tuple(slice(a[d], b[d]) for d in range(3))

            # window updates as slice + combine + dynamic_update_slice with
            # STATIC starts: jnp's .at[win].add lowers to HLO scatter even
            # for static windows, and scatter is the classic TPU lowering
            # cliff (serialized, no vectorization); DUS stays a dense fused
            # update on every backend
            starts = tuple(int(a[d]) for d in range(3))

            def win_update(x, new_region):
                return jax.lax.dynamic_update_slice(x, new_region, starts)

            if fusion_type == "AVG":
                w = inside
            elif fusion_type == "AVG_BLEND":
                w = inside * blend
            elif fusion_type == "MAX_INTENSITY":
                acc = win_update(acc, jnp.maximum(
                    acc[win], jnp.where(inside > 0, val, -jnp.inf)))
                wsum = win_update(wsum, wsum[win] + inside)
                continue
            elif fusion_type in ("FIRST_WINS", "LAST_WINS"):
                acc = win_update(acc, jnp.where(inside > 0, val, acc[win]))
                wsum = win_update(wsum, wsum[win] + inside)
                continue
            else:
                raise ValueError(f"unknown fusion type {fusion_type}")
            acc = win_update(acc, acc[win] + val * w)
            wsum = win_update(wsum, wsum[win] + w)
        if fusion_type in ("MAX_INTENSITY", "FIRST_WINS", "LAST_WINS"):
            fused = jnp.where(wsum > 0, acc, 0.0)
        else:
            fused = jnp.where(wsum > 0, acc / jnp.maximum(wsum, 1e-20), 0.0)
        if masks:
            info_max = (1.0 if out_dtype == "float32"
                        else float(np.iinfo(np.dtype(out_dtype)).max))
            return ((wsum > 0).astype(jnp.float32) * info_max).astype(
                np.dtype(out_dtype))
        return _convert_intensity_expr(fused, min_i, max_i, out_dtype)

    return jax.jit(impl)


def _convert_intensity_expr(block, min_i, max_i, out_dtype: str):
    """Map [min,max] -> full integer range (uint8/uint16) or pass float through
    (reference type converters, SparkAffineFusion.java:497-517)."""
    if out_dtype == "float32":
        return block.astype(jnp.float32)
    info = np.iinfo(np.dtype(out_dtype))
    scaled = (block - min_i) / (max_i - min_i) * float(info.max)
    return jnp.clip(jnp.round(scaled), 0, info.max).astype(np.dtype(out_dtype))


convert_intensity = jax.jit(
    _convert_intensity_expr, static_argnames=("out_dtype",)
)


def bucket_shape(shape: Sequence[int], quantum: int = 32) -> tuple[int, ...]:
    """Round patch shapes up so recompiles are bounded (shape bucketing —
    the central TPU dynamic-shape mitigation, SURVEY.md §7)."""
    return tuple(int(np.ceil(max(int(s), 1) / quantum)) * quantum for s in shape)


def bucket_views(n: int) -> int:
    """Pad view count to the next power of two (>=1)."""
    return 1 << max(0, int(np.ceil(np.log2(max(n, 1)))))
