"""Non-rigid fusion kernel: per-view control-point deformation grids applied
during resample + blend (XLA).

Role of ``NonRigidTools.fuseVirtualInterpolatedNonRigid`` called at
SparkNonRigidFusion.java:388-402: each view carries a regular grid of control
points (spacing ``cpd``, default 10 px) whose per-vertex affine models are
fitted host-side from corresponding interest points (moving-least-squares
with inverse-distance weights, alpha=1.0); the kernel trilinearly interpolates
the 12 model coefficients across the grid per output voxel, deforms the world
coordinate into the view's world frame, then applies the view's static
world->patch affine and samples exactly like the affine-fusion kernel.

TPU design: the deformation is a dense vector-valued trilinear interpolation
(8 gathers of 12-float vertex records) fused by XLA into the sampling kernel;
all shapes static (grid dims bucketed per block), views vmapped, padding
masked — one compile serves every block with the same bucket.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .fusion import _blend_weight, _combine_views, _trilinear_sample


def _trilinear_vec(grid: jnp.ndarray, pts: jnp.ndarray) -> jnp.ndarray:
    """Sample a vector-valued grid (Gx,Gy,Gz,C) at (N,3) float coords
    (grid units); clamped at the edges. Returns (N,C)."""
    gx, gy, gz, C = grid.shape
    p0 = jnp.floor(pts)
    f = pts - p0
    p0 = p0.astype(jnp.int32)
    x0 = jnp.clip(p0[:, 0], 0, gx - 1)
    y0 = jnp.clip(p0[:, 1], 0, gy - 1)
    z0 = jnp.clip(p0[:, 2], 0, gz - 1)
    x1 = jnp.clip(p0[:, 0] + 1, 0, gx - 1)
    y1 = jnp.clip(p0[:, 1] + 1, 0, gy - 1)
    z1 = jnp.clip(p0[:, 2] + 1, 0, gz - 1)
    flat = grid.reshape(-1, C)
    syz = gy * gz

    def g(xi, yi, zi):
        return jnp.take(flat, xi * syz + yi * gz + zi, axis=0)

    fx = f[:, 0:1]
    fy = f[:, 1:2]
    fz = f[:, 2:3]
    return (
        g(x0, y0, z0) * (1 - fx) * (1 - fy) * (1 - fz)
        + g(x1, y0, z0) * fx * (1 - fy) * (1 - fz)
        + g(x0, y1, z0) * (1 - fx) * fy * (1 - fz)
        + g(x1, y1, z0) * fx * fy * (1 - fz)
        + g(x0, y0, z1) * (1 - fx) * (1 - fy) * fz
        + g(x1, y0, z1) * fx * (1 - fy) * fz
        + g(x0, y1, z1) * (1 - fx) * fy * fz
        + g(x1, y1, z1) * fx * fy * fz
    )


def _sample_one_view_nonrigid(
    patch, grid, view_affine, patch_offset, img_dim, border, blend_range,
    block_origin, grid_origin, grid_spacing, block_shape,
):
    """Per view: deform world coords by the interpolated control-point model,
    map into patch coords, sample + blend. Returns (val, inside, w_blend).

    The control-grid interpolation is SEPARABLE: output voxels form a regular
    lattice, so their grid coordinates are affine per axis and the trilinear
    interpolation of the (Gx,Gy,Gz,12) vertex models is the tensor product of
    three 1-D interpolation matrices — three small GEMMs (MXU work) instead
    of 8×12 gathers per voxel. Only the final patch sampling gathers (its
    coordinates are data-dependent through the deformation)."""
    from .fusion import _separable_interp_matrix

    L = block_shape
    with jax.named_scope("deform"):
        so = grid  # (Gx,Gy,Gz,12)
        for d in range(3):
            pos = (block_origin[d] + jnp.arange(L[d], dtype=jnp.float32)
                   - grid_origin[d]) / grid_spacing[d]
            m = _separable_interp_matrix(pos, grid.shape[d])
            so = jnp.tensordot(so, m, axes=[[0], [1]])
        A = so.reshape(3, 4, *L)  # per-voxel affine coefficients
        wx = block_origin[0] + jnp.arange(
            L[0], dtype=jnp.float32)[:, None, None]
        wy = block_origin[1] + jnp.arange(
            L[1], dtype=jnp.float32)[None, :, None]
        wz = block_origin[2] + jnp.arange(
            L[2], dtype=jnp.float32)[None, None, :]
        deformed = [A[i, 0] * wx + A[i, 1] * wy + A[i, 2] * wz + A[i, 3]
                    for i in range(3)]
        p = jnp.stack([
            (view_affine[i, 0] * deformed[0] + view_affine[i, 1] * deformed[1]
             + view_affine[i, 2] * deformed[2] + view_affine[i, 3]).ravel()
            for i in range(3)
        ], axis=-1)  # (N,3) patch coords
    with jax.named_scope("sample"):
        val = _trilinear_sample(patch, p)
    with jax.named_scope("blend_weights"):
        lpos = p + patch_offset
        inside = jnp.all(
            (lpos >= 0.0) & (lpos <= img_dim - 1.0), axis=-1
        ).astype(jnp.float32)
        w_blend = _blend_weight(lpos, img_dim, border, blend_range)
    return val, inside, w_blend


def nonrigid_fuse_block_impl(
    patches: jnp.ndarray,        # (V, Px,Py,Pz) float32
    grids: jnp.ndarray,          # (V, Gx,Gy,Gz, 12) float32 vertex models
    view_affines: jnp.ndarray,   # (V, 3, 4) view-world -> patch coords
    patch_offsets: jnp.ndarray,  # (V, 3) patch origin in level coords
    img_dims: jnp.ndarray,       # (V, 3)
    borders: jnp.ndarray,        # (V, 3)
    blend_ranges: jnp.ndarray,   # (V, 3)
    valid: jnp.ndarray,          # (V,)
    block_origin: jnp.ndarray,   # (3,) world coords of output voxel (0,0,0)
    grid_origin: jnp.ndarray,    # (3,) world coords of grid vertex (0,0,0)
    grid_spacing: jnp.ndarray,   # (3,) cpd
    block_shape: tuple[int, int, int],
    fusion_type: str = "AVG_BLEND",
):
    """Fuse one output block under per-view non-rigid deformation.
    Returns (fused, weight-sum) blocks."""
    patches = patches.astype(jnp.float32)  # lossless transport downcast
    def one(*args):
        return _sample_one_view_nonrigid(*args, block_shape=block_shape)

    vals, insides, wblends = jax.vmap(
        one, in_axes=(0, 0, 0, 0, 0, 0, 0, None, None, None),
    )(patches, grids, view_affines, patch_offsets, img_dims, borders,
      blend_ranges, block_origin, grid_origin, grid_spacing)
    with jax.named_scope("accumulate"):
        fused, wsum = _combine_views(vals, insides, wblends, valid,
                                     fusion_type)
    return fused.reshape(block_shape), wsum.reshape(block_shape)


nonrigid_fuse_block = jax.jit(
    nonrigid_fuse_block_impl, static_argnames=("block_shape", "fusion_type")
)


# ---------------------------------------------------------------------------
# host-side control-grid fitting (moving least squares, IDW weights)
# ---------------------------------------------------------------------------

# vertices fitted at a time: their (chunk, M, 4) float64 temporaries stay in
# the cache (2 MB at M = 100) where the whole grid's 13 456 vertices made 30
# MB and more of them, mapped and faulted in anew for every fit
_FIT_CHUNK = 512


def fit_control_grid(
    targets: np.ndarray,         # (M,3) averaged world positions of unique IPs
    view_world: np.ndarray,      # (M,3) same IPs in this view's world frame
    grid_origin: np.ndarray,     # (3,)
    grid_dims: tuple[int, int, int],
    spacing: float,
    alpha: float = 1.0,
    reg_eps: float = 1e-6,
) -> np.ndarray:
    """Per-vertex affine models mapping target-world -> view-world.

    Weighted least squares per vertex with inverse-distance weights
    w_i = 1/(d^alpha + eps) (the MLS/IDW scheme of NonRigidTools, alpha=1.0,
    SparkNonRigidFusion.java:373-402). Falls back to the global affine (or
    translation) fit when points are scarce. Returns (Gx,Gy,Gz,12) float32.
    """
    return fit_vertex_models(targets, view_world, grid_origin, grid_dims,
                             spacing, alpha, reg_eps
                             ).reshape(*grid_dims, 12).astype(np.float32)


def fit_vertex_models(targets, view_world, grid_origin, grid_dims, spacing,
                      alpha: float = 1.0, reg_eps: float = 1e-6
                      ) -> np.ndarray:
    """``fit_control_grid`` before the cast: (G,3,4) float64, vertices in
    C order over the grid."""
    m = len(targets)
    verts = grid_origin + np.indices(grid_dims).reshape(3, -1).T * spacing
    out = np.zeros((len(verts), 3, 4))
    out[:, :, :3] = np.eye(3)
    if m == 0:
        return out
    if m < 4:
        # translation-only fallback: mean displacement
        out[:, :, 3] = (view_world - targets).mean(axis=0)
        return out

    # solve in vertex-centered coordinates (both sides), which keeps the
    # normal equations well-conditioned and makes the tiny identity
    # regularizer scale-free: fit maps (p - vert) -> (q - vert)
    x_id = np.zeros((4, 3))
    x_id[:3, :3] = np.eye(3)
    ph = np.ones((min(_FIT_CHUNK, len(verts)), m, 4))
    for s in range(0, len(verts), _FIT_CHUNK):
        v = verts[s:s + _FIT_CHUNK]
        p = ph[:len(v)]                                   # (g,M,4)
        np.subtract(targets[None], v[:, None], out=p[:, :, :3])
        qc = view_world[None] - v[:, None]
        d = np.sqrt(np.einsum("gmi,gmi->gm", p[:, :, :3], p[:, :, :3]))
        w = 1.0 / (d**alpha + 0.5)
        wp = (w[:, :, None] * p).transpose(0, 2, 1)       # (g,4,M)
        lam = reg_eps * w.sum(axis=1)[:, None, None]
        sol = np.linalg.solve(wp @ p + lam * np.eye(4),
                              wp @ qc + lam * x_id)       # (g,4,3)
        lin = np.swapaxes(sol[:, :3, :], 1, 2)            # (g,3,3)
        out[s:s + _FIT_CHUNK, :, :3] = lin
        out[s:s + _FIT_CHUNK, :, 3] = sol[:, 3, :] + v \
            - np.einsum("gij,gj->gi", lin, v)
    return out
