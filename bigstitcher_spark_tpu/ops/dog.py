"""Difference-of-Gaussians blob detection kernel (XLA).

Reference equivalent: ``DoGImgLib2.computeDoG`` called from
SparkInterestPointDetection.java:552-568 — two Gaussian blurs (sigma,
sigma*k), subtraction, 3x3x3 extrema, threshold, quadratic subpixel fit,
with the image normalized to [0,1] via min/maxIntensity.

TPU design: the blurs are separable 1-D passes (banded-Toeplitz GEMMs on
the MXU, or one FFT transfer-function product on CPU), the normalization
is folded into the response scale (the min offset cancels in the kernel
difference), extrema detection is a separable shifted-slice 3^3
max/min compared against the response — all dense, static
shapes, fused by XLA and vmapped over a batch of equally-shaped blocks.
Detections leave the device as a boolean mask + response volume; the sparse
tail (argwhere + 3-D quadratic refinement) runs on host where dynamic point
counts are natural.

Constants follow mpicbg's classic scale-space setup: k = 2^(1/4), response
scaled by 1/(k-1) so thresholds are comparable to the reference's defaults
(sigma=1.8, threshold=0.008).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DOG_K = float(2.0 ** (1.0 / 4.0))


def gaussian_kernel_1d(sigma: float) -> np.ndarray:
    """Normalized 1-D Gaussian, radius 3*sigma (imglib2 Gauss3-style support)."""
    r = max(1, int(np.ceil(3.0 * float(sigma))))
    x = np.arange(-r, r + 1, dtype=np.float64)
    k = np.exp(-(x**2) / (2.0 * float(sigma) ** 2))
    return (k / k.sum()).astype(np.float32)


def dog_halo(sigma: float) -> int:
    """Halo needed so core+1-ring response values are padding-free: the larger
    blur radius plus one voxel for the extremum neighborhood."""
    r2 = max(1, int(np.ceil(3.0 * float(sigma) * DOG_K)))
    return r2 + 1


@functools.lru_cache(maxsize=64)
def _toeplitz_band(n: int, kernel_bytes: bytes) -> np.ndarray:
    """(n, n + 2r) banded Toeplitz matrix applying a 1-D kernel along an
    axis of length n (rows select the VALID window of a padded axis)."""
    k = np.frombuffer(kernel_bytes, np.float32)
    m = np.zeros((n, n + k.size - 1), np.float32)
    for i in range(n):
        m[i, i:i + k.size] = k
    return m


def _blur_separable(x: jnp.ndarray, kernels) -> jnp.ndarray:
    """Separable 3-D Gaussian blur of an (X,Y,Z) volume with mirror extension
    (imglib2's extended-image semantics — no zero-padding edge responses).

    Each 1-D pass is a banded-Toeplitz MATMUL rather than a conv: the MXU is
    where TPU FLOPs live, and XLA:CPU's conv lowering is ~60x slower than its
    GEMM for these shapes (measured) — same math to float rounding."""
    for ax, k in enumerate(kernels):
        n = x.shape[ax]
        r = k.size // 2
        m = jnp.asarray(_toeplitz_band(int(n), np.asarray(k, np.float32)
                                       .tobytes()))
        xp = jnp.pad(x, [(r, r) if d == ax else (0, 0) for d in range(3)],
                     mode="reflect")
        x = jnp.moveaxis(
            jnp.tensordot(m, jnp.moveaxis(xp, ax, 0), axes=[[1], [0]]), 0, ax)
    return x


@functools.lru_cache(maxsize=64)
def _dog_transfer(shape: tuple, s1_bytes: bytes, s2_bytes: bytes):
    """Fourier transfer function of (G_s1 - G_s2) for an (X,Y,Z) grid:
    per-axis DFTs of the SAME truncated, normalized discrete kernels the
    Toeplitz path applies (so core responses agree to float rounding), as
    a separable outer product on the rfftn grid. Real-valued (kernels are
    even)."""
    k1 = np.frombuffer(s1_bytes, np.float32).astype(np.float64)
    k2 = np.frombuffer(s2_bytes, np.float32).astype(np.float64)

    def axis_hat(k, n, half):
        r = k.size // 2
        pad = np.zeros(n)
        pad[: r + 1] = k[r:]
        pad[n - r:] = k[:r]
        h = np.fft.rfft(pad) if half else np.fft.fft(pad)
        return np.real(h)

    hx1 = axis_hat(k1, shape[0], False)
    hy1 = axis_hat(k1, shape[1], False)
    hz1 = axis_hat(k1, shape[2], True)
    hx2 = axis_hat(k2, shape[0], False)
    hy2 = axis_hat(k2, shape[1], False)
    hz2 = axis_hat(k2, shape[2], True)
    H = (hx1[:, None, None] * hy1[None, :, None] * hz1[None, None, :]
         - hx2[:, None, None] * hy2[None, :, None] * hz2[None, None, :])
    return H.astype(np.float32)


def _dog_response_fft(x: jnp.ndarray, k1, k2) -> jnp.ndarray:
    """(G_s1 - G_s2) * x via one rfftn + one transfer multiply + one irfftn
    (circular edges; blocks carry halo >= the blur radius, so core values
    are edge-mode-independent). ~an order of magnitude fewer FLOPs than the
    two banded-matmul blur chains — the better trade on XLA:CPU, where GEMM
    throughput is the bottleneck rather than the MXU being free."""
    H = jnp.asarray(_dog_transfer(
        tuple(int(s) for s in x.shape),
        np.asarray(k1, np.float32).tobytes(),
        np.asarray(k2, np.float32).tobytes()))
    f = jnp.fft.rfftn(x)
    return jnp.fft.irfftn(f * H, s=x.shape).astype(jnp.float32)


def _blur_strategy() -> str:
    """'fft' on CPU, 'gemm' (Toeplitz matmuls on the MXU) elsewhere;
    BST_DOG_BLUR=fft|gemm overrides. Read at trace time — fixed per process."""
    from .. import config

    mode = config.get_str("BST_DOG_BLUR")
    if mode == "auto":
        return "fft" if jax.default_backend() == "cpu" else "gemm"
    return mode


def _window_extremum3(x: jnp.ndarray, op, fill) -> jnp.ndarray:
    """3x3x3 windowed max/min as three separable shifted-slice passes
    (2 elementwise ops per axis) — identical to ``reduce_window`` with SAME
    padding, but pure elementwise work instead of the generic window
    reduction, which lowers poorly on XLA:CPU and adds nothing on TPU."""
    for ax in range(3):
        xp = jnp.pad(x, [(1, 1) if d == ax else (0, 0) for d in range(3)],
                     constant_values=fill)
        n = x.shape[ax]
        x = op(op(lax.slice_in_dim(xp, 0, n, axis=ax),
                  lax.slice_in_dim(xp, 1, n + 1, axis=ax)),
               lax.slice_in_dim(xp, 2, n + 2, axis=ax))
    return x


def _tiebreak(shape, origin) -> jnp.ndarray:
    """Tiny deterministic per-voxel offset hashed from ABSOLUTE coordinates
    (block origin + local index), so plateau ties — e.g. a bead centered
    exactly between two voxels — resolve to exactly one detection, and
    identically so across block boundaries (halo consistency)."""
    ix = lax.broadcasted_iota(jnp.int32, shape, 0) + origin[0]
    iy = lax.broadcasted_iota(jnp.int32, shape, 1) + origin[1]
    iz = lax.broadcasted_iota(jnp.int32, shape, 2) + origin[2]
    h = (ix * 73856093 + iy * 19349663 + iz * 83492791) & 1023
    return h.astype(jnp.float32) * jnp.float32(2.0**-30)


@functools.partial(
    jax.jit, static_argnames=("sigma", "find_max", "find_min")
)
def dog_block(
    block: jnp.ndarray,
    min_intensity: jnp.ndarray,
    max_intensity: jnp.ndarray,
    threshold: jnp.ndarray,
    sigma: float,
    find_max: bool = True,
    find_min: bool = False,
    origin: jnp.ndarray | None = None,
):
    """DoG response + extrema mask for one (X,Y,Z) block.

    Returns (dog float32, mask bool). ``mask`` marks voxels that are a strict
    3x3x3 max of the response above ``threshold`` (or min below -threshold).
    The response equals DoG of the [0,1]-normalized input (DoGImgLib2
    normalization, SparkInterestPointDetection.java:552-568), with the
    1/(max-min) scale folded into the response instead of a separate
    normalization pass (the offset cancels; see inline comment).
    ``origin`` is the block's absolute voxel offset (for tie-breaking only).
    """
    x = block.astype(jnp.float32)
    s1 = float(sigma)
    s2 = float(sigma) * DOG_K
    k1 = gaussian_kernel_1d(s1)
    k2 = gaussian_kernel_1d(s2)
    # named scopes are metadata in the HLO: they name the kernel's phases
    # in a device trace and cost nothing at run time
    with jax.named_scope("blur"):
        if _blur_strategy() == "fft":
            diff = _dog_response_fft(x, k1, k2)
        else:
            diff = (_blur_separable(x, [k1] * 3)
                    - _blur_separable(x, [k2] * 3))
    # the [min,max]->[0,1] normalization (DoGImgLib2,
    # SparkInterestPointDetection.java:552-568) commutes with the DoG:
    # both blur kernels are normalized, so the constant offset cancels in
    # the difference and only the 1/(max-min) scale survives — folding it
    # into the response scale saves two full-volume passes over the input.
    # Degenerate max<=min (flat view, data-derived bounds): the old
    # normalization produced all-zero input => zero response; gate the
    # scale to 0 so blur roundoff is not amplified into fake detections
    inv_range = jnp.where(max_intensity > min_intensity,
                          1.0 / jnp.maximum(max_intensity - min_intensity,
                                            1e-20), 0.0)
    dog = diff * ((1.0 / (DOG_K - 1.0)) * inv_range)

    if origin is None:
        origin = jnp.zeros(3, jnp.int32)
    with jax.named_scope("extrema"):
        tb = _tiebreak(dog.shape, origin)
        mask = jnp.zeros(dog.shape, bool)
        if find_max:
            d = dog + tb
            mp = _window_extremum3(d, jnp.maximum, -jnp.inf)
            mask = mask | ((d >= mp) & (dog > threshold))
        if find_min:
            d = dog - tb
            mp = _window_extremum3(d, jnp.minimum, jnp.inf)
            mask = mask | ((d <= mp) & (dog < -threshold))
    return dog, mask


def dog_block_batch_impl(blocks, min_i, max_i, threshold, sigma,
                         find_max=True, find_min=False, origins=None):
    """vmapped ``dog_block`` over a leading batch axis (one compile serves
    every equally-shaped block of every view — strategy P3 of SURVEY §2.4).
    Un-jitted so the mesh layer can wrap it with batch-axis shardings."""
    if origins is None:
        origins = jnp.zeros((blocks.shape[0], 3), jnp.int32)
    return jax.vmap(
        lambda b, lo, hi, t, o: dog_block(b, lo, hi, t, sigma,
                                          find_max, find_min, o)
    )(blocks, min_i, max_i, threshold, origins)


dog_block_batch = functools.partial(
    jax.jit, static_argnames=("sigma", "find_max", "find_min")
)(dog_block_batch_impl)


# ---------------------------------------------------------------------------
# Compacted output: top-K candidates + on-device subpixel refinement.
#
# The dense (dog, mask) output costs two full volumes of D2H per block — on
# a wire-limited host link that dwarfs the compute. Detections are sparse
# (beads), so the TPU-idiomatic move is to compact on device: top-K extrema
# by |response|, the iterative 3-D quadratic refinement vectorized over the
# K candidates (fixed move count — no data-dependent control flow), and only
# (K,3)+(K,) scalars cross the boundary (~KB instead of ~MB).
# ---------------------------------------------------------------------------


def _gather3(dog_flat, p, shape):
    """dog values at clipped integer coords p (K,3) from the flat volume."""
    x = jnp.clip(p[:, 0], 0, shape[0] - 1)
    y = jnp.clip(p[:, 1], 0, shape[1] - 1)
    z = jnp.clip(p[:, 2], 0, shape[2] - 1)
    return jnp.take(dog_flat, (x * shape[1] + y) * shape[2] + z)


def _localize_quadratic_device(dog, p0, valid, max_moves: int = 4):
    """Vectorized device port of ``localize_quadratic``: central-difference
    gradient/Hessian, offset = -H^-1 g clipped to [-1,1]; bases that land
    past half-sample move one voxel and refit (fixed ``max_moves`` rounds)."""
    shape = dog.shape
    flat = dog.ravel()
    dims = jnp.array(shape, jnp.int32)
    p = p0.astype(jnp.int32)
    result = p.astype(jnp.float32)
    value = _gather3(flat, p, shape)
    active = valid

    eye = jnp.eye(3, dtype=jnp.int32)
    for _ in range(max_moves):
        ok = jnp.all((p >= 1) & (p <= dims - 2), axis=1)
        elig = active & ok
        c = _gather3(flat, p, shape)
        plus = [_gather3(flat, p + eye[d], shape) for d in range(3)]
        minus = [_gather3(flat, p - eye[d], shape) for d in range(3)]
        g = jnp.stack([0.5 * (plus[d] - minus[d]) for d in range(3)], axis=-1)
        diag = [plus[d] - 2.0 * c + minus[d] for d in range(3)]

        def cross(d, e):
            return 0.25 * (
                _gather3(flat, p + eye[d] + eye[e], shape)
                - _gather3(flat, p + eye[d] - eye[e], shape)
                - _gather3(flat, p - eye[d] + eye[e], shape)
                + _gather3(flat, p - eye[d] - eye[e], shape))

        # assemble by stacking (scatter-free; .at[:, d, e].set emits
        # per-row HLO scatters)
        hxy, hxz, hyz = cross(0, 1), cross(0, 2), cross(1, 2)
        H = jnp.stack([
            jnp.stack([diag[0], hxy, hxz], axis=-1),
            jnp.stack([hxy, diag[1], hyz], axis=-1),
            jnp.stack([hxz, hyz, diag[2]], axis=-1),
        ], axis=-2)
        det = jnp.linalg.det(H)
        det_ok = jnp.abs(det) > 1e-12
        Hsafe = jnp.where(det_ok[:, None, None], H,
                          jnp.eye(3, dtype=jnp.float32)[None])
        off = -jnp.linalg.solve(Hsafe, g[..., None])[..., 0]
        off = jnp.where(det_ok[:, None], jnp.clip(off, -1.0, 1.0), 0.0)
        upd = elig
        result = jnp.where(upd[:, None], p.astype(jnp.float32) + off, result)
        value = jnp.where(upd, c + 0.5 * jnp.sum(g * off, axis=-1), value)
        moved = jnp.abs(off) > 0.5
        needs = jnp.any(moved, axis=1) & det_ok & elig
        step = jnp.where(moved, jnp.sign(off).astype(jnp.int32), 0)
        p = jnp.where(needs[:, None], p + step, p)
        active = needs
    return result, value


def _pool_mean(x: jnp.ndarray, rel: tuple[int, int, int]) -> jnp.ndarray:
    """Average-pool by integer factors: the SHARED downsample kernel, traced
    inside the DoG program (a jitted fn called during tracing inlines into
    the same XLA computation), so the device pooling stays bit-identical to
    the host path's ``read_det_block`` pooling."""
    from .downsample import downsample_block

    return downsample_block(x, tuple(int(r) for r in rel))


def dog_block_topk_impl(block, min_i, max_i, threshold, origin, sigma,
                        find_max=True, find_min=False, k=2048, halo=0,
                        rel=(1, 1, 1)):
    """DoG + extrema + device-side subpixel, compacted to the K strongest
    candidates. Returns (idx (K,3) int32 base voxels, sub (K,3) float32
    subpixel coords, val (K,) refined response, valid (K,) bool,
    count () int32 total CORE extrema found — count > K means truncation).

    ``halo``: static halo width; extrema in the halo belong to neighboring
    blocks, so they are masked out BEFORE top-K — they must neither consume
    the K budget nor inflate the truncation count.

    ``rel``: residual downsampling factors applied ON DEVICE before
    everything else (openAndDownsample's in-memory averaging,
    SparkInterestPointDetection.java:1094-1114) — the block arrives at
    level resolution in its native dtype, so the wire carries uint16 and
    the pool/normalize/DoG chain is one fused program."""
    if any(int(r) != 1 for r in rel):
        block = _pool_mean(block, rel)
    dog, mask = dog_block(block, min_i, max_i, threshold, sigma,
                          find_max, find_min, origin)
    if halo > 0:
        # broadcasted-iota comparisons, NOT a full-volume .at[].set — the
        # latter lowers to an HLO scatter (a TPU serialization cliff)
        core = None
        for ax in range(3):
            i = lax.broadcasted_iota(jnp.int32, dog.shape, ax)
            m = (i >= halo) & (i < dog.shape[ax] - halo)
            core = m if core is None else (core & m)
        mask = mask & core
    k = int(min(k, int(np.prod(dog.shape))))
    with jax.named_scope("topk"):
        score = jnp.where(mask, jnp.abs(dog), -jnp.inf).ravel()
        _, flat_idx = jax.lax.top_k(score, k)
        valid = jnp.take(score, flat_idx) > -jnp.inf
        sy, sz = dog.shape[1], dog.shape[2]
        idx = jnp.stack([flat_idx // (sy * sz), (flat_idx // sz) % sy,
                         flat_idx % sz], axis=-1).astype(jnp.int32)
    with jax.named_scope("localize"):
        sub, val = _localize_quadratic_device(dog, idx, valid)
    count = mask.sum().astype(jnp.int32)
    return idx, sub, jnp.where(valid, val, 0.0), valid, count


def dog_block_topk_batch_impl(blocks, min_i, max_i, threshold, origins,
                              sigma, find_max=True, find_min=False, k=2048,
                              halo=0, rel=(1, 1, 1)):
    return jax.vmap(
        lambda b, lo, hi, t, o: dog_block_topk_impl(
            b, lo, hi, t, o, sigma, find_max, find_min, k, halo, rel)
    )(blocks, min_i, max_i, threshold, origins)


dog_block_topk_batch = functools.partial(
    jax.jit,
    static_argnames=("sigma", "find_max", "find_min", "k", "halo", "rel"),
)(dog_block_topk_batch_impl)


def dog_detect_extract_impl(block, min_i, max_i, threshold, origin, sigma,
                            find_max=True, find_min=False, k=2048, halo=0,
                            rel=(1, 1, 1), n_neighbors=3, redundancy=1,
                            rotation_invariant=True):
    """DoG detection + geometric descriptor extraction as ONE program:
    the K candidate peaks never leave HBM between top-K/subpixel and the
    kNN/frame math. Composes :func:`dog_block_topk_impl` with
    ops.descriptors.block_descriptors_impl on the block-LOCAL subpixel
    coords (descriptors are pure neighbor offsets, hence translation
    invariant — adding the block origin later cannot change them).
    Returns the topk 5-tuple plus (desc, dvalid)."""
    from .descriptors import block_descriptors_impl

    idx, sub, val, valid, count = dog_block_topk_impl(
        block, min_i, max_i, threshold, origin, sigma, find_max, find_min,
        k, halo, rel)
    desc, dvalid = block_descriptors_impl(
        sub, valid, n_neighbors, redundancy, rotation_invariant)
    return idx, sub, val, valid, count, desc, dvalid


def dog_detect_extract_batch_impl(blocks, min_i, max_i, threshold, origins,
                                  sigma, find_max=True, find_min=False,
                                  k=2048, halo=0, rel=(1, 1, 1),
                                  n_neighbors=3, redundancy=1,
                                  rotation_invariant=True):
    return jax.vmap(
        lambda b, lo, hi, t, o: dog_detect_extract_impl(
            b, lo, hi, t, o, sigma, find_max, find_min, k, halo, rel,
            n_neighbors, redundancy, rotation_invariant)
    )(blocks, min_i, max_i, threshold, origins)


dog_detect_extract_batch = functools.partial(
    jax.jit,
    static_argnames=("sigma", "find_max", "find_min", "k", "halo", "rel",
                     "n_neighbors", "redundancy", "rotation_invariant"),
)(dog_detect_extract_batch_impl)


def localize_quadratic(
    dog: np.ndarray, coords: np.ndarray, max_moves: int = 4
) -> tuple[np.ndarray, np.ndarray]:
    """3-D quadratic subpixel refinement of integer extrema (host-side).

    Fits the local paraboloid via central differences: offset = -H^{-1} g;
    if any |offset_d| > 0.5 the base voxel moves one step and the fit repeats
    (imglib2 SubpixelLocalization behavior, up to ``max_moves``).
    Returns (subpixel coords (N,3) float64, refined values (N,)).
    """
    if len(coords) == 0:
        return np.zeros((0, 3)), np.zeros(0)
    p = np.asarray(coords, np.int64).copy()
    shape = np.array(dog.shape)
    result = p.astype(np.float64)
    value = dog[tuple(p.T)].astype(np.float64)
    active = np.ones(len(p), bool)
    for _ in range(max_moves):
        idx = np.where(active)[0]
        if idx.size == 0:
            break
        q = p[idx]
        ok = np.all((q >= 1) & (q <= shape - 2), axis=1)
        idx = idx[ok]
        if idx.size == 0:
            break
        q = p[idx]
        g = np.empty((len(q), 3))
        H = np.empty((len(q), 3, 3))
        c = dog[tuple(q.T)].astype(np.float64)
        plus, minus = [], []
        for d in range(3):
            e = np.zeros(3, np.int64)
            e[d] = 1
            plus.append(dog[tuple((q + e).T)].astype(np.float64))
            minus.append(dog[tuple((q - e).T)].astype(np.float64))
            g[:, d] = 0.5 * (plus[d] - minus[d])
            H[:, d, d] = plus[d] - 2.0 * c + minus[d]
        for d in range(3):
            for e_ in range(d + 1, 3):
                ed = np.zeros(3, np.int64)
                ee = np.zeros(3, np.int64)
                ed[d] = 1
                ee[e_] = 1
                v = 0.25 * (
                    dog[tuple((q + ed + ee).T)] - dog[tuple((q + ed - ee).T)]
                    - dog[tuple((q - ed + ee).T)] + dog[tuple((q - ed - ee).T)]
                ).astype(np.float64)
                H[:, d, e_] = v
                H[:, e_, d] = v
        det_ok = np.abs(np.linalg.det(H)) > 1e-12
        off = np.zeros((len(q), 3))
        if det_ok.any():
            off[det_ok] = -np.linalg.solve(H[det_ok], g[det_ok][..., None])[..., 0]
        off = np.clip(off, -1.0, 1.0)
        # keep this fit as the current best answer; a base move only refits
        # (never discards), so an oscillating half-sample tie still converges
        result[idx] = q + off
        value[idx] = c + 0.5 * np.einsum("ij,ij->i", g, off)
        moved = np.abs(off) > 0.5
        needs_move = moved.any(axis=1) & det_ok
        active[:] = False
        active[idx[needs_move]] = True
        step = np.where(moved, np.sign(off).astype(np.int64), 0)
        p[idx[needs_move]] += step[needs_move]
    return result, value


def sample_trilinear(vol: np.ndarray, points: np.ndarray) -> np.ndarray:
    """n-linear interpolation of ``vol`` at float ``points`` (N,3) (host-side;
    the reference samples detection intensities the same way,
    SparkInterestPointDetection.java:581-606)."""
    if len(points) == 0:
        return np.zeros(0)
    p = np.asarray(points, np.float64)
    lo = np.clip(np.floor(p).astype(np.int64), 0,
                 np.array(vol.shape) - 2)
    f = np.clip(p - lo, 0.0, 1.0)
    out = np.zeros(len(p))
    for corner in range(8):
        d = np.array([(corner >> i) & 1 for i in range(3)])
        w = np.prod(np.where(d, f, 1.0 - f), axis=1)
        out += w * vol[tuple((lo + d).T)].astype(np.float64)
    return out
