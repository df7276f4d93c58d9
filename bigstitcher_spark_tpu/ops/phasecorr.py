"""FFT phase-correlation pairwise shift estimation (XLA + host refinement).

TPU-native re-design of the reference's stitching math (BigStitcher core
``PairwiseStitching``/``PhaseCorrelation2``, called at
SparkPairwiseStitching.java:247-267), split by what each side is good at:

- DEVICE (one fused, statically-shaped XLA computation per crop-shape
  bucket, vmapped over the batch): windowing, 3-D FFT phase correlation,
  3x3x3 local-maxima suppression, top-N peak extraction — the heavy regular
  compute.
- HOST (numpy, float64): scoring each peak's 2^3 periodic-wrap
  interpretations by true Pearson correlation over the overlap SLICES, a
  hill-climb to the best integer shift, quadratic subpixel refinement. These
  touch only the (dynamic-shaped) overlap boxes — a few dozen tiny
  reductions per pair that would each cost a full-volume masked pass under
  static shapes (the r3 kernel did exactly that and spent 2 orders of
  magnitude more HBM traffic there than on the FFTs).

Shift convention: the returned ``shift`` s satisfies a[x] ~= b[x + s]; the
correction to apply to view B's translation is ``-s`` (see
models/stitching.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _local_maxima(pcm: jnp.ndarray) -> jnp.ndarray:
    """Mask of voxels that are >= all neighbors in their 3x3x3 window,
    with periodic wrap (the PCM is circular). Separable roll-max: 2
    elementwise max ops per axis — ``reduce_window`` computes the same
    thing but lowers ~20x slower on XLA:CPU and no better on TPU."""
    pooled = pcm
    for ax in range(3):
        pooled = jnp.maximum(
            pooled,
            jnp.maximum(jnp.roll(pooled, 1, axis=ax),
                        jnp.roll(pooled, -1, axis=ax)))
    return pcm >= pooled


def _windowed(img: jnp.ndarray, ext: jnp.ndarray, fade_frac: float):
    """Mean-subtract over the actual extent and apply a cosine (Hann-edge)
    fade so the crop-edge discontinuity does not dominate the PCM — without
    this, smooth microscopy data (spectral energy at low k only) buries the
    true peak under zero-padding edge correlation."""
    with jax.named_scope("window"):
        n = jnp.prod(ext.astype(jnp.float32))
        mean = jnp.sum(img) / jnp.maximum(n, 1.0)
        w = img
        masks = []
        for ax in range(3):
            x = jnp.arange(img.shape[ax], dtype=jnp.float32)
            e = ext[ax].astype(jnp.float32)
            m = jnp.maximum(jnp.round(e * fade_frac), 1.0)
            d = jnp.minimum(x + 0.5, e - (x + 0.5))  # distance into the crop
            ramp = 0.5 * (1.0 - jnp.cos(jnp.pi * jnp.clip(d / m, 0.0, 1.0)))
            masks.append(jnp.where(x < e, ramp, 0.0))
        win = (masks[0][:, None, None] * masks[1][None, :, None]
               * masks[2][None, None, :])
        return (w - mean) * win


@functools.partial(jax.jit, static_argnames=("n_peaks",))
def pcm_peaks(
    a: jnp.ndarray,           # (X,Y,Z) float32 or uint16 (lossless
    b: jnp.ndarray,           # transport downcast), zero-padded crops
    ext_a: jnp.ndarray,       # (3,) int32 actual extent of a before padding
    ext_b: jnp.ndarray,       # (3,) int32
    n_peaks: int = 5,
    fade_frac: float = 0.25,
) -> jnp.ndarray:
    """Top-N local maxima of the phase-correlation matrix -> (n_peaks, 3)
    int32 wrapped indices. The PCM is computed on windowed copies; the
    correlation check happens on the raw crops host-side."""
    # crops may arrive as uint16 (lossless transport downcast when every
    # value is integral — halves h2d bytes on wire-limited links); the
    # kernel math is float32 either way
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    # named scopes are metadata in the HLO: they name the kernel's phases
    # in a device trace and cost nothing at run time
    with jax.named_scope("fft"):
        fa = jnp.fft.rfftn(_windowed(a, ext_a, fade_frac))
        fb = jnp.fft.rfftn(_windowed(b, ext_b, fade_frac))
    with jax.named_scope("normalise"):
        cross = fa * jnp.conj(fb)
        mag = jnp.abs(cross)
        # zero out negligible bins instead of normalizing their garbage
        # phase
        norm = jnp.where(mag > 1e-5 * jnp.max(mag),
                         cross / jnp.maximum(mag, 1e-30), 0.0)
    with jax.named_scope("fft"):
        pcm = jnp.fft.irfftn(norm, s=a.shape).astype(jnp.float32)

    with jax.named_scope("peak_search"):
        masked = jnp.where(_local_maxima(pcm), pcm, -jnp.inf)
        _, flat_idx = jax.lax.top_k(masked.ravel(), n_peaks)
        sy = a.shape[1] * a.shape[2]
        sz = a.shape[2]
        return jnp.stack(
            [flat_idx // sy, (flat_idx // sz) % a.shape[1],
             flat_idx % a.shape[2]],
            axis=-1,
        ).astype(jnp.int32)


pcm_peaks_batch = jax.jit(
    jax.vmap(pcm_peaks, in_axes=(0, 0, 0, 0, None, None)),
    static_argnames=("n_peaks",),
)


# ---------------------------------------------------------------------------
# host-side refinement (float64, overlap slices only)
# ---------------------------------------------------------------------------


def _sat(x: np.ndarray) -> np.ndarray:
    """3-D summed-area table with a zero border: S[i,j,k] = sum of
    x[:i,:j,:k]; box sums become 8 corner lookups. Cumsums run on
    contiguous arrays (cumsum into a strided border view is ~5x slower)."""
    c = np.cumsum(np.cumsum(np.cumsum(x, 0, dtype=np.float64), 1), 2)
    S = np.zeros(tuple(s + 1 for s in x.shape), np.float64)
    S[1:, 1:, 1:] = c
    return S


def _box_sum(S: np.ndarray, lo, hi) -> float:
    x0, y0, z0 = int(lo[0]), int(lo[1]), int(lo[2])
    x1, y1, z1 = int(hi[0]), int(hi[1]), int(hi[2])
    return (S[x1, y1, z1] - S[x0, y1, z1] - S[x1, y0, z1] - S[x1, y1, z0]
            + S[x0, y0, z1] + S[x0, y1, z0] + S[x1, y0, z0] - S[x0, y0, z0])


class _PearsonScorer:
    """Pearson r of a[x] vs b[x+s] over the rectangular overlap (the
    reference's per-peak true cross-correlation check), with the window
    sums S_a, S_aa, S_b, S_bb served by summed-area tables — only the
    cross term S_ab costs a pass over the overlap, ~6x less memory
    traffic per candidate than the naive centered-copy evaluation."""

    def __init__(self, a: np.ndarray, b: np.ndarray):
        self.a = a
        self.b = b
        self.ext_a = np.array(a.shape, np.int64)
        self.ext_b = np.array(b.shape, np.int64)
        self.Sa = _sat(a)
        self.Saa = _sat(a * a)
        self.Sb = _sat(b)
        self.Sbb = _sat(b * b)

    def r(self, s, min_overlap) -> float:
        lo = np.maximum(0, -s)
        hi = np.minimum(self.ext_a, self.ext_b - s)
        if np.any(hi - lo < 1):
            return -np.inf
        n = float(np.prod(hi - lo))
        if n < min_overlap:
            return -np.inf
        av = self.a[tuple(slice(int(lo[d]), int(hi[d])) for d in range(3))]
        bv = self.b[tuple(slice(int(lo[d] + s[d]), int(hi[d] + s[d]))
                          for d in range(3))]
        s_ab = float(np.einsum("ijk,ijk->", av, bv, dtype=np.float64,
                               casting="unsafe"))
        s_a = _box_sum(self.Sa, lo, hi)
        s_aa = _box_sum(self.Saa, lo, hi)
        s_b = _box_sum(self.Sb, lo + s, hi + s)
        s_bb = _box_sum(self.Sbb, lo + s, hi + s)
        va = s_aa - s_a * s_a / n
        vb = s_bb - s_b * s_b / n
        den = np.sqrt(max(va, 0.0) * max(vb, 0.0))
        if den <= 1e-12:
            return -1.0
        return float((s_ab - s_a * s_b / n) / den)


def _r_candidate(a, b, ext_a, ext_b, s, min_overlap) -> float:
    """One-shot Pearson r (kept for API compatibility; batch callers use
    ``_PearsonScorer`` to amortize the summed-area tables)."""
    return _PearsonScorer(np.asarray(a, np.float64),
                          np.asarray(b, np.float64)).r(
        np.asarray(s, np.int64), min_overlap)


def refine_peaks(
    crop_a: np.ndarray,       # unpadded crop of group A (any float dtype)
    crop_b: np.ndarray,
    peaks: np.ndarray,        # (n_peaks, 3) wrapped PCM indices
    fft_shape: tuple[int, int, int],
    min_overlap: float = 32.0,
    subpixel: bool = True,
) -> tuple[np.ndarray, float]:
    """Score peak wraps by true correlation, hill-climb (argmax over the 6
    unit neighbors + self per round, 3 rounds — the round-1..3 device-kernel
    search), then quadratic subpixel. Returns (shift (3,) f64, best r).
    Candidate r values are memoized: the subpixel fit reuses the final
    round's neighbor evaluations instead of recomputing them."""
    a = np.asarray(crop_a, np.float64)
    b = np.asarray(crop_b, np.float64)
    N = np.array(fft_shape, np.int64)
    scorer = _PearsonScorer(a, b)
    memo: dict[tuple, float] = {}

    def r_at(s):
        key = tuple(int(v) for v in s)
        if key not in memo:
            memo[key] = scorer.r(np.asarray(s, np.int64), min_overlap)
        return memo[key]

    best_s, best_r = np.zeros(3, np.int64), -np.inf
    for p in np.asarray(peaks, np.int64):
        for wrap in range(8):
            c = np.array([p[d] - (N[d] if (wrap >> d) & 1 else 0)
                          for d in range(3)])
            s = -c  # PCM index c names shift -c (see _windowed convention)
            r = r_at(s)
            if r > best_r:
                best_r, best_s = r, s
    if not np.isfinite(best_r):
        return best_s.astype(np.float64), -1.0

    # hill-climb on the true correlation: the PCM peak can be split across
    # voxels (windowing) so the best integer shift may be a neighbor
    unit = np.concatenate([np.zeros((1, 3), np.int64),
                           np.eye(3, dtype=np.int64),
                           -np.eye(3, dtype=np.int64)], axis=0)
    for _ in range(3):
        cand = best_s[None, :] + unit
        rc = [r_at(s) for s in cand]
        i = int(np.argmax(rc))
        if i == 0:
            break
        best_s, best_r = cand[i], rc[i]

    shift = best_s.astype(np.float64)
    if subpixel:
        for ax in range(3):
            e = np.zeros(3, np.int64)
            e[ax] = 1
            fp, fm = r_at(best_s + e), r_at(best_s - e)
            denom = fm - 2.0 * best_r + fp
            if abs(denom) > 1e-12 and np.isfinite(fp) and np.isfinite(fm):
                shift[ax] += float(np.clip(0.5 * (fm - fp) / denom, -0.5, 0.5))
    return shift, float(best_r)


def stitch_crops(
    a, b, ext_a, ext_b, n_peaks: int = 5, min_overlap: float = 32.0,
    subpixel: bool = True, fade_frac: float = 0.25,
) -> tuple[np.ndarray, float]:
    """Single-pair convenience: device PCM peaks + host refinement.
    ``a``/``b`` are padded crops; ``ext_*`` their true extents."""
    peaks = np.asarray(pcm_peaks(jnp.asarray(a), jnp.asarray(b),
                                 jnp.asarray(ext_a), jnp.asarray(ext_b),
                                 n_peaks, fade_frac))
    ea = tuple(int(v) for v in np.asarray(ext_a))
    eb = tuple(int(v) for v in np.asarray(ext_b))
    crop_a = np.asarray(a)[tuple(slice(0, s) for s in ea)]
    crop_b = np.asarray(b)[tuple(slice(0, s) for s in eb)]
    return refine_peaks(crop_a, crop_b, peaks, tuple(np.asarray(a).shape),
                        min_overlap=min_overlap, subpixel=subpixel)


def pad_to(crop: np.ndarray, shape: tuple[int, ...]) -> np.ndarray:
    out = np.zeros(shape, dtype=np.float32)
    sl = tuple(slice(0, s) for s in crop.shape)
    out[sl] = crop
    return out
