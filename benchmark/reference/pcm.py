"""Pairwise translation by phase correlation in plain numpy/scipy: the
yardstick for the stitching cell's ``correct``.

Copied in purpose from ``bench._np_phasecorr_pair`` (PERF.md section 7 lists
the original), brought to the semantics the stitching stage documents:
zero-padded power-of-two FFT phase correlation of the mean-free, edge-faded
crops; the N highest local maxima; each peak in its 2^3 wrap variants scored
by the true Pearson correlation of the overlap it implies; a hill-climb over
unit neighbours; a parabola through r at -1, 0, +1 per axis for the
sub-pixel part. A shift ``s`` says ``a[x]`` shows what ``b[x + s]`` shows.

The Pearson sums are taken directly over the centred overlap in
``score_dtype``: float64 is the reference, float32 the control (the stage
scores in float64 on the host; float32 is the step a port to the device
would be tempted by).
"""

from __future__ import annotations

import numpy as np
import scipy.fft
from scipy.ndimage import maximum_filter


def _faded(x: np.ndarray, shp, fade: float = 0.25) -> np.ndarray:
    out = np.zeros(shp, np.float32)
    y = x.astype(np.float32) - np.float32(x.mean(dtype=np.float64))
    for ax, n in enumerate(x.shape):
        m = max(round(n * fade), 1)
        d = np.minimum(np.arange(n) + 0.5, n - (np.arange(n) + 0.5))
        ramp = (0.5 * (1 - np.cos(np.pi * np.clip(d / m, 0, 1)))
                ).astype(np.float32)
        y = y * ramp.reshape([-1 if i == ax else 1 for i in range(3)])
    out[tuple(slice(0, n) for n in x.shape)] = y
    return out


def pcm_peaks(a: np.ndarray, b: np.ndarray, n_peaks: int) -> np.ndarray:
    """(n_peaks, 3) wrapped indices of the PCM's highest local maxima, and
    the FFT shape."""
    shp = tuple(1 << int(np.ceil(np.log2(max(sa, sb, 1))))
                for sa, sb in zip(a.shape, b.shape))
    fa = scipy.fft.rfftn(_faded(a, shp), workers=-1)
    fb = scipy.fft.rfftn(_faded(b, shp), workers=-1)
    cross = fa * np.conj(fb)
    mag = np.abs(cross)
    cross = np.where(mag > 1e-5 * mag.max(), cross / np.maximum(mag, 1e-30),
                     0)
    pcm = scipy.fft.irfftn(cross, s=shp, workers=-1)
    loc = pcm == maximum_filter(pcm, size=3, mode="wrap")
    flat = np.where(loc.ravel(), pcm.ravel(), -np.inf)
    top = np.argpartition(flat, -n_peaks)[-n_peaks:]
    top = top[np.argsort(flat[top])[::-1]]
    return np.stack(np.unravel_index(top, shp), axis=-1), shp


def pearson(a, b, s, min_overlap: float, dtype=np.float64) -> float:
    """r of a[x] against b[x + s] over their overlap; -inf where the
    overlap is too small."""
    s = np.asarray(s, np.int64)
    lo = np.maximum(0, -s)
    hi = np.minimum(a.shape, np.asarray(b.shape) - s)
    if np.any(hi - lo < 1) or float(np.prod(hi - lo)) < min_overlap:
        return -np.inf
    av = a[tuple(slice(lo[d], hi[d]) for d in range(3))].astype(dtype)
    bv = b[tuple(slice(lo[d] + s[d], hi[d] + s[d]) for d in range(3))
           ].astype(dtype)
    am = av - av.mean(dtype=dtype)
    bm = bv - bv.mean(dtype=dtype)
    den = np.sqrt((am * am).sum(dtype=dtype) * (bm * bm).sum(dtype=dtype))
    return float((am * bm).sum(dtype=dtype) / den) if den > 0 else -1.0


def stitch_pair(a: np.ndarray, b: np.ndarray, n_peaks: int = 5,
                min_overlap_px: float = 32.0, min_overlap_frac: float = 0.25,
                subpixel: bool = True, score_dtype=np.float64
                ) -> tuple[np.ndarray, float]:
    """(shift (3,) float64, r) between two un-padded crops."""
    peaks, shp = pcm_peaks(a, b, n_peaks)
    min_ov = max(min_overlap_px,
                 min_overlap_frac * min(a.size, b.size))
    memo: dict[tuple, float] = {}

    def r_at(s) -> float:
        key = tuple(int(v) for v in s)
        if key not in memo:
            memo[key] = pearson(a, b, key, min_ov, score_dtype)
        return memo[key]

    best_s, best_r = np.zeros(3, np.int64), -np.inf
    for p in peaks:
        for wrap in range(8):
            # index c of the PCM of (a, conj b) names the shift -c
            s = -np.array([p[d] - (shp[d] if (wrap >> d) & 1 else 0)
                           for d in range(3)])
            if r_at(s) > best_r:
                best_s, best_r = s, r_at(s)
    if not np.isfinite(best_r):
        return best_s.astype(np.float64), -1.0
    unit = np.concatenate([np.zeros((1, 3), np.int64),
                           np.eye(3, dtype=np.int64),
                           -np.eye(3, dtype=np.int64)])
    for _ in range(3):
        cand = best_s + unit
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
            den = fm - 2.0 * best_r + fp
            if abs(den) > 1e-12 and np.isfinite(fp) and np.isfinite(fm):
                shift[ax] += float(np.clip(0.5 * (fm - fp) / den, -0.5, 0.5))
    return shift, float(best_r)
