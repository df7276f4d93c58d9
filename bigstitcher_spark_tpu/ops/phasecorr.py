"""FFT phase-correlation pairwise shift estimation (XLA + host search).

TPU-native re-design of the reference's stitching math (BigStitcher core
``PairwiseStitching``/``PhaseCorrelation2``, called at
SparkPairwiseStitching.java:247-267), split by what each side is good at:

- DEVICE (one fused, statically-shaped XLA computation per crop-shape
  bucket, vmapped over the batch): windowing, 3-D FFT phase correlation,
  3x3x3 local-maxima suppression, top-N peak extraction — the heavy regular
  compute.
- HOST (numpy, float64): the search. Each peak's 2^3 periodic-wrap
  interpretations are scored by true Pearson correlation over the overlap
  box, a hill-climb finds the best integer shift, a parabola the subpixel
  part; r is ten float64 operations on five sums over the box (a, a^2, b,
  b^2, a*b).
- The SUMS run where the crops are. Crops that are whole uint16 numbers
  (stored-level voxels: what every translation-registered dataset gives)
  were sent to the device as uint16 for the PCM and stay there:
  ``pearson_sums`` takes a whole round's candidates in one call and sums in
  integers, in 16-bit limbs that cannot wrap, so the sums are exact and r
  has the bits a float64 sum would give it (where that is itself exact:
  under 2^53) — a float32 sum on the device would not (2e-6 px off the
  float64 shift; PERF.md section 2). Crops that are not whole uint16
  numbers (rendered, averaged, float data) take ``_PearsonScorer``: float64
  summed-area tables on the host, as before. The data decides, nothing else.

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
# candidate scoring: the five Pearson sums of a[x], b[x + s] over the overlap
# ---------------------------------------------------------------------------

# candidates one scorer call takes (a program's fixed list length): the
# 8 wraps of the default 5 peaks fit one call; longer lists go in turns
_MAX_CANDIDATES = 40


def _limb_weights(ndim: int, small: bool) -> list[int]:
    """Weights (in units of 16 bits) of the limbs ``_limb_sums`` returns."""
    weights = [0] if small else [0, 1]
    for _ in range(ndim - 1):
        weights = [w + k for w in weights for k in (0, 1)]
    return weights


def _limb_sums(x: jnp.ndarray, small: bool) -> list[jnp.ndarray]:
    """The exact sum of a uint32 array as uint32 limbs: the total is
    ``sum(limb << 16 * weight)`` over ``_limb_weights``. Every partial sum
    adds at most 2^16 numbers under 2^16 (an axis is at most 65536 long;
    a wider number is split into its two 16-bit halves first), so none
    can wrap. ``small`` says the values are under 2^16 already. The
    leading axis goes first: vector adds, no cross-lane work."""
    parts = [x] if small else [x & 0xFFFF, x >> 16]
    for ax in range(x.ndim):
        parts = [jnp.sum(p, axis=0, dtype=jnp.uint32) for p in parts]
        if ax + 1 < x.ndim:
            parts = [q for p in parts for q in (p & 0xFFFF, p >> 16)]
    return parts


# limb weights of one row of ``pearson_sums``, a list a sum
_SUM_LIMBS = [_limb_weights(3, small) for small in (True, False, True,
                                                     False, False)]


@jax.jit
def pearson_sums(
    a: jnp.ndarray,           # (B,X,Y,Z) uint16 zero-padded crops, resident
    b: jnp.ndarray,
    ext_a: jnp.ndarray,       # (B,3) int32 extents before padding
    ext_b: jnp.ndarray,
    pair: jnp.ndarray,        # () int32 row of the stacks
    shifts: jnp.ndarray,      # (K,3) int32 candidate shifts, padded
    n: jnp.ndarray,           # () int32 how many of them count
) -> jnp.ndarray:
    """For each of the first ``n`` candidates s the sums of a, a^2,
    b(x+s), b(x+s)^2 and a*b(x+s) over the overlap box ``lo = max(0, -s)``,
    ``hi = min(ext_a, ext_b - s)``, as (K, 32) uint32 limbs
    (``_SUM_LIMBS``); rows from ``n`` on are zero. Integer arithmetic
    throughout, exact for every uint16 input: a product is under 2^32 and
    ``_limb_sums`` cannot wrap. The shifted read is a roll of the padded
    stack (inside the box x + s never wraps), the box a mask from iotas."""
    shape = a.shape[1:]
    if max(shape) > 1 << 16:
        raise ValueError(f"an axis of {shape} is over 65536: the limbs "
                         "of _limb_sums could wrap")
    a = jax.lax.dynamic_index_in_dim(a, pair, 0, keepdims=False)
    b = jax.lax.dynamic_index_in_dim(b, pair, 0, keepdims=False)
    ea = jax.lax.dynamic_index_in_dim(ext_a, pair, 0, keepdims=False)
    eb = jax.lax.dynamic_index_in_dim(ext_b, pair, 0, keepdims=False)

    def one(k, out):
        s = shifts[k]
        lo = jnp.maximum(0, -s)
        hi = jnp.minimum(ea, eb - s)
        box = None
        for ax in range(3):
            i = jax.lax.iota(jnp.int32, shape[ax])
            m = ((i >= lo[ax]) & (i < hi[ax])).reshape(
                [-1 if d == ax else 1 for d in range(3)])
            box = m if box is None else box & m
        av = jnp.where(box, a, 0).astype(jnp.uint32)
        bv = jnp.where(box, jnp.roll(b, -s, axis=(0, 1, 2)), 0
                       ).astype(jnp.uint32)
        row = jnp.stack(_limb_sums(av, True) + _limb_sums(av * av, False)
                        + _limb_sums(bv, True) + _limb_sums(bv * bv, False)
                        + _limb_sums(av * bv, False))
        return jax.lax.dynamic_update_index_in_dim(out, row, k, 0)

    return jax.lax.fori_loop(
        0, n, one, jnp.zeros((shifts.shape[0], sum(map(len, _SUM_LIMBS))),
                             jnp.uint32))


def device_sums(a, b, ext_a, ext_b, pair: int):
    """The scorer of row ``pair`` of a bucket's resident uint16 stacks, in
    ``refine_peaks``' terms: (K,3) integer shifts in, a ``(s_a, s_aa, s_b,
    s_bb, s_ab)`` of Python integers each out. One call of ``pearson_sums``
    (and one fetch) for every ``_MAX_CANDIDATES`` of them, on the device
    that holds the stacks."""
    def sums(shifts: np.ndarray) -> list[tuple]:
        out = []
        for i in range(0, len(shifts), _MAX_CANDIDATES):
            turn = shifts[i:i + _MAX_CANDIDATES]
            part = np.zeros((_MAX_CANDIDATES, 3), np.int32)
            part[:len(turn)] = turn
            limbs = np.asarray(pearson_sums(a, b, ext_a, ext_b,
                                            np.int32(pair), part,
                                            np.int32(len(turn))))
            for row in limbs[:len(turn)].tolist():
                it = iter(row)
                out.append(tuple(sum(next(it) << 16 * w for w in ws)
                                 for ws in _SUM_LIMBS))
        return out

    return sums


def as_uint16_lossless(stack: np.ndarray) -> np.ndarray | None:
    """uint16 copy of the stack when every value survives the round-trip
    exactly (integral, in range — single-channel stored-level crops), else
    None. NaN/inf/out-of-range values are rejected by a min/max pre-check
    BEFORE the cast: casting them to uint16 is C-implementation-defined
    and raises numpy 'invalid value encountered in cast' RuntimeWarnings
    (ADVICE r5). Fractional in-range values cast quietly and fail the
    equality check."""
    if stack.dtype == np.uint16:
        return stack
    if stack.dtype.kind in "iu":
        if stack.size == 0:
            return stack.astype(np.uint16)
        mn, mx = stack.min(), stack.max()
        if mn < 0 or mx > np.iinfo(np.uint16).max:
            return None
        return stack.astype(np.uint16)
    if stack.dtype.kind != "f":
        return None
    if stack.size == 0:
        return stack.astype(np.uint16)
    mn, mx = stack.min(), stack.max()  # min/max propagate NaN
    if (not np.isfinite(mn) or not np.isfinite(mx)
            or mn < 0 or mx > np.iinfo(np.uint16).max):
        return None
    u = stack.astype(np.uint16)
    return u if np.array_equal(stack, u) else None


# ---------------------------------------------------------------------------
# host-side scoring (float64 summed-area tables) and the search
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


def _overlap(ext_a, ext_b, s):
    """Box of a's voxels that a[x] and b[x + s] share, and its volume
    (0 where there is none)."""
    lo = np.maximum(0, -s)
    hi = np.minimum(ext_a, ext_b - s)
    if np.any(hi - lo < 1):
        return lo, hi, 0.0
    return lo, hi, float(np.prod(hi - lo))


def _pearson_r(n: float, s_a, s_aa, s_b, s_bb, s_ab) -> float:
    """Pearson r from the five sums over n voxels, in float64 whatever
    number system the sums came in (an integer under 2^53 converts
    exactly)."""
    s_a, s_aa, s_b, s_bb, s_ab = (np.float64(v)
                                  for v in (s_a, s_aa, s_b, s_bb, s_ab))
    va = s_aa - s_a * s_a / n
    vb = s_bb - s_b * s_b / n
    den = np.sqrt(max(va, 0.0) * max(vb, 0.0))
    if den <= 1e-12:
        return -1.0
    return float((s_ab - s_a * s_b / n) / den)


class _PearsonScorer:
    """The host scorer, for crops that are not whole uint16 numbers: the
    five sums of a[x], b[x+s] over the rectangular overlap in float64,
    with the window sums S_a, S_aa, S_b, S_bb served by summed-area
    tables — only the cross term S_ab costs a pass over the overlap, ~6x
    less memory traffic per candidate than the naive centered-copy
    evaluation."""

    def __init__(self, a: np.ndarray, b: np.ndarray):
        self.a = a
        self.b = b
        self.ext_a = np.array(a.shape, np.int64)
        self.ext_b = np.array(b.shape, np.int64)
        self.Sa = _sat(a)
        self.Saa = _sat(a * a)
        self.Sb = _sat(b)
        self.Sbb = _sat(b * b)

    def sums(self, shifts: np.ndarray) -> list[tuple]:
        out = []
        for s in shifts:
            lo, hi, _n = _overlap(self.ext_a, self.ext_b, s)
            av = self.a[tuple(slice(int(lo[d]), int(hi[d]))
                              for d in range(3))]
            bv = self.b[tuple(slice(int(lo[d] + s[d]), int(hi[d] + s[d]))
                              for d in range(3))]
            s_ab = float(np.einsum("ijk,ijk->", av, bv, dtype=np.float64,
                                   casting="unsafe"))
            out.append((_box_sum(self.Sa, lo, hi),
                        _box_sum(self.Saa, lo, hi),
                        _box_sum(self.Sb, lo + s, hi + s),
                        _box_sum(self.Sbb, lo + s, hi + s), s_ab))
        return out


def refine_peaks(
    crop_a: np.ndarray,       # unpadded crop of group A, as stored (uint16)
    crop_b: np.ndarray,       # or float32; only its shape where sums is given
    peaks: np.ndarray,        # (n_peaks, 3) wrapped PCM indices
    fft_shape: tuple[int, int, int],
    min_overlap: float = 32.0,
    subpixel: bool = True,
    sums=None,
) -> tuple[np.ndarray, float]:
    """Score peak wraps by true correlation, hill-climb (argmax over the 6
    unit neighbors + self per round, 3 rounds — the round-1..3 device-kernel
    search), then quadratic subpixel. Returns (shift (3,) f64, best r).

    ``sums`` scores a list of candidates at once: (K,3) integer shifts in,
    the five sums (a, a^2, b, b^2, a*b over the overlap) of each out.
    ``device_sums`` gives exact integers from the crops' resident uint16
    stacks, and only the crops' shapes are read here; None takes the host
    float64 tables over the crops themselves. The search hands it a whole
    round's unscored candidates in one call (the wraps, then each round's
    new neighbours, then the parabola's), after the overlap test, and keeps
    every r: about five calls a pair. r is float64 from the sums either
    way, so where they are under 2^53 both scorers give the same bits."""
    ext_a = np.array(np.shape(crop_a), np.int64)
    ext_b = np.array(np.shape(crop_b), np.int64)
    if sums is None:
        sums = _PearsonScorer(np.asarray(crop_a, np.float64),
                              np.asarray(crop_b, np.float64)).sums
    N = np.array(fft_shape, np.int64)
    memo: dict[tuple, float] = {}

    def r_at(cands) -> list[float]:
        new = {}
        for s in cands:
            key = tuple(int(v) for v in s)
            if key not in memo and key not in new:
                n = _overlap(ext_a, ext_b, np.asarray(key, np.int64))[2]
                if n < max(min_overlap, 1.0):
                    memo[key] = -np.inf
                else:
                    new[key] = n
        if new:
            for (key, n), five in zip(
                    new.items(), sums(np.array(list(new), np.int64))):
                memo[key] = _pearson_r(n, *five)
        return [memo[tuple(int(v) for v in s)] for s in cands]

    # PCM index c names shift -c (see _windowed convention)
    wraps = [-np.array([p[d] - (N[d] if (wrap >> d) & 1 else 0)
                        for d in range(3)])
             for p in np.asarray(peaks, np.int64) for wrap in range(8)]
    best_s, best_r = np.zeros(3, np.int64), -np.inf
    for s, r in zip(wraps, r_at(wraps)):
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
        rc = r_at(cand)
        i = int(np.argmax(rc))
        if i == 0:
            break
        best_s, best_r = cand[i], rc[i]

    shift = best_s.astype(np.float64)
    if subpixel:
        # the final round's neighbours again unless the climb ran out of
        # rounds: the memo serves them
        rn = r_at(best_s[None, :] + unit[1:])
        for ax in range(3):
            fp, fm = rn[ax], rn[3 + ax]
            denom = fm - 2.0 * best_r + fp
            if abs(denom) > 1e-12 and np.isfinite(fp) and np.isfinite(fm):
                shift[ax] += float(np.clip(0.5 * (fm - fp) / denom, -0.5, 0.5))
    return shift, float(best_r)


def stitch_crops(
    a, b, ext_a, ext_b, n_peaks: int = 5, min_overlap: float = 32.0,
    subpixel: bool = True, fade_frac: float = 0.25,
) -> tuple[np.ndarray, float]:
    """Single-pair convenience: device PCM peaks, then the refinement with
    the scorer the data allows (whole uint16 numbers: the device's exact
    sums; anything else: the host's float64 tables). ``a``/``b`` are padded
    crops; ``ext_*`` their true extents."""
    a, b = np.asarray(a), np.asarray(b)
    ea = np.asarray(ext_a, np.int32)
    eb = np.asarray(ext_b, np.int32)
    ua = as_uint16_lossless(a)
    ub = as_uint16_lossless(b) if ua is not None else None
    sums = None
    if ub is not None:
        a_dev, b_dev = jax.device_put((ua[None], ub[None]))
        peaks = pcm_peaks(a_dev[0], b_dev[0], ea, eb, n_peaks, fade_frac)
        sums = device_sums(a_dev, b_dev, ea[None], eb[None], 0)
    else:
        peaks = pcm_peaks(a, b, ea, eb, n_peaks, fade_frac)
    crop_a = a[tuple(slice(0, int(s)) for s in ea)]
    crop_b = b[tuple(slice(0, int(s)) for s in eb)]
    return refine_peaks(crop_a, crop_b, np.asarray(peaks), a.shape,
                        min_overlap=min_overlap, subpixel=subpixel, sums=sums)


def pad_to(crop: np.ndarray, shape: tuple[int, ...]) -> np.ndarray:
    out = np.zeros(shape, dtype=np.float32)
    sl = tuple(slice(0, s) for s in crop.shape)
    out[sl] = crop
    return out
