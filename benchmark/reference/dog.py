"""Difference-of-Gaussians interest points in plain numpy float64, one
detection block at a time: the yardstick ``stages/detect.py`` holds the
program's stored points to. Imports nothing of the program.

For a block of the stored level the detection reads (the 2,2,1 level at the
defaults, made again from the seed by ``level_volume``), with a halo of
``halo(sigma)`` voxels on every side, mirrored at the image's border:

1. the voxels normalised to [0, 1] by the view's min and max, both those of
   the coarsest stored level, as the program documents when no intensity
   range is given;
2. two separable Gaussian blurs, sigma and sigma * 2^(1/4), each 1-D kernel
   of radius ceil(3 sigma) normalised to sum 1, the block extended by its
   own mirror without repeating the edge (numpy's ``reflect``);
3. the difference scaled by 1/(k - 1), k = 2^(1/4);
4. strict 3x3x3 maxima above the threshold whose voxel lies in the block's
   core;
5. each refined by a 3-D quadratic fit on central differences: offset
   -H^-1 g clipped to [-1, 1]; where an offset exceeds 0.5 the base moves
   one voxel that way and the fit repeats, up to 4 times (mpicbg's rule, as
   the program runs it on the device);
6. block coordinates to the view's detection grid, then to full resolution:
   a level voxel p of factors f sits at f p + (f - 1) / 2.

Departures from upstream's ``DoGImgLib2.computeDoG`` (BigStitcher via
imglib2), shared with the program unless marked:

- the kernels' radius is ceil(3 sigma); imglib2's ``Gauss3`` sizes its
  kernels by a rule of its own;
- sigma is used as given; imglib2's DoG detector first takes off an assumed
  image blur of 0.5 px;
- bright spots are maxima of (G_sigma - G_k sigma) / (k - 1); upstream finds
  them as minima of the opposite difference, which is the same set;
- the volume is filtered block by block with a halo, the block's own mirror
  beyond it, where upstream filters the whole extended view: the same
  response in the core and one voxel around it, but a fit that walks to the
  core's edge reads response values that saw the block's mirror;
- (reference only) maxima are strict; the program breaks ties between
  equal neighbours with a 2^-30-scale hash of the voxel's position;
- (reference only) every maximum is kept; the program keeps the K
  strongest of a block (4096 at the defaults) and counts the rest.
"""

from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

K = 2.0 ** 0.25
MAX_MOVES = 4
THREADS = min(8, os.cpu_count() or 1)


def taps(sigma: float) -> np.ndarray:
    """The normalised 1-D Gaussian of radius ceil(3 sigma), float64."""
    r = max(1, math.ceil(3.0 * sigma))
    x = np.arange(-r, r + 1, dtype=np.float64)
    g = np.exp(-x * x / (2.0 * sigma * sigma))
    return g / g.sum()


def halo(sigma: float) -> int:
    """The larger kernel's radius and one voxel for the 3x3x3 neighbours:
    with it the response of the core and the ring around it reads no
    voxel of the block's own mirror."""
    return max(1, math.ceil(3.0 * sigma * K)) + 1


# ------------------------------------------------------------ the voxels

def level_volume(acq, view: int, level: int) -> np.ndarray:
    """A view's whole stored level, (x, y, z) uint16, made from the seed
    a stored block a task (numpy releases the interpreter lock)."""
    size = acq.level_size(level)
    grid = [-(-s // b) for s, b in zip(size, acq.block)]
    out = np.zeros(size, np.uint16)

    def one(g):
        blk = acq.block_zyx(view, level, g).transpose(2, 1, 0)
        lo = [k * b for k, b in zip(g, acq.block)]
        out[tuple(slice(a, a + s) for a, s in zip(lo, blk.shape))] = blk

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(one, np.ndindex(*grid)))
    return out


def mirror_box(volume: np.ndarray, lo, hi) -> np.ndarray:
    """Voxels [lo, hi) of ``volume``, mirrored without edge repeat where
    the box leaves it (a box may reach one image size past an edge)."""
    index = []
    for d, n in enumerate(volume.shape):
        i = np.arange(int(lo[d]), int(hi[d]))
        i = np.abs(i)
        i = np.where(i > n - 1, 2 * (n - 1) - i, i)
        index.append(i)
    return volume[np.ix_(*index)]


# ------------------------------------------------------------- precision

def bfloat16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _blur_axis(x: np.ndarray, w: np.ndarray, axis: int,
               precision: str) -> np.ndarray:
    """One 1-D pass along ``axis``, the array extended by its mirror."""
    r = len(w) // 2
    n = x.shape[axis]
    xp = np.pad(x, [(r, r) if d == axis else (0, 0) for d in range(3)],
                mode="reflect")

    def shifted(j):
        return xp[tuple(slice(j, j + n) if d == axis else slice(None)
                        for d in range(3))]

    if precision == "float64":
        # the kernel is even: one product a pair of taps
        out = w[r] * shifted(r)
        for j in range(1, r + 1):
            out += w[r + j] * (shifted(r + j) + shifted(r - j))
        return out
    if precision != "bfloat16":
        raise ValueError(f"unknown precision {precision!r}")
    # bfloat16 values, taps and accumulation: every product and every
    # partial sum rounded, the taps in order
    wb = bfloat16(w.astype(np.float32))
    out = np.zeros(x.shape, np.float32)
    for j in range(len(w)):
        out = bfloat16(out + bfloat16(wb[j] * shifted(j)))
    return out


def blur(x: np.ndarray, sigma: float, precision: str = "float64"
         ) -> np.ndarray:
    """Separable Gaussian blur of an (x, y, z) block; slabs of the axis
    not being blurred go to threads."""
    w = taps(sigma)
    for axis in range(3):
        other = (axis + 1) % 3
        n = x.shape[other]
        cuts = np.linspace(0, n, min(THREADS, n) + 1).astype(int)
        out = np.empty(x.shape, np.float64 if precision == "float64"
                       else np.float32)

        def one(i, x=x, axis=axis, other=other, cuts=cuts, out=out):
            sl = tuple(slice(cuts[i], cuts[i + 1]) if d == other
                       else slice(None) for d in range(3))
            out[sl] = _blur_axis(x[sl], w, axis, precision)

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(one, range(len(cuts) - 1)))
        x = out
    return x


def response(block: np.ndarray, lo: float, hi: float, sigma: float,
             precision: str = "float64") -> np.ndarray:
    """(G_sigma - G_k sigma) / (k - 1) of the block normalised to [0, 1],
    float64; ``precision`` is that of the blurs."""
    x = (block.astype(np.float64) - lo) / (hi - lo) if hi > lo \
        else np.zeros(block.shape)
    if precision == "bfloat16":
        x = bfloat16(x)
    g1 = blur(x, sigma, precision).astype(np.float64)
    g2 = blur(x, sigma * K, precision).astype(np.float64)
    return (g1 - g2) / (K - 1.0)


# ------------------------------------------------------- maxima and fits

def maxima(dog: np.ndarray, core: tuple, floor: float) -> np.ndarray:
    """(M, 3) voxels of ``core`` (three slices of ``dog``, at least one
    voxel from its edge) that are strict maxima of their 3x3x3 neighbours
    and over ``floor``."""
    lo = np.array([s.start for s in core]) - 1
    hi = np.array([s.stop for s in core]) + 1
    m = dog[lo[0]:hi[0], lo[1]:hi[1], lo[2]:hi[2]]
    big = m
    for axis in range(3):
        n = big.shape[axis] - 2

        def sl(j, axis=axis, n=n):
            return tuple(slice(j, j + n) if d == axis else slice(None)
                         for d in range(3))

        big = np.maximum(np.maximum(big[sl(0)], big[sl(1)]), big[sl(2)])
        m = m[sl(1)]
    cand = np.argwhere((m == big) & (m > floor)) + lo + 1
    if not len(cand):
        return cand
    # strict: no neighbour equal to the centre
    c = dog[tuple(cand.T)]
    ties = np.zeros(len(cand), np.int64)
    for off in np.ndindex(3, 3, 3):
        ties += dog[tuple((cand + np.array(off) - 1).T)] == c
    return cand[ties == 1]


def localize(dog: np.ndarray, p0: np.ndarray,
             max_moves: int = MAX_MOVES) -> tuple[np.ndarray, np.ndarray]:
    """Quadratic sub-voxel positions (M, 3) and values (M,) of the maxima
    ``p0``, float64."""
    shape = np.array(dog.shape)
    p = np.asarray(p0, np.int64).copy()
    result = p.astype(np.float64)
    value = dog[tuple(p.T)].astype(np.float64)
    active = np.ones(len(p), bool)
    eye = np.eye(3, dtype=np.int64)

    def at(q):
        return dog[tuple(np.clip(q, 0, shape - 1).T)]

    for _ in range(max_moves):
        elig = active & np.all((p >= 1) & (p <= shape - 2), axis=1)
        c = at(p)
        plus = [at(p + eye[d]) for d in range(3)]
        minus = [at(p - eye[d]) for d in range(3)]
        g = np.stack([0.5 * (plus[d] - minus[d]) for d in range(3)], -1)
        h = np.empty((len(p), 3, 3))
        for d in range(3):
            h[:, d, d] = plus[d] - 2.0 * c + minus[d]
            for e in range(d + 1, 3):
                h[:, d, e] = h[:, e, d] = 0.25 * (
                    at(p + eye[d] + eye[e]) - at(p + eye[d] - eye[e])
                    - at(p - eye[d] + eye[e]) + at(p - eye[d] - eye[e]))
        ok = np.abs(np.linalg.det(h)) > 1e-12
        off = np.zeros((len(p), 3))
        if ok.any():
            off[ok] = -np.linalg.solve(h[ok], g[ok][..., None])[..., 0]
        off = np.clip(off, -1.0, 1.0)
        result = np.where(elig[:, None], p + off, result)
        value = np.where(elig, c + 0.5 * np.sum(g * off, axis=1), value)
        moved = np.abs(off) > 0.5
        active = moved.any(axis=1) & ok & elig
        p = np.where(active[:, None], p + np.where(moved, np.sign(off), 0)
                     .astype(np.int64), p)
    return result, value


def detect_block(block: np.ndarray, lo: float, hi: float, sigma: float,
                 threshold: float, margin: float = 0.0,
                 precision: str = "float64") -> dict:
    """The interest points of one block with its halo: ``pos`` (M, 3)
    sub-voxel positions in the block's own coordinates, ``value`` the
    response at each maximum's voxel. Maxima down to ``threshold * (1 -
    margin)`` are returned; ``required`` marks those over ``threshold * (1
    + margin)``, the ones a computation to float32's digits cannot put on
    the other side of the threshold."""
    h = halo(sigma)
    dog = response(block, lo, hi, sigma, precision)
    core = tuple(slice(h, n - h) for n in dog.shape)
    vox = maxima(dog, core, threshold * (1.0 - margin))
    value = dog[tuple(vox.T)] if len(vox) else np.zeros(0)
    pos, _ = localize(dog, vox) if len(vox) else (np.zeros((0, 3)), None)
    return {"pos": pos, "value": value,
            "required": value > threshold * (1.0 + margin)}


def full_resolution(det: np.ndarray, factors) -> np.ndarray:
    """Detection-grid positions to the view's full-resolution pixels."""
    f = np.asarray(factors, np.float64)
    return det * f + (f - 1.0) / 2.0
