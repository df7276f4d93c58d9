"""Affine fusion of one output box in plain numpy: the yardstick for the
fusion cells' ``correct``.

Copied in purpose from ``bench._baseline_fuse_block`` (PERF.md section 7
lists the original for a later PR to delete), with two changes: it takes
its source voxels from the seeded generator (``fixture.Acquisition.region``)
and not through the program's loader, and it computes in float64 so that
it is a finer truth than the float32 kernels it judges. Per view: inverse
affine coordinates, trilinear sample, cosine-edge blend weight over
``blend_range`` px, weighted average (AVG_BLEND), then the uint16
conversion of the reference's type converter (round half to even, clip).

``precision="bfloat16"`` is the control of "How correct is decided": the
same arithmetic with the sampled values, the weights and the accumulation
rounded to bfloat16 after every step (coordinates stay float32, as a kernel
that chased speed would keep them). It has to come out as not correct.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
from scipy.ndimage import map_coordinates

from .fixture import Acquisition, invert


def _quantizer(precision: str):
    if precision == "float64":
        return np.float64, lambda x: x
    if precision == "bfloat16":
        return np.float32, lambda x: x.astype(ml_dtypes.bfloat16).astype(
            np.float32)
    raise ValueError(f"no such precision: {precision}")


def fuse_box(acq: Acquisition, lo, shape, blend_range: float = 40.0,
             precision: str = "float64") -> tuple[np.ndarray, np.ndarray]:
    """Fuse world box [lo, lo+shape) over every registered view. Returns
    (uint16 block indexed (x, y, z), the summed blend weight of each voxel).
    """
    ft, q = _quantizer(precision)
    lo = np.asarray(lo, np.int64)
    acc = np.zeros(shape, ft)
    wsum = np.zeros(shape, ft)
    axes = [(np.arange(shape[d], dtype=ft) + ft(lo[d])).reshape(
        [-1 if i == d else 1 for i in range(3)]) for d in range(3)]
    corners = np.array([[x, y, z] for x in (lo[0], lo[0] + shape[0] - 1)
                        for y in (lo[1], lo[1] + shape[1] - 1)
                        for z in (lo[2], lo[2] + shape[2] - 1)], np.float64)
    dims = np.asarray(acq.size, np.float64)
    for v, model in enumerate(acq.registered):
        inv = invert(model)
        src = corners @ inv[:, :3].T + inv[:, 3]
        p_lo = np.maximum(np.floor(src.min(0)).astype(np.int64) - 1, 0)
        p_hi = np.minimum(np.ceil(src.max(0)).astype(np.int64) + 2,
                          np.asarray(acq.size))
        if np.any(p_hi <= p_lo):
            continue
        patch = acq.region(v, 0, p_lo, p_hi).astype(ft)
        inv = inv.astype(ft)
        w = None
        coords = []
        for i in range(3):
            li = (inv[i, 0] * axes[0] + inv[i, 1] * axes[1]
                  + inv[i, 2] * axes[2] + inv[i, 3])
            coords.append(li - ft(p_lo[i]))
            d = np.minimum(li, ft(dims[i] - 1.0) - li)
            ramp = 0.5 * (np.cos((1.0 - d / ft(blend_range)) * np.pi) + 1.0)
            wi = np.where(d < 0, ft(0), np.where(d < blend_range, ramp,
                                                 ft(1)))
            w = q(wi) if w is None else q(w * q(wi))
        val = q(map_coordinates(patch, coords, order=1, mode="constant",
                                cval=0.0, output=ft))
        acc = q(acc + q(val * w))
        wsum = q(wsum + w)
    fused = np.where(wsum > 0, q(acc / np.maximum(wsum, ft(1e-20))), ft(0))
    return np.clip(np.round(fused), 0, 65535).astype(np.uint16), wsum
