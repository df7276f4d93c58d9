"""DoG detection of a batch of blocks with halo, top-K maxima out: the
least a chip has to do for one call, from the call's shapes alone.

Per voxel of a block with its halo: the two separable Gaussian blurs by
their taps, 2 (2r + 1) flops an axis and a blur (radius ceil(3 sigma) and
ceil(3 sigma 2^(1/4)): 13 and 15 taps at sigma 1.8), the difference and its
scale (2), and the comparison with the 26 neighbours (26). The blocks come
in once from HBM in their stored type and ``candidates`` maxima go out
(three int32 coordinates, three float32 sub-voxel ones, a float32 value, a
validity byte). Not counted: the zero bands of the banded-Toeplitz GEMMs
the program blurs with, so that any way of blurring is read against the
same work. The HBM bound binds on a v5e: a batch of 8 uint16 blocks of
528x528x144 is 642 MB, 0.78 ms at 819 GB/s, against 63 Gflop, 0.32 ms at
197 TFLOP/s.
"""

import math


def ops_and_bytes(call: dict) -> tuple[float, float]:
    sigma = call["sigma"]
    r1 = max(1, math.ceil(3 * sigma))
    r2 = max(1, math.ceil(3 * sigma * 2 ** 0.25))
    vox = math.prod(call["shape"])
    per_voxel = 3 * 2 * (2 * r1 + 1) + 3 * 2 * (2 * r2 + 1) + 2 + 26
    flops = call["blocks"] * vox * per_voxel
    nbytes = call["blocks"] * (vox * call["itemsize"]
                               + call["candidates"] * (3 * 4 + 3 * 4 + 4 + 1))
    return flops, nbytes
