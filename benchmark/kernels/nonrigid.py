"""Non-rigid fusion of one compute block: the least a chip has to do for
one call, from the call's shapes alone (the views' source boxes in, their
control grids in, the block out), whatever implements the sampling.

Bytes: every voxel of each view's source box once (uint16; the box is
what the block's corners span in the view's pixels: a 256x256x128 block
is 256x256x35 pixels of a view along whose z, calibrated 4, it lies, and
twice that of a view rotated by 45 degrees), each view's grid of 12
float32 coefficients a vertex, one uint16 voxel out. Flops, a voxel and view: as ``fuse_affine.py`` (8
taps at 2, ~30 of blend weights and inside test, 2 for the weighted sum)
plus two affine applications (the interpolated model, then the view's
own: 18 each); a view: the separable interpolation of the 12 coefficients
over the grid, one axis after another (a row of a linear interpolation
matrix has two entries: 3 flops for every element that comes out, on
(Lx, Gy, Gz), (Lx, Ly, Gz) and (Lx, Ly, Lz) by 12); a voxel: a divide.
The coefficient field need not touch HBM: it can be made a tile at a
time. The HBM bound binds on a v5e, as in ``fuse_affine.py``: a four-view
block's 4.2 Gflop are 0.02 ms at the MXU figure against 0.06 ms for the
cell's 49.7 MB a block (30.3 of source boxes, 2.6 of grids, 16.8 out).
"""


def ops_and_bytes(call: dict) -> tuple[float, float]:
    vox, views = call["voxels"], len(call["patches"])
    lx, ly, lz = call["block"]
    gx, gy, gz = call["grid"]
    interp = 3 * 12 * (lx * gy * gz + lx * ly * gz + lx * ly * lz)
    flops = vox * (views * (8 * 2 + 30 + 2 + 2 * 18) + 4) + views * interp
    nbytes = 2 * sum(call["patches"]) + views * gx * gy * gz * 12 * 4 \
        + 2 * vox
    return flops, nbytes
