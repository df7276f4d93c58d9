"""Affine fusion of one compute block, whatever kernel does it (shifted
slices for translations, a gather for general affines): the least a chip
has to do for one call, from the call's shapes alone.

Per view that reaches the block: about one uint16 source voxel in from HBM
for every output voxel (the sampled box of a rotated view is as large as
the block), 8 taps of trilinear interpolation at 2 flops, ~30 flops for the
three cosine blend weights and the inside test, 2 for the weighted sum.
Per block: one divide and one uint16 voxel out. The HBM bound binds on a
v5e: a four-view block of 8.4 Mvox moves 84 MB (0.10 ms) against 1.6 Gflop
(0.008 ms at the MXU figure, which an elementwise kernel cannot reach — so
the share this gives is an upper estimate of how far the kernel is from the
chip's limit).
"""


def ops_and_bytes(call: dict) -> tuple[float, float]:
    vox, views = call["voxels"], call["views"]
    flops = vox * (views * (8 * 2 + 30 + 2) + 4)
    nbytes = vox * (views * 2 + 2)
    return flops, nbytes
