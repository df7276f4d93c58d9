"""Each ops-and-bytes function against shapes counted by hand."""

import math

import pytest

from benchmark.kernels import fuse_affine, pcm


def test_pcm_counts_a_two_pair_bucket():
    # the face bucket of grid1k: 2 pairs of (64, 512, 256) float32 crops
    flops, nbytes = pcm.ops_and_bytes(
        {"fft_shape": [64, 512, 256], "pairs": 2, "peaks": 5})
    n = 64 * 512 * 256
    assert n == 8388608 and math.log2(n) == 23
    assert nbytes == 2 * (2 * n * 4 + 5 * 3 * 4) == 134217848
    assert flops == 2 * (3 * 2.5 * n * 23 + 12 * n) == pytest.approx(3.1e9,
                                                                     rel=0.01)
    # 134 MB at 819 GB/s: 0.164 ms; the issue's check of PR 22's reading
    assert nbytes / 819e9 == pytest.approx(0.164e-3, rel=0.01)
    assert nbytes / 819e9 > 10 * flops / 197e12   # the HBM bound binds


@pytest.mark.parametrize("views", [1, 2, 4])
def test_fuse_counts_a_compute_block(views):
    vox = 256 * 256 * 128
    flops, nbytes = fuse_affine.ops_and_bytes({"voxels": vox,
                                               "views": views})
    assert nbytes == vox * 2 * (views + 1)
    assert flops == vox * (48 * views + 4)
    assert nbytes / 819e9 > flops / 197e12
