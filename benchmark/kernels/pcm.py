"""Phase correlation of a batch of padded crop pairs, top-N peaks: the
least a chip has to do for one call, from the call's shapes alone.

Per pair: two float32 crops of the FFT box come in from HBM once and
``peaks`` indices go out (the spectra and the correlation matrix need not
touch HBM in the ideal); two real forward FFTs and one inverse at about
2.5 N log2 N flops each, plus ~12 N for the cross-power spectrum and its
normalisation. On a v5e the HBM bound binds by a factor of ten or more:
8.4 M points are 67 MB at 819 GB/s = 82 us against 1.5 Gflop at
197 TFLOP/s = 8 us.
"""

import math


def ops_and_bytes(call: dict) -> tuple[float, float]:
    n = math.prod(call["fft_shape"])
    pairs = call["pairs"]
    flops = pairs * (3 * 2.5 * n * math.log2(n) + 12 * n)
    nbytes = pairs * (2 * n * 4 + call["peaks"] * 3 * 4)
    return flops, nbytes
