#!/bin/bash
# CI smoke for the device-side global solvers on the CPU fallback:
# asserts (1) the device relax path is actually taken when enabled
# (bst_solve_device_ms_total grows, exactly one compiled while_loop call
# per relax), (2) it agrees with the numpy reference on the same graph,
# (3) BST_SOLVE_DEVICE=0 falls back cleanly to the host path without
# touching the device counters, and (4) the intensity CG path engages
# and matches the dense solve.
set -euo pipefail

REPO=$(cd "$(dirname "$0")/.." && pwd)
PYTHON=${PYTHON:-python3}

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

echo '[smoke] device solver engage + parity + fallback ...'
(cd "$REPO" && $PYTHON - <<'EOF'
import numpy as np

from bigstitcher_spark_tpu import config
from bigstitcher_spark_tpu.io.spimdata import ViewId
from bigstitcher_spark_tpu.models import solver as S
from bigstitcher_spark_tpu.models.intensity import smoothness_pairs
from bigstitcher_spark_tpu.observe import metrics as _metrics
from bigstitcher_spark_tpu.ops import models as M
from bigstitcher_spark_tpu.ops.intensity import (
    match_stats,
    solve_intensity_coefficients,
)

rng = np.random.default_rng(0)
tiles = [(ViewId(0, i),) for i in range(12)]
corners = np.array([[x, y, z] for x in (0, 100) for y in (0, 100)
                    for z in (0, 50)], float)
links = []
for i in range(len(tiles)):
    for j in (i + 1, i + 4):
        if j >= len(tiles) or (j == i + 1 and i % 4 == 3):
            continue
        shift = rng.uniform(-3, 3, 3)
        links.append(S.MatchLink(tiles[i], tiles[j], corners,
                                 corners + shift, np.full(8, 0.9)))
fixed = {tiles[0]}
params = S.SolverParams(model=M.AFFINE, regularization=M.RIGID)

ms = _metrics.counter("bst_solve_device_ms_total", stage="relax")

# 1) enabled (the default): the device path must be TAKEN
assert config.get_bool("BST_SOLVE_DEVICE"), "BST_SOLVE_DEVICE must default on"
before = ms.value
dev = S.relax(links, tiles, fixed, params)
assert ms.value > before, "device relax did not engage"
print(f"  device relax: {dev.iterations} sweeps, err {dev.error:.4g}")

# 2) parity with the numpy reference
with config.overrides({"BST_SOLVE_DEVICE": False}):
    before = ms.value
    ref = S.relax(links, tiles, fixed, params)
    # 3) clean fallback: numpy path, device counter untouched
    assert ms.value == before, "fallback still ran the device kernel"
assert dev.iterations == ref.iterations
np.testing.assert_allclose(dev.history, ref.history, rtol=1e-9, atol=1e-9)
for k in ref.corrections:
    np.testing.assert_allclose(dev.corrections[k], ref.corrections[k],
                               rtol=1e-7, atol=1e-9)
print("  numpy parity ok (identical sweep count, history to 1e-9)")

# 4) intensity CG engages and matches the dense solve
dims, n_views = (4, 4, 4), 2
C = int(np.prod(dims)) * n_views
matches = []
for _ in range(120):
    ca, cb = rng.integers(0, C, 2)
    if ca == cb:
        continue
    x = rng.uniform(100, 1000, 40)
    y = rng.uniform(0.8, 1.2) * x + rng.uniform(-20, 20)
    matches.append((int(ca), int(cb), *match_stats(x / 500, y / 500)))
smooth = smoothness_pairs(dims, n_views)
msi = _metrics.counter("bst_solve_device_ms_total", stage="intensity")
before = msi.value
cg = solve_intensity_coefficients(C, matches, 0.1, smooth_pairs=smooth)
assert msi.value > before, "intensity CG did not engage"
dense = solve_intensity_coefficients(C, matches, 0.1, smooth_pairs=smooth,
                                     backend="numpy")
np.testing.assert_allclose(cg, dense, rtol=1e-6, atol=1e-6)
print("  intensity CG parity ok")
EOF
)

echo '[smoke] solver smoke OK'
