#!/bin/bash
# CI smoke for the closed telemetry loop on the CPU fallback:
#   1. record a real tiny-fusion run (telemetry + history) under a
#      deliberately starved chunk cache so the advisor has a genuine
#      bottleneck to find, and assert `bst tune advise` fires a rule;
#   2. run a 2-trial `bst tune run` and assert it writes a profile with
#      every trial recorded as a tune-trial history record;
#   3. replay a fusion under the stored profile via `bst tune apply`
#      and assert it exits cleanly.
# Exits 0 only if every step did.
set -euo pipefail

REPO=$(cd "$(dirname "$0")/.." && pwd)
PYTHON=${PYTHON:-python3}
WORK=$(mktemp -d /tmp/bst-tune-smoke.XXXXXX)
HIST="$WORK/history"
trap 'rm -rf "$WORK"' EXIT

# 2 virtual devices, not the usual 8: this smoke's fixture is 64 tiny
# views and the per-view dispatch overhead of a wide virtual mesh on a
# small CI core count dominates the actual work
export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2"

# run from the repo so the package imports; every path below is absolute
bst () { (cd "$REPO" && $PYTHON -m bigstitcher_spark_tpu.cli.main "$@"); }

echo '[smoke] building tiny fixture ...'
# 64 single-chunk tiles: enough chunk-cache traffic to clear the
# advisor's 64-lookup significance floor with a genuinely starved cache
(cd "$REPO" && $PYTHON - "$WORK" <<'EOF'
import sys
from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project
make_synthetic_project(sys.argv[1] + "/proj", n_tiles=(8, 8, 1),
                       tile_size=(16, 16, 8), overlap=4, jitter=0.0,
                       n_beads_per_tile=3)
EOF
)

echo '[smoke] recording a starved-cache fusion run ...'
bst create-fusion-container -x "$WORK/proj/dataset.xml" \
    -o "$WORK/proj/fused.ome.zarr" -s ZARR -d UINT16 \
    --minIntensity 0 --maxIntensity 65535
# a ~4-chunk cache (each 16x16x8 uint16 tile is one 4096-byte chunk):
# every lookup misses and almost every insert evicts, the exact thrash
# signature the chunk_cache_thrash rule looks for. The knob applies to
# this run only, not this shell's exported env — --telemetry-dir +
# BST_HISTORY_DIR close the recording loop.
BST_HISTORY_DIR="$HIST" BST_CHUNK_CACHE_BYTES=20000 \
    bst affine-fusion -o "$WORK/proj/fused.ome.zarr" \
    --telemetry-dir "$WORK/tel"

echo '[smoke] advising on the recorded run ...'
ADVICE=$(bst tune advise --history-dir "$HIST" --json)
echo "$ADVICE"
echo "$ADVICE" | grep -q '"rule"' \
    || { echo 'FAIL: advisor fired no rule on a starved-cache run'; exit 1; }

echo '[smoke] 2-trial autotune ...'
bst tune run --history-dir "$HIST" --workload tiny-fusion \
    --trials 1 --max-trials 2 --knob BST_WRITE_THREADS
test -f "$HIST/profiles.json" \
    || { echo 'FAIL: tune run wrote no profile store'; exit 1; }
bst tune list --history-dir "$HIST" | grep -q tiny-fusion \
    || { echo 'FAIL: stored profile not listed'; exit 1; }
TRIALS=$(bst history list --history-dir "$HIST" --tool tune-trial --json \
    | grep -c '"id"')
[ "$TRIALS" -ge 2 ] \
    || { echo "FAIL: expected >=2 tune-trial records, got $TRIALS"; exit 1; }

echo '[smoke] replaying a fusion under the stored profile ...'
bst tune apply --history-dir "$HIST" auto
bst tune apply --history-dir "$HIST" auto \
    affine-fusion -o "$WORK/proj/fused.ome.zarr"

echo '[smoke] OK'
