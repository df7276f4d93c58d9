#!/bin/bash
# CI smoke for the `bst serve` daemon on the CPU fallback: start a
# detached daemon on a scratch socket, submit one tiny affine fusion
# through it, list the job table, drain cleanly, and exit 0 only if every
# step did. The idle timeout guarantees a crashed client can never leak a
# resident daemon into the CI host.
set -euo pipefail

REPO=$(cd "$(dirname "$0")/.." && pwd)
PYTHON=${PYTHON:-python3}
WORK=$(mktemp -d /tmp/bst-serve-smoke.XXXXXX)
SOCK="$WORK/bst.sock"
trap 'rm -rf "$WORK"' EXIT

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

# run from the repo so the package imports; every path below is absolute
bst () { (cd "$REPO" && $PYTHON -m bigstitcher_spark_tpu.cli.main "$@"); }

# live-exporter probe (python, not curl — curl is not on every CI host):
# prints the body, exits non-zero on a non-200 status
fetch () { $PYTHON -c '
import sys, urllib.request
with urllib.request.urlopen(sys.argv[1], timeout=10) as r:
    sys.stdout.write(r.read().decode())
' "$1"; }

# a free TCP port for the daemon's HTTP exporter
PORT=$($PYTHON -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1", 0)); print(s.getsockname()[1]); s.close()')
export BST_METRICS_PORT="$PORT"

echo '[smoke] building tiny fixture ...'
(cd "$REPO" && $PYTHON - "$WORK" <<'EOF'
import sys
from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project
make_synthetic_project(sys.argv[1] + "/proj", n_tiles=(2, 1, 1),
                       tile_size=(64, 64, 32), overlap=16, jitter=1.0,
                       n_beads_per_tile=20)
EOF
)

echo '[smoke] starting daemon ...'
(bst serve --detach --socket "$SOCK" --slots 1 \
    --idle-timeout 300)

echo '[smoke] submitting fusion ...'
(bst submit --socket "$SOCK" create-fusion-container \
     -x "$WORK/proj/dataset.xml" -o "$WORK/proj/fused.ome.zarr" \
     -s ZARR -d UINT16 --minIntensity 0 --maxIntensity 65535 && \
 bst submit --socket "$SOCK" affine-fusion -o "$WORK/proj/fused.ome.zarr")

echo '[smoke] job table:'
(bst jobs --socket "$SOCK")

echo '[smoke] live exporter ...'
# /healthz must answer 200 with ok:true on a healthy draining-free daemon
fetch "http://127.0.0.1:$PORT/healthz" | grep -q '"ok": true'
# /metrics must expose a declared bst_serve_* series with live values
fetch "http://127.0.0.1:$PORT/metrics" | grep -q '^bst_serve_jobs_submitted_total 2'
fetch "http://127.0.0.1:$PORT/metrics" | grep -q '^bst_process_uptime_seconds'
echo '[smoke] live view:'
(bst top --once --socket "$SOCK")
echo '[smoke] trace dump:'
(bst trace-dump --socket "$SOCK" --out "$WORK/live-trace.json")
test -s "$WORK/live-trace.json"

echo '[smoke] draining ...'
(bst serve --stop --socket "$SOCK")

echo '[smoke] ok'
