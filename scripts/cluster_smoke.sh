#!/bin/bash
# CI smoke for the pod observability fabric on one host: a detached
# `bst serve` daemon hosts the telemetry relay collector, two local
# worker processes push into it (BST_TELEMETRY_RELAY + identity-only
# BST_PROCESS_ID ranks), and the daemon's aggregated live plane must
# show them: /metrics carries host/process_index-labeled series from
# BOTH ranks, /healthz flips to 503 naming the rank whose process is
# killed (and recovers when it restarts), `bst top --cluster` renders
# the per-host rows, and `bst trace-dump --cluster` folds every rank's
# live flight-recorder ring into one Perfetto file trace-report loads.
set -euo pipefail

REPO=$(cd "$(dirname "$0")/.." && pwd)
PYTHON=${PYTHON:-python3}
WORK=$(mktemp -d /tmp/bst-cluster-smoke.XXXXXX)
SOCK="$WORK/bst.sock"
WORKER_PIDS=""
cleanup () {
    for pid in $WORKER_PIDS; do kill -9 "$pid" 2>/dev/null || true; done
    rm -rf "$WORK"
}
trap cleanup EXIT

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
# a silent rank flips the pod verdict after 2s (read per evaluation)
export BST_STALL_TIMEOUT_S=2

bst () { (cd "$REPO" && $PYTHON -m bigstitcher_spark_tpu.cli.main "$@"); }

# live-plane probe: prints "<status> <body>" even for non-200 answers;
# tolerates the consumer (grep -q) closing the pipe early
fetch () { $PYTHON -c '
import sys, urllib.request, urllib.error
try:
    with urllib.request.urlopen(sys.argv[1], timeout=10) as r:
        code, body = r.status, r.read().decode()
except urllib.error.HTTPError as e:
    code, body = e.code, e.read().decode()
try:
    print(code, body)
except BrokenPipeError:
    pass
' "$1"; }

retry () {  # retry <seconds> <command...>
    local deadline=$(( $(date +%s) + $1 )); shift
    until "$@"; do
        [ "$(date +%s)" -lt "$deadline" ] || return 1
        sleep 0.5
    done
}

free_port () { $PYTHON -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1", 0)); print(s.getsockname()[1]); s.close()'; }
PORT=$(free_port)
RPORT=$(free_port)
export BST_METRICS_PORT="$PORT"

echo '[smoke] starting daemon (collector + exporter) ...'
(bst serve --detach --socket "$SOCK" --slots 1 --idle-timeout 300 \
    --relay "127.0.0.1:$RPORT")

# a relayed worker: identity-only rank id, pushes heartbeats + metric
# snapshots until killed (the relay bring-up rides init_distributed)
cat > "$WORK/worker.py" <<'EOF'
import os, time
from bigstitcher_spark_tpu.parallel.distributed import init_distributed
init_distributed()
from bigstitcher_spark_tpu.observe import metrics, progress, relay, trace
assert relay.client() is not None, "worker did not become a push client"
rank = int(os.environ["BST_PROCESS_ID"])
metrics.counter("bst_io_read_bytes_total", op="smoke",
                path="native").inc(1000 + rank)
hb = progress.Heartbeat("smoke-stage", total=100000, every_s=0.0)
while True:
    with trace.span("barrier", stage="smoke"):
        hb.tick()
    time.sleep(0.05)
EOF

start_worker () {  # start_worker <rank> -> pid
    # the WHOLE backgrounded subshell redirects to the log, so the
    # command substitution capturing the pid never waits on the worker
    (
        cd "$REPO"
        export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"
        export BST_TELEMETRY_RELAY="127.0.0.1:$RPORT"
        export BST_PROCESS_ID=$1 BST_RELAY_INTERVAL_S=0.2 BST_METRICS_PORT=0
        exec $PYTHON "$WORK/worker.py"
    ) > "$WORK/worker-$1.log" 2>&1 &
    echo $!
}

echo '[smoke] starting two relayed workers ...'
W0=$(start_worker 0); W1=$(start_worker 1)
WORKER_PIDS="$W0 $W1"

echo '[smoke] waiting for both ranks on the aggregated /metrics ...'
has_rank () { fetch "http://127.0.0.1:$PORT/metrics" | grep -q "process_index=\"$1\""; }
retry 90 has_rank 0
retry 90 has_rank 1
# each rank's own workload counter arrives host/process_index-labeled
# (retried: a rank's very first snapshot can predate its counter inc)
has_counter () {
    fetch "http://127.0.0.1:$PORT/metrics" | grep -q \
        "bst_io_read_bytes_total{host=\"[^\"]*\",process_index=\"$1\",op=\"smoke\",path=\"native\"} $2"
}
retry 30 has_counter 0 1000
retry 30 has_counter 1 1001

echo '[smoke] pod verdict healthy while both ranks beat ...'
fetch "http://127.0.0.1:$PORT/healthz" | grep -q '"ok": true'

echo '[smoke] cluster view:'
(bst top --cluster --once --socket "$SOCK")

echo '[smoke] killing rank 1 -> /healthz must flip 503 naming it ...'
kill -9 "$W1"
unhealthy () {  # 503 AND the silent-rank entry names process_index 1
    local body
    body=$(fetch "http://127.0.0.1:$PORT/healthz")
    echo "$body" | head -1 | grep -q '^503 ' \
        && echo "$body" | grep -q '"process_index": 1'
}
retry 30 unhealthy
echo '[smoke] restarting rank 1 -> /healthz must recover ...'
W1=$(start_worker 1)
WORKER_PIDS="$W0 $W1"
healthy () { fetch "http://127.0.0.1:$PORT/healthz" | head -1 | grep -q '^200 '; }
retry 90 healthy

echo '[smoke] cluster trace dump ...'
(bst trace-dump --cluster --socket "$SOCK" --out "$WORK/pod-trace.json")
test -s "$WORK/pod-trace.json"
(bst trace-report "$WORK/pod-trace.json" > "$WORK/trace-report.txt")
test -s "$WORK/trace-report.txt"

echo '[smoke] draining ...'
kill -9 $WORKER_PIDS 2>/dev/null || true
WORKER_PIDS=""
(bst serve --stop --socket "$SOCK")

echo '[smoke] ok'
