#!/bin/bash
# Multi-host launcher for the block-writing stages (affine-fusion, resave,
# nonrigid-fusion, downsample) — the role the reference fills with
# flintstone/spark-janelia (src/main/scripts/flintstone-sge-example.sh:29-119).
#
# Every process runs the SAME bst command; jax.distributed wires them into
# one runtime and each takes its deterministic slice of the block grid
# (bigstitcher_spark_tpu/parallel/distributed.py). Output chunks are
# disjoint, so no cross-host traffic happens outside the stage barriers.
#
# Usage:
#   # all N processes on THIS machine — the CPU test world only
#   # (JAX_PLATFORMS=cpu): a TPU chip belongs to one process, so on a TPU
#   # host every one of N local processes would claim every chip. ONE
#   # process drives all the chips of a TPU host; just run `bst <tool>`.
#   JAX_PLATFORMS=cpu scripts/pod_launch.sh -n 4 -- affine-fusion -o /data/fused.zarr
#
#   # one process per host on a cluster (run on every host, ids 0..N-1):
#   scripts/pod_launch.sh -n 4 -c head-node:8476 -i $HOST_ID -- \
#       affine-fusion -o /shared/fused.zarr
#
#   # Cloud TPU pod slices: jax autodetects the topology — just export
#   # BST_DISTRIBUTED=1 and run `bst <tool> ...` on every worker
#   # (gcloud compute tpus tpu-vm ssh ... --worker=all --command="...").
#
# SLURM: sbatch with --ntasks=N and run
#   scripts/pod_launch.sh -n $SLURM_NTASKS -c $MASTER:8476 -i $SLURM_PROCID -- ...
set -euo pipefail

NUM=2
COORD=""
PID=""
while [[ $# -gt 0 ]]; do
  case "$1" in
    -n|--num-processes) NUM="$2"; shift 2 ;;
    -c|--coordinator)   COORD="$2"; shift 2 ;;
    -i|--process-id)    PID="$2"; shift 2 ;;
    --) shift; break ;;
    *) echo "unknown option $1 (expected -n/-c/-i -- <bst args>)"; exit 2 ;;
  esac
done
[[ $# -gt 0 ]] || { echo "missing bst command after --"; exit 2; }

BST=${BST:-"python -m bigstitcher_spark_tpu.cli.main"}

if [[ -z "$PID" ]]; then
  # local mode: all N processes on this machine against a local coordinator
  # (free port picked by binding, not guessed)
  if [[ -z "$COORD" ]]; then
    PORT=$(python - <<'PY'
import socket
s = socket.socket(); s.bind(("127.0.0.1", 0)); print(s.getsockname()[1])
PY
)
    COORD="127.0.0.1:$PORT"
  fi
  echo "[pod_launch] $NUM local processes, coordinator $COORD"
  pids=()
  # a worker that dies leaves its peers blocked on the jax.distributed
  # barrier forever — fail fast: first nonzero exit kills the rest
  trap 'kill "${pids[@]}" 2>/dev/null' EXIT
  for i in $(seq 0 $((NUM - 1))); do
    BST_COORDINATOR="$COORD" BST_NUM_PROCESSES="$NUM" BST_PROCESS_ID="$i" \
      $BST "$@" > >(sed "s/^/[p$i] /") 2>&1 &
    pids+=($!)
  done
  remaining=$NUM
  while (( remaining > 0 )); do
    set +e
    wait -n
    rc=$?
    set -e
    if (( rc != 0 )); then
      echo "[pod_launch] a worker failed (rc=$rc); terminating the rest"
      kill "${pids[@]}" 2>/dev/null || true
      wait || true
      exit "$rc"
    fi
    remaining=$((remaining - 1))
  done
  trap - EXIT
  exit 0
fi

[[ -n "$COORD" ]] || { echo "-c coordinator required with -i"; exit 2; }
echo "[pod_launch] process $PID/$NUM, coordinator $COORD"
exec env BST_COORDINATOR="$COORD" BST_NUM_PROCESSES="$NUM" \
     BST_PROCESS_ID="$PID" $BST "$@"
