#!/bin/bash
# CI smoke for the multi-host execution world on one machine: two REAL
# local CPU processes form a jax.distributed world (gloo collectives)
# with the cross-host block exchange on, run the streamed
# resave -> create(rank 0) -> fuse pipeline SPMD, and exit 0 only if
# - both ranks pulled remote-owned chunks over TCP
#   (bst_dag_xhost_bytes_total > 0 on the resaved edge),
# - the elided intermediate re-read ZERO container bytes,
# - the fused s0 volume is BITWISE identical across both ranks AND to a
#   single-process run of the same spec,
# - the global solve mesh spanned both processes and the default-on
#   pair split covered the task list exactly once.
set -euo pipefail

REPO=$(cd "$(dirname "$0")/.." && pwd)
PYTHON=${PYTHON:-python3}
WORK=$(mktemp -d /tmp/bst-multihost-smoke.XXXXXX)
WORKER_PIDS=""
cleanup () {
    for pid in $WORKER_PIDS; do kill -9 "$pid" 2>/dev/null || true; done
    rm -rf "$WORK"
}
trap cleanup EXIT

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"

free_port () { $PYTHON -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1",0)); print(s.getsockname()[1]); s.close()'; }

COORD_PORT=$(free_port)
XPORT0=$(free_port)
XPORT1=$(free_port)

echo '[smoke] building tiny fixture ...'
(cd "$REPO" && $PYTHON - "$WORK" <<'EOF'
import sys
from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project
make_synthetic_project(sys.argv[1] + "/proj", n_tiles=(2, 1, 1),
                       tile_size=(64, 64, 32), overlap=16, jitter=1.0,
                       n_beads_per_tile=20, seed=7)
EOF
)

cat > "$WORK/worker.py" <<'EOF'
import hashlib, json, os, sys
import numpy as np
from bigstitcher_spark_tpu.parallel.distributed import init_distributed, world
joined = init_distributed()   # False in the single-process golden run
from bigstitcher_spark_tpu import config
from bigstitcher_spark_tpu.dag.executor import run_pipeline
from bigstitcher_spark_tpu.io.chunkstore import ChunkStore
from bigstitcher_spark_tpu.ops import solve as OS
from bigstitcher_spark_tpu.parallel import pairsched

rank, pc = world()
assert joined or pc == 1, "worker failed to join the jax world"
proj = sys.argv[1]
xml = os.path.join(proj, "dataset.xml")
rexml = os.path.join(proj, "re.xml")

if pc > 1:
    # the global solve mesh must be auto-on and span both processes
    assert OS.global_enabled(), "BST_SOLVE_GLOBAL auto must follow the world"
    with config.overrides({"BST_SOLVE_SHARD": 1}):
        n, g = OS.solve_layout(64)
        ndev, nproc = OS.global_axis_span(n, g)
    assert g and nproc == pc, (n, g, ndev, nproc)
    # the default-on pair split covers the list exactly once
    assert pairsched.multihost_active()

tasks = [pairsched.PairTask(index=i, cost=float(1 + i % 4))
         for i in range(11)]
ran = []
vals = pairsched.run_pair_tasks(
    tasks, lambda t: (ran.append(t.index), t.index * 3)[1],
    stage="smoke")
assert vals == [i * 3 for i in range(11)], vals
assert len(ran) == 11 if pc == 1 else 0 < len(ran) < 11, ran

spec = {
    "name": "mh-smoke",
    "datasets": {
        "resaved": {"path": os.path.join(proj, "resaved.n5"),
                    "ephemeral": True},
        "fused": {"path": os.path.join(proj, "fused.n5")},
    },
    "stages": [
        {"id": "resave", "tool": "resave",
         "args": ["-x", xml, "-xo", rexml, "-o", "@resaved", "--N5",
                  "--blockSize", "32,32,16", "-ds", "1,1,1"],
         "writes": ["resaved"]},
        {"id": "create", "tool": "create-fusion-container",
         "args": ["-x", rexml, "-o", "@fused", "-s", "N5", "-d", "UINT16",
                  "--minIntensity", "0", "--maxIntensity", "65535",
                  "--blockSize", "32,32,16"],
         "after": ["resave"], "ranks": [0]},
        {"id": "fuse", "tool": "affine-fusion", "args": ["-o", "@fused"],
         "after": ["create"], "reads": ["resaved"], "writes": ["fused"]},
    ],
}
res = run_pipeline(spec, workdir=proj)
d = res.to_dict()
assert res.ok, d
edge = {e["edge"]: e for e in d["edges"]}["resaved"]
ds = ChunkStore.open(os.path.join(proj, "fused.n5")).open_dataset("ch0tp0/s0")
arr = ds.read((0, 0, 0), ds.shape)
print("RESULT " + json.dumps({
    "rank": rank, "world": pc,
    "xhost_bytes": int(edge["bytes_xhost"]),
    "reread": int(edge["bytes_reread"]),
    "local_pairs": len(ran),
    "s0_sha": hashlib.sha256(
        np.ascontiguousarray(arr).tobytes()).hexdigest(),
}), flush=True)
EOF

echo '[smoke] launching 2-process world ...'
for RANK in 0 1; do
    env BST_COORDINATOR="127.0.0.1:$COORD_PORT" \
        BST_NUM_PROCESSES=2 BST_PROCESS_ID=$RANK \
        BST_DAG_EXCHANGE_ADDR="127.0.0.1:$XPORT0,127.0.0.1:$XPORT1" \
        $PYTHON "$WORK/worker.py" "$WORK/proj" \
        > "$WORK/rank$RANK.log" 2>&1 &
    WORKER_PIDS="$WORKER_PIDS $!"
done
FAIL=0
for pid in $WORKER_PIDS; do wait "$pid" || FAIL=1; done
WORKER_PIDS=""
if [ "$FAIL" != 0 ]; then
    echo '[smoke] a rank failed:'; tail -n 40 "$WORK"/rank*.log; exit 1
fi

echo '[smoke] running the single-process golden ...'
rm -rf "$WORK/golden" && mkdir -p "$WORK/golden"
(cd "$REPO" && $PYTHON - "$WORK/golden" <<'EOF'
import sys
from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project
make_synthetic_project(sys.argv[1] + "/proj", n_tiles=(2, 1, 1),
                       tile_size=(64, 64, 32), overlap=16, jitter=1.0,
                       n_beads_per_tile=20, seed=7)
EOF
)
env -u BST_NUM_PROCESSES -u BST_PROCESS_ID -u BST_COORDINATOR \
    -u BST_DAG_EXCHANGE_ADDR \
    $PYTHON "$WORK/worker.py" "$WORK/golden/proj" \
    > "$WORK/golden.log" 2>&1 || {
        echo '[smoke] golden run failed:'; tail -n 40 "$WORK/golden.log"
        exit 1
    }

echo '[smoke] verifying parity ...'
$PYTHON - "$WORK" <<'EOF'
import json, sys
work = sys.argv[1]
def report(path):
    for line in open(path):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise SystemExit(f"no RESULT in {path}")
r0, r1 = report(f"{work}/rank0.log"), report(f"{work}/rank1.log")
g = report(f"{work}/golden.log")
assert (r0["world"], r1["world"], g["world"]) == (2, 2, 1)
for r in (r0, r1):
    assert r["xhost_bytes"] > 0, r      # chunks really crossed the wire
    assert r["reread"] == 0, r          # ... and were never re-decoded
assert r0["local_pairs"] + r1["local_pairs"] == 11, (r0, r1)
assert r0["s0_sha"] == r1["s0_sha"] == g["s0_sha"], (r0, r1, g)
print(f"[smoke] parity OK: {r0['xhost_bytes']} + {r1['xhost_bytes']} B "
      f"cross-host, 0 B re-read, pair split "
      f"{r0['local_pairs']}+{r1['local_pairs']}=11, "
      f"fused sha {r0['s0_sha'][:12]} == 1-process golden")
EOF

echo '[smoke] ok'
