#!/bin/bash
# CI smoke for the `bst pipeline` streaming stage-DAG executor on the CPU
# fallback: build a tiny fixture, generate the canonical streamed
# resave -> create -> fuse -> downsample -> detect spec with
# `bst pipeline init`, run it end to end, and exit 0 only if every stage
# finished and the elided intermediate re-read zero container bytes.
set -euo pipefail

REPO=$(cd "$(dirname "$0")/.." && pwd)
PYTHON=${PYTHON:-python3}
WORK=$(mktemp -d /tmp/bst-pipeline-smoke.XXXXXX)
trap 'rm -rf "$WORK"' EXIT

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

bst () { (cd "$REPO" && $PYTHON -m bigstitcher_spark_tpu.cli.main "$@"); }

echo '[smoke] building tiny fixture ...'
(cd "$REPO" && $PYTHON - "$WORK" <<'EOF'
import sys
from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project
make_synthetic_project(sys.argv[1] + "/proj", n_tiles=(2, 1, 1),
                       tile_size=(64, 64, 32), overlap=16, jitter=1.0,
                       n_beads_per_tile=20)
EOF
)

echo '[smoke] generating spec ...'
bst pipeline init "$WORK/pipeline.json" -x "$WORK/proj/dataset.xml"

echo '[smoke] dry-run plan:'
bst pipeline run --dryRun "$WORK/pipeline.json"

echo '[smoke] running streamed pipeline ...'
bst pipeline run --summary "$WORK/summary.json" "$WORK/pipeline.json"

echo '[smoke] verifying summary ...'
(cd "$REPO" && $PYTHON - "$WORK/summary.json" <<'EOF'
import json, sys
s = json.load(open(sys.argv[1]))
assert s["ok"], s
assert s["containers_elided"] >= 1, s
assert s["blocks_streamed"] > 0, s
assert s["bytes_reread"] == 0, s   # elided edge never re-read the container
print(f"[smoke] {s['blocks_streamed']} blocks streamed, "
      f"{s['bytes_elided']} B elided, {s['bytes_reread']} B re-read, "
      f"{s['containers_elided']} container(s) elided")
EOF
)

echo '[smoke] running streamed pipeline with the HBM handoff enabled ...'
export BST_DAG_HANDOFF_BYTES=$((1 << 30))
bst pipeline run --summary "$WORK/summary-handoff.json" "$WORK/pipeline.json"

echo '[smoke] verifying handoff summary ...'
(cd "$REPO" && $PYTHON - "$WORK/summary-handoff.json" <<'EOF'
import json, sys
s = json.load(open(sys.argv[1]))
assert s["ok"], s
# device-resident handoff traffic happened on at least one streamed edge,
# and no handoff edge (nor any other streamed edge) re-read the container
handoff = [e for e in s["edges"] if e.get("blocks_handoff", 0) > 0]
assert handoff, s["edges"]
assert s.get("blocks_handoff", 0) > 0, s
# ... and a consumer was actually SERVED device arrays on one of them
assert sum(e["bytes_handoff"] for e in handoff) > 0, handoff
for e in handoff:
    assert e["bytes_reread"] == 0, e
assert s["bytes_reread"] == 0, s
print(f"[smoke] handoff: {s['blocks_handoff']} blocks served from device "
      f"({sum(e['bytes_handoff'] for e in handoff)} B), "
      f"{sum(e['bytes_spilled'] for e in handoff)} B spilled, "
      f"0 B re-read on handoff edges")
EOF
)
unset BST_DAG_HANDOFF_BYTES

echo '[smoke] ok'
