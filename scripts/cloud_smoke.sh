#!/bin/bash
# CI smoke for the tiered storage IO engine over the in-repo S3-protocol
# fake (utils/s3_fake.py) with injected per-request latency:
#   1. resave the same tiny dataset onto the fake S3 root AND a plain
#      local root (the parity reference), and assert the resaved s0 is
#      bit-identical across the two;
#   2. affine-fuse over s3 with the async prefetcher + NVMe spill tier
#      under an undersized chunk LRU and assert the prefetcher actually
#      served consumer reads (prefetch hit bytes > 0);
#   3. rerun the same fusion warm in the same process and assert it read
#      ZERO chunk bytes from the remote store (memory LRU + disk tier
#      served everything);
#   4. assert both fused volumes are bitwise identical to the local-root
#      fusion.
# Exits 0 only if every assertion held.
set -euo pipefail

REPO=$(cd "$(dirname "$0")/.." && pwd)
PYTHON=${PYTHON:-python3}
WORK=$(mktemp -d /tmp/bst-cloud-smoke.XXXXXX)
trap 'rm -rf "$WORK"' EXIT

export JAX_PLATFORMS=cpu
# the fake accepts and ignores SigV4, but tensorstore's s3 driver
# insists on finding credentials before it signs anything
export AWS_ACCESS_KEY_ID=${AWS_ACCESS_KEY_ID:-smoke}
export AWS_SECRET_ACCESS_KEY=${AWS_SECRET_ACCESS_KEY:-smokesecret}

# cold leg + warm rerun must share one process: the decoded-chunk LRU
# and the run-scoped disk tier are process-lived, exactly like a
# `bst serve` daemon running two jobs back to back — so the whole
# sequence drives the real CLI commands through one interpreter
(cd "$REPO" && $PYTHON - "$WORK" <<'EOF'
import hashlib
import os
import sys

import numpy as np
from click.testing import CliRunner

from bigstitcher_spark_tpu.cli.main import cli
from bigstitcher_spark_tpu.io import chunkcache, prefetch, uris
from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, bump_remote_pin
from bigstitcher_spark_tpu.observe import metrics
from bigstitcher_spark_tpu.utils.s3_fake import S3FakeServer
from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

work = sys.argv[1]
srv = S3FakeServer().start()          # latency stays 0 through resave
uris.set_s3_endpoint(srv.endpoint)
uris.set_s3_region("us-east-1")
runner = CliRunner()


def ok(args):
    r = runner.invoke(cli, args, catch_exceptions=False)
    assert r.exit_code == 0, r.output


def sha(uri, dataset):
    data = np.asarray(ChunkStore.open(uri).open_dataset(dataset).read_full())
    return hashlib.sha256(np.ascontiguousarray(data).tobytes()).hexdigest()


proj = make_synthetic_project(os.path.join(work, "proj"),
                              n_tiles=(2, 1, 1), tile_size=(64, 64, 32),
                              overlap=16, jitter=0.0, n_beads_per_tile=10,
                              seed=7)
print("[smoke] resaving onto fake s3 + local parity root ...")
resave = ["--N5", "--blockSize", "16,16,16", "-ds", "1,1,1; 2,2,1"]
xml_s3 = os.path.join(work, "resaved-s3.xml")
xml_local = os.path.join(work, "resaved-local.xml")
local_n5 = os.path.join(work, "src.n5")
ok(["resave", "-x", proj.xml_path, "-xo", xml_s3,
    "-o", "s3://smoke/src.n5", *resave])
ok(["resave", "-x", proj.xml_path, "-xo", xml_local,
    "-o", local_n5, *resave])
s0 = "setup0/timepoint0/s0"
assert sha("s3://smoke/src.n5", s0) == sha(local_n5, s0), \
    "resaved s0 over the fake s3 differs from the local root"

fused_s3 = "s3://smoke/fused.zarr"
fused_local = os.path.join(work, "fused-local.zarr")
for uri, xml in ((fused_s3, xml_s3), (fused_local, xml_local)):
    ok(["create-fusion-container", "-x", xml, "-o", uri, "-s", "ZARR",
        "-d", "UINT16", "--blockSize", "32,32,32",
        "--minIntensity", "0", "--maxIntensity", "65535"])
ok(["affine-fusion", "-o", fused_local])
sha_local = sha(fused_local, "0")

# tiered engine on: prefetcher + disk tier under a chunk LRU sized far
# below the source working set, so spills (and the warm rerun's
# promotes) genuinely cross the disk tier
os.environ.update({"BST_PREFETCH_BYTES": str(64 << 20),
                   "BST_PREFETCH_THREADS": "4",
                   "BST_REMOTE_CACHE": "run",
                   "BST_DISK_TIER_BYTES": str(64 << 20),
                   "BST_DISK_TIER_DIR": os.path.join(work, "tier"),
                   "BST_CHUNK_CACHE_BYTES": str(128 << 10),
                   "BST_TILE_CACHE_BYTES": "0"})
prefetch.reset()
chunkcache.get_cache().clear()
bump_remote_pin()
srv.latency_s = 0.02

remote_read = metrics.counter("bst_io_remote_read_bytes_total")
pf_hit_bytes = metrics.counter("bst_io_prefetch_hit_bytes_total")
tier_hit_bytes = metrics.counter("bst_io_disktier_hit_bytes_total")

print("[smoke] cold fusion over s3 (prefetch + disk tier) ...")
ok(["affine-fusion", "-o", fused_s3])
prefetch.drain(timeout_s=10)
assert pf_hit_bytes.value > 0, \
    "prefetcher served no consumer reads on the cold leg"
print(f"[smoke]   prefetch hit bytes: {pf_hit_bytes.value}")

print("[smoke] warm rerun (must not touch the remote store) ...")
before = remote_read.value
tier_before = tier_hit_bytes.value
ok(["affine-fusion", "-o", fused_s3])
prefetch.drain(timeout_s=10)
leaked = remote_read.value - before
assert leaked == 0, \
    f"warm rerun re-read {leaked} chunk bytes from the remote store"
assert tier_hit_bytes.value > tier_before, \
    "warm rerun never promoted a chunk from the disk tier"
print(f"[smoke]   disk tier hit bytes: {tier_hit_bytes.value - tier_before}")

srv.latency_s = 0.0                    # parity readback untimed
assert sha(fused_s3, "0") == sha_local, \
    "fused output over the tiered s3 path differs from the local root"
srv.stop()
print("[smoke] parity ok: fused s3 == fused local, resaved s0 s3 == local")
EOF
)

echo '[smoke] PASS: prefetch hits > 0, warm rerun read 0 remote bytes,'
echo '[smoke]       fused + resaved outputs bit-identical to local root'
