"""Registry of every ``bst_*`` metric series name.

A typo'd metric string fails silently: the registry happily creates a
fresh zero-valued series, dashboards and BENCH columns read the intended
name, and the counter "works" while reporting nothing. Declaring every
name exactly once here — and lint-enforcing (analysis/checks.py,
``metric-name``) that any ``bst_*`` string literal elsewhere in the
package appears in this table — turns that silent drift into a tier-1
test failure.

Keys are the exposition names; values are one-line help strings (also
usable as Prometheus # HELP text). Names follow prometheus conventions:
``_total`` for counters, unit suffixes (``_bytes``, ``_ms``, ``_seconds``,
``_pct``) for everything else.
"""

from __future__ import annotations

METRICS: dict[str, str] = {
    # chunk IO (io/chunkstore.py), labeled by path taken
    "bst_io_read_bytes_total": "bytes read per (op, implementation path)",
    "bst_io_read_ops_total": "chunk-level read operations per path",
    "bst_io_write_bytes_total": "bytes written per (op, implementation path)",
    "bst_io_write_ops_total": "chunk-level write operations per path",
    "bst_io_read_seconds_total":
        "seconds inside chunk reads that missed the decoded LRU (fetch + "
        "decode), summed over threads, per path — L1's busy time",
    "bst_io_write_seconds_total":
        "seconds inside container writes (encode + store), summed over "
        "threads, per path",
    # remote object-store traffic (io/chunkstore.py): the subset of the
    # io totals above that crossed the network to an s3/gs root — the
    # remote_read_stall advisor evidence and the warm-leg "zero remote
    # rereads" assertion of scripts/cloud_smoke.sh
    "bst_io_remote_read_bytes_total":
        "bytes decoded from remote (s3/gs) object stores",
    "bst_io_remote_write_bytes_total":
        "bytes uploaded to remote (s3/gs) object stores",
    # async chunk prefetcher (io/prefetch.py)
    "bst_io_prefetch_bytes_total":
        "decoded bytes fetched ahead of the consumer by the prefetch pool",
    "bst_io_prefetch_hit_total":
        "prefetched chunks later consumed from the decoded LRU",
    "bst_io_prefetch_miss_total":
        "prefetched chunks dropped unconsumed (evicted from the tracking "
        "window before any reader wanted them — wasted read-ahead)",
    "bst_io_prefetch_hit_bytes_total":
        "bytes of prefetched chunks later consumed from the decoded LRU",
    # NVMe/local-disk spill tier under the decoded LRU (io/disktier.py)
    "bst_io_disktier_hit_bytes_total":
        "bytes promoted back to the memory LRU from the disk spill tier",
    "bst_io_disktier_spill_bytes_total":
        "bytes the memory LRU spilled to the disk tier on eviction",
    "bst_io_disktier_evict_bytes_total":
        "bytes evicted from the disk tier (budget pressure/invalidation)",
    "bst_io_disktier_bytes": "current disk-tier resident bytes",
    "bst_io_disktier_entries": "current disk-tier entry count",
    # multipart-parallel remote uploads (io/chunkstore.py)
    "bst_io_upload_inflight":
        "remote chunk uploads currently in flight in the upload pool",
    # decoded-chunk LRU cache (io/chunkcache.py)
    "bst_chunk_cache_hits_total": "decoded-chunk cache hits",
    "bst_chunk_cache_misses_total": "decoded-chunk cache misses",
    "bst_chunk_cache_hit_bytes_total": "bytes served from the chunk cache",
    "bst_chunk_cache_miss_bytes_total": "bytes decoded on cache miss",
    "bst_chunk_cache_evictions_total": "chunk-cache LRU evictions",
    "bst_chunk_cache_evict_bytes_total": "bytes evicted from the chunk cache",
    "bst_chunk_cache_invalidations_total":
        "chunk-cache entries dropped by write/remove invalidation",
    "bst_chunk_cache_bytes": "current chunk-cache resident bytes",
    "bst_chunk_cache_entries": "current chunk-cache entry count",
    # host<->device transfers (parallel/mesh.py, models/, ops drivers)
    "bst_xfer_h2d_bytes_total": "host-to-device bytes shipped",
    "bst_xfer_d2h_bytes_total": "device-to-host bytes fetched",
    "bst_xfer_h2d_bytes_saved_total":
        "H2D bytes avoided by native-dtype transport (vs f32 upload)",
    "bst_xfer_d2h_bytes_saved_total":
        "D2H bytes avoided by on-device output conversion",
    "bst_mesh_drain_rows_total":
        "work items whose outputs a per-device drain worker fetched and "
        "consumed, labeled by device — the evidence that every device of "
        "a sharded stage did work",
    # fused multiscale epilogue (models/affine_fusion.py): pyramid-level
    # bytes that rode the fusion drain instead of a container re-read pass
    "bst_epilogue_d2h_bytes_total":
        "pyramid-level bytes fetched device-to-host by the fusion epilogue",
    "bst_epilogue_write_bytes_total":
        "pyramid-level bytes written by the fusion epilogue drain",
    # HBM-resident composite tile cache (models/affine_fusion.py)
    "bst_tile_cache_hits_total": "composite tile cache hits",
    "bst_tile_cache_misses_total": "composite tile cache misses",
    "bst_tile_cache_hit_bytes_total": "tile bytes served device-resident",
    "bst_tile_cache_evict_bytes_total": "tile bytes evicted from HBM",
    # in-flight dispatch window (utils/devicemem.py)
    "bst_inflight_bytes": "bytes currently dispatched but not drained",
    "bst_inflight_bytes_highwater": "high-water mark of in-flight bytes",
    "bst_inflight_windows_total":
        "dispatch windows opened, per source of their byte budget",
    "bst_inflight_budget_bytes":
        "byte budget of the newest dispatch window, per source",
    # retry layer (parallel/retry.py)
    "bst_retry_rounds_total": "block retry rounds executed",
    "bst_blocks_failed_total": "blocks that failed (per exception class)",
    # multi-host barriers (parallel/distributed.py)
    "bst_barrier_seconds": "per-name barrier wait time histogram",
    # stage progress (observe/progress.py)
    "bst_stage_items_done_total": "work items completed per stage",
    "bst_stage_item_seconds":
        "one work item's seconds, where its completion is counted (a "
        "block's attempt; a pair task's dispatch, or its equal share of "
        "the batched drain that served its segment), histogram per stage",
    # work done in the end-to-end metrics' own units, as it completes
    "bst_stitching_pairs_total":
        "tile pairs whose shift left the stitching drain (refined)",
    "bst_stitching_groups_total":
        "view groups aggregated for a pair's side, labeled by combine: "
        "single (one image, handed on as read) | average (a mean over "
        "channels or illuminations was computed: float32) | brightest "
        "(one of several stored images picked)",
    "bst_detection_blocks_whole_total":
        "detection blocks whose core extrema all fit the device's K "
        "candidates (counted where the block's outputs are consumed)",
    "bst_detection_blocks_truncated_total":
        "detection blocks with more core extrema than the K candidates "
        "that leave the device: the K strongest kept, a warning raised",
    "bst_detection_points_total":
        "interest points a view keeps after the overlap and brightest-N "
        "filters, summed over the views detected",
    "bst_stitching_refine_pairs_total":
        "tile pairs refined, labeled by scorer: device (whole uint16 "
        "crops: exact integer sums on the bucket's resident stacks) | "
        "host (any other crops: float64 summed-area tables)",
    "bst_stitching_refine_candidates_total":
        "candidate shifts the device scorer summed (each a pass over the "
        "pair's two resident stacks)",
    "bst_stitching_pack_buckets_total":
        "shape buckets packed for upload, labeled by path: stored (every "
        "crop arrived uint16 and was copied straight into a zeroed uint16 "
        "stack) | cast (float32 crops the lossless check let through as "
        "uint16) | float (uploaded as float32)",
    "bst_fusion_voxels_total":
        "output voxels whose block the fusion driver has written",
    "bst_fusion_blocks_total":
        "blocks a fusion driver sent to a kernel, labeled by kernel: the "
        "per-block affine driver's shift | sep | gather (which one a "
        "block's views allowed, counted at the choice), the non-rigid "
        "driver's nonrigid (counted as the block is written)",
    # non-rigid fusion's host fits (models/nonrigid_fusion.py)
    "bst_nonrigid_control_points_total":
        "unique interest points that entered a control-grid fit, summed "
        "over the fits (a view and compute block each)",
    "bst_nonrigid_fit_seconds_total":
        "seconds inside fit_control_grid, summed over the pool's threads",
    # JAX's own compile events (observe/compiles.py), by phase: trace =
    # jaxpr tracing, lower = jaxpr to MLIR, backend_compile = XLA build or
    # persistent-cache load (cache_load is the load's own part of that)
    "bst_jax_compile_events_total": "JAX compile-pipeline events per phase",
    "bst_jax_compile_seconds_total":
        "seconds inside JAX's compile pipeline per phase — a stage's "
        "first-call cost",
    # process start (cli/main.py), seconds since the interpreter started
    "bst_process_start_imports_seconds":
        "seconds from process start until the bst package was imported",
    "bst_process_start_backend_seconds":
        "seconds from process start until the first jax.devices() returned",
    # pair-parallel scheduler (parallel/pairsched.py)
    "bst_pair_dispatch_total": "pair tasks dispatched per (stage, device)",
    "bst_pair_busy_ms_total":
        "host time inside the device's dispatch and drain calls, "
        "milliseconds per (stage, device) — a host clock, not device busy",
    "bst_pair_redispatch_total":
        "pair tasks re-dispatched after a device failure",
    "bst_pair_device_util_pct":
        "host time inside the devices' dispatch and drain over devices x "
        "stage wall, percent — not device busy (see --trace-device)",
    "bst_pair_proc_busy_ms_total":
        "per-process host time inside the devices' dispatch and drain, "
        "milliseconds (stage, process) — the multihost split-imbalance "
        "evidence",
    "bst_pair_proc_util_pct":
        "per-process host time inside the devices' dispatch and drain over "
        "devices x stage wall, percent",
    # timeline flight recorder (observe/trace.py)
    "bst_trace_events_total": "trace events recorded into the ring buffer",
    "bst_trace_events_dropped_total":
        "trace events dropped by ring-buffer overflow (newest events win)",
    # compiled-fn bucket table (parallel/mesh.py + the composite factory
    # call site in models/affine_fusion.py): whether a kernel request hit
    # an already-built bucket (warm, no recompile) or built a new one
    "bst_compiled_fn_warm_hits_total":
        "kernel-bucket requests served by an already-built compiled fn",
    "bst_compiled_fn_cold_builds_total":
        "kernel-bucket requests that built (compiled) a new fn",
    # live HTTP exporter + process self-gauges (observe/httpexport.py):
    # refreshed at every scrape so a dashboard sees the resident process
    # itself, not only its workload
    "bst_process_uptime_seconds": "seconds since this process started",
    "bst_process_rss_bytes": "resident-set size of this process",
    "bst_process_threads": "live thread count of this process",
    "bst_process_open_fds": "open file descriptors of this process",
    "bst_http_requests_total":
        "live-exporter HTTP requests served, labeled by endpoint",
    # manifest history store (observe/history.py)
    "bst_history_records_total":
        "run/job manifests appended to the BST_HISTORY_DIR history store",
    # cross-host telemetry relay (observe/relay.py): non-zero ranks push
    # metric snapshots / heartbeats / warn events to the rank-0 collector
    # through a bounded queue that drops (and counts) under backpressure
    "bst_relay_sent_total":
        "relay messages shipped to the collector by this push client",
    "bst_relay_send_bytes_total":
        "serialized relay bytes shipped to the collector",
    "bst_relay_dropped_total":
        "relay messages dropped instead of blocking the producing rank, "
        "labeled by reason (queue = bounded queue full, conn = collector "
        "unreachable)",
    "bst_relay_reconnects_total":
        "successful relay client reconnects after a lost collector",
    "bst_relay_recv_total":
        "relay messages received by this collector, labeled by type",
    "bst_relay_ranks_connected":
        "push clients currently connected to this relay collector",
    # serve daemon (serve/): queue + lifecycle + per-job cache warmth
    "bst_serve_jobs_submitted_total": "jobs accepted by the serve daemon",
    "bst_serve_jobs_completed_total":
        "jobs finished, labeled by terminal status (ok/error/cancelled)",
    "bst_serve_queue_depth": "jobs currently queued (not yet running)",
    "bst_serve_active_jobs": "jobs currently executing",
    "bst_serve_wait_seconds":
        "queue wait (submit to start) histogram per job",
    "bst_serve_compile_warm_hits_total":
        "per-job warm compiled-fn bucket hits observed by the daemon "
        "(the amortized-compile win of a resident process)",
    "bst_serve_jobs_stalled":
        "RUNNING jobs whose stage.progress has not advanced for "
        "BST_STALL_TIMEOUT_S (the stall watchdog's live gauge)",
    # device-side global solvers (ops/solve.py, models/solver.py,
    # ops/intensity.py): the compiled-relaxation / CG hot path
    "bst_solve_iterations_total":
        "relaxation sweeps (or CG steps) executed inside compiled device "
        "solve loops, labeled by stage where applicable",
    "bst_solve_links_dropped_total":
        "links removed by the iterative drop-worst-link solve",
    "bst_solve_device_ms_total":
        "wall milliseconds spent inside compiled device solve kernels, "
        "labeled by stage (relax / intensity)",
    # streaming stage-DAG executor (dag/): producer->consumer block
    # exchange that replaces intermediate-container round-trips
    "bst_dag_blocks_streamed_total":
        "output blocks published on streamed pipeline edges",
    "bst_dag_bytes_elided_total":
        "streamed-edge bytes consumers read from the in-memory handoff "
        "(decoded-chunk cache) instead of re-reading the container",
    "bst_dag_bytes_reread_total":
        "streamed-edge bytes consumers had to decode from the container "
        "(handoff miss — evicted or never published)",
    "bst_dag_ephemeral_write_bytes_total":
        "bytes written to elided (memory-backed) intermediate containers "
        "that never touch disk",
    "bst_dag_exchange_bytes":
        "published-but-unconsumed bytes in the block-exchange ledger",
    "bst_dag_exchange_blocks":
        "published-but-unconsumed blocks in the block-exchange ledger",
    "bst_dag_producer_stall_seconds_total":
        "seconds producers stalled on block-exchange backpressure",
    "bst_dag_consumer_wait_seconds_total":
        "seconds consumers waited for input blocks not yet produced",
    "bst_dag_handoff_blocks_total":
        "producer chunks published DEVICE-resident into the HBM handoff "
        "cache (skipping even the host decoded-chunk LRU)",
    "bst_dag_handoff_bytes_served_total":
        "streamed-edge bytes consumers read as device arrays straight "
        "from the HBM handoff cache (zero D2H, zero container decode)",
    "bst_dag_handoff_spill_bytes_total":
        "handoff-cache bytes spilled to the host decoded-chunk LRU "
        "(budget pressure, a host-side read, or the end-of-run flush)",
    "bst_dag_handoff_bytes":
        "device bytes currently resident in the HBM handoff cache",
    "bst_dag_stages_completed_total":
        "pipeline stages finished, labeled by terminal status",
    "bst_dag_containers_elided_total":
        "ephemeral intermediate containers elided to memory (never "
        "materialized on disk)",
    # cross-host streamed edges (dag/exchange.py): rank-addressed block
    # exchange that extends streamed-edge gating across process boundaries
    "bst_dag_xhost_fetches_total":
        "remote-owned chunks fetched over the cross-host block exchange",
    "bst_dag_xhost_bytes_total":
        "streamed-edge bytes fetched from peer ranks over TCP (each "
        "remote-owned chunk fetched once into the local decoded LRU)",
    "bst_dag_xhost_served_bytes_total":
        "streamed-edge bytes this rank served to fetching peers",
    "bst_dag_xhost_stall_seconds_total":
        "seconds producers blocked on a peer's bounded exchange queue "
        "(cross-host backpressure)",
    "bst_dag_xhost_peers_connected":
        "exchange peer connections currently established by this rank",
    # telemetry-loop closer (tune/): advisor rules + autotuner trials +
    # daemon-side profile application
    "bst_tune_trials_total":
        "autotuner trial executions, labeled by workload",
    "bst_tune_rules_fired_total":
        "advisor diagnoses emitted, labeled by rule",
    "bst_tune_profiles_applied_total":
        "tuned profiles applied to submitted jobs by the serve daemon",
}

# Every trace/profiling SPAN name, declared exactly once — the same
# silent-drift argument as METRICS above: a typo'd span name would mint a
# fresh timeline series the trace-report and the span aggregates both
# miss. The ``span-name`` lint check (analysis/checks.py) enforces that
# every literal passed to ``profiling.span`` / ``trace.record`` /
# ``trace.instant`` appears here and bans dynamically constructed names;
# dynamic identity (device ordinal, block offset, pair index, bytes)
# belongs in the span's attribution kwargs, never in the name.
SPANS: dict[str, str] = {
    # affine fusion driver (models/affine_fusion.py)
    "fusion.stage": "one fuse_volume call, whichever driver (tree root)",
    "fusion.plan":
        "per-block view selection and source-box plans, before any read",
    "fusion.h2d":
        "per-block explicit upload of the staged kernel inputs, to done",
    "fusion.kernel":
        "fused XLA computation: per-block, the call until the outputs are "
        "ready; composite/sharded, the dispatch",
    "fusion.prefetch": "host-side source-box prefetch for one view patch",
    "fusion.h2d_tiles": "composite-path tile upload into HBM",
    "fusion.d2h":
        "device-to-host fetch of fused output (slab or block); per-block, "
        "every fetch of the block with the output-conversion round trip",
    "fusion.write": "container write of fused output (slab or block)",
    # fused multiscale epilogue: pyramid levels computed in HBM and shipped
    # in the same drain as the full-res volume (never a second full-res
    # pass — trace-counted by the tier-1 single-drain test)
    "fusion.epilogue.kernel":
        "on-device downsample-pyramid computation (epilogue dispatch)",
    "fusion.epilogue.d2h": "device-to-host fetch of an epilogue pyramid slab",
    "fusion.epilogue.write":
        "container write of an epilogue pyramid slab or block",
    # detection / stitching / matching / nonrigid drivers
    "detection.stage":
        "one detect_project call: the project loaded, detected, stored "
        "and saved (tree root)",
    "detection.plan":
        "every view's stored level chosen and block grid laid out, "
        "before any read",
    "detection.minmax":
        "one view's min/max estimate: its coarsest stored level read "
        "whole and scanned (item = setup)",
    "detection.read":
        "one block's read with halo, mirror-padded at the image border, "
        "on a build thread (item = block)",
    "detection.consume":
        "the host tail of one block: candidates masked to the core, "
        "shifted to view coordinates and sorted (item = block)",
    "detection.save":
        "the points written to interestpoints.n5 and the project XML "
        "saved with their registration",
    "detection.kernel": "DoG + localization device computation",
    "detection.extract":
        "descriptor-extraction device dispatch of the STAGED two-pass "
        "detect+extract path (absent when the fused program runs)",
    "stitching.stage": "one stitch_all_pairs call (tree root)",
    "stitching.plan": "view grouping and overlapping-pair planning",
    "stitching.extract": "overlap crop extraction for one pair batch",
    "stitching.aggregate":
        "one group's channels and illuminations combined into the image "
        "the pair is correlated on (a child of stitching.extract)",
    "stitching.kernel":
        "one shape bucket's host packing, implicit upload and dispatch of "
        "the phase-correlation program (host time; the device's part is "
        "in a --trace-device trace)",
    "stitching.pack":
        "host-only part of stitching.kernel: one bucket's two padded "
        "stacks written (stored uint16 crops copied straight into a zeroed "
        "uint16 stack; float32 crops padded, stacked and checked for the "
        "lossless cast) and the extents gathered",
    "stitching.kernel_sync": "PCM device completion sync",
    "stitching.refine": "one bucket's Pearson refinement of PCM peaks",
    "stitching.refine.pair":
        "one pair's Pearson refinement on its pool thread",
    "stitching.refine.score":
        "one device scorer call with its fetch: a round's unscored "
        "candidates of one pair",
    "stitching.store": "driver-side collect of kept pair results",
    # project model (io/spimdata.py) — L4
    "spimdata.load": "project XML fetch and parse",
    "spimdata.save": "project XML serialize and write",
    # JAX's compile pipeline (observe/compiles.py)
    "jax.compile":
        "one JAX compile-pipeline event (instant at its end; stage = the "
        "span open meanwhile, item = phase and function, bytes unused)",
    "nonrigid.stage": "one fuse_nonrigid_volume call (tree root)",
    "nonrigid.unique_points":
        "interest points and correspondences loaded and joined into unique "
        "points (beside the root: once a channel and timepoint)",
    "nonrigid.plan":
        "one block's control grids fitted and source boxes found, its "
        "views side by side on the pool (item = block), a batch ahead of "
        "the device",
    "nonrigid.fit": "one view's control grid of one block (item = view)",
    "nonrigid.prefetch":
        "one view's source box of one block read and decoded (item = "
        "view), the block's views side by side",
    "nonrigid.h2d":
        "explicit upload of one batch's stacked inputs until it is done",
    "nonrigid.kernel":
        "one batch's dispatch (its compile, the first time) and, where its "
        "outputs are fetched, the wait until they are ready",
    "nonrigid.d2h": "fetch of one finished batch's fused blocks",
    "nonrigid.write": "nonrigid fused block write",
    "matching.group_pair": "descriptor matching for one view-group pair",
    "matching.pair": "descriptor matching for one view pair",
    # shared mesh work loop (parallel/mesh.py)
    "mesh.d2h": "batched device_get of one sharded batch's outputs",
    # pair-work scheduler (parallel/pairsched.py)
    "pair.dispatch": "one pair task's device dispatch on its worker",
    "pair.drain": "one segment's batched fetch + host post-processing",
    "pair.redispatch": "pair task re-dispatched after a device failure",
    # retry / IO / multihost (parallel/retry.py, io/chunkstore.py,
    # parallel/distributed.py)
    "retry.attempt": "one work item's processing attempt",
    "block.fail": "a work item's attempt raised (instant)",
    "io.read": "chunk-level container read (instant, bytes attributed)",
    "io.write": "chunk-level container write (instant, bytes attributed)",
    "io.prefetch":
        "async read-ahead of one future work item's chunks into the "
        "decoded LRU (prefetch pool worker, bytes attributed)",
    "io.disktier":
        "disk spill-tier file IO (stage=spill/load, bytes attributed)",
    "io.upload":
        "one chunk's remote object-store put in the bounded upload pool",
    "barrier": "cross-host barrier wait (alignment anchor for merge)",
    # serve daemon (serve/daemon.py)
    "serve.job": "one submitted job's full execution on its slot",
    "serve.submit": "a job was accepted into the queue (instant)",
    "serve.cancel": "a cancel request was applied to a job (instant)",
    "serve.shutdown": "the daemon began draining/shutting down (instant)",
    "serve.stall":
        "the watchdog flagged a running job as stalled (instant)",
    "serve.trace_dump":
        "the live flight-recorder ring was snapshotted on demand (instant)",
    # device-side global solvers (models/solver.py, ops/intensity.py)
    "solve.relax":
        "one compiled global-solve kernel invocation (the whole "
        "lax.while_loop relaxation or CG iteration, dispatch to done)",
    "solve.reduce":
        "host fetch of a device solve's final models/errors (the single "
        "drain point of a solve call)",
    "solve.global":
        "a global-mesh solve kernel spanning every process's devices on "
        "the links axis (psum-sharded relax or intensity CG)",
    # multihost pair split (parallel/pairsched.py)
    "pair.allgather":
        "cross-process allgather merging each rank's pair-task results "
        "after a processes-first split",
    # cross-host telemetry relay (observe/relay.py)
    "relay.send":
        "one relay message's serialization + socket send on the client's "
        "relay thread (never the producing hot path)",
    "relay.connect":
        "the relay client (re)connected to its collector (instant)",
    "relay.dump":
        "a cluster-wide flight-recorder pull: request every connected "
        "rank's live ring, fold with the local one into one Perfetto file",
    # streaming stage-DAG executor (dag/executor.py, dag/stream.py)
    "dag.stage": "one pipeline stage's full execution on its thread",
    "dag.wait":
        "a consumer stage blocked for input blocks not yet produced",
    "dag.stall": "a producer stage blocked on block-exchange backpressure",
    "dag.publish": "a producer published an output block (instant)",
    "dag.handoff_publish":
        "a producer published a block device-resident into the HBM "
        "handoff cache (instant)",
    "dag.handoff_read":
        "a consumer's gated read assembled device-resident from the HBM "
        "handoff cache (zero D2H)",
    "dag.handoff_spill":
        "handoff-cache chunks materialized to the host tier (eviction, "
        "host read, or flush)",
    "dag.cleanup": "ephemeral intermediate-container cleanup",
    "dag.xhost_fetch":
        "one remote-owned chunk fetched from a peer rank over TCP",
    "dag.xhost_serve":
        "this rank served one chunk to a fetching peer",
    # telemetry-loop closer (tune/)
    "tune.advise": "one advisor pass over a recorded run's evidence",
    "tune.trial": "one autotuner trial execution under candidate overrides",
}


def declared() -> frozenset[str]:
    return frozenset(METRICS)


def declared_spans() -> frozenset[str]:
    return frozenset(SPANS)
