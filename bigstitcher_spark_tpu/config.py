"""Central registry of every ``BST_*`` runtime knob.

The Spark reference centralizes tuning in spark-defaults / ``--conf``;
here the equivalent surface grew organically as ~22 scattered
``os.environ`` reads, two of them frozen at import time (io/uris.py) so
setting them after import was silently ignored. This module is now the
ONLY place in the package allowed to touch ``os.environ`` for ``BST_*``
names — ``bst lint`` (analysis/) machine-checks that — and every knob is
declared exactly once with its type, default and documentation.

Reads go through :func:`get` (or the typed wrappers) and hit the
environment at CALL time, so tests and long-lived processes can retune
without re-importing, and ``bst`` subprocesses launched with a mutated
environment behave the way the caller expects. Unparseable values fall
back to the declared default (a typo'd budget must not crash a pod run
mid-stage), matching the historical behavior of the inline reads.

Above the environment sits a PER-CONTEXT override layer
(:func:`overrides`): a ``contextvars``-scoped dict of raw knob strings
consulted before ``os.environ``. This is how the ``bst serve`` daemon
gives each resident job its own configuration — N concurrent jobs in one
process cannot share a mutable ``os.environ`` (mutating it from a job
leaks into every other job; the ``env-mutation`` lint check bans exactly
that). Override values parse with the SAME rules as environment strings,
and :mod:`utils.threads` carries the context into worker threads so a
job's pools and device workers see the job's values, not the daemon's.

``bst config`` renders :func:`resolve` — every knob, its resolved value,
and whether it came from an override, the environment or the default —
which is also what ``bst env`` embeds so diagnostics always show the
full surface.
"""

from __future__ import annotations

import contextvars
import os
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any

# explicit falsy spellings for bool knobs: anything else set-and-nonempty
# is truthy, so a stray BST_PAIR_SHARD=2 or =true cannot silently flip a
# feature OFF (the failure mode called out at parallel/pairsched.py)
_FALSY = frozenset({"0", "false", "no", "off"})


@dataclass(frozen=True)
class Tunable:
    """Autotune metadata for a knob the ``bst tune`` searcher may move.

    ``lo``/``hi`` bound numeric (int/bytes) knobs; ``scale`` says how the
    searcher steps between candidates (``pow2`` halves/doubles, ``linear``
    adds/subtracts ``step``). bool knobs need no bounds (the candidate set
    is the flip) and str knobs draw candidates from the knob's declared
    ``choices``. Declaring a Tunable is a statement of SAFETY, not value:
    every value in range must be performance-only — it may never change
    job output bytes (tests/test_tune.py asserts this for the profile
    application path)."""

    lo: int | float | None = None
    hi: int | float | None = None
    scale: str = "pow2"
    step: int | float = 1

    def as_dict(self) -> dict:
        return {"lo": self.lo, "hi": self.hi, "scale": self.scale,
                "step": self.step}


@dataclass(frozen=True)
class Knob:
    """One declared ``BST_*`` variable.

    ``kind`` drives parsing: ``str`` verbatim, ``int`` via int(), ``bytes``
    via int(float()) clamped >= 0 (accepts "2e9"), ``bool`` via the
    explicit-falsy rule above. ``consumer`` records which layer reads it:
    ``runtime`` (this package), ``wrapper`` (the ./install shell wrappers),
    ``tests`` (the pytest suite) — non-runtime knobs are declared so docs,
    ``bst config`` and the doc-drift test cover the whole surface, not
    because the package reads them. ``tunable`` marks knobs the ``bst tune`` autotuner may search
    (performance-only knobs with safe kind-aware bounds)."""

    name: str
    kind: str
    default: Any
    doc: str
    consumer: str = "runtime"
    choices: tuple[str, ...] | None = None
    tunable: Tunable | None = None


KNOBS: dict[str, Knob] = {}


def _knob(name: str, kind: str, default, doc: str, *,
          consumer: str = "runtime", choices=None, tunable=None) -> None:
    if name in KNOBS:
        raise ValueError(f"knob {name} declared twice")
    KNOBS[name] = Knob(name, kind, default, doc, consumer,
                       tuple(choices) if choices else None, tunable)


def tunable_knobs() -> dict[str, Knob]:
    """The declared-tunable subset of the registry, for `bst tune`."""
    return {n: k for n, k in KNOBS.items() if k.tunable is not None}


# -- IO / caching ----------------------------------------------------------
_knob("BST_NATIVE_IO", "bool", True,
      "Use the native C++ chunk codec (zstd/lz4/raw N5 + zarr v2) for "
      "GIL-free reads/writes when built; 0 forces tensorstore.")
_knob("BST_CHUNK_CACHE_BYTES", "bytes", 1 << 30,
      "Byte budget of the process-wide decoded-chunk LRU cache "
      "(io/chunkcache.py); 0 disables caching entirely.",
      tunable=Tunable(lo=64 << 20, hi=16 << 30))
_knob("BST_TILE_CACHE_BYTES", "bytes", int(2e9),
      "Byte budget of the HBM-resident composite fusion tile cache keyed "
      "by dataset signature + write generation; 0 disables.",
      tunable=Tunable(lo=64 << 20, hi=32 << 30))
_knob("BST_WRITE_THREADS", "int", 8,
      "Concurrent writer threads for the pipelined device-volume drain "
      "(fusion full-res + epilogue pyramid slabs). ~8 MB slabs over ~8 "
      "streams measured best on the wire-limited link; h5py containers "
      "always clamp to 1 (single-writer rule).",
      tunable=Tunable(lo=1, hi=64))
_knob("BST_S3_REGION", "str", None,
      "Default AWS region for s3:// roots (the reference's --s3Region); "
      "io.uris.set_s3_region() overrides at runtime.")
_knob("BST_S3_ENDPOINT", "str", None,
      "Custom S3-protocol endpoint (MinIO / on-prem stores / test fakes); "
      "io.uris.set_s3_endpoint() overrides at runtime.")
_knob("BST_REMOTE_CACHE", "str", "run",
      "Decoded-chunk LRU eligibility of REMOTE object stores (s3/gs). "
      "'run' (default) caches their chunks keyed by a per-run pin plus "
      "the dataset metadata object's content signature — coherent "
      "against this process's own writes (generation-bump invalidation) "
      "and against any store mutation that rewrites the metadata object; "
      "an external process mutating chunk objects mid-run is outside the "
      "contract (documented coherence window, README 'Configuration'). "
      "'off' restores the historical bypass bit-identically.",
      choices=("run", "off"))
_knob("BST_PREFETCH_BYTES", "bytes", 256 << 20,
      "Byte budget of the async chunk prefetcher (io/prefetch.py): the "
      "mesh/pairsched/dag drivers enqueue their known FUTURE work items' "
      "source boxes and a small thread pool fetches them into the "
      "decoded-chunk LRU ahead of the consumer, bounded by this many "
      "fetched-but-unconsumed bytes. 0 disables prefetch entirely "
      "(drivers take the exact pre-prefetch paths).",
      tunable=Tunable(lo=32 << 20, hi=8 << 30))
_knob("BST_PREFETCH_THREADS", "int", 4,
      "Worker threads of the async chunk prefetcher; 0 disables prefetch "
      "like BST_PREFETCH_BYTES=0.",
      tunable=Tunable(lo=1, hi=32))
_knob("BST_DISK_TIER_BYTES", "bytes", 0,
      "Byte budget of the NVMe/local-disk spill tier under the decoded-"
      "chunk LRU (io/disktier.py): entries the memory LRU evicts under "
      "budget pressure spill to a run-scoped local directory and promote "
      "back on hit instead of re-fetching from the (possibly remote) "
      "store. 0 (default) disables the tier bit-identically.",
      tunable=Tunable(lo=256 << 20, hi=1 << 40))
_knob("BST_DISK_TIER_DIR", "str", None,
      "Directory of the disk spill tier (put it on local NVMe). Default: "
      "a bst-disktier-<pid> directory under the system temp dir, removed "
      "at process exit.")
_knob("BST_UPLOAD_THREADS", "int", 8,
      "Concurrent upload workers for direct writes to REMOTE object "
      "stores (s3/gs): a multi-chunk box splits per storage chunk and "
      "the chunk puts run through a bounded pool with retry/backoff "
      "(parallel/retry.py) instead of one serialized tensorstore write. "
      "0 or 1 restores the single serialized write path.",
      tunable=Tunable(lo=1, hi=64))

# -- device memory / dispatch windows --------------------------------------
_knob("BST_INFLIGHT_BYTES", "bytes", None,
      "Process-wide byte budget for dispatched-but-undrained device work "
      "(utils/devicemem.py). Default: derived from the backend's "
      "memory_stats (60% of free HBM), 2e9 where the runtime reports "
      "nothing (XLA:CPU).",
      tunable=Tunable(lo=128 << 20, hi=64 << 30))
_knob("BST_PAIR_INFLIGHT_BYTES", "bytes", None,
      "PER-DEVICE byte budget for a pair stage's in-flight work "
      "(stitching PCM, descriptor/intensity matching). Default: each "
      "device's own memory_stats-derived budget.",
      tunable=Tunable(lo=64 << 20, hi=64 << 30))
_knob("BST_DEVICE_TILE_BUDGET", "bytes", int(4e9),
      "Device-residency budget for the whole-volume composite fusion "
      "path (tiles + f32 accumulators must fit or the driver falls back "
      "to the per-block path).")
_knob("BST_PER_DEV_BUDGET", "bytes", int(1e9),
      "Per-device staging budget the fusion drivers use to pack several "
      "blocks per dispatch (per_dev).")
_knob("BST_EARLY_DISPATCH", "bool", True,
      "Allow the sharded work loop to dispatch batches ahead of the one "
      "currently draining; 0 forces strict one-batch-at-a-time.",
      tunable=Tunable())
_knob("BST_PAIR_SHARD", "bool", True,
      "Spread the pair-parallel stages over every local device "
      "(parallel/pairsched.py); 0 pins them to one device.",
      tunable=Tunable())

# -- kernels ---------------------------------------------------------------
_knob("BST_DOG_BLUR", "str", "auto",
      "DoG blur strategy: fft (rfftn transfer multiply, the CPU win) or "
      "gemm (Toeplitz matmuls on the MXU); auto picks per backend.",
      choices=("auto", "fft", "gemm"), tunable=Tunable())
_knob("BST_FUSED_DETECT", "bool", True,
      "Compile DoG detection + descriptor extraction into ONE per-block "
      "jitted program when a detection run requests descriptors "
      "(models/detection.py): peaks never leave HBM between detect and "
      "extract. 0 runs the staged two-dispatch path (bitwise-equal "
      "output, one extra kernel round-trip per block).",
      tunable=Tunable())

# -- global solvers (ops/solve.py) -----------------------------------------
_knob("BST_SOLVE_DEVICE", "bool", True,
      "Run the global registration relaxation and the intensity "
      "coefficient solve as jit-compiled device iteration (one "
      "lax.while_loop per solve, float64); 0 restores the host numpy "
      "reference path. Both paths share convergence semantics and agree "
      "to ≤1e-6 (documented in tests/test_solve_device.py).")
_knob("BST_SOLVE_SHARD", "int", 500000,
      "Point-row threshold above which a device solve shards its link "
      "rows across all local devices (rows grouped by owner tile via "
      "pairsched cost-weighted placement, per-sweep segment moments "
      "reduced with psum over the 1-D solve mesh axis). Sharded and "
      "single-device solves are bit-identical. 0 disables sharding.")
_knob("BST_SOLVE_GLOBAL", "str", "auto",
      "Span the sharded solve's 1-D links axis across ALL processes' "
      "devices instead of only the local ones (the global solve mesh). "
      "auto enables it exactly when the jax world has >1 process; 1 "
      "forces the global mesh (requires an initialized multi-process "
      "runtime); 0 pins the solve mesh to local devices. Owner-tile row "
      "grouping makes the cross-host psum exact, so global and "
      "single-host solves are bit-identical.",
      choices=("auto", "1", "0"))

# -- multi-host runtime ----------------------------------------------------
_knob("BST_COORDINATOR", "str", None,
      "host:port of process 0 for jax.distributed multi-host init "
      "(scripts/pod_launch.sh sets it).")
_knob("BST_NUM_PROCESSES", "int", None,
      "World size of the multi-host runtime; also the event-log filename "
      "fallback before backend init.")
_knob("BST_PROCESS_ID", "int", None,
      "This process's rank in the multi-host runtime; event-log filename "
      "fallback before backend init.")
_knob("BST_DISTRIBUTED", "bool", False,
      "On autodetecting platforms (Cloud TPU pods, SLURM): let "
      "jax.distributed.initialize() discover the topology.")
_knob("BST_PAIR_MULTIHOST", "str", "auto",
      "Split the pair-parallel stages (stitching PCM, descriptor and "
      "intensity matching) across the processes of a multi-host world "
      "before the local LPT device placement. auto enables the split "
      "exactly when the jax world has >1 process (every rank computes "
      "its cost-weighted slice, results allgather back so every rank "
      "returns the full list); 1 forces it; 0 keeps every rank "
      "computing every pair.",
      choices=("auto", "1", "0"))

# -- telemetry -------------------------------------------------------------
_knob("BST_TRACE_BUFFER_BYTES", "bytes", 64 << 20,
      "Byte budget of the --trace flight-recorder ring buffer "
      "(observe/trace.py); overflow keeps the NEWEST events and counts "
      "drops in bst_trace_events_dropped_total.")
_knob("BST_TRACE_PATH", "str", None,
      "Explicit output path for the --trace Perfetto JSON. Default: "
      "trace-{process}.json in the telemetry dir when one is set, else "
      "./bst-trace.json.")
_knob("BST_METRICS_PORT", "int", 0,
      "TCP port of the embedded live HTTP exporter (observe/httpexport.py: "
      "/metrics Prometheus text, /healthz liveness, /status + /jobs JSON) "
      "on BST_METRICS_HOST; 0 disables. The `bst serve` daemon and long "
      "one-shot runs both honor it; `bst serve --metrics-port 0` asks the "
      "OS for a free port instead.")
_knob("BST_METRICS_HOST", "str", "127.0.0.1",
      "Bind address of the live HTTP exporter. The default keeps the "
      "plane host-local; a pod's rank-0 exporter sets 0.0.0.0 (or a "
      "specific interface) so dashboards can scrape the aggregated view "
      "from outside the host. The exporter has NO auth — only widen the "
      "bind on a trusted network (see README 'Live monitoring').")
_knob("BST_TELEMETRY_RELAY", "str", None,
      "host:port of the pod telemetry collector (observe/relay.py). When "
      "set, rank 0 of a multi-process world (and any `bst serve` daemon) "
      "hosts the collector at that address and every other process pushes "
      "periodic metric snapshots, health heartbeats and warn/error events "
      "to it over TCP, so the rank-0 live plane (/metrics /healthz "
      "/cluster, `bst top --cluster`) covers the whole pod. Unset (the "
      "default) the relay is fully off: zero overhead, byte-identical "
      "telemetry.")
_knob("BST_RELAY_INTERVAL_S", "float", 2.0,
      "Seconds between a relay push client's metric-snapshot heartbeats. "
      "Must be comfortably below BST_STALL_TIMEOUT_S, past which a "
      "silent rank flips the pod /healthz to 503.")
_knob("BST_RELAY_QUEUE", "int", 256,
      "Bounded length of the relay client's outbound message queue. A "
      "slow or absent collector fills it and further messages drop (and "
      "count in bst_relay_dropped_total) — the producing rank's hot path "
      "never blocks on telemetry.",
      tunable=Tunable(lo=64, hi=8192))
_knob("BST_HISTORY_DIR", "str", None,
      "Directory of the cross-run manifest history store "
      "(observe/history.py): every finalized run/job manifest appends a "
      "compact record there for `bst history` / `bst perf-diff` (and, "
      "eventually, `bst tune` replay). Unset disables recording.")

# -- serve daemon ----------------------------------------------------------
_knob("BST_SERVE_SOCKET", "str", None,
      "Unix-domain socket path of the `bst serve` daemon (`bst submit` / "
      "`bst jobs` / `bst cancel` connect here). Default: "
      "bst-serve-<uid>.sock in the system temp dir.")
_knob("BST_SERVE_SLOTS", "int", 2,
      "Concurrent job slots of the `bst serve` daemon. Per-job byte-window "
      "budgets (BST_INFLIGHT_BYTES / BST_PAIR_INFLIGHT_BYTES) split by this "
      "count unless the job overrides them, so concurrent jobs share the "
      "derived HBM windows instead of each claiming the whole budget.")
_knob("BST_SERVE_IDLE_TIMEOUT", "int", 0,
      "Seconds of no connections AND no jobs after which a `bst serve` "
      "daemon exits on its own (0 = run until shutdown). CI smoke runs "
      "set it so a crashed client can never leak a resident daemon.")
_knob("BST_STALL_TIMEOUT_S", "int", 300,
      "Stall watchdog threshold of the `bst serve` daemon: a RUNNING job "
      "whose stage.progress has not advanced for this many seconds is "
      "flagged `stalled` (bst_serve_jobs_stalled gauge, a job.stall warn "
      "event on its sink, non-200 /healthz) until progress resumes or it "
      "is cancelled. 0 disables the watchdog.")
_knob("BST_PROFILE_AUTO", "bool", False,
      "Let the `bst serve` daemon resolve the best matching tuned profile "
      "(BST_HISTORY_DIR/profiles.json, written by `bst tune run`) for "
      "every submitted job that does not name one — the always-on "
      "equivalent of `bst submit --profile auto`. Profile knobs apply "
      "through per-job config.overrides(), under any explicit --set.")

# -- streaming stage-DAG executor (dag/) -----------------------------------
_knob("BST_DAG_EXCHANGE_BYTES", "bytes", 256 << 20,
      "Byte budget of the block-exchange ledger between a streaming "
      "pipeline's producer and consumer stages (dag/stream.py): a "
      "producer whose published-but-unconsumed blocks exceed this stalls "
      "until consumers catch up (unless a consumer is starved waiting "
      "for unpublished blocks — then the producer always proceeds). "
      "0 disables backpressure. Full in-memory elision additionally "
      "needs BST_CHUNK_CACHE_BYTES >= this budget, or evicted handoff "
      "chunks fall back to a container decode.",
      tunable=Tunable(lo=32 << 20, hi=8 << 30))
_knob("BST_DAG_EXCHANGE_ADDR", "str", None,
      "Comma-separated, rank-ordered host:port list of the cross-host "
      "block-exchange endpoints (dag/exchange.py) — entry i is where "
      "rank i serves the blocks its producer stages write. When set in "
      "a multi-process world, `bst pipeline` runs multi-host: a "
      "consumer stage on one rank can read an edge produced on another "
      "(the gated read fetches the covering chunks once over TCP into "
      "the local decoded-chunk LRU, accounted as "
      "bst_dag_xhost_bytes_total). Unset, pipelines stay single-process "
      "and remote edges are an error.")
_knob("BST_DAG_HANDOFF_BYTES", "bytes", 0,
      "Byte budget of the DEVICE-resident (HBM) handoff cache between a "
      "streaming pipeline's producer and consumer stages (dag/stream.py): "
      "a producer publishing device arrays keeps its covered chunks in "
      "HBM and the consumer's gated read is served as device arrays with "
      "zero D2H + zero container decode; over budget the oldest chunks "
      "spill to the host decoded-chunk LRU (backpressure semantics are "
      "unchanged — spilled chunks still count as published). 0 disables "
      "the device tier bit-identically (publishers drain to host as "
      "before).",
      tunable=Tunable(lo=64 << 20, hi=8 << 30))

# -- install wrappers ------------------------------------------------------
_knob("BST_DEVICES", "int", None,
      "Virtual CPU mesh size (xla_force_host_platform_device_count) "
      "exported by the ./install shell wrappers — the local[N] analogue.",
      consumer="wrapper")

# -- test suite ------------------------------------------------------------
_knob("BST_TEST_TPU", "bool", False,
      "Run the pytest suite against the real TPU instead of the forced "
      "8-device virtual CPU mesh (tests/conftest.py).", consumer="tests")
_knob("BST_BIG_TESTS", "bool", False,
      "Enable the slow large-N scaling tests (e.g. the 1e5-descriptor "
      "matcher case).", consumer="tests")


# -- per-context override layer --------------------------------------------
# Raw knob strings layered OVER the environment for the current
# contextvars context: the serve daemon's per-job configuration isolation
# (each job reads its own values, no process-env mutation, worker threads
# inherit via utils.threads). Values are stored as the same raw strings
# the environment would carry, so parsing/fallback semantics are
# identical; None masks an environment value back to the declared default.
_OVERRIDES: contextvars.ContextVar[dict[str, str | None] | None] = \
    contextvars.ContextVar("bst-config-overrides", default=None)


def validate_overrides(mapping: dict) -> dict[str, str | None]:
    """Normalize an override mapping: every key must be a declared knob
    (raises KeyError otherwise — an undeclared override is a typo that
    would otherwise silently do nothing), values become raw strings
    (bools as the canonical "1"/"0"), None stays None (mask-to-default)."""
    out: dict[str, str | None] = {}
    for name, v in mapping.items():
        if name not in KNOBS:
            raise KeyError(f"override for undeclared knob {name!r} — "
                           f"declare it in config.py first")
        if v is None:
            out[name] = None
        elif isinstance(v, bool):
            out[name] = "1" if v else "0"
        else:
            out[name] = str(v)
    return out


@contextmanager
def overrides(mapping: dict | None):
    """Layer ``mapping`` (knob name -> raw value) over the environment for
    the duration of the ``with`` block in THIS context. Nested scopes
    stack (inner wins); worker threads spawned through utils.threads see
    the caller's layered view. An empty/None mapping is a no-op scope."""
    cur = _OVERRIDES.get() or {}
    token = _OVERRIDES.set({**cur, **validate_overrides(mapping or {})})
    try:
        yield
    finally:
        _OVERRIDES.reset(token)


def current_overrides() -> dict[str, str | None]:
    """The active override layer (flattened), for diagnostics and for
    handing a job's configuration across process boundaries."""
    return dict(_OVERRIDES.get() or {})


def raw_value(name: str) -> str | None:
    """The override-or-environment string for a DECLARED knob (KeyError
    otherwise); unset and set-but-empty both read as None. The package's
    single ``BST_*`` environment touchpoint."""
    knob = KNOBS[name]
    ov = _OVERRIDES.get()
    if ov is not None and knob.name in ov:
        v = ov[knob.name]
        return None if v is None or v == "" else v
    v = os.environ.get(knob.name)
    return None if v is None or v == "" else v


def _parse(knob: Knob, raw: str):
    if knob.kind == "str":
        if knob.choices and raw not in knob.choices:
            # raise like any unparseable value so get() falls back AND
            # source() reports "default" — returning the default here
            # would make `bst config` label the operator's typo as (env)
            raise ValueError(f"{raw!r} not in {knob.choices}")
        return raw
    if knob.kind == "bool":
        return raw.strip().lower() not in _FALSY
    if knob.kind == "int":
        return int(raw)
    if knob.kind == "bytes":
        return max(0, int(float(raw)))
    if knob.kind == "float":
        return float(raw)
    raise AssertionError(f"unknown knob kind {knob.kind}")


def get(name: str):
    """Resolved value of a declared knob, read from the environment at
    call time; unparseable values fall back to the declared default."""
    knob = KNOBS[name]
    raw = raw_value(name)
    if raw is None:
        return knob.default
    try:
        return _parse(knob, raw)
    except (ValueError, TypeError):
        return knob.default


def source(name: str) -> str:
    """Where :func:`get` resolves ``name`` from right now: ``"override"``
    (a config.overrides scope is active for it), ``"env"`` or
    ``"default"`` (unset, empty, masked, or unparseable)."""
    knob = KNOBS[name]
    raw = raw_value(name)
    if raw is None:
        return "default"
    try:
        _parse(knob, raw)
    except (ValueError, TypeError):
        return "default"
    ov = _OVERRIDES.get()
    if ov is not None and knob.name in ov:
        return "override"
    return "env"


# typed wrappers: call sites read as what they mean, and the linter can
# pair each knob with the declared kind
def get_bool(name: str) -> bool:
    v = get(name)
    return bool(v)


def get_int(name: str) -> int | None:
    return get(name)


def get_bytes(name: str) -> int | None:
    return get(name)


def get_str(name: str) -> str | None:
    return get(name)


def get_float(name: str) -> float | None:
    return get(name)


def resolve() -> list[dict]:
    """Every knob with its resolved value — the ``bst config`` payload."""
    out = []
    for name in sorted(KNOBS):
        k = KNOBS[name]
        out.append({
            "name": name,
            "value": get(name),
            "source": source(name),
            "default": k.default,
            "kind": k.kind,
            "consumer": k.consumer,
            "doc": k.doc,
            "tunable": k.tunable.as_dict() if k.tunable else None,
        })
    return out


def describe(verbose: bool = False) -> str:
    """Human-readable resolved-config dump (``bst config`` / ``bst env``).

    One line per knob: name, resolved value, and ``(env)`` /
    ``(override)`` when something overrides the default; ``verbose`` adds
    the docs."""
    lines = []
    for row in resolve():
        mark = ("  (env)" if row["source"] == "env"
                else "  (override)" if row["source"] == "override" else "")
        lines.append(f"{row['name']}={row['value']}{mark}")
        if verbose:
            lines.append(f"    [{row['kind']}, default {row['default']!r}, "
                         f"{row['consumer']}] {row['doc']}")
    return "\n".join(lines)
