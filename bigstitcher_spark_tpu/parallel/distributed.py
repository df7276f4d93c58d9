"""Multi-host scale-out: process bootstrap + deterministic work partition.

The reference scales by launching Spark executors on many nodes (LSF/SGE via
flintstone, EMR/Dataproc — src/main/scripts/flintstone-sge-example.sh:29-119,
pom.xml:200-260); work items are distributed by the Spark driver. The TPU
analogue (SURVEY §2.5) is SPMD: every host runs the SAME driver program,
``jax.distributed.initialize`` wires the processes into one runtime (ICI
within a pod slice, DCN across), and each process takes a deterministic
slice of the same host-side work list, sharding it over its LOCAL devices.
Block writers own disjoint output chunks (the reference's no-shuffle
invariant), so no cross-host communication is needed for fusion / resave /
downsample / nonrigid — exactly like the reference's executors.

Launch recipe (two hosts):

    # host 0                                           # host 1
    BST_COORDINATOR=host0:8476 \
    BST_NUM_PROCESSES=2 BST_PROCESS_ID=0 \
    bst affine-fusion -o out.zarr                      ... BST_PROCESS_ID=1 ...

(or on Cloud TPU pods just run the command on every worker —
``jax.distributed.initialize()`` autodetects the topology there).

Stages that COLLECT results to the project XML (detection, matching,
stitching, solver) historically ran single-process; with the global
execution mesh they join the scale-out too: the pair-parallel stages
split across processes and :func:`allgather_object` merges the results so
every rank still holds the full list (parallel/pairsched.py), and the
sharded device solves span every process's devices over one global
"links" mesh axis (ops/solve.py, BST_SOLVE_GLOBAL).
"""

from __future__ import annotations

from typing import Sequence

_initialized = [False]


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    start_relay: bool = True,
) -> bool:
    """Initialize the multi-host runtime (jax.distributed) once per process.

    Arguments default to ``BST_COORDINATOR`` / ``BST_NUM_PROCESSES`` /
    ``BST_PROCESS_ID``; returns True when a multi-process runtime was set up,
    False for the ordinary single-process case (no env, no args).

    The telemetry relay (observe/relay.py) brings up beside the runtime
    whenever ``BST_TELEMETRY_RELAY`` is set — rank 0 collects, everyone
    else pushes — so the pod's live plane exists from the first stage.
    ``start_relay=False`` skips it (short management/client tools that
    have nothing live to report)."""
    try:
        if _initialized[0]:
            return True
        from .. import config

        coordinator_address = (coordinator_address
                               or config.get_str("BST_COORDINATOR"))
        # topology knobs parse via raw_value + int() so a malformed value
        # aborts the launch loudly — config.get's unparseable-falls-back
        # rule would silently run this host single-process while the rest
        # of the pod blocks at the first barrier
        raw_np = config.raw_value("BST_NUM_PROCESSES")
        if num_processes is None and raw_np is not None:
            num_processes = int(raw_np)
        raw_pid = config.raw_value("BST_PROCESS_ID")
        if process_id is None and raw_pid is not None:
            process_id = int(raw_pid)
        import jax

        if coordinator_address is None and num_processes is None:
            if config.get_bool("BST_DISTRIBUTED"):
                # Cloud TPU pod / SLURM: topology autodetected by jax
                _enable_cpu_collectives(jax)
                jax.distributed.initialize()
                _initialized[0] = True
                return True
            return False
        _enable_cpu_collectives(jax)
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized[0] = True
        return True
    finally:
        if start_relay:
            _relay_bringup()


def _enable_cpu_collectives(jax_mod) -> None:
    """Select the gloo cross-process collectives for the CPU backend
    BEFORE it initializes — without it a multi-process CPU world raises
    "Multiprocess computations aren't implemented on the CPU backend" at
    the first psum. Harmless on accelerator platforms (the flag only
    affects XLA:CPU)."""
    jax_mod.config.update("jax_cpu_collectives_implementation", "gloo")


def _relay_bringup() -> None:
    """Knob-gated, idempotent, and never fatal: losing the pod's live
    view must not block the launch it observes."""
    from ..observe import relay

    try:
        relay.ensure_started()
    except Exception as e:
        from ..observe import log

        log(f"telemetry relay disabled: {e!r}", stage="observe")


def barrier(name: str = "bst") -> None:
    """Cross-host barrier for read-after-write stage boundaries (e.g. s0
    copy -> pyramid level 1, level k -> level k+1): a later stage may read
    chunks another process wrote, so all processes must pass the boundary
    together. No-op at world size 1 (the reference gets the same ordering
    from Spark's stage-by-stage collect).

    Wait time is recorded per barrier name — it is the straggler signal of
    a pod run (a process stuck in IO shows up as everyone else's barrier
    seconds)."""
    if world()[1] <= 1:
        return
    import time

    from jax.experimental import multihost_utils

    from .. import profiling
    from ..observe import events, metrics

    t0 = time.perf_counter()
    # the trace span doubles as the multihost clock-alignment anchor: all
    # processes leave sync_global_devices together, so telemetry-merge can
    # shift per-process traces onto one timeline via equal-named exits
    with profiling.span("barrier", stage=name):
        multihost_utils.sync_global_devices(name)
    dt = time.perf_counter() - t0
    metrics.histogram("bst_barrier_seconds", name=name).observe(dt)
    events.emit("barrier", name=name, seconds=round(dt, 4))


def world() -> tuple[int, int]:
    """(process_index, process_count) of the current runtime."""
    import jax

    return jax.process_index(), jax.process_count()


def partition_items(
    items: Sequence,
    process_index: int | None = None,
    process_count: int | None = None,
) -> list:
    """This process's slice of a work list: strided round-robin
    ``items[i::count]`` — deterministic, covers every item exactly once
    across processes, degenerates to the full list at world size 1, and
    interleaves neighbouring (similar-cost) blocks across hosts for balance.
    """
    if process_index is None or process_count is None:
        pi, pc = world()
        process_index = pi if process_index is None else process_index
        process_count = pc if process_count is None else process_count
    if process_count <= 1:
        return list(items)
    if not (0 <= process_index < process_count):
        raise ValueError(
            f"process_index {process_index} outside world size {process_count}")
    return list(items[process_index::process_count])


def partition_indices_weighted(
    costs: Sequence[float],
    process_index: int | None = None,
    process_count: int | None = None,
) -> list[int]:
    """Cost-aware process partition: LPT over the whole world, same
    greedy as pairsched's device placement (heaviest first into the
    least-loaded bin, ties by index / lowest bin) so a heavy-tailed pair
    list doesn't straggle one process the way strided round-robin can.
    Deterministic: every process computes the SAME assignment from the
    same costs. Returns THIS process's item indices in ascending
    (original) order; degenerates to range(len) at world size 1."""
    if process_index is None or process_count is None:
        pi, pc = world()
        process_index = pi if process_index is None else process_index
        process_count = pc if process_count is None else process_count
    n = len(costs)
    if process_count <= 1:
        return list(range(n))
    if not (0 <= process_index < process_count):
        raise ValueError(
            f"process_index {process_index} outside world size {process_count}")
    order = sorted(range(n), key=lambda i: (-max(float(costs[i]), 0.0), i))
    loads = [0.0] * process_count
    mine: list[int] = []
    for i in order:
        b = loads.index(min(loads))
        loads[b] += max(float(costs[i]), 1e-9)
        if b == process_index:
            mine.append(i)
    mine.sort()
    return mine


def partition_items_weighted(
    items: Sequence,
    costs: Sequence[float],
    process_index: int | None = None,
    process_count: int | None = None,
) -> list:
    """:func:`partition_items` with LPT cost balancing: this process's
    slice of ``items`` (original relative order preserved), where slices
    are chosen so per-process total cost is near-equal. ``costs`` must
    align with ``items``; cost-free callers should keep the round-robin
    :func:`partition_items`."""
    if len(items) != len(costs):
        raise ValueError(
            f"items/costs length mismatch: {len(items)} != {len(costs)}")
    idx = partition_indices_weighted(costs, process_index, process_count)
    return [items[i] for i in idx]


def allgather_object(obj):
    """Gather one picklable object per process; every rank returns the
    rank-ordered list ``[obj_0, ..., obj_{pc-1}]``. This is the merge
    primitive behind the multihost pair split (each process computes its
    slice, everyone ends with the full result list — the SPMD analogue
    of Spark's driver-side collect). World size 1 returns ``[obj]``
    without touching the runtime. Collective: every process must call it
    the same number of times, in the same order."""
    pi, pc = world()
    if pc <= 1:
        return [obj]
    import pickle

    import numpy as np
    from jax.experimental import multihost_utils

    blob = np.frombuffer(
        pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL), dtype=np.uint8)
    sizes = np.asarray(multihost_utils.process_allgather(
        np.array([blob.size], dtype=np.int64))).reshape(pc)
    buf = np.zeros(int(sizes.max()), dtype=np.uint8)
    buf[:blob.size] = blob
    rows = np.asarray(multihost_utils.process_allgather(buf))
    return [pickle.loads(rows[i, :int(sizes[i])].tobytes())
            for i in range(pc)]
