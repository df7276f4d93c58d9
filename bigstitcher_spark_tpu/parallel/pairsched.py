"""Pair-work mesh scheduler: spread shape-bucketed pair batches over every
local device.

The block-parallel stages (fusion/detection/downsample/resave) scale via
``run_sharded_batches`` — a stacked batch axis sharded over a 1-D mesh. The
PAIR-parallel stages (stitching phase correlation, descriptor matching,
intensity matching) cannot take that shape: their work items are whole
per-pair programs (an FFT over one bucket's padded crop stack, a kNN +
RANSAC cascade over one pair's descriptors, one pair's cell-sample fits)
with host post-processing between device calls. Before this module they all
ran on the default device — batched and pipelined, but leaving every other
chip idle (the round-5 VERDICT's first open item; JAMPI/SparkCL make the
same move for Spark matmul / heterogeneous accelerator clusters).

Design:

- **Placement** is cost-weighted greedy (LPT): tasks sorted by descending
  cost (FFT volume for PCM, descriptor count for kNN/RANSAC, sample count
  for intensity) land on the least-loaded device; ties break by task order
  so placement is deterministic. Greedy-on-min guarantees
  ``max_load - min_load <= max task cost``.
- **Affinity** is per-thread: one worker thread per device runs its queue
  under ``jax.default_device(dev)`` (thread-local in jax), so every
  dispatch a task makes — including multi-step host/device cascades like
  RANSAC — lands on its device with no caller changes.
- **Windows** are per device: each worker bounds dispatched-but-undrained
  bytes with its own ``InflightWindow`` whose budget derives from THAT
  device's ``memory_stats`` (``BST_PAIR_INFLIGHT_BYTES`` overrides,
  ``utils.devicemem`` fallback divided by the local device count
  otherwise).
- **Drains** are device-affine, segmented and pipelined: with a split
  ``dispatch``/``drain``, a worker groups its dispatches into segments of
  up to half its byte budget and hands each WHOLE segment to one batched
  ``drain`` call (one pipelined ``jax.device_get`` per segment — the
  round-trip economics of the r5 stitching drain, now per device), always
  dispatching the next segment before draining the previous so the device
  computes while outputs cross the wire. At most two segments (~the
  budget) are pinned per device, and devices never wait on each other.
- **Failures** re-dispatch: a task whose device call dies is retried on
  the OTHER devices (round-robin, the observed device excluded) so one
  poisoned chip degrades capacity instead of killing the run.
- **Drains may write**: a ``drain`` callback runs on its device's own
  worker thread and may write its tasks' disjoint output chunks directly
  (the chunkstore is thread-safe and write-generation-aware) instead of
  collecting results back to the caller — the same device-owns-its-output
  rule the sharded work loop's ``device_drain`` mode (parallel.mesh)
  applies to the block-parallel fusion/downsample drivers, keeping every
  result's D2H and write on the worker track that computed it.

Instrumented through ``observe.metrics``: per-device dispatch counters
and HOST time inside each device's dispatch and drain calls
(``bst_pair_dispatch_total`` / ``bst_pair_busy_ms_total``, labeled
``stage``+``device``) and a per-stage gauge of that time over devices x
wall (``bst_pair_device_util_pct``) — the multichip dry run,
``chip_smoke.py`` and the bench ``"io"`` columns read these to prove the
spread. They are host clocks: a drain that refines on the host counts in
full. What the device itself did is in a ``--trace-device`` trace.

``BST_PAIR_SHARD=0`` opts out (single-device, today's pipelined path);
one local device degrades to the same thing automatically.
"""

from __future__ import annotations

import threading
import time

from dataclasses import dataclass
from typing import Any, Callable, Sequence

from .. import config, profiling
from ..observe import events, metrics as _metrics, progress as _progress
from ..observe import trace as _trace
from ..utils import cancel as _cancel
from ..utils.threads import ctx_thread
from .retry import RetryError

# placement treats zero-cost tasks as infinitesimally heavy so they still
# spread round-robin instead of piling onto one bin
_MIN_COST = 1e-9

# failed tasks are re-attempted on this many OTHER devices before the
# stage gives up (one poisoned device must not kill the run; a task that
# fails everywhere is genuinely broken)
_MAX_REDISPATCH = 3


def pair_devices(n_devices: int | None = None, devices=None) -> list:
    """The devices a pair stage may schedule on: local devices, optionally
    limited to the first ``n_devices`` (the dryrun's single-device control
    runs), or collapsed to one by the ``BST_PAIR_SHARD=0`` opt-out."""
    import jax

    devs = list(devices) if devices is not None else list(jax.local_devices())
    # only explicit falsy spellings opt out (config.get_bool's rule) — a
    # stray BST_PAIR_SHARD=2 or =true must not silently collapse every
    # pair stage to one device
    if not config.get_bool("BST_PAIR_SHARD"):
        devs = devs[:1]
    if n_devices is not None:
        devs = devs[: max(1, int(n_devices))]
    return devs


@dataclass
class PairTask:
    """One schedulable unit of pair work.

    ``index`` is the result slot (callers number tasks 0..N-1; outputs come
    back in that order regardless of placement). ``cost`` drives placement
    (any stage-appropriate proxy: FFT volume, descriptor count, sample
    count). ``nbytes`` is the device-resident estimate charged against the
    owning device's in-flight window while the task is dispatched but not
    yet drained (0 for tasks that run dispatch-to-result in one step)."""

    index: int
    cost: float = 1.0
    nbytes: int = 0
    tag: Any = None


def assign_tasks(tasks: Sequence[PairTask], n_bins: int) -> list[list[PairTask]]:
    """Cost-weighted greedy (LPT) placement: heaviest task first onto the
    least-loaded bin; deterministic (ties by bin index, stable task order).
    Guarantees ``max_load - min_load <= max task cost``."""
    bins: list[list[PairTask]] = [[] for _ in range(max(n_bins, 1))]
    loads = [0.0] * len(bins)
    for t in sorted(tasks, key=lambda t: (-max(t.cost, 0.0), t.index)):
        b = min(range(len(bins)), key=lambda i: (loads[i], i))
        bins[b].append(t)
        loads[b] += max(t.cost, _MIN_COST)
    return bins


_TLS = threading.local()


def concurrent_pair_workers() -> int:
    """Number of device workers in THIS thread's scheduler run (1 outside
    a worker thread) — shared host-side resources sized per drain (e.g.
    the stitching refinement thread budget) divide by actual concurrency,
    not the host's device count."""
    return getattr(_TLS, "n_workers", 1)


class _StageMeters:
    """Per-(stage, device) dispatch counters, host time inside dispatch and
    drain, and the stage gauge of that time over devices x wall, shared by
    every worker of one run."""

    def __init__(self, stage: str, n_dev: int):
        self.stage = stage
        self.dispatch = [
            _metrics.counter("bst_pair_dispatch_total", stage=stage,
                             device=str(i)) for i in range(n_dev)
        ]
        self.busy_ms = [
            _metrics.counter("bst_pair_busy_ms_total", stage=stage,
                             device=str(i)) for i in range(n_dev)
        ]
        self.redispatch = _metrics.counter("bst_pair_redispatch_total",
                                           stage=stage)
        self.util = _metrics.gauge("bst_pair_device_util_pct", stage=stage)
        self._busy_s = [0.0] * n_dev
        self._lock = threading.Lock()

    def add_busy(self, di: int, seconds: float) -> None:
        # float increment: many sub-ms tasks must not truncate to 0
        self.busy_ms[di].inc(seconds * 1000.0)
        with self._lock:
            self._busy_s[di] += seconds

    def finish(self, wall_s: float) -> None:
        n = len(self._busy_s)
        if n and wall_s > 0:
            busy = sum(self._busy_s)
            self.util.set(round(100.0 * busy / (n * wall_s), 1))
            _record_process_util(self.stage, busy, wall_s, n)


# last-run per-stage busy/util of THIS process's pair scheduler, keyed by
# stage — the relay snapshot payload behind `bst top --cluster`'s PAIR
# column and the bench multihost extra's per-process io numbers
_PROC_UTIL: dict[str, dict] = {}
_PROC_UTIL_LOCK = threading.Lock()


def _record_process_util(stage: str, busy_s: float, wall_s: float,
                         n_dev: int) -> None:
    try:
        from .distributed import world

        pi, pc = world()
    except Exception:  # pragma: no cover - backend not initialized
        pi, pc = 0, 1
    util = round(100.0 * busy_s / (n_dev * wall_s), 1) if wall_s > 0 else 0.0
    _metrics.counter("bst_pair_proc_busy_ms_total", stage=stage,
                     process=str(pi)).inc(busy_s * 1000.0)
    _metrics.gauge("bst_pair_proc_util_pct", stage=stage,
                   process=str(pi)).set(util)
    with _PROC_UTIL_LOCK:
        _PROC_UTIL[stage] = {
            "process": pi, "world": pc, "n_dev": n_dev,
            "busy_s": round(busy_s, 3), "wall_s": round(wall_s, 3),
            "util_pct": util,
        }


def process_util_snapshot() -> dict:
    """Per-stage {busy_s, wall_s, util_pct, ...} of this process's last
    pair-scheduler runs — merged into the telemetry relay snapshot so the
    collector can show cross-process imbalance live."""
    with _PROC_UTIL_LOCK:
        return {k: dict(v) for k, v in _PROC_UTIL.items()}


def _run_queue(queue, di, dispatch, drain, window, results, failures,
               meters: _StageMeters, hb: _progress.Heartbeat):
    """One device's pipelined loop. Without ``drain``, tasks run
    dispatch-to-result in order. With ``drain``, dispatches accumulate
    into SEGMENTS of up to half the device's byte budget; each segment
    drains in ONE batched call, and the next segment always dispatches
    before the previous one drains — so at most two segments (~the
    budget) are pinned while the device computes ahead of the fetch.
    Failures are collected, never raised (the caller re-dispatches them
    on other devices)."""
    if drain is None:
        for t in queue:
            if _cancel.cancelled():
                # abandon the queue quietly: the caller's post-join cancel
                # check raises ONE Cancelled for the stage instead of a
                # missing-results RetryError per abandoned task
                return
            try:
                t0 = time.perf_counter()
                with profiling.span("pair.dispatch", device=di,
                                    stage=meters.stage, item=t.index,
                                    nbytes=t.nbytes or None):
                    results[t.index] = (True, dispatch(t))
                dt = time.perf_counter() - t0
                meters.add_busy(di, dt)
                meters.dispatch[di].inc()
                hb.tick(seconds=dt)
            except Exception as e:  # noqa: BLE001 - re-dispatched by caller
                failures.append((t, di, e))
        return

    half = max(1, window.budget // 2)
    seg: list[tuple[PairTask, Any]] = []
    seg_bytes = 0
    prev: list[tuple[PairTask, Any]] | None = None

    def flush(group):
        tasks = [t for t, _ in group]
        try:
            t0 = time.perf_counter()
            with profiling.span("pair.drain", device=di, stage=meters.stage,
                                nbytes=sum(t.nbytes for t in tasks) or None):
                outs = drain(tasks, [h for _, h in group])
            dt = time.perf_counter() - t0
            meters.add_busy(di, dt)
            for t, r in zip(tasks, outs):
                results[t.index] = (True, r)
            # one batched drain serves the whole segment: each of its
            # tasks is charged an equal share of it
            hb.tick(len(tasks), seconds=dt / len(tasks))
        except Exception:  # noqa: BLE001 - isolate, then re-dispatch
            # a batched-drain error usually belongs to ONE task's host
            # post-processing: drain each task singly so its healthy
            # neighbours keep their (already computed) results and only
            # the offender re-dispatches; a dead device fails every
            # single drain too and the whole group re-dispatches as
            # before
            for t, h in group:
                try:
                    results[t.index] = (True, drain([t], [h])[0])
                    hb.tick()
                except Exception as e:  # noqa: BLE001
                    failures.append((t, di, e))
        finally:
            for t in tasks:
                window.release(t.nbytes)

    for t in queue:
        if _cancel.cancelled():
            # release what is pinned, then abandon (see above)
            for group in (prev, seg):
                for pt, _ in (group or ()):
                    window.release(pt.nbytes)
            return
        if seg and seg_bytes + t.nbytes > half:
            if prev is not None:
                flush(prev)
            prev, seg, seg_bytes = seg, [], 0
        try:
            t0 = time.perf_counter()
            with profiling.span("pair.dispatch", device=di,
                                stage=meters.stage, item=t.index,
                                nbytes=t.nbytes or None):
                out = dispatch(t)
            meters.add_busy(di, time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001 - re-dispatched by caller
            failures.append((t, di, e))
            continue
        meters.dispatch[di].inc()
        window.charge(t.nbytes)
        seg.append((t, out))
        seg_bytes += t.nbytes
    if prev is not None:
        flush(prev)
    if seg:
        flush(seg)


def multihost_active(explicit: bool | None = None) -> bool:
    """Whether the pair stages split their task lists across the
    processes of the execution world before local device placement. An
    explicit ``multihost=`` argument wins; the ``BST_PAIR_MULTIHOST``
    knob (default ``auto``) otherwise turns the split ON exactly when
    the jax world has more than one process. A single-process world
    never splits — there is nothing to split."""
    try:
        from .distributed import world

        pc = world()[1]
    except Exception:  # pragma: no cover - backend not initializable
        pc = 1
    if pc <= 1:
        return False
    if explicit is not None:
        return bool(explicit)
    return (config.get_str("BST_PAIR_MULTIHOST") or "auto") != "0"


def _merge_multihost(stage: str, results: list,
                     err: BaseException | None, pi: int, pc: int) -> list:
    """Exchange per-process pair results so every rank returns the FULL
    task-index-ordered list (the SPMD analogue of the reference's
    driver-side collect). A failing rank reports its error INTO the
    gather, so healthy peers raise a ``RetryError`` naming it instead of
    deadlocking on a collective that will never complete."""
    from .distributed import allgather_object

    if err is not None:
        payload = ("err", f"{type(err).__name__}: {err}")
    else:
        payload = ("ok", {i: r[1] for i, r in enumerate(results)
                          if r is not None})
    # the gather doubles as the stage barrier: time spent here is the
    # straggler signal of an imbalanced split
    with profiling.span("pair.allgather", stage=stage):
        gathered = allgather_object(payload)
    if err is not None:
        raise err
    bad = [f"rank {r}: {p[1]}" for r, p in enumerate(gathered)
           if p[0] == "err"]
    if bad:
        raise RetryError(
            f"{stage}: multihost pair split failed on peer process(es) — "
            f"{'; '.join(bad[:3])}")
    merged = list(results)
    for r, (_, vals) in enumerate(gathered):
        if r == pi:
            continue
        for i, v in vals.items():
            if merged[i] is None:
                merged[i] = (True, v)
    return merged


def run_pair_tasks(
    tasks: Sequence[PairTask],
    dispatch: Callable[[PairTask], Any],
    drain: Callable[[PairTask, Any], Any] | None = None,
    *,
    devices=None,
    n_devices: int | None = None,
    stage: str = "pairs",
    budget_bytes: int | None = None,
    multihost: bool | None = None,
    prefetch_boxes=None,
) -> list:
    """Run pair tasks across the execution world; results in task-index
    order.

    ``dispatch(task)`` runs under the task's assigned device
    (``jax.default_device``); with ``drain`` it returns un-fetched device
    handles and ``drain(tasks, handles)`` later fetches + post-processes a
    whole SEGMENT of them in one batched call (the pipelined segmented
    mode the stitching PCM uses — one ``jax.device_get`` round-trip per
    memory-bounded segment, the device computing the next segment while
    this one's peak tables cross the wire); without ``drain`` it returns
    the final result directly (the mode for host/device cascades like
    descriptor matching and intensity fits).

    One local device (or ``BST_PAIR_SHARD=0``) runs the same pipelined loop
    inline on the caller's thread — no placement, no extra threads, the
    pre-sharding behavior. Tasks whose device call fails are re-dispatched
    on the other devices (round-robin) before the stage raises
    ``RetryError``.

    In a multi-process world the task list splits across PROCESSES first
    (cost-aware LPT via ``distributed.partition_indices_weighted``) and
    this process's local devices second; after the local slice completes,
    the per-process results allgather back so EVERY rank returns the full
    list — callers keep the single-process contract unchanged. This is
    the default whenever ``jax.process_count() > 1``
    (:func:`multihost_active`, knob ``BST_PAIR_MULTIHOST``); pass
    ``multihost=False`` to pin a call to every-rank-computes-everything,
    or ``True`` to split even when the knob says 0.

    ``prefetch_boxes(task) -> [(dataset, offset, shape), ...]`` names the
    source crops ``dispatch(task)`` will read; when the async prefetcher
    (io/prefetch.py) is enabled this process's local queue is fed to it
    up front — its byte budget paces how far ahead of dispatch order the
    remote fetches actually run. Advisory only; off by default."""
    tasks = list(tasks)
    n_slots = max((t.index for t in tasks), default=-1) + 1
    covered = {t.index for t in tasks}
    if multihost_active(multihost):
        from .distributed import partition_indices_weighted, world

        pi, pc = world()
        mine = set(partition_indices_weighted(
            [max(t.cost, 0.0) for t in tasks], pi, pc))
        local = [t for k, t in enumerate(tasks) if k in mine]
        events.emit("pair.multihost", stage=stage, process=pi, world=pc,
                    local=len(local), total=len(tasks))
        err: BaseException | None = None
        results: list = [None] * n_slots
        try:
            results = _run_local(local, dispatch, drain, devices,
                                 n_devices, stage, budget_bytes, n_slots,
                                 prefetch_boxes)
        except BaseException as e:  # noqa: BLE001 - reported into gather
            err = e
        results = _merge_multihost(stage, results, err, pi, pc)
    else:
        results = _run_local(tasks, dispatch, drain, devices, n_devices,
                             stage, budget_bytes, n_slots, prefetch_boxes)
    missing = [i for i, r in enumerate(results)
               if r is None and i in covered]
    if missing:
        raise RetryError(
            f"{stage}: {len(missing)} pair task(s) produced no result "
            f"(indices {missing[:8]}...)")
    return [None if r is None else r[1] for r in results]


def _feed_pair_prefetch(tasks, prefetch_boxes) -> None:
    """Submit every queued task's source crops to the async prefetcher
    (io/prefetch.py) before the device workers start: box enumeration
    runs on the prefetch workers and the prefetch byte budget paces how
    far ahead of dispatch order the remote fetches actually get."""
    if prefetch_boxes is None:
        return
    from ..io import prefetch as _prefetch

    if not _prefetch.enabled():
        return
    for t in tasks:
        _prefetch.submit(lambda t=t: prefetch_boxes(t))


def _run_local(
    tasks: list[PairTask],
    dispatch: Callable[[PairTask], Any],
    drain,
    devices,
    n_devices: int | None,
    stage: str,
    budget_bytes: int | None,
    n_slots: int,
    prefetch_boxes=None,
) -> list:
    """This process's share of a pair run over its local devices; returns
    the raw slot list (``(True, value)`` at completed indices, ``None``
    elsewhere) for :func:`run_pair_tasks` to merge/unwrap."""
    if not tasks:
        return [None] * n_slots
    _feed_pair_prefetch(tasks, prefetch_boxes)
    devs = pair_devices(n_devices, devices)
    n_dev = len(devs)
    results: list = [None] * n_slots
    failures: list[tuple[PairTask, int, Exception]] = []
    meters = _StageMeters(stage, n_dev)
    # live done/total heartbeat (PR-1 progress events): long pair stages
    # must be distinguishable from hung ones while workers run
    hb = _progress.Heartbeat(f"pairs-{stage}", len(tasks))
    t_start = time.perf_counter()

    if n_dev <= 1:
        import jax

        from ..utils.devicemem import InflightWindow, pair_budget

        window = InflightWindow(*(
            (budget_bytes, "caller") if budget_bytes is not None
            else pair_budget(devs[0] if devs else None, 1)))
        # pin to the RESOLVED device: an explicit devices=[...] selection
        # must route work there, not to the process default
        with jax.default_device(devs[0] if devs else None):
            _run_queue(tasks, 0, dispatch, drain, window, results, failures,
                       meters, hb)
    else:
        import jax

        queues = assign_tasks(tasks, n_dev)
        n_active = sum(1 for q in queues if q)

        def worker(di: int):
            from ..utils.devicemem import InflightWindow, pair_budget

            _TLS.n_workers = n_active
            window = InflightWindow(*(
                (budget_bytes, "caller") if budget_bytes is not None
                else pair_budget(devs[di], n_active)))
            with jax.default_device(devs[di]):
                _run_queue(queues[di], di, dispatch, drain, window, results,
                           failures, meters, hb)

        threads = [
            # ctx_thread: workers inherit the caller's job scope (config
            # overrides size their windows, events land in the job's log,
            # the cancel token can poison their queues)
            ctx_thread(worker, (di,), name=f"bst-pair-{stage}-{di}")
            for di in range(n_dev) if queues[di]
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

    # a cancelled stage abandons its queues above; raise the ONE Cancelled
    # here (the existing re-dispatch path is the poison point: a cancelled
    # task must never fail over to the next device)
    _cancel.check(f"pairs-{stage}")

    # re-dispatch failed tasks on devices OTHER than the one observed
    # failing (single-device runs retry in place — there is nowhere else).
    # This runs serially on the caller's thread after the workers join: a
    # device that dies early turns its queue's tail into sequential work,
    # a deliberate simplicity/size tradeoff — device death is rare and
    # capacity (not latency) is what must survive it.
    if failures:
        import jax

        for t, bad_di, err in list(failures):
            _cancel.check(f"pairs-{stage}")
            last = err
            retried = False
            for k in range(1, max(n_dev, 2)):
                di = (bad_di + k) % n_dev
                if k > _MAX_REDISPATCH:
                    break
                meters.redispatch.inc()
                events.emit("pair.redispatch", stage=stage, task=t.index,
                            from_device=bad_di, to_device=di,
                            error=repr(err)[:200])
                _trace.instant("pair.redispatch", device=di, stage=stage,
                               item=t.index)
                try:
                    with jax.default_device(devs[di]):
                        out = dispatch(t)
                        meters.dispatch[di].inc()
                        results[t.index] = (
                            True,
                            drain([t], [out])[0] if drain is not None
                            else out)
                    hb.tick()
                    retried = True
                    break
                except Exception as e:  # noqa: BLE001 - try next device
                    last = e
            if not retried:
                meters.finish(time.perf_counter() - t_start)
                hb.finish(failed=1)
                raise RetryError(
                    f"pair task {t.index} ({stage}) failed on device "
                    f"{bad_di} and every re-dispatch target: {last!r}"
                ) from last

    meters.finish(time.perf_counter() - t_start)
    hb.finish()
    return results
