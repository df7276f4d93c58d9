"""Device-mesh sharding of the block work list.

TPU-native replacement of the reference's Spark data parallelism (§2.4 P1):
a batch of output blocks becomes the leading axis of the stacked kernel
inputs, sharded over a 1-D ``jax.sharding.Mesh`` — each device fuses its
shard of blocks; no collectives are needed because block writes are disjoint
(the reference's no-shuffle property, the Spark map at
SparkAffineFusion.java:480-482). Multi-host scale-out uses the same mesh
spanning hosts (ICI within pod, DCN across — jax.distributed).

``make_sharded_fuser`` serves the production per-block fusion driver
(models/affine_fusion.fuse_volume with devices > 1): both the general
gather kernel and the translation shifted-slice kernel batch over blocks,
with intensity conversion fused into the same device computation so each
block crosses the host boundary exactly twice (patch in, converted block
out).
"""

from __future__ import annotations

import functools
import threading

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import fusion as F
from ..observe import metrics as _metrics
from .. import config, observe, profiling

BLOCK_AXIS = "blocks"

# host<->device transfer accounting: stacked batch inputs are
# the h2d side, fetched outputs the d2h side. The *_saved counters record
# bytes the native-dtype transport kept OFF the wire versus shipping
# float32 (uint8/uint16 stacks cast to f32 on device, integer outputs
# converted to storage dtype on device) so artifacts can prove the
# reduction without a counterfactual run.
_H2D_BYTES = _metrics.counter("bst_xfer_h2d_bytes_total")
_D2H_BYTES = _metrics.counter("bst_xfer_d2h_bytes_total")
_H2D_SAVED = _metrics.counter("bst_xfer_h2d_bytes_saved_total")
_D2H_SAVED = _metrics.counter("bst_xfer_d2h_bytes_saved_total")


# which device's shard the current thread is draining (set by the
# per-device drain workers of run_sharded_batches); consumers use it to
# attribute their spans — e.g. models/affine_fusion's `fusion.write` — to
# the owning device's trace track instead of an anonymous host thread
_DRAIN_TLS = threading.local()


def drain_device() -> int | None:
    """Device ordinal whose shard the calling thread is draining, or None
    outside a per-device drain worker."""
    return getattr(_DRAIN_TLS, "device", None)


def narrow_dtype_savings(arrays) -> int:
    """Wire bytes saved by shipping sub-float32-width integer arrays
    natively instead of as the float32 the kernels compute in."""
    return sum(a.size * 4 - a.nbytes for a in arrays
               if getattr(a, "dtype", None) is not None
               and a.dtype.kind in "iu" and a.dtype.itemsize < 4)


def _commit_host_args(fn, shardings):
    """Multi-process runtimes refuse host numpy args to a jit with
    non-replicated shardings (JAX cannot tell host-local data from
    global); commit them onto their shardings explicitly first — all
    devices here are local, so the device_put is an ordinary H2D.
    Single-process dispatch passes through untouched."""
    def dispatch(*args, **kwargs):
        if jax.process_count() > 1:
            args = tuple(
                jax.device_put(a, s)
                if not isinstance(a, jax.Array)
                and not s.is_fully_replicated else a
                for a, s in zip(args, shardings))
        return fn(*args, **kwargs)
    return dispatch


@functools.lru_cache(maxsize=8)
def _cached_mesh(n_devices: int | None) -> Mesh:
    # LOCAL devices only: under jax.distributed each process works an
    # independent slice of the grid (partition_items), so its mesh must not
    # span other hosts' devices — a global mesh fed different per-process
    # inputs violates the multi-controller SPMD contract (all collectives /
    # cross-host programs here go through barrier() instead)
    devs = list(jax.local_devices())
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (BLOCK_AXIS,))


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    # cached per device count: a stable Mesh identity lets the jitted fuser
    # cache (make_sharded_fuser) hit across volumes/runs instead of
    # recompiling per call
    if devices is not None:
        return Mesh(np.array(list(devices)), (BLOCK_AXIS,))
    return _cached_mesh(n_devices)


# warm-vs-cold accounting for the compiled-fn bucket tables (this one and
# the composite factory in ops.fusion): a resident `bst serve` process
# amortizes compiles across jobs, and these counters are how that claim
# becomes a recorded per-job delta instead of an anecdote
_COMPILE_WARM = _metrics.counter("bst_compiled_fn_warm_hits_total")
_COMPILE_COLD = _metrics.counter("bst_compiled_fn_cold_builds_total")
# per-namespace LRU MIRRORS of the lru_caches being fronted, same
# capacity and same request sequence (record runs right before the
# factory call), so eviction here tracks eviction there — an unbounded
# seen-set would keep reporting "warm" for signatures the bounded
# lru_cache already dropped and must recompile
_BUCKET_CAPS = {"sharded": 64, "composite": 32, "solve": 32,
                "solve_cg": 16}
_BUCKET_LRU: dict[str, "OrderedDict"] = {}
_BUCKET_LOCK = threading.Lock()


def record_compile_bucket(key) -> bool:
    """Register one compiled-fn bucket request; returns True (and counts a
    warm hit) when ``key`` is still resident in its factory's bounded
    cache, else counts a cold build. ``key[0]`` names the factory
    namespace. Shared by every lru_cache'd kernel-factory call site."""
    from collections import OrderedDict

    ns = key[0] if isinstance(key, tuple) and key \
        and isinstance(key[0], str) else "default"
    cap = _BUCKET_CAPS.get(ns, 64)
    with _BUCKET_LOCK:
        lru = _BUCKET_LRU.setdefault(ns, OrderedDict())
        warm = key in lru
        lru[key] = True
        lru.move_to_end(key)
        while len(lru) > cap:
            lru.popitem(last=False)
    (_COMPILE_WARM if warm else _COMPILE_COLD).inc()
    return warm


def make_sharded_fuser(
    mesh: Mesh,
    block_shape: tuple[int, int, int],
    fusion_type: str = "AVG_BLEND",
    kernel: str = "gather",           # gather | shift
    with_coeffs: bool = False,
    out_dtype: str | None = None,     # fuse intensity conversion on device
    masks: bool = False,
    pyramid: tuple = (),              # per-level relative factors: the
                                      # fused multiscale epilogue
):
    """The compiled-fn bucket table's front door: resolve (building if
    needed) the sharded fuser for this signature and record whether the
    request was warm. See :func:`_build_sharded_fuser` for the kernel
    semantics."""
    key = (mesh, block_shape, fusion_type, kernel, with_coeffs, out_dtype,
           masks, pyramid)
    record_compile_bucket(("sharded",) + key)
    return _build_sharded_fuser(*key)


@functools.lru_cache(maxsize=64)
def _build_sharded_fuser(
    mesh: Mesh,
    block_shape: tuple[int, int, int],
    fusion_type: str = "AVG_BLEND",
    kernel: str = "gather",
    with_coeffs: bool = False,
    out_dtype: str | None = None,
    masks: bool = False,
    pyramid: tuple = (),
):
    """Compile a fuser for a BATCH of blocks sharded over the mesh.

    lru_cache'd so repeated volumes (multi-channel/timepoint loops, repeated
    runs) reuse the jitted callable instead of recompiling per call.

    Inputs get a leading batch axis B (a multiple of mesh size; pad with
    valid=0 blocks). Returns ``fn(*arrays) -> (out (B,*block_shape), wsum[,
    level1, ...])`` where ``out`` is already intensity-converted when
    ``out_dtype`` is given (min_i/max_i are appended scalar args in that
    case). ``pyramid`` chains per-block downsample levels as a kernel
    epilogue — each a strided f32 mean of the previous level quantized to
    the storage dtype between steps (ops.downsample.convert_storage), the
    exact container-reread semantics — so the whole pyramid ships in the
    block's one drain; callers must pre-check divisibility
    (models.affine_fusion.eligible_epilogue_levels)."""
    if kernel == "gather":
        def core(p, a, o, d, b, r, v, io, c=None, ca=None):
            return F.fuse_block_impl(
                p, a, o, d, b, r, v, block_shape=block_shape,
                fusion_type=fusion_type, inside_offs=io, coeffs=c,
                coeff_affines=ca,
            )

        n_in = 10 if with_coeffs else 8
    elif kernel == "sep":
        def core(p, dg, t, o, d, b, r, v, io):
            return F.fuse_block_sep_impl(
                p, dg, t, o, d, b, r, v, block_shape=block_shape,
                fusion_type=fusion_type, inside_offs=io,
            )

        n_in = 9
    elif kernel == "shift":
        def core(p, f, l, d, b, r, v, io):  # noqa: E741
            return F.fuse_block_shift_impl(
                p, f, l, d, b, r, v, block_shape=block_shape,
                fusion_type=fusion_type, inside_offs=io,
            )

        n_in = 8
    else:
        raise ValueError(f"unknown kernel {kernel}")

    def one(args, min_i, max_i):
        fused, wsum = core(*args)
        if masks:
            fused = (wsum > 0).astype(jnp.float32)
            if out_dtype is not None and out_dtype != "float32":
                fused = (fused * float(np.iinfo(np.dtype(out_dtype)).max)
                         ).astype(np.dtype(out_dtype))
        elif out_dtype is not None:
            fused = F._convert_intensity_expr(fused, min_i, max_i, out_dtype)
        levels = []
        if pyramid:
            from ..ops.downsample import convert_storage, downsample_block

            cur = fused
            dt = out_dtype or "float32"
            for rel in pyramid:
                cur = convert_storage(
                    downsample_block(cur, tuple(int(f) for f in rel)), dt)
                levels.append(cur)
        return (fused, wsum, *levels)

    def batched(min_i, max_i, *arrays):
        return jax.vmap(lambda *a: one(a, min_i, max_i))(*arrays)

    shard = NamedSharding(mesh, P(BLOCK_AXIS))
    repl = NamedSharding(mesh, P())
    in_shardings = (repl, repl) + (shard,) * n_in
    return _commit_host_args(jax.jit(
        batched,
        in_shardings=in_shardings,
        out_shardings=(shard,) * (2 + len(pyramid)),
    ), in_shardings)


def pad_batch(arrays: Sequence[np.ndarray], batch: int) -> list[np.ndarray]:
    """Pad each stacked input along axis 0 to ``batch`` (extra entries are
    all-zero => valid mask 0 => no-op blocks). Device-resident inputs
    (a streaming handoff edge feeding this stage) pad on device — they
    must never round-trip through host memory here."""
    out = []
    for a in arrays:
        if a.shape[0] == batch:
            out.append(a)
        elif isinstance(a, jax.Array):
            import jax.numpy as jnp

            pad = jnp.zeros((batch - a.shape[0],) + a.shape[1:], a.dtype)
            out.append(jnp.concatenate([a, pad], axis=0))
        else:
            pad = np.zeros((batch - a.shape[0],) + a.shape[1:], a.dtype)
            out.append(np.concatenate([a, pad], axis=0))
    return out


def stack_inputs(inputs: Sequence, j: int):
    """Stack input ``j`` of every build result along a new batch axis —
    on host for numpy inputs, ON DEVICE when any item arrived as a jax
    array (a device-resident handoff read): ``np.stack`` over jax arrays
    would silently device_get every one of them."""
    parts = [inp[j] for inp in inputs]
    if any(isinstance(p, jax.Array) for p in parts):
        import jax.numpy as jnp

        # handoff chunks arrive committed to their PRODUCER's device;
        # stacking mixed placements is an error, so gather onto one
        # device first (D2D for device parts). Host-origin parts of a
        # mixed batch DO cross the wire — account them here, since the
        # dispatch-side H2D counter sees only the final device stack.
        dev0 = jax.local_devices()[0]
        _H2D_BYTES.inc(sum(int(p.nbytes) for p in parts
                           if not isinstance(p, jax.Array)))
        return jnp.stack([jax.device_put(jnp.asarray(p), dev0)
                          for p in parts])
    if len(parts) == 1:
        return np.asarray(parts[0])[None]   # a view: one block, no copy
    return np.stack(parts)


def run_sharded_batches(
    items: Sequence,
    build,
    kernel,
    consume,
    n_dev: int,
    pool,
    label: str = "batch",
    progress: bool = False,
    per_dev: int = 1,
    multihost: bool = False,
    out_bytes_per_item: int = 0,
    workspace_mult: float = 2.0,
    device_drain: bool = False,
    device_consume=None,
    prefetch_boxes=None,
    fetch=None,
):
    """The shared multi-device work loop: every sharded stage driver (fusion,
    detection, nonrigid, downsample) is this pattern — the TPU replacement of
    the reference's ``sc.parallelize(workItems).map`` (§2.4 P1/P3).

    ``items`` are grouped ``n_dev`` at a time; ``build(item)`` stages one
    item's kernel inputs on the host (a tuple of equally-shaped numpy arrays
    within one call site's bucket); the stacked + padded batch runs through
    ``kernel(*stacked) -> array | tuple`` (a jit with batch-axis in/out
    shardings, one block per device); ``consume(item, *outs_i)`` handles item
    ``i``'s slice of each output (e.g. disjoint chunk writes — no locks
    needed, the reference's no-shuffle invariant).

    Host prefetch for batch k+1 overlaps device compute for batch k, and
    staged batches are dispatched AHEAD of batch k's fetch, as many as a
    BYTE budget allows: each dispatch is charged real bytes — stacked
    inputs x ``workspace_mult`` (kernel intermediates/FFT workspace) plus
    ``out_bytes_per_item`` per item for device-resident outputs — against
    the backend's free-memory budget (utils.devicemem: ``memory_stats``
    when the runtime reports them, ``BST_INFLIGHT_BYTES`` override,
    conservative constant otherwise). The device computes ahead while
    outputs cross the wire and write; a window that does not fit stops
    growing, and the CURRENT batch always dispatches so progress never
    blocks (``BST_EARLY_DISPATCH=0`` opts out of dispatch-ahead entirely,
    degenerating to strict one-batch-at-a-time). Batches are resubmitted
    on failure via run_with_retry, and completed batches are tracked so
    retry rounds neither re-run them nor leak prefetch futures;
    early-dispatched results are keyed per batch and rebuilt on retry, so
    failure granularity is unchanged. ``per_dev`` packs that many items
    per device per batch (compute-light kernels amortize dispatch by
    batching more).

    ``multihost=True`` (block-writing stages only — outputs must be disjoint
    chunks) first takes this process's deterministic slice of ``items``, so
    the same driver run on N hosts covers the grid exactly once
    (parallel.distributed; the reference's executor model, SURVEY §2.5).

    ``device_drain=True`` replaces the driver's single batched
    ``jax.device_get`` + consume fan-out with PER-DEVICE drain workers:
    each device's shard of the batch outputs is fetched by its own thread
    (one pipelined ``device_get`` per device, ``mesh.d2h`` span attributed
    to that device's trace track) which then runs ``consume`` for exactly
    the items that computed on that device — so the driver thread performs
    zero D2H and zero writes, one device's wire transfer overlaps another
    device's chunk writes, and writers still own disjoint chunks (the
    no-shuffle invariant, now per device; ROADMAP item 3b). Callers must
    only enable it when ``consume`` tolerates ``n_dev``-way concurrency —
    h5py-backed containers (single-writer) must keep the default path.

    ``device_consume(item, *device_rows) -> bool`` is an optional
    pre-fetch hook: it sees each item's output rows as DEVICE arrays
    before any D2H, and returning True claims the item — its rows are
    never fetched and ``consume`` never runs for it (the streaming
    handoff publish path: the row stays in HBM for the downstream
    stage). Rows it declines are fetched lazily, so a batch it fully
    claims does zero D2H.

    ``prefetch_boxes(item) -> [(dataset, offset, shape), ...]`` names the
    source boxes ``build(item)`` will read. When the async prefetcher is
    enabled (io/prefetch.py) the loop feeds it batches ahead of the build
    frontier — roughly batch k+2's boxes while batch k runs — so remote
    chunk fetches overlap device compute instead of serializing inside
    ``build``. Purely advisory: with the prefetcher off (the knobs' zero
    defaults) nothing is enqueued and no code path changes.

    ``fetch(outs) -> host arrays`` takes the place of the driver's batched
    ``jax.device_get`` under ``mesh.d2h`` (the plain path only: neither
    ``device_drain`` nor ``device_consume``) where a driver brackets the
    wait for its kernel and the transfer with spans of its own."""
    from .retry import run_with_retry

    if multihost:
        from .distributed import partition_items

        items = partition_items(items)
    from ..utils.devicemem import InflightWindow

    group = n_dev * max(1, per_dev)
    batches = [list(items[i:i + group]) for i in range(0, len(items), group)]
    if not batches:
        return
    drain_pool = None
    if device_drain:
        from ..utils.threads import CtxThreadPool

        # context-propagating: drain workers read job-scoped config
        # (write knobs) and emit into the job's event scope
        drain_pool = CtxThreadPool(max_workers=max(1, n_dev),
                                   thread_name_prefix="bst-dev-drain")
    window = InflightWindow()

    fed = [0]  # batches [0, fed) already submitted to the async prefetcher

    def feed_prefetch(upto: int) -> None:
        if prefetch_boxes is None:
            return
        from ..io import prefetch as _prefetch

        if not _prefetch.enabled():
            return
        upto = min(upto, len(batches))
        while fed[0] < upto:
            b = batches[fed[0]]
            fed[0] += 1
            _prefetch.submit(lambda b=b: [box for it in b
                                          for box in prefetch_boxes(it)])

    feed_prefetch(2)
    prefetched = {0: [pool.submit(build, it) for it in batches[0]]}
    dispatched: dict[int, tuple] = {}   # bi -> (outs, charged bytes)
    completed: set[int] = set()

    def batch_cost(input_bytes: int, n_items: int) -> int:
        return (int(input_bytes * max(workspace_mult, 1.0))
                + n_items * int(out_bytes_per_item))

    def stack_and_dispatch(inputs, n_items):
        # pad to a multiple of n_dev (the sharding constraint), NOT to the
        # full group size: a tail batch of 4 on 1 device must not run as 8
        # blocks of which half are zero work (the jit re-specializes once
        # per distinct tail size; full batches all share one shape)
        stacked = pad_batch(
            [stack_inputs(inputs, j) for j in range(len(inputs[0]))],
            -(-len(inputs) // max(n_dev, 1)) * max(n_dev, 1),
        )
        if n_dev > 1 and any(isinstance(a, jax.Array) for a in stacked):
            # a handoff-fed input is committed to ONE device; the sharded
            # kernels pin batch-leading args to the block mesh, so re-place
            # it there (same-mesh D2D — the bytes never revisit the host)
            spread = NamedSharding(make_mesh(n_dev), P(BLOCK_AXIS))
            stacked = [jax.device_put(a, spread) if isinstance(a, jax.Array)
                       else a for a in stacked]
        nbytes = sum(a.nbytes for a in stacked)
        # only HOST-origin inputs cross the wire: a device-stacked input
        # (handoff-fed stage) contributes zero H2D
        host = [a for a in stacked if not isinstance(a, jax.Array)]
        _H2D_BYTES.inc(sum(a.nbytes for a in host))
        _H2D_SAVED.inc(narrow_dtype_savings(host))
        outs = kernel(*stacked)
        outs = outs if isinstance(outs, (tuple, list)) else (outs,)
        cost = batch_cost(nbytes, n_items)
        window.charge(cost)
        return outs, cost

    def dispatch_ahead(bi):
        """Dispatch every staged later batch that fits the byte budget, so
        the device computes ahead while batch ``bi`` drains; keep host
        prefetch one batch past the dispatch frontier."""
        if not config.get_bool("BST_EARLY_DISPATCH"):
            # opting out of dispatch-ahead must NOT kill host-side build
            # prefetch — the next batch still stages while this one drains
            nxt = bi + 1
            if (nxt < len(batches) and nxt not in prefetched
                    and nxt not in dispatched and nxt not in completed):
                prefetched[nxt] = [pool.submit(build, it)
                                   for it in batches[nxt]]
            return
        for j in range(bi + 1, len(batches)):
            if j in completed or j in dispatched:
                continue
            futs = prefetched.get(j)
            if futs is None:
                # stage TWO batches deep: j's futures are checked next
                # turn, so without j+1 already building the check would
                # always land on a just-submitted batch and the window
                # could never grow past one
                for k in (j, j + 1):
                    if (k < len(batches) and k not in prefetched
                            and k not in dispatched and k not in completed):
                        prefetched[k] = [pool.submit(build, it)
                                         for it in batches[k]]
                return
            if not all(f.done() for f in futs):
                return
            if any(f.exception() is not None for f in futs):
                # a build error belongs to batch j: its own process_batch
                # re-stages and raises so retry accounting blames it
                return
            est = batch_cost(sum(sum(int(a.nbytes) for a in f.result())
                                 for f in futs), len(batches[j]))
            if not window.fits(est):
                return
            del prefetched[j]
            try:
                dispatched[j] = stack_and_dispatch(
                    [f.result() for f in futs], len(batches[j]))
            except Exception:
                # stacking/dispatch error: same blame rule as above
                return
            nxt = j + 1
            if (nxt < len(batches) and nxt not in prefetched
                    and nxt not in dispatched and nxt not in completed):
                prefetched[nxt] = [pool.submit(build, it)
                                   for it in batches[nxt]]

    def process_batch(bi_batch):
        from ..utils import cancel as _cancel

        # between batches is the loop's safe point: a `bst cancel` poisons
        # the NEXT dispatch, in-flight device work drains normally and the
        # Cancelled unwinds through the retry layer without re-dispatch
        _cancel.check(label)
        bi, batch = bi_batch
        if bi in completed:
            return
        ent = dispatched.pop(bi, None)
        if ent is None:
            futs = prefetched.pop(bi, None)
            if futs is None:  # retry round: prefetch again
                futs = [pool.submit(build, it) for it in batch]
            # the CURRENT batch dispatches regardless of the window budget
            # (forward progress must never block on the ledger)
            ent = stack_and_dispatch([f.result() for f in futs], len(batch))
        outs, cost = ent
        # grow the in-flight window BEFORE fetching: the device computes
        # ahead while this batch's outputs cross the wire and write (the
        # fetch below only waits on THIS batch's buffers — a data
        # dependency)
        dispatch_ahead(bi)
        # read-ahead stays two batches past the build frontier (which
        # dispatch_ahead just advanced to ~bi+2)
        feed_prefetch(bi + 4)
        keep = list(range(len(batch)))
        try:
            if drain_pool is not None:
                _drain_per_device(outs, batch, consume, drain_pool, label, bi,
                                  device_consume)
            elif device_consume is None and fetch is not None:
                outs = fetch(outs)
            elif device_consume is None:
                # device-array nbytes are free to read pre-fetch: the span
                # carries the batch's wire payload for the trace-report D2H
                # decomposition
                d2h_nbytes = sum(int(getattr(o, "nbytes", 0)) for o in outs)
                with profiling.span("mesh.d2h", stage=label, item=int(bi),
                                    nbytes=d2h_nbytes):
                    outs = jax.device_get(list(outs))  # pipelined batch fetch
            else:
                # handoff publish first: claimed rows stay in HBM and are
                # never fetched; only the declined remainder crosses D2H
                keep = [i for i, it in enumerate(batch)
                        if not device_consume(it, *(o[i] for o in outs))]
                if keep:
                    rows = [[o[i] for i in keep] for o in outs]
                    d2h_nbytes = sum(int(getattr(r, "nbytes", 0))
                                     for rs in rows for r in rs)
                    with profiling.span("mesh.d2h", stage=label, item=int(bi),
                                        nbytes=d2h_nbytes):
                        outs = jax.device_get(rows)
                else:
                    outs = None
        finally:
            # drained or dead, the buffers leave the ledger either way —
            # a fetch error must not shrink the window for the whole run
            window.release(cost)
        # builds that finished under this batch's kernel go to the device
        # before its outputs are written: the first call above finds the
        # second batch of a run still staging, and the device would sit
        # through the first one's writes
        dispatch_ahead(bi)
        if drain_pool is None and outs is not None:
            flat = (list(outs) if device_consume is None
                    else [d for ds_ in outs for d in ds_])
            _D2H_BYTES.inc(sum(int(getattr(d, "nbytes", 0)) for d in flat))
            _D2H_SAVED.inc(narrow_dtype_savings(flat))
            # with device_consume unset keep == range(len(batch)) and the
            # outputs are whole batch arrays, so row k IS item gi; with it
            # set the outputs were gathered per kept row
            wfuts = [
                pool.submit(consume, batch[gi], *(o[k] for o in outs))
                for k, gi in enumerate(keep)
            ]
            for w in wfuts:
                w.result()
        completed.add(bi)
        if progress:
            observe.log(f"  {label}: batch {bi + 1}/{len(batches)} done",
                        stage=label)

    try:
        run_with_retry(list(enumerate(batches)), process_batch, label=label)
    finally:
        if drain_pool is not None:
            drain_pool.shutdown(wait=True)
        for _outs, cost in dispatched.values():
            window.release(cost)  # keep the process-wide gauge honest


def _drain_per_device(outs, batch, consume, drain_pool, label, bi,
                      device_consume=None):
    """Fetch + consume one dispatched batch with one drain worker per
    device shard. Shards are grouped by their batch-axis row start (the
    1-D block sharding is contiguous, so row start order == mesh device
    order); each worker fetches its device's shard of every output in one
    pipelined ``device_get`` and consumes exactly the rows that device
    computed, writes included. Errors propagate to the caller (the retry
    layer re-runs the whole batch; chunk writes are idempotent).
    ``device_consume`` (see run_sharded_batches) is offered each row as
    device arrays before the shard fetch; a shard whose rows are all
    claimed does zero D2H."""
    per_dev: dict[int, list] = {}
    for oi, o in enumerate(outs):
        shards = getattr(o, "addressable_shards", None) or []
        if not shards:   # already-committed single-device array
            per_dev.setdefault(0, [None] * len(outs))[oi] = o
            continue
        for sh in shards:
            r0 = int(sh.index[0].start or 0) if sh.index else 0
            per_dev.setdefault(r0, [None] * len(outs))[oi] = sh.data

    def drain_rows(di, r0):
        _DRAIN_TLS.device = di
        try:
            parts = per_dev[r0]
            _metrics.counter("bst_mesh_drain_rows_total", device=di).inc(
                max(0, min(int(parts[0].shape[0]), len(batch) - r0)))
            if device_consume is None:
                nb = sum(int(getattr(p, "nbytes", 0)) for p in parts)
                with profiling.span("mesh.d2h", stage=label, item=int(bi),
                                    device=di, nbytes=nb):
                    datas = jax.device_get(parts)
                _D2H_BYTES.inc(sum(int(getattr(d, "nbytes", 0))
                                   for d in datas))
                _D2H_SAVED.inc(narrow_dtype_savings(datas))
                for li in range(int(datas[0].shape[0])):
                    gi = r0 + li
                    if gi >= len(batch):
                        break    # batch-axis padding rows carry no work
                    consume(batch[gi], *(d[li] for d in datas))
                return
            todo = []
            for li in range(int(parts[0].shape[0])):
                gi = r0 + li
                if gi >= len(batch):
                    break        # batch-axis padding rows carry no work
                if device_consume(batch[gi], *(p[li] for p in parts)):
                    continue     # claimed: the row stays in HBM
                todo.append(li)
            if not todo:
                return           # whole shard claimed on device: zero D2H
            rows = [[p[li] for li in todo] for p in parts]
            nb = sum(int(getattr(r, "nbytes", 0)) for rs in rows for r in rs)
            with profiling.span("mesh.d2h", stage=label, item=int(bi),
                                device=di, nbytes=nb):
                datas = jax.device_get(rows)
            flat = [d for ds_ in datas for d in ds_]
            _D2H_BYTES.inc(sum(int(getattr(d, "nbytes", 0)) for d in flat))
            _D2H_SAVED.inc(narrow_dtype_savings(flat))
            for k, li in enumerate(todo):
                consume(batch[r0 + li], *(d[k] for d in datas))
        finally:
            _DRAIN_TLS.device = None

    futs = [drain_pool.submit(drain_rows, di, r0)
            for di, r0 in enumerate(sorted(per_dev))]
    for f in futs:
        f.result()


def shard_jit(fn, mesh: Mesh, n_in: int, n_repl: int = 0, n_out=None,
              static_argnames=()):
    """jit ``fn`` with the first ``n_repl`` args replicated and the remaining
    ``n_in`` batch-leading args (and all outputs) sharded over the mesh's
    block axis."""
    shard = NamedSharding(mesh, P(BLOCK_AXIS))
    repl = NamedSharding(mesh, P())
    out_shardings = shard if n_out is None else (shard,) * n_out
    in_shardings = (repl,) * n_repl + (shard,) * n_in
    return _commit_host_args(jax.jit(
        fn,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        static_argnames=static_argnames,
    ), in_shardings)
