"""Timeline flight recorder: bounded ring buffer of begin/end trace events.

The span aggregates (:mod:`profiling`) can say `fusion.d2h` took 13.8 s
total — they cannot say whether it OVERLAPPED `fusion.write`, how long a
device sat idle between dispatches, or which per-block chain was the
critical path. Those are exactly the questions the measured frontier
raises (PERF §3g–k: D2H + writes dwarf compute while the kernel runs at
376 Mvox/s), and what the streaming stage-DAG executor and the autotuner
(ROADMAP items 2 and 5) need answered before they can schedule overlap.

This module is the recorder only: a process-wide, thread-safe, bounded
ring of timestamped begin/end/instant events carrying thread id, device
ordinal, stage, work-item identity (block offset / pair index) and byte
payload. Analysis lives in :mod:`..analysis.tracereport` (the
``bst trace-report`` CLI); export is Chrome/Perfetto ``trace_event``
JSON, loadable directly in ``ui.perfetto.dev``, one track per device and
per host thread.

Cost model:

- **off (default)**: ``enabled()`` is one dict read; nothing allocates.
  ``profiling.span`` (the one span primitive) pays one extra truthiness
  check and sets no context variable.
- **on**: one lock + tuple append per event. The ring is sized in bytes
  (``BST_TRACE_BUFFER_BYTES`` / ``_EVENT_COST_BYTES``) and OVERFLOW
  KEEPS THE NEWEST events (the tail of a run is where the frontier is);
  drops are counted (``bst_trace_events_dropped_total``), never silent.

Enable with ``--trace`` (every tool, ``cli/common.py``) or
``trace.configure()``; the file lands at ``BST_TRACE_PATH``, else next
to the telemetry file set as ``trace-{pi:05d}-of-{pc:05d}.json`` (so
``bst telemetry-merge`` can fold + barrier-align a pod run's traces),
else ``./bst-trace.json``.

Span NAMES are literals declared in ``observe/metric_names.py``'s
``SPANS`` table — the ``span-name`` lint check bans dynamic names, and
reusing :mod:`profiling`'s names means the trace and the span aggregates
can never disagree about what was measured. Dynamic identity (device,
block offset, pair index, bytes) rides in the event's args instead.

Every event carries an ``id`` and the ``parent`` that was open where it
was recorded (0 = none): :func:`profiling.span` holds the open span in
the context variable below, which ``utils.threads`` carries across a
pool hop, so the events of one call form a tree whatever thread they ran
on. With ``configure(device=True)`` (``--trace-device``) a JAX profiler
session runs alongside and :func:`finalize` folds its device timeline
into the same file (:mod:`.devicetrace`).
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
from collections import deque

from . import metrics as _metrics
from .. import config

SCHEMA = "bst-trace/2"
MERGED_SCHEMA = "bst-merged-trace/1"

# amortized python-side cost of one buffered event tuple (10-slot tuple +
# interned strings + int refs); sizes the ring from the byte knob
_EVENT_COST_BYTES = 192
_MIN_CAPACITY = 64

# device-track ids in the exported trace: Perfetto tids are plain ints,
# so device ordinals map to a reserved high range and host threads to
# small first-appearance indices — one track per device, one per thread
_DEVICE_TID_BASE = 10_000

_EVENTS_TOTAL = _metrics.counter("bst_trace_events_total")
_EVENTS_DROPPED = _metrics.counter("bst_trace_events_dropped_total")

_lock = threading.Lock()
_STATE: dict = {
    "enabled": False,
    "buf": None,           # deque of (ts, ph, name, tid, device, stage,
    "capacity": 0,         #           item, nbytes, id, parent)
    "recorded": 0,
    "dropped": 0,
    "path": None,          # explicit output override (beats the knob)
    "last_path": None,     # where finalize() wrote, for CLI echo
    "device_dir": None,    # live JAX profiler session's directory
    "device": None,        # its reduction (devicetrace.reduce), at finalize
}
_thread_names: dict[int, str] = {}


class OpenSpan:
    """One span that is open now: what its children and the events
    recorded under it need to know. ``children`` collects the closed
    direct children's ``(start, end)`` (perf_counter seconds) for the
    parent's self time."""

    __slots__ = ("id", "root", "name", "children")

    def __init__(self, name: str, parent: "OpenSpan | None"):
        self.id = next(_ids)
        self.root = parent.root if parent is not None else self.id
        self.name = name
        self.children: list[tuple[float, float]] = []


_ids = itertools.count(1)    # next() on it is atomic under the GIL
# the innermost open span of this context; set only by profiling.span and
# only while something records
CURRENT: contextvars.ContextVar[OpenSpan | None] = contextvars.ContextVar(
    "open_span", default=None)


def trace_name(process_index: int, process_count: int) -> str:
    return f"trace-{process_index:05d}-of-{process_count:05d}.json"


def configure(buffer_bytes: int | None = None, path: str | None = None,
              device: bool = False) -> None:
    """Start recording into a fresh ring. ``buffer_bytes`` defaults to the
    ``BST_TRACE_BUFFER_BYTES`` knob; ``path`` overrides the output
    resolution of :func:`finalize`; ``device`` starts a JAX profiler
    session beside the ring, which :func:`finalize` reduces into the
    trace file's ``device N (XLA)`` tracks."""
    from . import compiles

    if buffer_bytes is None:
        buffer_bytes = config.get_bytes("BST_TRACE_BUFFER_BYTES")
    cap = max(_MIN_CAPACITY, int(buffer_bytes) // _EVENT_COST_BYTES)
    compiles.listen()
    _stop_device_session()
    with _lock:
        _thread_names.clear()  # OS thread idents get recycled across runs
        _STATE["buf"] = deque(maxlen=cap)
        _STATE["capacity"] = cap
        _STATE["recorded"] = 0
        _STATE["dropped"] = 0
        _STATE["path"] = path
        _STATE["last_path"] = None
        _STATE["device"] = None
        _STATE["enabled"] = True
    if device:
        from . import devicetrace

        d = devicetrace.start()
        with _lock:
            _STATE["device_dir"] = d


def enabled() -> bool:
    return _STATE["enabled"]


def last_path() -> str | None:
    return _STATE["last_path"]


def device_session() -> bool:
    """Whether a profiler session runs beside the ring (``--trace-device``)."""
    return _STATE["device_dir"] is not None


def record(ph: str, name: str, *, device: int | None = None,
           stage: str | None = None, item=None, nbytes: int | None = None,
           ts: float | None = None, id: int | None = None,
           parent: int | None = None) -> None:
    """Append one event (``ph``: ``"B"`` begin / ``"E"`` end / ``"i"``
    instant); no-op unless configured. ``ts`` is wall-clock seconds
    (defaulted) — wall clock, not a monotonic counter, because multihost
    merge aligns traces across processes via shared barrier exits.
    ``id``/``parent`` are the span's (``profiling.span`` passes them for
    its begin and end); left out, the event gets an id of its own and the
    span open in this context for its parent."""
    if not _STATE["enabled"]:
        return
    t = time.time() if ts is None else ts
    tid = threading.get_ident()
    if id is None:
        id = next(_ids)
    if parent is None:
        cur = CURRENT.get()
        parent = cur.id if cur is not None else 0
    with _lock:
        buf = _STATE["buf"]
        if buf is None:
            return
        if tid not in _thread_names:
            _thread_names[tid] = threading.current_thread().name
        if len(buf) == _STATE["capacity"]:
            _STATE["dropped"] += 1     # deque drops the OLDEST: newest win
            _EVENTS_DROPPED.inc()
        buf.append((t, ph, name, tid, device, stage, item, nbytes, id,
                    parent))
        _STATE["recorded"] += 1
        _EVENTS_TOTAL.inc()


def instant(name: str, *, device: int | None = None, stage: str | None = None,
            item=None, nbytes: int | None = None) -> None:
    record("i", name, device=device, stage=stage, item=item, nbytes=nbytes)


def stats() -> dict:
    with _lock:
        return {
            "enabled": _STATE["enabled"],
            "recorded": _STATE["recorded"],
            "dropped": _STATE["dropped"],
            "buffered": len(_STATE["buf"]) if _STATE["buf"] is not None else 0,
            "capacity_events": _STATE["capacity"],
        }


def snapshot() -> list[dict]:
    """The buffered events as dicts (oldest first) — the test/report
    surface that needs no file round-trip."""
    with _lock:
        items = list(_STATE["buf"]) if _STATE["buf"] is not None else []
    out = []
    for t, ph, name, tid, device, stage, item, nbytes, sid, parent in items:
        rec = {"ts": t, "ph": ph, "name": name, "tid": tid, "id": sid,
               "parent": parent}
        if device is not None:
            rec["device"] = device
        if stage is not None:
            rec["stage"] = stage
        if item is not None:
            rec["item"] = item
        if nbytes is not None:
            rec["nbytes"] = nbytes
        out.append(rec)
    return out


def _stop_device_session() -> dict | None:
    """Stop the profiler session, if one runs, and return its reduction
    on the ring's clock (None without a session or a device trace)."""
    with _lock:
        d, _STATE["device_dir"] = _STATE["device_dir"], None
    if d is None:
        return None
    from . import devicetrace

    return devicetrace.stop_and_reduce(d, snapshot())


def reset() -> None:
    """Stop recording and drop the buffer (test isolation)."""
    _stop_device_session()
    with _lock:
        _thread_names.clear()
        _STATE["enabled"] = False
        _STATE["buf"] = None
        _STATE["capacity"] = 0
        _STATE["recorded"] = 0
        _STATE["dropped"] = 0
        _STATE["path"] = None
        _STATE["device"] = None


def export(process_index: int = 0, process_count: int = 1) -> dict:
    """The Chrome/Perfetto ``trace_event`` JSON document: ``B``/``E``/``i``
    events in microseconds, device-attributed events routed to one track
    per device ordinal, host events to one track per thread, plus the
    ``M`` metadata naming every track. After a ``--trace-device`` run the
    reduced device timeline rides along (``device N (XLA)`` tracks, its
    totals under ``bst.device`` and the clock join's ``bst.clock_*``)."""
    with _lock:
        items = list(_STATE["buf"]) if _STATE["buf"] is not None else []
        tnames = dict(_thread_names)
        recorded, dropped = _STATE["recorded"], _STATE["dropped"]
        reduced = _STATE["device"]

    tid_index: dict[int, int] = {}
    for _t, _ph, _n, tid, device, *_rest in items:
        if device is None and tid not in tid_index:
            tid_index[tid] = len(tid_index) + 1

    meta = [{
        "ph": "M", "name": "process_name", "pid": process_index,
        "args": {"name": f"bst process {process_index}/{process_count}"},
    }]
    used_device_tids: set[int] = set()
    events = []
    for t, ph, name, tid, device, stage, item, nbytes, sid, parent in items:
        if device is not None:
            out_tid = _DEVICE_TID_BASE + int(device)
            used_device_tids.add(out_tid)
        else:
            out_tid = tid_index[tid]
        args = {"id": sid, "parent": parent}
        if stage is not None:
            args["stage"] = stage
        if item is not None:
            args["item"] = item
        if nbytes is not None:
            args["bytes"] = int(nbytes)
        if device is not None:
            args["device"] = int(device)
        ev = {"name": name, "cat": name.split(".")[0], "ph": ph,
              "ts": round(t * 1e6, 1), "pid": process_index, "tid": out_tid,
              "args": args}
        if ph == "i":
            ev["s"] = "t"
        events.append(ev)
    for dt in sorted(used_device_tids):
        meta.append({"ph": "M", "name": "thread_name", "pid": process_index,
                     "tid": dt,
                     "args": {"name": f"device {dt - _DEVICE_TID_BASE}"}})
        meta.append({"ph": "M", "name": "thread_sort_index",
                     "pid": process_index, "tid": dt,
                     "args": {"sort_index": dt - _DEVICE_TID_BASE}})
    for tid, idx in tid_index.items():
        meta.append({"ph": "M", "name": "thread_name", "pid": process_index,
                     "tid": idx,
                     "args": {"name": tnames.get(tid, f"thread {tid}")}})
    bst = {"schema": SCHEMA, "process_index": process_index,
           "process_count": process_count, "recorded": recorded,
           "dropped": dropped}
    if reduced is not None:
        from . import devicetrace

        dmeta, devents, summary = devicetrace.perfetto(
            reduced, process_index)
        meta += dmeta
        events += devents
        bst.update(summary)
    return {"traceEvents": meta + events, "displayTimeUnit": "ms",
            "bst": bst}


def dump(path: str) -> str:
    """Write the ring's CURRENT contents as Perfetto JSON to ``path``
    WITHOUT stopping the recorder — :func:`export` copies the buffer
    under the ring lock, so the snapshot is consistent while events keep
    flowing (the on-demand ``bst trace-dump`` path; :func:`finalize` is
    the end-of-run variant that also stops recording)."""
    from . import events as _events

    pi, pc = _events.world()
    doc = export(pi, pc)
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    # pid AND thread id: two concurrent daemon-op dumps to one path must
    # not interleave into a shared temp file
    tmp = f"{path}.tmp{os.getpid()}-{threading.get_ident()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, default=str)
        f.write("\n")
    os.replace(tmp, path)   # a live dump must never expose a torn file
    return path


def dump_live(path: str) -> str:
    """:func:`dump` with an explicit not-recording error — the daemon op
    / CLI surface of the on-demand flight-recorder snapshot."""
    if not _STATE["enabled"]:
        raise RuntimeError(
            "flight recorder is not recording — enable it with --trace "
            "(the serve daemon records always)")
    return dump(path)


def finalize(dir_hint: str | None = None) -> str | None:
    """Write the trace (if recording) and stop. Output resolution:
    explicit ``configure(path=)`` > the ``BST_TRACE_PATH`` knob >
    ``dir_hint`` (the telemetry dir, when a run has one) >
    ``./bst-trace.json``. Idempotent — returns the path, or None when
    nothing was recording."""
    from . import events as _events

    if not _STATE["enabled"]:
        return None
    path = _STATE["path"] or config.get_str("BST_TRACE_PATH")
    if path is None:
        pi, pc = _events.world()
        path = os.path.join(dir_hint, trace_name(pi, pc)) if dir_hint \
            else os.path.abspath("bst-trace.json")
    reduced = _stop_device_session()
    with _lock:
        _STATE["device"] = reduced
    path = dump(path)
    with _lock:
        _STATE["enabled"] = False
        _STATE["buf"] = None
        _STATE["last_path"] = path
    return path


# -- multihost fold ---------------------------------------------------------

def _barrier_exits(doc: dict) -> dict[tuple, float]:
    """(barrier stage, occurrence index FROM THE END) -> exit timestamp
    (µs). Barrier EXITS are the alignment anchor: every process leaves
    ``sync_global_devices`` together, so equal-keyed exits mark the same
    wall-clock instant regardless of per-host clock skew. Occurrences are
    indexed from the tail (-1 = last) because ring overflow keeps the
    NEWEST events — processes that dropped different numbers of early
    barriers still pair their surviving tails correctly."""
    per_stage: dict = {}
    for ev in doc.get("traceEvents", ()):
        if ev.get("name") == "barrier" and ev.get("ph") == "E":
            stage = (ev.get("args") or {}).get("stage")
            per_stage.setdefault(stage, []).append(float(ev["ts"]))
    return {(stage, i - len(ts)): t
            for stage, ts in per_stage.items()
            for i, t in enumerate(ts)}


class MergedTracePath(str):
    """The merged-trace output path, carrying the merged ``bst`` metadata
    as ``.bst`` so callers (telemetry-merge) need not re-parse the — for
    a pod run, potentially very large — file they just wrote."""

    bst: dict


def merge_traces(directory: str,
                 output: str | None = None) -> MergedTracePath | None:
    """Fold per-process ``trace-*.json`` files into one
    ``merged-trace.json``, aligning each process's clock to process 0 via
    the shared barrier exit events; returns the output path (a str
    subclass exposing the merged metadata as ``.bst``) or None when the
    directory has no traces."""
    import glob as _glob

    paths = sorted(_glob.glob(os.path.join(directory, "trace-*-of-*.json")))
    if not paths:
        return None
    docs = []
    for p in paths:
        with open(p, encoding="utf-8") as f:
            docs.append(json.load(f))
    docs.sort(key=lambda d: d.get("bst", {}).get("process_index", 0))
    ref = _barrier_exits(docs[0])
    merged: list = []
    offsets: dict[int, float] = {}
    unaligned: list[int] = []
    for doc in docs:
        pid = doc.get("bst", {}).get("process_index", 0)
        off = 0.0
        if doc is not docs[0]:
            own = _barrier_exits(doc)
            deltas = sorted(ref[k] - own[k] for k in ref if k in own)
            if deltas:
                off = deltas[len(deltas) // 2]   # median: straggler-robust
            else:
                unaligned.append(pid)
        offsets[pid] = round(off, 1)
        for ev in doc.get("traceEvents", ()):
            if off and "ts" in ev:
                ev = {**ev, "ts": round(ev["ts"] + off, 1)}
            merged.append(ev)
    out = output or os.path.join(directory, "merged-trace.json")
    # recorded/dropped totals ride along so trace-report on the merged
    # file still surfaces ring overflow — drops are never silent
    bst = {"schema": MERGED_SCHEMA,
           "process_count": len(docs),
           "recorded": sum(int(d.get("bst", {}).get("recorded") or 0)
                           for d in docs),
           "dropped": sum(int(d.get("bst", {}).get("dropped") or 0)
                          for d in docs),
           "clock_offsets_us": offsets,
           "unaligned_processes": unaligned}
    with open(out, "w", encoding="utf-8") as f:
        json.dump({"traceEvents": merged, "displayTimeUnit": "ms",
                   "bst": bst}, f, default=str)
        f.write("\n")
    res = MergedTracePath(out)
    res.bst = bst
    return res
