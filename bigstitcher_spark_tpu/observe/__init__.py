"""Structured run telemetry: event log, metrics registry, run manifests.

The Spark reference gets observability for free from its runtime — an
event log, a history server, per-stage task counts and retry accounting.
This package is the TPU port's equivalent, threaded through every layer:

- :mod:`.events` — append-only JSONL event log, one file per process
  named by ``(process_index, process_count)`` so multi-host runs never
  collide;
- :mod:`.metrics` — always-on thread-safe counter/gauge/histogram
  registry with a Prometheus-style textfile export;
- :mod:`.progress` — per-stage heartbeats (done/total, rate, ETA) and
  stage summary records;
- :mod:`.manifest` — the per-run manifest written at command end plus
  the ``bst telemetry-merge`` fold of N per-process files.

Activation is one call — ``observe.configure(telemetry_dir)`` — wired to
the shared ``--telemetry-dir`` / ``--profile`` CLI options; disabled (the
default) every ``events.emit`` is a single ``is None`` check and nothing
touches the filesystem.
"""

from __future__ import annotations

import os
import sys
import time

from . import compiles, events, manifest, metrics, progress, trace  # noqa: F401

_STATE: dict = {
    "dir": None,
    "started_at": None,
    "metrics_baseline": None,
    "enabled_profiling": False,
}


def configure(telemetry_dir: str, profile: bool = True) -> None:
    """Activate telemetry into ``telemetry_dir`` for the rest of this run.

    Opens the per-process event log (lazily), snapshots the metrics
    registry so the manifest reports this run's deltas, resets the stage
    records, and (by default) enables the span profiler so the manifest
    carries the span-stat table."""
    from .. import profiling

    d = os.path.abspath(telemetry_dir)
    os.makedirs(d, exist_ok=True)
    events.configure(d)
    compiles.listen()
    progress.reset_records()
    _STATE["dir"] = d
    _STATE["started_at"] = time.time()
    _STATE["metrics_baseline"] = metrics.get_registry().snapshot()
    if profile and not profiling.get().enabled:
        profiling.enable(True)
        _STATE["enabled_profiling"] = True
    events.emit("run.start", argv=list(sys.argv), pid=os.getpid())


def active() -> bool:
    return _STATE["dir"] is not None


def telemetry_dir() -> str | None:
    return _STATE["dir"]


def log(message: str, stage: str | None = None, echo: bool = True,
        **fields) -> None:
    """Structured replacement for the drivers' bare ``print``: always an
    event (when telemetry is on), a stdout line only when ``echo`` —
    callers pass their existing ``progress``/``verbose`` flag, so console
    behavior is unchanged while the event log sees everything."""
    if events.enabled():
        events.emit("log", stage=stage, message=message, **fields)
    if echo:
        print(message)


def finalize(tool: str | None = None, params: dict | None = None,
             status: str = "ok", error: str | None = None) -> str | None:
    """End the telemetry run: write the Prometheus textfile and the run
    manifest, close the event log, restore profiler state. Idempotent —
    returns the manifest path, or None when telemetry was never
    configured."""
    from .. import profiling

    if not active():
        return None
    d = _STATE["dir"]
    pi, pc = events.world()
    reg = metrics.get_registry()
    prom_path = os.path.join(d, f"metrics-{pi:05d}-of-{pc:05d}.prom")
    with open(prom_path, "w", encoding="utf-8") as f:
        f.write(reg.render_prometheus())
    spans = {k: {"count": s.count, "total_s": round(s.total_s, 3),
                 "self_s": round(s.self_s, 3),
                 "max_s": round(s.max_s, 3), "min_s": round(s.min_s, 3)}
             for k, s in profiling.get().stats().items()}
    seconds = time.time() - _STATE["started_at"]
    events.emit("run.end", status=status, seconds=round(seconds, 3),
                error=error)
    # archive the flight-recorder ring (if one is recording) next to the
    # manifest, so a traced run's timeline travels with its telemetry —
    # unless BST_TRACE_PATH/configure(path=) sent it elsewhere, in which
    # case the manifest must point at the real location, not a dangling
    # dir-local basename
    trace_path = trace.finalize(dir_hint=d)
    if trace_path is not None and \
            os.path.dirname(os.path.abspath(trace_path)) == \
            os.path.abspath(d):
        trace_path = os.path.basename(trace_path)
    ev_path = events.close()
    path = manifest.write_manifest(
        d,
        tool=tool,
        argv=list(sys.argv),
        params=params,
        world=(pi, pc),
        started_at=_STATE["started_at"],
        seconds=seconds,
        status=status,
        error=error,
        spans=spans,
        metrics_delta=reg.snapshot_delta(_STATE["metrics_baseline"]),
        # job-scoped stage records belong to their JobRun manifests, not
        # the process-wide one (a serve daemon's own manifest would
        # otherwise re-report every job's stages)
        stages=[r for r in progress.records() if "job" not in r],
        events_file=os.path.basename(ev_path) if ev_path else None,
        trace_file=trace_path,
    )
    progress.reset_records()
    if _STATE["enabled_profiling"]:
        profiling.enable(False)
    _STATE.update(dir=None, started_at=None, metrics_baseline=None,
                  enabled_profiling=False)
    _record_history(path)
    return path


def _record_history(manifest_path: str | None,
                    job: str | None = None) -> None:
    """Append a finalized manifest to the BST_HISTORY_DIR store (no-op
    when the knob is unset); history IO must never fail the run it
    records."""
    if manifest_path is None:
        return
    try:
        from . import history

        history.record_manifest(manifest_path, job=job)
    except Exception:
        pass


class JobRun:
    """Scoped telemetry for ONE job inside a long-lived process (the
    ``bst serve`` daemon's per-job manifests).

    Where :func:`configure`/:func:`finalize` own the whole process run,
    a JobRun owns one job's slice of it: its own event-log sink
    (``events-job-<label>-*.jsonl`` in its own directory, routed by the
    job's context scope so concurrent jobs never interleave), its own
    metric DELTAS (registry snapshot at open, delta at finalize — the
    process registry stays shared, which is the point: warm caches are
    visible as per-job hit deltas), its own span-count deltas, and its
    own stage records (tagged by the event scope, popped at finalize).

    Use as a context manager around the job's execution on the job's
    thread — worker threads inherit the scope via utils.threads — then
    call :meth:`finalize` for the manifest.
    """

    def __init__(self, label: str, directory: str, tool: str | None = None):
        from .. import profiling

        self.label = str(label)
        self.dir = os.path.abspath(directory)
        self.tool = tool
        self.started_at = time.time()
        events.open_job(self.label, self.dir)
        self._metrics_baseline = metrics.get_registry().snapshot()
        self._span_baseline = {
            k: (s.count, s.total_s, s.self_s)
            for k, s in profiling.get().stats().items()}
        self._token = None
        self._finalized = False

    def __enter__(self):
        self._token = events.activate_job(self.label)
        events.emit("job.start", job=self.label, tool=self.tool,
                    pid=os.getpid())
        return self

    def __exit__(self, *exc):
        if self._token is not None:
            events.deactivate_job(self._token)
            self._token = None
        return False

    def finalize(self, status: str = "ok", error: str | None = None,
                 params: dict | None = None,
                 argv: list[str] | None = None) -> str | None:
        """Write the job's manifest into its directory and close its event
        sink. Idempotent; returns the manifest path."""
        from .. import profiling

        if self._finalized:
            return None
        self._finalized = True
        seconds = time.time() - self.started_at
        # the job.end record must land in the JOB's log regardless of
        # which thread finalizes
        token = events.activate_job(self.label)
        try:
            events.emit("job.end", job=self.label, status=status,
                        seconds=round(seconds, 3), error=error)
        finally:
            events.deactivate_job(token)
        ev_path = events.close_job(self.label)
        spans = {}
        for k, s in profiling.get().stats().items():
            c0, t0, self0 = self._span_baseline.get(k, (0, 0.0, 0.0))
            if s.count <= c0:
                continue
            # count/total/self are true deltas; min/max are
            # process-lifetime aggregates (the profiler keeps no
            # per-interval extrema)
            spans[k] = {"count": s.count - c0,
                        "total_s": round(s.total_s - t0, 3),
                        "self_s": round(s.self_s - self0, 3),
                        "max_s": round(s.max_s, 3),
                        "min_s": round(s.min_s, 3)}
        reg = metrics.get_registry()
        path = manifest.write_manifest(
            self.dir,
            tool=self.tool,
            argv=argv if argv is not None else [],
            params=params,
            world=events.world(),
            started_at=self.started_at,
            seconds=seconds,
            status=status,
            error=error,
            spans=spans,
            metrics_delta=reg.snapshot_delta(self._metrics_baseline),
            stages=progress.take_records(self.label),
            events_file=os.path.basename(ev_path) if ev_path else None,
        )
        _record_history(path, job=self.label)
        return path
