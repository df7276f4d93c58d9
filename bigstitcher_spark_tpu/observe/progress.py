"""Per-stage progress heartbeats and stage summary records.

``Heartbeat`` is the per-block progress channel of the block-writing
drivers (affine fusion, resave, downsample, nonrigid): rate-limited
``stage.progress`` events with done/total, blocks/s and ETA, plus a final
``stage.end`` record that captures ETA-vs-actual for the run manifest.
``record_stage`` lets a driver file its own end-of-stage summary (block /
voxel totals from its stats object).

Stage records accumulate only while telemetry is configured, so library
use (bench loops, tests) never grows unbounded state.
"""

from __future__ import annotations

import threading
import time

from . import events, metrics

_rec_lock = threading.Lock()
_records: list[dict] = []

# live last-progress row for the telemetry relay (observe/relay.py): the
# push client ships it with every heartbeat so `bst top --cluster` shows
# a remote rank's stage/done/total without any event-log plumbing.
# Tracking is OFF by default — a run without an active relay client pays
# nothing beyond the existing events.enabled() check.
_live_lock = threading.Lock()
_live: dict | None = None
_track_live = False
_track_count = 0


def set_live_tracking(on: bool) -> None:
    """Refcounted on/off (tests run several relay clients in one
    process; production runs exactly one)."""
    global _track_live, _live, _track_count
    with _live_lock:
        _track_count = max(0, _track_count + (1 if on else -1))
        _track_live = _track_count > 0
        if not _track_live:
            _live = None


def latest() -> dict | None:
    """The most recent stage-progress row (relay tracking only)."""
    with _live_lock:
        return dict(_live) if _live is not None else None


def _set_live(**row) -> None:
    global _live
    with _live_lock:
        if _track_live:
            _live = {k: v for k, v in row.items() if v is not None}


def reset_records() -> None:
    with _rec_lock:
        _records.clear()


def records() -> list[dict]:
    with _rec_lock:
        return [dict(r) for r in _records]


def take_records(job: str) -> list[dict]:
    """Remove and return the stage records filed under ``job``'s event
    scope (the serve daemon's per-job manifests): popping them keeps a
    long-lived daemon's record list from growing per job, and keeps job
    stages out of the daemon's own run manifest."""
    with _rec_lock:
        mine = [dict(r) for r in _records if r.get("job") == job]
        _records[:] = [r for r in _records if r.get("job") != job]
    for r in mine:
        r.pop("job", None)
    return mine


def _append_record(rec: dict) -> None:
    if not events.enabled():
        return
    # records filed inside a job's event scope carry the job label so a
    # daemon can split concurrent jobs' stage tables into their manifests
    job = events.current_job()
    if job is not None:
        rec = {**rec, "job": job}
    with _rec_lock:
        _records.append(rec)


def record_stage(stage: str, **fields) -> None:
    """File a driver's end-of-stage summary (manifest ``stages`` table)."""
    rec = {"stage": stage, **{k: v for k, v in fields.items()
                              if v is not None}}
    events.emit("stage.summary", **rec)
    _append_record(rec)


class Heartbeat:
    """Thread-safe done/total progress for one work list.

    ``tick`` per completed item; emits ``stage.progress`` at most every
    ``every_s`` seconds (always on completion). The first emitted ETA is
    kept so the manifest can show estimate-vs-actual.
    """

    def __init__(self, stage: str, total: int, every_s: float = 2.0):
        self.stage = stage
        self.total = int(total)
        self.every_s = every_s
        self._lock = threading.Lock()
        self._done = 0
        self._retry_rounds = 0
        self._t0 = time.perf_counter()
        self._last_emit = self._t0
        self._eta_first_s: float | None = None
        self._counter = metrics.counter("bst_stage_items_done_total",
                                        stage=stage)
        self._item_seconds = metrics.histogram("bst_stage_item_seconds",
                                               stage=stage)
        self._finished = False
        _set_live(stage=stage, done=0, total=self.total,
                  ts=round(time.time(), 3))
        events.emit("stage.start", stage=stage, total=self.total)

    def tick(self, n: int = 1, seconds: float | None = None) -> None:
        """``n`` items done; ``seconds`` is what ONE of them took, where
        the caller timed it (``bst_stage_item_seconds``)."""
        self._counter.inc(n)
        if seconds is not None:
            for _ in range(n):
                self._item_seconds.observe(seconds)
        with self._lock:
            self._done += n
            if not events.enabled() and not _track_live:
                return
            now = time.perf_counter()
            done, total = self._done, self.total
            if now - self._last_emit < self.every_s and done < total:
                return
            self._last_emit = now
            elapsed = now - self._t0
            rate = done / max(elapsed, 1e-9)
            eta_s = (total - done) / max(rate, 1e-9)
            if self._eta_first_s is None:
                # projected total duration at the first estimate
                self._eta_first_s = elapsed + eta_s
        _set_live(stage=self.stage, done=done, total=total,
                  rate_per_s=round(rate, 3), eta_s=round(eta_s, 1),
                  ts=round(time.time(), 3))
        if events.enabled():
            events.emit("stage.progress", stage=self.stage, done=done,
                        total=total, rate_per_s=round(rate, 3),
                        eta_s=round(eta_s, 1))

    def retry_round(self) -> None:
        with self._lock:
            self._retry_rounds += 1

    def finish(self, **extra) -> dict:
        with self._lock:
            if self._finished:
                return {}
            self._finished = True
            elapsed = time.perf_counter() - self._t0
            rec = {
                "stage": self.stage,
                "done": self._done,
                "total": self.total,
                "seconds": round(elapsed, 3),
                "rate_per_s": round(self._done / max(elapsed, 1e-9), 3),
                "retry_rounds": self._retry_rounds,
            }
            if self._eta_first_s is not None:
                rec["eta_first_s"] = round(self._eta_first_s, 3)
                rec["eta_error_s"] = round(elapsed - self._eta_first_s, 3)
        rec.update({k: v for k, v in extra.items() if v is not None})
        _set_live(stage=self.stage, done=rec["done"], total=rec["total"],
                  rate_per_s=rec["rate_per_s"], finished=True,
                  ts=round(time.time(), 3))
        events.emit("stage.end", **rec)
        _append_record(rec)
        return rec
