"""Lightweight tracing/profiling: named spans with aggregate wall-clock.

The reference only prints per-stage ``currentTimeMillis`` deltas
(SparkAffineFusion.java:424,470,698); we keep per-span aggregates
(count/total/self/min/max) queryable in-process and printable per stage.
Zero overhead when disabled.

``span`` is the ONE way to open a span. It is also the begin/end source
for the timeline flight recorder (:mod:`.observe.trace`): when tracing is
on, every span forwards its begin/end (plus optional device/stage/item/
byte attribution) to the ring buffer under the SAME name, so the trace
and the aggregates can never disagree about what was measured. A span
knows the span that was open where it started (its parent) through a
context variable that ``utils.threads`` carries across a pool hop, so a
call's spans form one tree whatever threads they ran on; a span's SELF
time is its duration less the union of its direct children's intervals.
While the ring records, each span also opens a
``jax.profiler.TraceAnnotation`` carrying its id: a no-op without a
profiler session, and in any device trace taken meanwhile every program
span then sits on the profiler's own clock beside "XLA Ops".
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict
from dataclasses import dataclass

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .observe import trace as _trace


@dataclass
class SpanStat:
    count: int = 0
    total_s: float = 0.0
    max_s: float = 0.0
    min_s: float = 0.0
    self_s: float = 0.0


class Profiler:
    def __init__(self):
        self.enabled = False
        self._stats: dict[str, SpanStat] = defaultdict(SpanStat)
        self._lock = threading.Lock()

    def reset(self):
        with self._lock:
            self._stats.clear()

    def record(self, name: str, dt: float, self_s: float | None = None):
        with self._lock:
            s = self._stats[name]
            s.min_s = dt if s.count == 0 else min(s.min_s, dt)
            s.count += 1
            s.total_s += dt
            s.max_s = max(s.max_s, dt)
            s.self_s += dt if self_s is None else self_s

    def stats(self) -> dict[str, SpanStat]:
        with self._lock:
            return {k: SpanStat(v.count, v.total_s, v.max_s, v.min_s,
                                v.self_s)
                    for k, v in self._stats.items()}

    def report(self) -> str:
        # stats() snapshots under the lock — iterating self._stats directly
        # here raced with concurrent record() calls mutating the dict.
        # Sorted by total_s DESC so the hot span is the first line.
        stats = self.stats()
        lines = ["span                            count    total_s     "
                 "mean_s      min_s      max_s     self_s"]
        for k in sorted(stats, key=lambda k: (-stats[k].total_s, k)):
            s = stats[k]
            lines.append(
                f"{k:<30} {s.count:>6} {s.total_s:>10.3f} "
                f"{s.total_s / max(s.count, 1):>10.3f} "
                f"{s.min_s:>10.3f} {s.max_s:>10.3f} {s.self_s:>10.3f}")
        return "\n".join(lines)


_global = Profiler()


def enable(on: bool = True):
    _global.enabled = on


def get() -> Profiler:
    return _global


def _self_seconds(t0: float, t1: float,
                  children: list[tuple[float, float]]) -> float:
    """``t1 - t0`` less the union of the children's intervals, each
    clipped to the span (a child on another thread may outlive it)."""
    covered, edge = 0.0, t0
    for a, b in sorted(children):
        a, b = max(a, edge), min(b, t1)
        if b > a:
            covered += b - a
            edge = b
    return (t1 - t0) - covered


@contextlib.contextmanager
def span(name: str, *, device: int | None = None, stage: str | None = None,
         item=None, nbytes: int | None = None):
    """Aggregate-profiled (and, when tracing, timeline-recorded) span.

    The attribution kwargs cost nothing off the hot path: disabled, the
    whole call is two truthiness checks and an immediate yield, and no
    context variable is set."""
    tracing = _trace.enabled()
    if not _global.enabled and not tracing:
        yield
        return
    parent = _trace.CURRENT.get()
    me = _trace.OpenSpan(name, parent)
    token = _trace.CURRENT.set(me)
    parent_id = parent.id if parent is not None else 0
    note = None
    if tracing:
        _trace.record("B", name, device=device, stage=stage, item=item,
                      nbytes=nbytes, id=me.id, parent=parent_id)
        note = _TraceAnnotation(name, id=me.id)
        note.__enter__()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        if note is not None:
            note.__exit__(None, None, None)
        _trace.CURRENT.reset(token)
        if parent is not None:
            parent.children.append((t0, t1))
        if _global.enabled:
            _global.record(name, t1 - t0, _self_seconds(t0, t1, me.children))
        if tracing:
            _trace.record("E", name, device=device, stage=stage, item=item,
                          nbytes=nbytes, id=me.id, parent=parent_id)


def device_sync(x):
    """Block until the device array(s) in `x` have been computed, by
    fetching one element of each: a data dependency holds on any backend,
    whatever its ``block_until_ready`` promises. On the local TPU v5e
    (JAX 0.9.0, libtpu 0.0.34) ``block_until_ready`` IS a completion
    barrier — scripts/chip_probe.py, PR 21: 134.8 ms for a program whose
    HBM-bandwidth lower bound is 104.9 ms, after a 0.2 ms dispatch, and a
    following one-element fetch adds 1.7 ms — so there this is a barrier
    plus a few-byte D2H per leaf."""
    import jax
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(x):
        if hasattr(leaf, "dtype") and hasattr(leaf, "ndim") and leaf.size:
            # direct one-element index: no full-size ravel intermediate
            np.asarray(leaf[(0,) * leaf.ndim] if leaf.ndim else leaf)
    return x
