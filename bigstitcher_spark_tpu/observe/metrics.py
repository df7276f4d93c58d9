"""Thread-safe in-process metrics registry with Prometheus textfile export.

The Spark reference gets per-stage task counts, byte totals and retry
accounting from the Spark metrics system for free; here every layer
(chunk IO, transfers, retry, stage drivers) feeds one process-wide
registry. The registry is ALWAYS on — a counter update is one lock
acquisition per chunk-level operation, invisible next to the IO it
accounts — while the event log and manifests only activate with
``--telemetry-dir``; a run manifest's ``metrics`` block is a delta of the
same registry.

Series are keyed by ``(name, sorted(labels))``; handles stay valid across
``reset()`` (values are zeroed in place, series are never dropped), so hot
paths may cache the returned Counter/Gauge/Histogram objects.
"""

from __future__ import annotations

import bisect
import threading


class Counter:
    """Monotonic counter (resettable only via the registry)."""

    __slots__ = ("_lock", "_v")

    def __init__(self):
        self._lock = threading.Lock()
        self._v = 0

    def inc(self, v: int | float = 1) -> None:
        with self._lock:
            self._v += v

    @property
    def value(self) -> int | float:
        return self._v

    def _reset(self) -> None:
        with self._lock:
            self._v = 0


class Gauge:
    """Last-value gauge."""

    __slots__ = ("_lock", "_v")

    def __init__(self):
        self._lock = threading.Lock()
        self._v = 0

    def set(self, v: int | float) -> None:
        with self._lock:
            self._v = v

    def inc(self, v: int | float = 1) -> None:
        with self._lock:
            self._v += v

    @property
    def value(self) -> int | float:
        return self._v

    def _reset(self) -> None:
        with self._lock:
            self._v = 0


DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
                   30.0, 60.0, 300.0)


class Histogram:
    """Cumulative-bucket histogram (Prometheus semantics)."""

    __slots__ = ("_lock", "buckets", "_counts", "_sum", "_count")

    def __init__(self, buckets=DEFAULT_BUCKETS):
        self._lock = threading.Lock()
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # +1 = +Inf
        self._sum = 0.0
        self._count = 0

    def observe(self, v: float) -> None:
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def value(self) -> dict:
        with self._lock:
            return {"count": self._count, "sum": self._sum}

    def cumulative_counts(self) -> list[int]:
        with self._lock:
            out, acc = [], 0
            for c in self._counts:
                acc += c
                out.append(acc)
            return out

    def _reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.buckets) + 1)
            self._sum = 0.0
            self._count = 0


def _series_key(name: str, labels: dict) -> str:
    if not labels:
        return name
    inner = ",".join(f'{k}="{_escape(str(v))}"'
                     for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


def _escape(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._series: dict[str, Counter | Gauge | Histogram] = {}
        self._labels: dict[str, dict] = {}
        self._types: dict[str, str] = {}

    def _get(self, cls, typ: str, name: str, labels: dict, **kw):
        key = _series_key(name, labels)
        with self._lock:
            if self._types.setdefault(name, typ) != typ:
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{self._types[name]}, not {typ}")
            m = self._series.get(key)
            if m is None:
                m = cls(**kw)
                self._series[key] = m
                self._labels[key] = dict(labels)
            return m

    def counter(self, name: str, /, **labels) -> Counter:
        return self._get(Counter, "counter", name, labels)

    def gauge(self, name: str, /, **labels) -> Gauge:
        return self._get(Gauge, "gauge", name, labels)

    def histogram(self, name: str, /, buckets=DEFAULT_BUCKETS,
                  **labels) -> Histogram:
        return self._get(Histogram, "histogram", name, labels,
                         buckets=buckets)

    def reset(self) -> None:
        """Zero every series in place (cached handles stay valid)."""
        with self._lock:
            for m in self._series.values():
                m._reset()

    def snapshot(self) -> dict:
        """``{series_key: value}`` — numbers for counters/gauges,
        ``{"count", "sum"}`` dicts for histograms."""
        with self._lock:
            items = list(self._series.items())
        return {k: m.value for k, m in items}

    def snapshot_delta(self, baseline: dict | None) -> dict:
        """Current snapshot minus ``baseline`` (series absent from the
        baseline count from zero). Gauges report their current value."""
        cur = self.snapshot()
        if not baseline:
            return cur
        out = {}
        with self._lock:
            types = {k: type(m) for k, m in self._series.items()}
        for k, v in cur.items():
            b = baseline.get(k)
            if types.get(k) is Gauge or b is None:
                out[k] = v
            elif isinstance(v, dict):
                out[k] = {"count": v["count"] - b.get("count", 0),
                          "sum": v["sum"] - b.get("sum", 0.0)}
            else:
                out[k] = v - b
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition (textfile-collector compatible)."""
        with self._lock:
            items = sorted(self._series.items())
            labels = dict(self._labels)
            types = dict(self._types)
        lines: list[str] = []
        seen_type: set[str] = set()
        for key, m in items:
            name = key.split("{", 1)[0]
            if name not in seen_type:
                seen_type.add(name)
                lines.append(f"# TYPE {name} {types[name]}")
            if isinstance(m, Histogram):
                lab = labels[key]
                cum = m.cumulative_counts()
                for edge, c in zip((*m.buckets, "+Inf"), cum):
                    le = {**lab, "le": edge}
                    lines.append(f"{_series_key(name + '_bucket', le)} {c}")
                v = m.value
                suffix = key[len(name):]
                lines.append(f"{name}_sum{suffix} {_fmt(v['sum'])}")
                lines.append(f"{name}_count{suffix} {v['count']}")
            else:
                lines.append(f"{key} {_fmt(m.value)}")
        return "\n".join(lines) + "\n"


def _fmt(v) -> str:
    if isinstance(v, float) and not v.is_integer():
        return repr(v)
    return str(int(v))


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _REGISTRY


def counter(name: str, /, **labels) -> Counter:
    return _REGISTRY.counter(name, **labels)


def gauge(name: str, /, **labels) -> Gauge:
    return _REGISTRY.gauge(name, **labels)


def histogram(name: str, /, buckets=DEFAULT_BUCKETS, **labels) -> Histogram:
    return _REGISTRY.histogram(name, buckets=buckets, **labels)
