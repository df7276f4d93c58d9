"""The device's own timeline in the program's recorder.

``--trace-device`` runs a JAX profiler session (python tracer off) beside
the flight recorder for the command's length. At ``trace.finalize`` the
xplane is reduced HERE, in the program: per device, the "XLA Modules"
events and the union of the "XLA Ops" intervals, placed on the host's
clock and written into the same Perfetto file as the host spans
(``device N (XLA) modules`` / ``device N (XLA) busy`` tracks). Per-op
events are not exported — only per-module events and the ten largest op
totals — so a long run's file stays small.

The clock join needs no anchor of its own: every ``profiling.span``
opened while the ring records also opens a ``TraceAnnotation`` carrying
its id, so each span is one: ``offset = median(ring begin - annotation
start)``, and what is left over (the residuals' 95th percentile, their
maximum, and the drift from the first tenth of the anchors to the last)
says how far the two clocks can be trusted over the whole run.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import statistics
import tempfile

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Perfetto tids of the XLA tracks: above the ring's device-attributed
# tracks (trace._DEVICE_TID_BASE), two a device
_XLA_TID_BASE = 20_000
TRACK_TAG = "(XLA)"
_TOP_OPS = 10


def start() -> str:
    """Start the profiler session; returns its (temporary) directory."""
    import jax

    d = tempfile.mkdtemp(prefix="bst-xplane-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    return d


def stop_and_reduce(directory: str, ring: list[dict]) -> dict | None:
    """Stop the session and reduce its xplane against the ring's events;
    the raw trace is deleted. None when the profiler wrote nothing."""
    import jax

    try:
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        return reduce_planes(load_planes(paths[0]), ring) if paths else None
    finally:
        shutil.rmtree(directory, ignore_errors=True)


def load_planes(path: str) -> dict:
    """What the reduction reads of an xplane file: per device plane the
    module and op events ``(name, start_ns, dur_ns)``, and of the host
    planes the annotations that carry a span id ``(id, start_ns)``."""
    from jax.profiler import ProfileData

    devices: dict[str, dict] = {}
    anchors: list[tuple[int, float]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            dev = plane.name.split(":")[-1].split()[0]
            lines = {ln.name: ln for ln in plane.lines}
            devices[dev] = {
                key: [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in lines[name].events] if name in lines else []
                for key, name in (("modules", MODULES_LINE),
                                  ("ops", OPS_LINE))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    # only program spans have a dot in their name: skip the
                    # runtime's own events without walking their stats
                    if "." not in ev.name or "::" in ev.name:
                        continue
                    for key, value in ev.stats:
                        if key == "id":
                            anchors.append((int(value), float(ev.start_ns)))
    return {"devices": devices, "anchors": anchors}


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def module_name(event_name: str) -> str:
    """``jit_pcm_peaks(7300973694350408273)`` -> ``jit_pcm_peaks``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def _clock(anchors: list, ring: list[dict]) -> dict:
    """Offset (ns) that puts the profiler's clock on the ring's, from
    every span that is in both, and what the join leaves over."""
    began = {ev["id"]: ev["ts"] for ev in ring if ev["ph"] == "B"}
    deltas = [(began[i] * 1e9, began[i] * 1e9 - start)
              for i, start in anchors if i in began]
    if not deltas:
        return {"anchors": 0, "offset_ns": 0.0}
    deltas.sort()
    offset = statistics.median(d for _t, d in deltas)
    resid = sorted(abs(d - offset) for _t, d in deltas)
    tenth = max(1, len(deltas) // 10)
    drift = (statistics.median(d for _t, d in deltas[-tenth:])
             - statistics.median(d for _t, d in deltas[:tenth]))
    return {"anchors": len(deltas), "offset_ns": offset,
            "residual_us": resid[int(0.95 * (len(resid) - 1))] / 1e3,
            "residual_max_us": resid[-1] / 1e3,
            "drift_us": drift / 1e3}


def reduce_planes(planes: dict, ring: list[dict]) -> dict | None:
    """Per device the module events and the busy union, in unix seconds
    on the ring's clock; the largest op totals; the clock join. None when
    no device ran an operation (an XLA:CPU run)."""
    clock = _clock(planes["anchors"], ring)
    off = clock["offset_ns"]
    devices, ops = {}, {}
    for dev, lines in planes["devices"].items():
        busy = _union([((s + off) / 1e9, (s + d + off) / 1e9)
                       for _n, s, d in lines["ops"] or lines["modules"]])
        if not busy:
            continue
        devices[dev] = {
            "modules": [(module_name(n), (s + off) / 1e9,
                         (s + d + off) / 1e9)
                        for n, s, d in lines["modules"]],
            "busy": [tuple(iv) for iv in busy]}
        for n, _s, d in lines["ops"]:
            head = n.split(" = ", 1)[0].lstrip("%")
            ops[head] = ops.get(head, 0.0) + d / 1e9
    if not devices:
        return None
    return {"devices": devices, "clock": clock,
            "top_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:_TOP_OPS]}


def module_totals(reduced: dict) -> dict:
    """{module name: [calls, seconds]} over all devices."""
    out: dict = {}
    for lines in reduced["devices"].values():
        for name, a, b in lines["modules"]:
            m = out.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += b - a
    return out


def perfetto(reduced: dict, pid: int) -> tuple[list, list, dict]:
    """(track metadata, ``X`` events, what goes into the file's ``bst``
    metadata) of a reduction, for ``trace.export``."""
    meta, events, busy_s = [], [], {}
    for k, (dev, lines) in enumerate(sorted(reduced["devices"].items())):
        for j, (kind, rows) in enumerate((
                ("modules", lines["modules"]),
                ("busy", [("xla.busy", a, b) for a, b in lines["busy"]]))):
            tid = _XLA_TID_BASE + 2 * k + j
            meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                         "tid": tid, "args": {
                             "name": f"device {dev} {TRACK_TAG} {kind}"}})
            meta.append({"ph": "M", "name": "thread_sort_index", "pid": pid,
                         "tid": tid, "args": {"sort_index": tid}})
            for name, a, b in rows:
                events.append({"name": name, "cat": "xla." + kind, "ph": "X",
                               "ts": round(a * 1e6, 3),
                               "dur": round((b - a) * 1e6, 3), "pid": pid,
                               "tid": tid, "args": {"device": dev}})
        busy_s[dev] = round(sum(b - a for a, b in lines["busy"]), 6)
    clock = reduced["clock"]
    return meta, events, {
        "device": {
            "busy_s": busy_s,
            "modules": {k: [n, round(s, 6)]
                        for k, (n, s) in module_totals(reduced).items()},
            "top_ops": [[k, round(s, 6)] for k, s in reduced["top_ops"]]},
        "clock_anchors": clock["anchors"],
        **{"clock_" + k: round(clock[k], 1)
           for k in ("residual_us", "residual_max_us", "drift_us")
           if k in clock}}
