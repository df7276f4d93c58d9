"""From a JAX profiler trace (xplane) to numbers: device busy time as the
union of the intervals in which an operation ran, device time per jitted
module, the operations that took most time, and the longest idle gaps, each
named after the program span that was open on the host meanwhile.

The profiler's clock is tied to the host's by an annotation the harness
writes right after the trace starts (``bench.anchor``, stamped with
``time.time_ns()``): wherever the profiler's zero lies, the anchor's start
in the trace is that instant.

``reduce_planes`` works on plain tuples so that it can be checked on a
small recorded trace (tests/benchmark_tests) and on hand-made ones.
"""

from __future__ import annotations

import glob
import os
import re
import time

ANCHOR = "bench.anchor"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def start(trace_dir: str) -> int:
    """Start tracing with the python tracer off (a 30 s window of it is
    hundreds of MB and slows the host), write the anchor and return its
    instant on the host's clock (unix ns)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(ANCHOR):
        anchor_unix_ns = time.time_ns()
        time.sleep(0.001)
    return anchor_unix_ns


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load_planes(path: str) -> list:
    """[(plane name, [(line name, [(event name, start_ns, dur_ns)])])]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(plane.name, [(line.name, [(ev.name, float(ev.start_ns),
                                       float(ev.duration_ns))
                                      for ev in line.events])
                          for line in plane.lines])
            for plane in data.planes]


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def open_spans(ring: list) -> list:
    """[(name, start_s, end_s)] from the program's begin/end ring (unix
    seconds); spans still open at the end are dropped."""
    stacks: dict = {}
    out = []
    for ev in ring:
        key = (ev["tid"], ev["name"])
        if ev["ph"] == "B":
            stacks.setdefault(key, []).append(ev["ts"])
        elif ev["ph"] == "E" and stacks.get(key):
            out.append((ev["name"], stacks[key].pop(), ev["ts"]))
    return out


def _span_over(spans: list, a: float, b: float) -> str:
    """The innermost span open at the middle of [a, b]: of those that
    hold that instant, the one that began last."""
    mid = (a + b) / 2
    holding = [(s, name) for name, s, e in spans if s <= mid <= e]
    return max(holding)[1] if holding else "(no span)"


def reduce_planes(planes: list, anchor_unix_ns: float | None, t0: float,
                  t1: float, ring: list) -> dict | None:
    """Reduce to the window [t0, t1] (unix seconds). None if the trace has
    no device plane with operations (an XLA:CPU rehearsal)."""
    offset_ns = 0.0
    if anchor_unix_ns is not None:
        found = [s for _p, lines in planes for _l, evs in lines
                 for n, s, _d in evs if n == ANCHOR]
        if found:
            offset_ns = anchor_unix_ns - min(found)
    lo, hi = t0 * 1e9, t1 * 1e9
    spans = open_spans(ring)
    busy, modules, ops, gaps = {}, {}, {}, []
    for pname, lines in planes:
        if not pname.startswith("/device:"):
            continue
        dev = pname.split(":")[-1].split()[0]
        by_line = dict(lines)
        op_events = [e for ln in OPS_LINES for e in by_line.get(ln, [])]
        mod_events = [e for ln in MODULE_LINES for e in by_line.get(ln, [])]
        clipped = []
        for name, s, d in op_events or mod_events:
            a, b = max(s + offset_ns, lo), min(s + d + offset_ns, hi)
            if b > a:
                clipped.append((a, b))
                if op_events:
                    ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
        if not clipped:
            continue
        merged = _union(clipped)
        busy[dev] = sum(b - a for a, b in merged) / 1e9
        for name, s, d in mod_events:
            a, b = max(s + offset_ns, lo), min(s + d + offset_ns, hi)
            if b > a:
                m = modules.setdefault(_module_name(name), [0, 0.0])
                m[0] += 1
                m[1] += (b - a) / 1e9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((f"device{dev}:"
                             + _span_over(spans, a / 1e9, b / 1e9),
                             (b - a) / 1e9))
    if not busy:
        return None
    top = sorted([(f"module:{k}", v[1]) for k, v in modules.items()]
                 + list(ops.items()), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s_mean": sum(busy.values()) / len(busy),
        "busy_s_max": max(busy.values()),
        "busy_s": busy,
        "modules": modules,
        "breakdown": {
            "device_ops": [[re.sub(r"[^A-Za-z0-9_.:-]", "_", k)[:64], v]
                           for k, v in top],
            "idle_gaps": [list(g) for g in
                          sorted(gaps, key=lambda g: -g[1])[:10]]},
    }


def reduce_dir(trace_dir: str, anchor_unix_ns: float | None, t0: float,
               t1: float, ring: list) -> dict | None:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return None
    return reduce_planes(load_planes(paths[0]), anchor_unix_ns, t0, t1, ring)
