"""``bst trace-report``: the questions span AGGREGATES cannot answer.

``profiling`` can say `fusion.d2h` totalled 13.8 s; only the timeline can
say whether those seconds hid under `fusion.write`, how long each device
sat idle between dispatches, and which per-block causal chain
(dispatch → kernel → d2h → write) ended the run. This module turns a
flight-recorder trace (``observe/trace.py`` Perfetto JSON, single file or
the ``telemetry-merge`` fold of a pod run) into exactly those numbers:

- per-stage wall-clock decomposed into **compute / d2h / write / idle**
  (union time per category, so N overlapping writes count once);
- **pairwise overlap** seconds + percentages between the categories —
  the direct measurement of "does D2H overlap the writes", the 0.64×
  frontier question (ROADMAP "Known gap");
- per-track (device / writer thread) busy/idle and the largest idle
  gaps — the scheduler-shaped holes items 2–3 must fill;
- the **device's own busy and idle time** where the trace carries
  ``device N (XLA)`` tracks (``--trace-device``), each long gap named
  after the span open on every host thread meanwhile (innermost by the
  events' ``parent``); without those tracks every "compute" number is
  host time inside dispatch and sync calls and is labelled
  **host-inferred**;
- the **critical path**: per-item causal chains reassembled from the
  events' work-item identity, the chain that finishes last, and its
  top-k blocking segments by duration.

Everything here is pure computation over the parsed JSON — the CLI shim
lives in ``cli/telemetry_tools.py``.
"""

from __future__ import annotations

import glob
import json
import os


def _category(name: str) -> str:
    if name.endswith(".d2h"):
        return "d2h"
    if name.endswith(".write"):
        return "write"
    if name.endswith(".kernel") or name.endswith(".kernel_sync") \
            or name.endswith(".dispatch"):
        return "compute"
    if name.endswith(".prefetch") or name.endswith(".extract"):
        return "read"
    if name.endswith(".h2d_tiles") or name.endswith(".h2d"):
        return "h2d"
    if name.endswith((".pack", ".plan", ".store")):
        return "host"
    return "other"


def _group(name: str, args: dict) -> str:
    """Report group for one interval: the span-name prefix, except the
    generic layers (mesh loop, retry wrapper, pair scheduler) which
    borrow their stage label's first token — ``mesh.d2h`` inside a
    ``"fusion batch …"`` stage belongs to the fusion story."""
    head = name.split(".")[0]
    if head in ("mesh", "retry", "pair", "barrier"):
        stage = str(args.get("stage") or "")
        tok = stage.split(" ")[0].split(".")[0].split("-")[0]
        return tok or head
    return head


def load_events(path: str) -> tuple[list[dict], dict]:
    """Flat event list + metadata from a trace file, a telemetry dir
    (preferring ``merged-trace.json``, else every ``trace-*.json``), or a
    merged trace."""
    paths: list[str]
    if os.path.isdir(path):
        merged = os.path.join(path, "merged-trace.json")
        per_proc = sorted(glob.glob(os.path.join(path, "trace-*-of-*.json")))
        # a merged fold is preferred — unless a per-process trace is NEWER
        # (the dir was reused for another run after the last telemetry-merge),
        # in which case the stale merge would silently report the old run
        if os.path.exists(merged) and not any(
                os.path.getmtime(p) > os.path.getmtime(merged)
                for p in per_proc):
            paths = [merged]
        else:
            paths = per_proc
        if not paths:
            raise FileNotFoundError(
                f"no merged-trace.json or trace-*.json under {path}")
    else:
        paths = [path]
    events: list[dict] = []
    meta: dict = {"files": [os.path.basename(p) for p in paths],
                  "recorded": 0, "dropped": 0,
                  "unaligned_processes": [], "clock": {}}
    for p in paths:
        with open(p, encoding="utf-8") as f:
            doc = json.load(f)
        b = doc.get("bst", {})
        meta["recorded"] += int(b.get("recorded") or 0)
        meta["dropped"] += int(b.get("dropped") or 0)
        # the device-trace clock join of a --trace-device run
        meta["clock"].update({k: v for k, v in b.items()
                              if k.startswith("clock_")
                              and not isinstance(v, dict)})
        meta["unaligned_processes"] += b.get("unaligned_processes") or []
        events.extend(doc.get("traceEvents", ()))
    # concatenating several PER-PROCESS traces puts unaligned host clocks
    # on one timeline — every cross-process number (overlap, idle,
    # critical path) is then skewed; the CLI warns and points at
    # telemetry-merge, which barrier-aligns the clocks first
    meta["unmerged"] = len(paths) > 1
    return events, meta


def build_intervals(events: list[dict]) -> tuple[list[dict], dict]:
    """Pair B/E events into intervals (seconds); returns (intervals,
    track_names). Pairing is a per-(pid, tid, name) LIFO stack — Chrome
    ``B``/``E`` semantics; unmatched begins (ring overflow tore their
    end off) are dropped rather than invented."""
    stacks: dict[tuple, list] = {}
    track_names: dict[tuple, str] = {}
    out: list[dict] = []
    for ev in events:
        ph = ev.get("ph")
        if ph == "M":
            if ev.get("name") == "thread_name":
                track_names[(ev.get("pid", 0), ev.get("tid", 0))] = \
                    (ev.get("args") or {}).get("name", "")
            continue
        if ph not in ("B", "E", "X"):
            continue
        key = (ev.get("pid", 0), ev.get("tid", 0), ev.get("name"))
        ts = float(ev.get("ts", 0.0)) / 1e6
        if ph == "X":
            out.append({"name": ev.get("name"), "start": ts,
                        "end": ts + float(ev.get("dur", 0.0)) / 1e6,
                        "pid": key[0], "tid": key[1],
                        "args": ev.get("args") or {},
                        "xla": _is_xla(ev)})
        elif ph == "B":
            stacks.setdefault(key, []).append((ts, ev.get("args") or {}))
        else:
            stack = stacks.get(key)
            if stack:
                t0, args = stack.pop()
                if ts < t0:
                    continue   # wall clock stepped backwards (NTP/suspend)
                               # mid-span: drop rather than go negative
                merged = {**args, **(ev.get("args") or {})}
                out.append({"name": key[2], "start": t0, "end": ts,
                            "pid": key[0], "tid": key[1], "args": merged})
    out.sort(key=lambda iv: (iv["start"], iv["end"]))
    return out, track_names


def _union(ivs: list[dict]) -> list[tuple[float, float]]:
    if not ivs:
        return []
    spans = sorted((iv["start"], iv["end"]) for iv in ivs)
    merged = [list(spans[0])]
    for s, e in spans[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _total(union: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in union)


def _intersect(a: list[tuple[float, float]],
               b: list[tuple[float, float]]) -> float:
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            out += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _pct(x: float, denom: float) -> float:
    return round(100.0 * x / denom, 1) if denom > 0 else 0.0


def analyze(path: str, top: int = 5) -> dict:
    """The decomposition as DATA: load a trace (file or telemetry dir)
    and return the :func:`build_report` dict — the machine face of
    ``bst trace-report`` that `bst tune advise` (and any script) consumes
    without parsing the rendered table. The report additionally carries
    the resolved source ``files``."""
    events, meta = load_events(path)
    rep = build_report(events, meta, top=top)
    rep["files"] = meta.get("files", [])
    return rep


def _is_xla(ev: dict) -> bool:
    """An event of a ``device N (XLA)`` track (observe/devicetrace.py)."""
    return str(ev.get("cat", "")).startswith("xla.")


def _open_on_each_thread(intervals: list[dict], track_names: dict,
                         at: float) -> dict:
    """{host thread: innermost span open at ``at``}. Innermost by the
    events' ``parent``: of the spans of one thread that hold the instant,
    the one that is no other's parent (traces older than the ids: the one
    that began last)."""
    holding: dict[tuple, list[dict]] = {}
    for iv in intervals:
        # a span attributed to a device is drawn on that device's ring
        # track, not on the thread that ran it: no host thread's answer
        if iv["start"] <= at <= iv["end"] and "device" not in iv["args"]:
            holding.setdefault((iv["pid"], iv["tid"]), []).append(iv)
    out = {}
    for key, ivs in holding.items():
        parents = {iv["args"].get("parent") for iv in ivs}
        leaves = [iv for iv in ivs if iv["args"].get("id") not in parents
                  or iv["args"].get("id") is None]
        out[track_names.get(key) or f"tid {key[1]}"] = max(
            leaves or ivs, key=lambda iv: iv["start"])["name"]
    return out


def _device_report(xla: list[dict], host: list[dict], track_names: dict,
                   t0: float, t1: float, top: int) -> list[dict]:
    """Per device: busy (the union of its XLA ops) and idle over the
    trace's wall clock, its modules, and its longest gaps with what every
    host thread was in meanwhile."""
    by_dev: dict[str, dict] = {}
    for iv in xla:
        d = by_dev.setdefault(str(iv["args"].get("device")),
                              {"busy": [], "modules": {}})
        if iv["name"] == "xla.busy":
            d["busy"].append(iv)
        else:
            m = d["modules"].setdefault(iv["name"], [0, 0.0])
            m[0] += 1
            m[1] += iv["end"] - iv["start"]
    out = []
    for dev, d in sorted(by_dev.items()):
        busy = [(max(a, t0), min(b, t1)) for a, b in _union(d["busy"])
                if min(b, t1) > max(a, t0)]
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        gaps = sorted(((b - a, a) for a, b in zip(edges[0::2], edges[1::2])
                       if b > a), reverse=True)[:max(1, top)]
        out.append({
            "device": dev,
            "busy_s": round(_total(busy), 6),
            "busy_pct": round(100.0 * _total(busy) / (t1 - t0), 2)
            if t1 > t0 else 0.0,
            "modules": {k: [n, round(s, 6)]
                        for k, (n, s) in sorted(d["modules"].items())},
            "largest_gaps": [
                {"seconds": round(g, 6), "at_s": round(a - t0, 6),
                 "open": _open_on_each_thread(host, track_names, a + g / 2)}
                for g, a in gaps]})
    return out


def span_tree(intervals: list[dict]) -> list[dict]:
    """The spans as a tree, by the events' ``id``/``parent``: one row per
    path of names from a root, with count, total seconds and SELF seconds
    (a span's duration less the union of its direct children's intervals,
    whatever thread they ran on). Rows come parents first, siblings by
    total time. Empty for a trace recorded before the ids."""
    by_id = {iv["args"]["id"]: iv for iv in intervals
             if iv["args"].get("id") is not None}
    kids: dict = {}
    for iv in by_id.values():
        kids.setdefault(iv["args"].get("parent"), []).append(iv)
    rows: dict[tuple, list] = {}

    def walk(iv, path):
        path = path + (iv["name"],)
        mine = kids.get(iv["args"]["id"], [])
        covered = _total(_union(
            [{"start": max(c["start"], iv["start"]),
              "end": min(c["end"], iv["end"])} for c in mine
             if min(c["end"], iv["end"]) > max(c["start"], iv["start"])]))
        row = rows.setdefault(path, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += iv["end"] - iv["start"]
        row[2] += iv["end"] - iv["start"] - covered
        for c in mine:
            walk(c, path)

    for iv in by_id.values():
        if iv["args"].get("parent") not in by_id:
            walk(iv, ())
    out = []

    def emit(prefix):
        below = [p for p in rows if p[:-1] == prefix]
        for p in sorted(below, key=lambda p: -rows[p][1]):
            n, total, self_s = rows[p]
            out.append({"path": list(p), "count": n,
                        "total_s": round(total, 6),
                        "self_s": round(self_s, 6),
                        "leaf": not any(q[:-1] == p for q in rows)})
            emit(p)

    emit(())
    return out


def build_report(events: list[dict], meta: dict | None = None,
                 top: int = 5) -> dict:
    intervals, track_names = build_intervals(events)
    # the device's own timeline is reported apart: it is no host span
    xla = [iv for iv in intervals if iv.get("xla")]
    intervals = [iv for iv in intervals if not iv.get("xla")]
    rep: dict = {"events": len([e for e in events
                                if e.get("ph") in ("B", "E", "X", "i")]),
                 "intervals": len(intervals),
                 "recorded": (meta or {}).get("recorded", 0),
                 "dropped": (meta or {}).get("dropped", 0),
                 "stages": {}, "tracks": [],
                 "critical_path": None, "top_blocking": [],
                 "device_source": "xla" if xla else "host-inferred",
                 "clock": (meta or {}).get("clock") or {}}
    if not intervals:
        return rep
    t0 = min(iv["start"] for iv in intervals)
    t1 = max(iv["end"] for iv in intervals)
    rep["wall_s"] = round(t1 - t0, 6)
    # what the host alone would say of the device: the union of the spans
    # around dispatch and sync calls, over the wall clock
    rep["host_inferred_compute_pct"] = _pct(_total(_union(
        [iv for iv in intervals if _category(iv["name"]) == "compute"])),
        t1 - t0)
    if xla:
        rep["devices"] = _device_report(xla, intervals, track_names,
                                        t0, t1, top)
    rep["span_tree"] = span_tree(intervals)

    # -- per-stage category decomposition + pairwise overlap ---------------
    by_group: dict[str, list[dict]] = {}
    for iv in intervals:
        by_group.setdefault(_group(iv["name"], iv["args"]), []).append(iv)
    for group, ivs in sorted(by_group.items()):
        g0 = min(iv["start"] for iv in ivs)
        g1 = max(iv["end"] for iv in ivs)
        wall = g1 - g0
        unions = {}
        for cat in ("compute", "d2h", "write", "read", "h2d", "host",
                    "other"):
            unions[cat] = _union([iv for iv in ivs
                                  if _category(iv["name"]) == cat])
        busy = _union(ivs)
        entry = {
            "wall_s": round(wall, 6),
            "idle_s": round(max(0.0, wall - _total(busy)), 6),
            "idle_pct": _pct(max(0.0, wall - _total(busy)), wall),
            "overlap": {},
        }
        for cat in ("compute", "d2h", "write", "read", "h2d", "host"):
            tot = _total(unions[cat])
            if tot:
                entry[f"{cat}_s"] = round(tot, 6)
                entry[f"{cat}_pct"] = _pct(tot, wall)
        for a, b in (("d2h", "write"), ("compute", "d2h"),
                     ("compute", "write")):
            ta, tb = _total(unions[a]), _total(unions[b])
            if ta and tb:
                ov = _intersect(unions[a], unions[b])
                entry["overlap"][f"{a}_{b}"] = {
                    "seconds": round(ov, 6),
                    f"pct_of_{a}": _pct(ov, ta),
                    f"pct_of_{b}": _pct(ov, tb),
                }
        rep["stages"][group] = entry

    # -- per-track (device / thread) busy, idle, largest gaps --------------
    by_track: dict[tuple, list[dict]] = {}
    for iv in intervals:
        by_track.setdefault((iv["pid"], iv["tid"]), []).append(iv)
    for (pid, tid), ivs in sorted(by_track.items()):
        busy = _union(ivs)
        first, last = busy[0][0], busy[-1][1]
        span = last - first
        gaps = [(busy[i + 1][0] - busy[i][1], busy[i][1])
                for i in range(len(busy) - 1)]
        gaps.sort(reverse=True)
        rep["tracks"].append({
            "pid": pid, "tid": tid,
            "name": track_names.get((pid, tid)) or f"tid {tid}",
            "busy_s": round(_total(busy), 6),
            "span_s": round(span, 6),
            "util_pct": _pct(_total(busy), span),
            "largest_gaps": [{"seconds": round(g, 6),
                              "at_s": round(at - t0, 6)}
                             for g, at in gaps[:3] if g > 0],
        })

    # -- critical path over per-item causal chains -------------------------
    chains: dict[tuple, list[dict]] = {}
    for iv in intervals:
        item = iv["args"].get("item")
        if item is None or iv["name"] == "retry.attempt":
            continue   # the attempt wrapper CONTAINS the chain segments
        key = (_group(iv["name"], iv["args"]), json.dumps(item))
        chains.setdefault(key, []).append(iv)
    if chains:
        crit_key = max(chains, key=lambda k: max(iv["end"]
                                                 for iv in chains[k]))
        segs = sorted(chains[crit_key], key=lambda iv: iv["start"])
        path = []
        prev_end = None
        for iv in segs:
            if prev_end is not None and iv["start"] - prev_end > 1e-6:
                path.append({"name": "(wait)", "start_s":
                             round(prev_end - t0, 6),
                             "seconds": round(iv["start"] - prev_end, 6)})
            path.append({"name": iv["name"],
                         "start_s": round(iv["start"] - t0, 6),
                         "seconds": round(iv["end"] - iv["start"], 6)})
            prev_end = iv["end"] if prev_end is None \
                else max(prev_end, iv["end"])
        rep["critical_path"] = {
            "stage": crit_key[0],
            "item": json.loads(crit_key[1]),
            "total_s": round(max(iv["end"] for iv in segs)
                             - segs[0]["start"], 6),
            "ends_at_s": round(max(iv["end"] for iv in segs) - t0, 6),
            "segments": path,
        }
        rep["top_blocking"] = sorted(
            path, key=lambda s: -s["seconds"])[:max(1, top)]
    return rep


def render_report(rep: dict) -> str:
    lines = []
    lines.append(
        f"trace: {rep.get('wall_s', 0.0):.3f}s wall, "
        f"{rep['intervals']} interval(s) from {rep['events']} event(s)"
        + (f", {rep['dropped']} DROPPED by ring overflow"
           if rep.get("dropped") else ""))
    # the device's own numbers, where the trace has them; the host's
    # guess is printed beside them, and alone it is called what it is
    inferred = rep.get("device_source") != "xla"
    for d in rep.get("devices", ()):
        lines.append(
            f"device {d['device']} (XLA): busy {d['busy_s']:.3f}s "
            f"({d['busy_pct']:.2f}% of the wall clock), idle "
            f"{100.0 - d['busy_pct']:.2f}% | host-inferred compute "
            f"{rep.get('host_inferred_compute_pct', 0.0):.1f}%")
        for name, (n, s) in d["modules"].items():
            lines.append(f"  module {name}: {n} call(s), {s:.6f}s")
        for g in d["largest_gaps"]:
            held = ", ".join(f"{t}={n}" for t, n in sorted(g["open"].items()))
            lines.append(f"  gap {g['seconds']:.3f}s @{g['at_s']:.3f}s: "
                         + (held or "(no span open)"))
    clock = rep.get("clock") or {}
    if clock.get("clock_anchors"):
        lines.append(
            f"clock join: {clock['clock_anchors']} span anchors, residual "
            f"p95 {clock.get('clock_residual_us', 0.0):.1f}us (max "
            f"{clock.get('clock_residual_max_us', 0.0):.1f}us), drift "
            f"{clock.get('clock_drift_us', 0.0):.1f}us first tenth to last")
    if inferred and rep["stages"]:
        lines.append(
            "device numbers below are host-inferred: this trace has no "
            "device (XLA) tracks, so 'compute' is host time inside "
            "dispatch and sync calls "
            f"({rep.get('host_inferred_compute_pct', 0.0):.1f}% of the "
            "wall clock) — record with --trace-device for the device's own")
    for group, e in rep["stages"].items():
        parts = []
        # a stage's "compute" is the host's clock around dispatch and
        # sync calls whether or not the device's own tracks are there
        for cat, label in (("compute", "compute (host-inferred)"),
                           ("d2h", "d2h"),
                           ("write", "write"), ("read", "read"),
                           ("h2d", "h2d"), ("host", "host")):
            if f"{cat}_s" in e:
                parts.append(f"{label} {e[f'{cat}_s']:.3f}s "
                             f"({e[f'{cat}_pct']:.0f}%)")
        parts.append(f"idle {e['idle_s']:.3f}s ({e['idle_pct']:.0f}%)")
        lines.append(f"[{group}] wall {e['wall_s']:.3f}s: "
                     + " | ".join(parts))
        for pair, ov in e["overlap"].items():
            a, b = pair.split("_", 1)
            pa = ov.get(f"pct_of_{a}", 0.0)
            pb = ov.get(f"pct_of_{b}", 0.0)
            lines.append(f"  overlap {a}<->{b}: {ov['seconds']:.3f}s "
                         f"({pa:.0f}% of {a}, {pb:.0f}% of {b})")
    if rep["tracks"]:
        lines.append("tracks:")
        for t in rep["tracks"]:
            gaps = ", ".join(f"{g['seconds']:.3f}s @{g['at_s']:.3f}s"
                             for g in t["largest_gaps"]) or "none"
            lines.append(f"  p{t['pid']} {t['name']}: busy {t['busy_s']:.3f}s"
                         f" ({t['util_pct']:.0f}% of its {t['span_s']:.3f}s"
                         f" span), largest gaps: {gaps}")
    if rep.get("span_tree"):
        lines.append("span tree (count, total, self = total less the "
                     "union of the children):")
        for row in rep["span_tree"]:
            depth = len(row["path"]) - 1
            label = "  " * depth + row["path"][-1]
            lines.append(f"  {label:<40} {row['count']:>6} "
                         f"{row['total_s']:>10.3f}s "
                         f"self {row['self_s']:>9.3f}s"
                         + (f" ({_pct(row['self_s'], row['total_s']):.1f}%"
                            " of the root has no named child)"
                            if depth == 0 and not row["leaf"] else ""))
    cp = rep.get("critical_path")
    if cp:
        lines.append(f"critical path [{cp['stage']} item {cp['item']}]: "
                     f"{cp['total_s']:.3f}s, ends at "
                     f"+{cp['ends_at_s']:.3f}s")
        lines.append("  " + " -> ".join(
            f"{s['name']} {s['seconds']:.3f}s" for s in cp["segments"]))
        lines.append("top blocking segments:")
        for i, s in enumerate(rep["top_blocking"], 1):
            lines.append(f"  {i}. {s['name']} {s['seconds']:.3f}s "
                         f"(at +{s['start_s']:.3f}s)")
    return "\n".join(lines)
