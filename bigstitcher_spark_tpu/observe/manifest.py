"""Per-run manifests and the multi-process merge report.

One manifest per process per run
(``manifest-{process_index:05d}-of-{process_count:05d}.json``), written
next to the event log when the run finishes: CLI argv + resolved config,
world size, device kind/count, the span-stat table from ``profiling``,
the run's metric deltas (IO bytes, transfer bytes, retry rounds, block
counters) and per-stage summaries (done/total, blocks/s, ETA-vs-actual).
``merge_run`` folds N per-process files (a pod run) into one report — the
role of the Spark history server's application summary.
"""

from __future__ import annotations

import glob
import json
import os
import time

from . import process as _process

SCHEMA = "bst-run-manifest/1"
MERGED_SCHEMA = "bst-merged-report/1"


def manifest_name(process_index: int, process_count: int) -> str:
    return f"manifest-{process_index:05d}-of-{process_count:05d}.json"


def device_info() -> dict:
    """Device inventory, plus what the run chose on its way to the device:
    peak device memory, the compile cache and its entry count, whether
    chunk IO went through the native codec. (The in-flight budgets the
    run's dispatch windows were given, and their sources, are in the
    manifest's ``metrics``: ``bst_inflight_windows_total{source}``.)
    ``chip_smoke.py`` reads these to tell an accelerator run from a
    degraded one. A backend that cannot initialize is recorded as
    ``{"error": ...}`` — never as an empty inventory."""
    import jax

    from ..io import native_blockio

    try:
        devs = jax.devices()
    except RuntimeError as e:   # a failing run still gets its manifest
        return {"error": repr(e)}
    local = jax.local_devices()
    cache_dir = jax.config.jax_compilation_cache_dir
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "local_device_count": len(local),
        "device_count": len(devs),
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in local],
        "compile_cache": {
            "dir": cache_dir,
            "entries": (len(os.listdir(cache_dir))
                        if os.path.isdir(cache_dir) else 0)},
        "native_codec_loaded": native_blockio.loaded(),
    }


def _json_default(o):
    if hasattr(o, "tolist"):
        return o.tolist()
    return str(o)


def write_manifest(
    directory: str,
    *,
    tool: str | None,
    argv: list[str],
    params: dict | None,
    world: tuple[int, int],
    started_at: float,
    seconds: float,
    status: str,
    error: str | None,
    spans: dict,
    metrics_delta: dict,
    stages: list[dict],
    events_file: str | None,
    trace_file: str | None = None,
) -> str:
    pi, pc = world
    doc = {
        "schema": SCHEMA,
        "tool": tool,
        "argv": list(argv),
        "params": params or {},
        "world": {"process_index": pi, "process_count": pc},
        "device": device_info(),
        "process": _process.summary(),
        "started_at": time.strftime("%Y-%m-%dT%H:%M:%S",
                                    time.localtime(started_at)),
        "seconds": round(seconds, 3),
        "status": status,
        "spans": spans,
        "metrics": metrics_delta,
        "stages": stages,
        "events_file": events_file,
    }
    if trace_file:
        doc["trace_file"] = trace_file
    if error:
        doc["error"] = error
    path = os.path.join(directory, manifest_name(pi, pc))
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, default=_json_default)
        f.write("\n")
    os.replace(tmp, path)
    return path


def _merge_numeric(dst: dict, src: dict) -> None:
    for k, v in src.items():
        if isinstance(v, dict):
            node = dst.setdefault(k, {})
            if isinstance(node, dict):
                _merge_numeric(node, v)
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            dst[k] = dst.get(k, 0) + v


def _merge_spans(dst: dict, src: dict) -> None:
    for name, s in src.items():
        d = dst.setdefault(name, {"count": 0, "total_s": 0.0, "max_s": 0.0})
        d["count"] += s.get("count", 0)
        d["total_s"] = round(d["total_s"] + s.get("total_s", 0.0), 3)
        d["max_s"] = max(d["max_s"], s.get("max_s", 0.0))
        if "min_s" in s:   # pre-min_s manifests merge without it
            d["min_s"] = min(d.get("min_s", s["min_s"]), s["min_s"])


def merge_run(directory: str) -> dict:
    """Fold every per-process manifest + event log in ``directory`` into
    one report: summed counters, merged span table, per-stage totals and
    a failure breakdown by exception class."""
    from . import events as ev

    man_paths = sorted(glob.glob(os.path.join(directory, "manifest-*.json")))
    ev_paths = sorted(glob.glob(os.path.join(directory, "events-*.jsonl")))
    if not man_paths and not ev_paths:
        raise FileNotFoundError(
            f"no manifest-*.json or events-*.jsonl under {directory}")

    processes: list[dict] = []
    metrics_sum: dict = {}
    spans: dict = {}
    stages: dict[str, dict] = {}
    wall_s = 0.0
    for p in man_paths:
        with open(p, encoding="utf-8") as f:
            m = json.load(f)
        w = m.get("world", {})
        processes.append({
            "process_index": w.get("process_index"),
            "process_count": w.get("process_count"),
            "tool": m.get("tool"),
            "status": m.get("status"),
            "seconds": m.get("seconds"),
            "device": m.get("device", {}),
            "manifest": os.path.basename(p),
        })
        wall_s = max(wall_s, float(m.get("seconds") or 0.0))
        _merge_numeric(metrics_sum, m.get("metrics", {}))
        _merge_spans(spans, m.get("spans", {}))
        for rec in m.get("stages", []):
            name = rec.get("stage", "?")
            d = stages.setdefault(name, {"stage": name})
            _merge_numeric(d, {k: v for k, v in rec.items() if k != "stage"})

    event_count = 0
    failures_by_exception: dict[str, int] = {}
    for p in ev_paths:
        for rec in ev.iter_events(p):
            event_count += 1
            if rec.get("type") == "block.fail" and rec.get("exception"):
                exc = rec["exception"]
                failures_by_exception[exc] = (
                    failures_by_exception.get(exc, 0) + 1)

    total_done = sum(int(s.get("done") or s.get("blocks") or 0)
                     for s in stages.values())
    report = {
        "schema": MERGED_SCHEMA,
        "directory": os.path.abspath(directory),
        "processes": processes,
        "process_count": (max((p["process_count"] or 1 for p in processes),
                              default=len(ev_paths) or 1)),
        "wall_clock_s": round(wall_s, 3),
        "items_done": total_done,
        "items_per_s": round(total_done / wall_s, 3) if wall_s else None,
        "stages": sorted(stages.values(), key=lambda s: s["stage"]),
        "spans": spans,
        "metrics": metrics_sum,
        "events": event_count,
        "failures_by_exception": failures_by_exception,
    }
    return report
