"""One run of one cell:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (backend, seeded fixture, one warm pass that compiles or loads every
program the cell uses), then a window of whole passes, then the comparison
that decides ``correct`` on what the timed passes stored. The last line of
standard output is the result. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result; ``--rehearse`` runs
the cell's toy size on whatever JAX finds and says so in the line — a test
of this harness, never a measurement. ``--control`` puts the reference in
the next lower precision in the program's place (no program, no chip, no
window) and holds it to the same limits: it has to come out not correct.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse             # noqa: E402
import importlib.util       # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import statistics           # noqa: E402
import sys                  # noqa: E402

from . import files, fixtures, readers, window       # noqa: E402
from .reference.fixture import Acquisition           # noqa: E402


def say(msg: str) -> None:
    print(f"[bench {time.time() - T_PROCESS:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def load_cell(name: str, rehearse: bool) -> dict:
    """The cell with its configuration and traffic; at toy size where
    ``rehearse``."""
    cell = files.cell(name)
    config = files.config(cell["config"])
    traffic = files.traffic(cell["traffic"])
    params = dict(config["fixture"])
    if rehearse:
        cell = {**cell, **cell.get("rehearsal", {})}
        traffic = {**traffic, "options": {
            **traffic["options"], **cell.get("options_override", {})}}
        params.update(config["rehearsal_fixture"])
        # a toy volume would fit the composite driver that no acquisition
        # takes: the rehearsal may hold the program to the cell's driver
        os.environ.update(cell.get("env", {}))
    return {"name": name, "cell": cell, "config": config, "traffic": traffic,
            "fixture_params": params, "chips": int(cell["chips"])}


def build_stage(job: dict, fixture_dir: str, work_dir: str, seed: int,
                chips: int):
    """The cell's stage adapter over one seeded fixture."""
    return files.stage(job["traffic"]["stage"]).Stage({
        "cell": job["cell"], "config": job["config"],
        "traffic": job["traffic"],
        "acq": Acquisition(job["fixture_params"], seed),
        "fixture_dir": fixture_dir, "work_dir": work_dir, "chips": chips,
        "seed": seed})


def compile_listener() -> dict:
    """Count the programs JAX builds or loads from its cache."""
    import jax.monitoring

    seen = {"n": 0}

    def on_duration(event: str, _seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def cache_entries() -> int:
    import jax

    d = jax.config.jax_compilation_cache_dir
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


def judge(numbers: dict, limits: dict, whole: bool = True
          ) -> tuple[dict, bool]:
    """Each number that has a limit, beside it, and whether none is over.
    A run of the program has to bring a number for every limit (``whole``);
    the control brings those its precision can move."""
    if whole and set(limits) - set(numbers):
        raise KeyError(f"no number for {sorted(set(limits) - set(numbers))}")
    compared = {k: {"value": numbers[k], "limit": lim}
                for k, lim in limits.items() if k in numbers}
    return compared, all(c["value"] <= c["limit"] for c in compared.values())


def report(compared: dict) -> None:
    """Each number compared beside its limit, last on standard error."""
    for k, c in compared.items():
        print(f"compared {k} = {c['value']!r} (limit {c['limit']!r})"
              + ("" if c["value"] <= c["limit"] else "  <-- over"),
              file=sys.stderr)
    sys.stderr.flush()


def control(args, job: dict) -> int:
    """The cell's control through the comparison that decides ``correct``:
    the stage adapter's reference in the next lower precision, at the
    cell's own size, from the seed. Plain numpy: it needs no chip."""
    stage = build_stage(job, "", "", args.seed, job["chips"])
    compared, correct = judge(stage.control(), job["cell"]["limits"],
                              whole=False)
    report(compared)
    print(json.dumps({"control": True, "correct": correct,
                      "compared": compared}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on any platform: tests this harness, "
                         "measures nothing")
    ap.add_argument("--control", action="store_true",
                    help="the lower-precision reference in the program's "
                         "place, held to the cell's limits: not correct")
    args = ap.parse_args(argv)
    job = load_cell(args.workload, args.rehearse)
    if args.control:
        return control(args, job)
    if args.seconds is None:
        ap.error("--seconds is required")
    if importlib.util.find_spec("bigstitcher_spark_tpu") is None:
        say("no bigstitcher_spark_tpu package beside benchmark/: nothing "
            "to measure. No result.")
        return 5
    fixture = fixtures.SeededFixture(job["fixture_params"], args.seed)
    work_dir = os.path.join(fixtures.CACHE, "work",
                            f"{args.workload}-{args.seed}-{os.getpid()}")
    try:
        return measure(args, job, fixture, work_dir)
    finally:
        fixture.abandon()
        shutil.rmtree(work_dir, ignore_errors=True)


def measure(args, job: dict, fixture, work_dir: str) -> int:
    cell, traffic = job["cell"], job["traffic"]

    # ---- set-up: the backend comes up while the fixture child works
    import jax

    import bigstitcher_spark_tpu  # noqa: F401  (places the compile cache)
    from bigstitcher_spark_tpu import observe, profiling

    from . import trace_reduce

    devices = jax.devices()
    chips = job["chips"]
    if args.rehearse:
        chips = min(chips, len(devices))
    elif devices[0].platform != "tpu" or len(devices) < chips:
        say(f"needs {chips} TPU chip(s); JAX found {len(devices)} x "
            f"{devices[0].platform}. No result.")
        return 3
    used = devices[:chips]
    compiles = compile_listener()
    t_backend = time.time()
    fixture.wait()
    t_fixture = time.time()
    os.makedirs(work_dir)
    stage = build_stage(job, fixture.path, work_dir, args.seed, chips)
    # stage tables (which driver ran) need the program's telemetry on; the
    # span table and the ring only in the traced run
    observe.configure(os.path.join(work_dir, "telemetry"),
                      profile=bool(args.trace))
    if args.trace:
        observe.trace.configure()
    warm = stage.run_pass(-1)
    setup_s = time.time() - T_PROCESS
    clocks = {"setup_backend_s": t_backend - T_PROCESS,
              "setup_fixture_s": t_fixture - t_backend,
              "setup_warm_s": setup_s - (t_fixture - T_PROCESS)}
    say(f"set-up {setup_s:.2f} s ({clocks}); driver {warm['path']!r}")

    # ---- the window
    registry = observe.metrics.get_registry()
    entries0, compiles0 = cache_entries(), compiles["n"]
    profiling.get().reset()
    counters0 = registry.snapshot()
    trace_dir = os.path.join(work_dir, "xplane")
    anchor_ns = trace_reduce.start(trace_dir) if args.trace else None
    t_window = time.time()
    win = window.run_window(stage.run_pass, args.seconds)
    if args.trace:
        trace_reduce.stop()
    passes, window_s = win["passes"], win["window_s"]
    counters = registry.snapshot_delta(counters0)
    spans = {k: s.total_s for k, s in profiling.get().stats().items()}
    ring = observe.trace.snapshot() if args.trace else []
    compiled = max(compiles["n"] - compiles0, cache_entries() - entries0)
    rate = sum(p["work"] for p in passes) * stage.unit_scale / window_s
    say(f"window {window_s:.2f} s, {len(passes)} passes "
        f"({', '.join(format(p['seconds'], '.2f') for p in passes)} s), "
        f"{traffic['end_to_end']} {rate:.4f}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    stage.release()
    stored = sum(os.path.getsize(os.path.join(dp, f))
                 for dp, _d, fs in os.walk(work_dir) for f in fs)
    say(f"the run's passes left {stored / 1e6:.0f} MB in {work_dir} "
        "(deleted when the run ends)")

    # ---- correct: what the timed passes stored, against the reference
    t_check = time.time()
    numbers = stage.check(passes)
    if "expect_path" in cell:
        numbers["path_mismatch"] = float(sum(
            p["path"] != cell["expect_path"] for p in passes))
    compared, correct = judge(numbers, cell["limits"])
    noted = {k: v for k, v in numbers.items() if k not in compared}
    say(f"comparison took {time.time() - t_check:.1f} s; not held to a "
        f"limit: {noted}")

    metrics = {}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(passes), "failed": 0,
              "metrics": metrics, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if not args.trace:
        unit = next(m["unit"] for m in files.manifest()["end_to_end"]
                    if m["name"] == traffic["end_to_end"])
        metrics[traffic["end_to_end"]] = {"value": rate, "unit": unit}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        seconds = [p["seconds"] for p in passes]
        values = {**clocks, "compiles_in_window": float(compiled),
                  "pass_cv_pct": (100.0 * statistics.pstdev(seconds)
                                  / statistics.fmean(seconds)
                                  if len(seconds) > 1 else None),
                  "hbm_peak_GB": peak / 1e9 if peak else None,
                  "traced_rate": rate}
        ctx = {"values": values, "window_s": window_s, "spans": spans,
               "counters": counters, "calls": stage.kernel_calls(passes)}
        reduced = trace_reduce.reduce_dir(
            trace_dir, anchor_ns, t_window, t_window + window_s, ring)
        if reduced is not None:
            ctx["modules"] = reduced["modules"]
            values["device_idle_pct"] = 100.0 * (
                1.0 - reduced["busy_s_max"] / window_s)
            device["busy_s"] = reduced["busy_s_mean"]
            device["window_s"] = window_s
            result["breakdown"] = reduced["breakdown"]
            if used[0].platform == "tpu":
                ctx["peaks"] = files.peaks(used[0].device_kind)
        for name in cell["per_layer"]:
            value = readers.read(name, ctx)
            if value is not None:
                metrics[name] = {"value": value,
                                 "unit": files.metric(name)["unit"]}
    # each number compared beside its limit: last on standard error, and
    # last in the line
    result["compared"] = compared
    report(compared)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
