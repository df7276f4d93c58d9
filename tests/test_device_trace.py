"""The device's own timeline in the program's recorder (ISSUE 25, part C),
and the join of the two clocks.

- a CPU ``jax.profiler`` session of two seconds and more: one offset puts
  every span's ``TraceAnnotation`` inside its ring span, in the ring's
  order, and both joins (the benchmark's ONE anchor, the program's median
  over every span) lie within what the spans bracket — held by order and
  containment, not by milliseconds, so a loaded machine does not fail it;
- the program-side reduction of the recorded chip trace
  ``tests/benchmark_tests/data/v5e_small.xplane.pb`` (read only) gives
  the module counts ``test_bm_trace_reduce.py`` expects of it;
- ``trace-report`` reads device busy, idle and gaps from ``(XLA)`` tracks
  and says "host-inferred" where there are none;
- the jitted kernels' module names still match the patterns of the
  benchmark's kernel metrics, so a rename fails here instead of silencing
  a metric on the chip.
"""

import glob
import json
import os
import re
import time

import numpy as np
import pytest
from click.testing import CliRunner

from bigstitcher_spark_tpu import profiling
from bigstitcher_spark_tpu.analysis.tracereport import (
    build_report, load_events, render_report,
)
from bigstitcher_spark_tpu.observe import devicetrace, metrics, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "tests", "benchmark_tests", "data",
                        "v5e_small.xplane.pb")


@pytest.fixture(autouse=True)
def _clean_state():
    trace.reset()
    profiling.enable(False)
    profiling.get().reset()
    yield
    trace.reset()
    profiling.enable(False)
    profiling.get().reset()


def test_ring_and_annotations_agree_over_two_seconds(tmp_path):
    import jax
    from jax.profiler import ProfileData

    trace.configure(buffer_bytes=1 << 20)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        # the benchmark's join: one annotation at the window's start,
        # stamped with the host's clock (benchmark/trace_reduce.start)
        with jax.profiler.TraceAnnotation("bench.anchor"):
            anchor_unix_ns = time.time_ns()
            time.sleep(0.001)
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < 2.2:
            with profiling.span("fusion.stage"):
                with profiling.span("fusion.kernel", item=n):
                    time.sleep(0.01)
            n += 1
    finally:
        jax.profiler.stop_trace()
    ring = trace.snapshot()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    anchor, notes = None, {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                a, b = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if ev.name == "bench.anchor":
                    anchor = (a, b)
                elif ev.name in ("fusion.stage", "fusion.kernel"):
                    notes[dict(ev.stats)["id"]] = (ev.name, a, b)
    assert anchor is not None
    begins = {e["id"]: e for e in ring if e["ph"] == "B"}
    ends = {e["id"]: e["ts"] * 1e9 for e in ring if e["ph"] == "E"}
    assert len(begins) == 2 * n and n > 50
    assert set(notes) == set(begins) == set(ends)   # a span, an annotation
    assert all(notes[i][0] == e["name"] for i, e in begins.items())
    # in order: both clocks tell the spans' story in the same sequence,
    # and a kernel's annotation lies inside its stage's
    assert sorted(notes, key=lambda i: notes[i][1]) \
        == sorted(begins, key=lambda i: (begins[i]["ts"], i))
    for i, e in begins.items():
        if e["name"] == "fusion.kernel":
            _name, a, b = notes[e["parent"]]
            assert a <= notes[i][1] <= notes[i][2] <= b
    # what they bracket: a span stamps the ring, opens its annotation,
    # closes it and stamps the ring again, so whatever else the machine is
    # doing, ONE offset puts every annotation inside its ring span: at
    # least the largest (begin - annotation start), at most the smallest
    # (end - annotation end). A stalled thread widens one span's bounds
    # and narrows nobody's; clocks that run apart leave no such offset.
    # RESOLUTION is what two software clocks may differ by in a reading
    # (the ring's float seconds resolve a quarter of a microsecond)
    RESOLUTION = 50e3
    lo = max(e["ts"] * 1e9 - notes[i][1] for i, e in begins.items())
    hi = min(ends[i] - notes[i][2] for i in begins)
    assert lo <= hi + RESOLUTION, f"no one offset: {(lo - hi) / 1e3:.1f} us"
    # the benchmark's join reads the host's clock inside ONE annotation:
    # its offset is bracketed by that annotation's two ends
    assert anchor_unix_ns - anchor[1] <= hi + RESOLUTION
    assert anchor_unix_ns - anchor[0] >= lo - RESOLUTION
    # the program's own join takes every span as an anchor (the median of
    # begin - annotation start): under it every annotation ends inside its
    # ring span, and at least half start inside it
    planes = devicetrace.load_planes(path)
    clock = devicetrace._clock(planes["anchors"], ring)
    assert clock["anchors"] == 2 * n
    off = clock["offset_ns"]
    assert all(notes[i][2] + off <= ends[i] + RESOLUTION for i in begins)
    inside = sum(notes[i][1] + off >= e["ts"] * 1e9 - RESOLUTION
                 for i, e in begins.items())
    assert inside >= n
    assert {"residual_us", "drift_us"} <= set(clock)    # trace-report's
    # a CPU run has no device plane: nothing to place
    assert devicetrace.reduce_planes(planes, ring) is None


def _recorded():
    return devicetrace.reduce_planes(devicetrace.load_planes(RECORDED), [])


def test_recorded_v5e_trace_reduces_in_the_program():
    reduced = _recorded()
    assert list(reduced["devices"]) == ["0"]
    totals = devicetrace.module_totals(reduced)
    assert totals["jit_pcm_peaks"][0] == 3
    assert totals["jit_fuse_block_shift_impl"][0] == 3
    busy = sum(b - a for a, b in reduced["devices"]["0"]["busy"])
    assert 0 < busy <= sum(s for _n, s in totals.values()) * 1.001
    assert reduced["clock"]["anchors"] == 0    # recorded before the ids
    assert 0 < len(reduced["top_ops"]) <= 10
    assert reduced["top_ops"][0][1] >= reduced["top_ops"][-1][1]
    # as Perfetto tracks: two a device, per-module X events, no per-op ones
    meta, events, bst = devicetrace.perfetto(reduced, 0)
    names = [m["args"]["name"] for m in meta if m["name"] == "thread_name"]
    assert names == ["device 0 (XLA) modules", "device 0 (XLA) busy"]
    assert sum(e["cat"] == "xla.modules" for e in events) == 6
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in events)
    assert bst["device"]["modules"]["jit_pcm_peaks"][0] == 3
    assert bst["device"]["busy_s"]["0"] == pytest.approx(busy, abs=1e-6)
    assert bst["clock_anchors"] == 0 and "clock_residual_us" not in bst


def _host(ph, name, ts, tid, sid, parent):
    return {"name": name, "cat": name.split(".")[0], "ph": ph,
            "ts": ts * 1e6, "pid": 0, "tid": tid,
            "args": {"id": sid, "parent": parent}}


def test_trace_report_reads_the_device_and_names_its_gaps():
    reduced = _recorded()
    meta, xla, _bst = devicetrace.perfetto(reduced, 0)
    starts = sorted(e["ts"] / 1e6 for e in xla)
    t0, t1 = starts[0] - 0.01, starts[-1] + 0.05
    # the main thread sits in refine under drain under the stage; a pool
    # thread refines one pair, caused by refine (its parent)
    events = meta + xla + [
        {"ph": "M", "name": "thread_name", "pid": 0, "tid": 1,
         "args": {"name": "MainThread"}},
        {"ph": "M", "name": "thread_name", "pid": 0, "tid": 2,
         "args": {"name": "pool-0"}},
        _host("B", "stitching.stage", t0, 1, 1, 0),
        _host("B", "pair.drain", t0 + 0.001, 1, 2, 1),
        _host("B", "stitching.refine", t0 + 0.002, 1, 3, 2),
        _host("B", "stitching.refine.pair", t0 + 0.003, 2, 4, 3),
        _host("E", "stitching.refine.pair", t1 - 0.003, 2, 4, 3),
        _host("E", "stitching.refine", t1 - 0.002, 1, 3, 2),
        _host("E", "pair.drain", t1 - 0.001, 1, 2, 1),
        _host("E", "stitching.stage", t1, 1, 1, 0),
    ]
    rep = build_report(events, {"clock": {"clock_anchors": 12,
                                          "clock_residual_us": 40.0}})
    assert rep["device_source"] == "xla"
    dev, = rep["devices"]
    busy = sum(b - a for a, b in reduced["devices"]["0"]["busy"])
    assert dev["busy_s"] == pytest.approx(busy, abs=1e-6)
    assert dev["busy_pct"] == pytest.approx(100 * busy / (t1 - t0), abs=0.02)
    assert dev["modules"]["jit_pcm_peaks"][0] == 3
    # the sleeps between the recorded calls are the longest gaps; each is
    # named by the INNERMOST span of every host thread, by parent
    gap = dev["largest_gaps"][0]
    assert gap["seconds"] > 0.015
    assert gap["open"] == {"MainThread": "stitching.refine",
                           "pool-0": "stitching.refine.pair"}
    # the device's tracks are no host spans: not in the stage table, the
    # track list or the tree
    assert set(rep["stages"]) == {"stitching", "pair"} or \
        set(rep["stages"]) == {"stitching"}
    assert not any("XLA" in t["name"] for t in rep["tracks"])
    assert [r["path"][-1] for r in rep["span_tree"]] == [
        "stitching.stage", "pair.drain", "stitching.refine",
        "stitching.refine.pair"]
    text = render_report(rep)
    assert "device 0 (XLA): busy" in text
    assert "host-inferred compute" in text   # the host's guess, beside it
    assert "clock join: 12 span anchors, residual p95 40.0us" in text
    assert "MainThread=stitching.refine, pool-0=stitching.refine.pair" \
        in text


def test_without_device_tracks_the_report_says_host_inferred():
    events = [
        _host("B", "stitching.kernel", 0.0, 1, 1, 0),
        _host("E", "stitching.kernel", 1.0, 1, 1, 0),
        _host("B", "stitching.refine", 1.0, 1, 2, 0),
        _host("E", "stitching.refine", 4.0, 1, 2, 0),
    ]
    rep = build_report(events)
    assert rep["device_source"] == "host-inferred"
    assert "devices" not in rep
    assert rep["host_inferred_compute_pct"] == 25.0
    text = render_report(rep)
    assert "host-inferred" in text
    assert "compute (host-inferred) 1.000s" in text
    assert "device 0 (XLA)" not in text


def test_categories_of_the_new_spans():
    from bigstitcher_spark_tpu.analysis.tracereport import _category

    assert _category("stitching.pack") == "host"
    assert _category("fusion.plan") == "host"
    assert _category("stitching.store") == "host"
    assert _category("fusion.h2d") == "h2d"
    assert _category("fusion.h2d_tiles") == "h2d"
    assert _category("stitching.kernel") == "compute"


@pytest.mark.parametrize("metric", ["fuse_kernel_ms", "fuse_kernel_roofline",
                                    "pcm_kernel_ms", "pcm_roofline",
                                    "detect_kernel_ms",
                                    "detect_kernel_roofline"])
def test_kernel_module_names_match_the_benchmarks_patterns(metric):
    """The benchmark finds a kernel's device time by its XLA module name
    (``jit_<function>``): lower each kernel a cell runs and hold the
    module's name to the patterns of the metric's file."""
    import jax.numpy as jnp

    from bigstitcher_spark_tpu.ops import dog as D
    from bigstitcher_spark_tpu.ops import fusion as F
    from bigstitcher_spark_tpu.ops import phasecorr as P

    with open(os.path.join(ROOT, "benchmark", "metrics",
                           metric + ".json")) as f:
        patterns = json.load(f)["reader"]["modules"]
    v = 2
    per_view = (jnp.zeros((v, 3)), jnp.ones((v, 3)), jnp.zeros((v, 3)),
                jnp.ones((v, 3)), jnp.ones((v,)))
    static = dict(block_shape=(8, 8, 4), fusion_type="AVG_BLEND")
    lowered = {
        "fuse": [
            F.fuse_block.lower(jnp.zeros((v, 10, 10, 6), jnp.uint16),
                               jnp.zeros((v, 3, 4)), *per_view, **static),
            F.fuse_block_shift.lower(jnp.zeros((v, 9, 9, 5), jnp.uint16),
                                     jnp.zeros((v, 3)), *per_view,
                                     **static)],
        "pcm": [
            P.pcm_peaks_batch.lower(
                jnp.zeros((2, 8, 8, 4), jnp.uint16),
                jnp.zeros((2, 8, 8, 4), jnp.uint16),
                jnp.ones((2, 3), jnp.int32), jnp.ones((2, 3), jnp.int32),
                5, 0.25)],
        "detect": [
            D.dog_block_topk_batch.lower(
                jnp.zeros((2, 20, 20, 20), jnp.uint16), jnp.zeros((2,)),
                jnp.ones((2,)), jnp.full((2,), 0.008),
                jnp.zeros((2, 3), jnp.int32), sigma=1.8, k=16, halo=8)],
    }[metric.split("_")[0]]
    for low in lowered:
        text = low.as_text()
        name = re.search(r"module @(\w+)", text).group(1)
        assert any(re.fullmatch(p, name) for p in patterns), \
            f"{name} matches none of {patterns}: {metric} would fall silent"


def test_kernel_phases_are_named_in_the_lowered_program():
    import jax.numpy as jnp

    from bigstitcher_spark_tpu.ops import phasecorr as P

    text = P.pcm_peaks_batch.lower(
        jnp.zeros((2, 8, 8, 4), jnp.uint16),
        jnp.zeros((2, 8, 8, 4), jnp.uint16),
        jnp.ones((2, 3), jnp.int32), jnp.ones((2, 3), jnp.int32),
        5, 0.25).as_text(debug_info=True)
    for phase in ("window", "fft", "normalise", "peak_search"):
        assert re.search(rf'loc\("(?:[^"]*/)?{phase}/', text), phase


@pytest.fixture()
def fused_project(tmp_path):
    from bigstitcher_spark_tpu.cli.main import cli
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    proj = make_synthetic_project(
        str(tmp_path / "p"), n_tiles=(2, 1, 1), tile_size=(32, 32, 16),
        overlap=8, jitter=0.0, seed=11, n_beads_per_tile=6)
    out = str(tmp_path / "fused.ome.zarr")
    r = CliRunner().invoke(cli, [
        "create-fusion-container", "-x", proj.xml_path, "-o", out,
        "-s", "ZARR", "-d", "UINT16", "--blockSize", "16,16,8",
        "--minIntensity", "0", "--maxIntensity", "65535",
    ], catch_exceptions=False)
    assert r.exit_code == 0, r.output
    return proj, out


def test_per_block_path_counts_its_bytes_and_brackets_each_leg(
        fused_project):
    """H2D of the staged inputs, the kernel alone, and every fetch of the
    block in ONE ``fusion.d2h`` with the output-conversion round trip; the
    transfer counters see both legs of it."""
    from bigstitcher_spark_tpu.io.chunkstore import ChunkStore
    from bigstitcher_spark_tpu.io.container import read_container_meta
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.affine_fusion import fuse_volume

    proj, out = fused_project
    sd = SpimData.load(proj.xml_path)
    store = ChunkStore.open(out)
    meta = read_container_meta(store)
    reg = metrics.get_registry()
    before = reg.snapshot()
    trace.configure(buffer_bytes=8 << 20)
    stats = fuse_volume(
        sd, ViewLoader(sd), sd.view_ids(), store.open_dataset("0"),
        meta.bbox, block_size=tuple(meta.block_size), block_scale=(1, 1, 1),
        fusion_type="AVG_BLEND", out_dtype="uint16", min_intensity=0,
        max_intensity=65535, zarr_ct=(0, 0), devices=1,
        device_resident=False)
    d = reg.snapshot_delta(before)
    n = stats.blocks - stats.skipped_empty
    vox = int(np.prod(meta.block_size))
    assert n > 1
    # down: the float32 block and its float32 weights at the static
    # compute shape, then the uint16 block at its own (clipped) size; up:
    # the staged inputs and the float32 block again
    assert d["bst_xfer_d2h_bytes_total"] == n * vox * (4 + 4) \
        + stats.voxels * 2
    assert d["bst_xfer_h2d_bytes_total"] > stats.voxels * 4
    assert d["bst_fusion_voxels_total"] == stats.voxels
    snap = trace.snapshot()
    begins = {e["id"]: e for e in snap if e["ph"] == "B"}
    by_name = {}
    for e in begins.values():
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["fusion.stage"]) == 1
    for name in ("fusion.plan", "fusion.h2d", "fusion.kernel", "fusion.d2h",
                 "fusion.write"):
        assert len(by_name[name]) == n, name
        # each hangs under its block's attempt, which hangs under the stage
        for e in by_name[name]:
            attempt = begins[e["parent"]]
            assert attempt["name"] == "retry.attempt"
            assert begins[attempt["parent"]]["name"] == "fusion.stage"
    assert all(e["nbytes"] > 0 for e in by_name["fusion.h2d"])


def test_cli_trace_device_and_the_process_start(fused_project, tmp_path):
    """``--trace-device`` implies ``--trace``; on XLA:CPU there is no
    device plane, so the file has host tracks only and the report says its
    numbers are host-inferred. The manifest carries the process start."""
    from bigstitcher_spark_tpu.cli.main import cli

    _, out = fused_project
    tel = str(tmp_path / "tel")
    runner = CliRunner()
    r = runner.invoke(cli, [
        "affine-fusion", "-o", out, "--blockScale", "1,1,1",
        "--devices", "1", "--trace-device", "--telemetry-dir", tel,
    ], catch_exceptions=False)
    assert r.exit_code == 0, r.output
    assert not trace.enabled() and not trace.device_session()
    events, meta = load_events(tel)
    assert any(e.get("name") == "fusion.stage" for e in events)
    assert not any(str(e.get("cat", "")).startswith("xla.") for e in events)
    r = runner.invoke(cli, ["trace-report", tel], catch_exceptions=False)
    assert r.exit_code == 0, r.output
    assert "host-inferred" in r.output and "span tree" in r.output
    with open(os.path.join(tel, "manifest-00000-of-00001.json")) as f:
        man = json.load(f)
    proc = man["process"]
    assert proc["started_at"] <= time.time()
    assert 0 < proc["imports_s"] <= proc["backend_s"]
    assert set(proc["compile_s"]) == {"trace", "lower", "backend_compile",
                                      "cache_load"}
    assert "self_s" in man["spans"]["fusion.stage"]
    assert man["spans"]["fusion.stage"]["self_s"] <= \
        man["spans"]["fusion.stage"]["total_s"]
    assert "bst_process_start_backend_seconds" in man["metrics"]
