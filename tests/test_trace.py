"""Timeline flight recorder (observe/trace.py) + ``bst trace-report``.

The acceptance contract of the tracing PR: a ``--trace`` affine-fusion
run produces a Perfetto-loadable trace whose begin/end events pair up,
with one d2h and one write interval per output block on the per-block
path; the report computes overlap percentages and a named critical path
on a hand-built trace with KNOWN answers; ring overflow keeps the newest
events and counts drops; and with tracing off nothing records while the
span aggregates still work (the zero-overhead gate).
"""

import json
import os
import threading

import numpy as np
import pytest
from click.testing import CliRunner

from bigstitcher_spark_tpu import profiling
from bigstitcher_spark_tpu.observe import trace
from bigstitcher_spark_tpu.analysis.tracereport import (
    build_intervals,
    build_report,
    load_events,
    render_report,
)


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """The recorder is process-global; never leak it between tests."""
    trace.reset()
    profiling.enable(False)
    profiling.get().reset()
    yield
    trace.reset()
    profiling.enable(False)
    profiling.get().reset()


def _pairing_ok(events):
    """Every B has a matching E per (pid, tid, name) series."""
    counts = {}
    for ev in events:
        if ev.get("ph") in ("B", "E"):
            key = (ev.get("pid", 0), ev.get("tid", 0), ev.get("name"))
            b, e = counts.get(key, (0, 0))
            counts[key] = (b + (ev["ph"] == "B"), e + (ev["ph"] == "E"))
    return all(b == e for b, e in counts.values()), counts


class TestRecorder:
    def test_off_by_default_records_nothing(self):
        assert not trace.enabled()
        trace.record("B", "fusion.kernel")
        trace.instant("io.read", nbytes=10)
        with profiling.span("fusion.write"):
            pass
        s = trace.stats()
        assert s["recorded"] == 0 and s["buffered"] == 0

    def test_span_aggregates_unchanged_when_tracing_off(self):
        # the zero-overhead gate: profiling on, tracing off — the span
        # table fills while the flight recorder records NOTHING
        profiling.enable(True)
        with profiling.span("fusion.kernel", item=(0, 0, 0), nbytes=64):
            pass
        stats = profiling.get().stats()
        assert stats["fusion.kernel"].count == 1
        assert trace.stats()["recorded"] == 0

    def test_trace_without_profiling_leaves_aggregates_empty(self):
        trace.configure(buffer_bytes=1 << 20)
        with profiling.span("fusion.kernel"):
            pass
        assert profiling.get().stats() == {}
        assert trace.stats()["recorded"] == 2  # the B and the E

    def test_begin_end_pairing_across_threads(self):
        trace.configure(buffer_bytes=1 << 20)

        def work(i):
            with profiling.span("pair.dispatch", device=i % 2, item=i):
                with profiling.span("fusion.kernel", item=i):
                    pass
            trace.instant("io.read", nbytes=i)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = trace.snapshot()
        assert len(snap) == 8 * 5  # 2 B/E pairs + 1 instant per thread
        ok, counts = _pairing_ok(
            [{"ph": e["ph"], "tid": e["tid"], "name": e["name"]}
             for e in snap])
        assert ok, counts

    def test_overflow_keeps_newest_and_counts_drops(self):
        trace.configure(buffer_bytes=0)  # clamps to _MIN_CAPACITY events
        cap = trace.stats()["capacity_events"]
        n = cap + 36
        for i in range(n):
            trace.instant("io.read", item=i)
        s = trace.stats()
        assert s["recorded"] == n
        assert s["buffered"] == cap
        assert s["dropped"] == 36
        items = [e["item"] for e in trace.snapshot()]
        assert items == list(range(36, n))  # oldest 36 gone, newest kept

    def test_reset_stops_recording(self):
        trace.configure(buffer_bytes=1 << 20)
        trace.instant("io.read")
        trace.reset()
        assert not trace.enabled()
        trace.instant("io.read")
        assert trace.stats()["recorded"] == 0

    def test_thread_names_reset_between_runs(self):
        # OS thread idents recycle: a stale first-run name must not label
        # a later run's tracks
        trace.configure(buffer_bytes=1 << 20)
        t = threading.Thread(target=lambda: trace.instant("io.read"),
                             name="first-run-writer")
        t.start(); t.join()
        doc = trace.export(0, 1)
        assert any("first-run-writer" in (e.get("args") or {}).get(
            "name", "") for e in doc["traceEvents"] if e["ph"] == "M")
        trace.configure(buffer_bytes=1 << 20)
        trace.instant("io.read")
        doc = trace.export(0, 1)
        assert not any("first-run-writer" in (e.get("args") or {}).get(
            "name", "") for e in doc["traceEvents"] if e["ph"] == "M")


class TestExport:
    def test_perfetto_document_structure(self):
        trace.configure(buffer_bytes=1 << 20)
        with profiling.span("fusion.kernel", device=2, item=[0, 0, 0],
                        nbytes=4096):
            pass
        with profiling.span("fusion.write", item=[0, 0, 0], nbytes=2048):
            pass
        trace.instant("pair.redispatch", device=2, item=7)
        doc = trace.export(0, 1)
        evs = doc["traceEvents"]
        # metadata names the tracks: the process, device 2's track, and
        # the host thread's track
        meta = [e for e in evs if e["ph"] == "M"]
        names = {(e["name"], e.get("tid")) for e in meta}
        assert ("process_name", None) in names
        dev_tids = [e["tid"] for e in meta if e["name"] == "thread_name"
                    and "device 2" in e["args"]["name"]]
        assert len(dev_tids) == 1
        # device-attributed events ride the device track
        kernel_b = next(e for e in evs
                        if e.get("name") == "fusion.kernel"
                        and e["ph"] == "B")
        assert kernel_b["tid"] == dev_tids[0]
        assert kernel_b["args"]["bytes"] == 4096
        assert kernel_b["args"]["item"] == [0, 0, 0]
        # host event on a small host-thread track, instants flagged
        write_b = next(e for e in evs
                       if e.get("name") == "fusion.write"
                       and e["ph"] == "B")
        assert write_b["tid"] != kernel_b["tid"]
        inst = next(e for e in evs if e["ph"] == "i")
        assert inst["s"] == "t"
        # timestamps are microseconds, monotonic non-decreasing per track
        assert doc["bst"]["recorded"] == 5
        assert doc["bst"]["dropped"] == 0
        # round-trips through JSON (Perfetto-loadable)
        json.loads(json.dumps(doc))

    def test_finalize_resolution_and_idempotence(self, tmp_path,
                                                 monkeypatch):
        # explicit configure(path=) wins
        p = str(tmp_path / "explicit.json")
        trace.configure(buffer_bytes=1 << 20, path=p)
        trace.instant("io.read")
        assert trace.finalize() == p
        assert os.path.exists(p)
        assert not trace.enabled()
        assert trace.finalize() is None  # idempotent
        assert trace.last_path() == p

        # the BST_TRACE_PATH knob beats the dir hint
        p2 = str(tmp_path / "knob.json")
        monkeypatch.setenv("BST_TRACE_PATH", p2)
        trace.configure(buffer_bytes=1 << 20)
        trace.instant("io.read")
        assert trace.finalize(dir_hint=str(tmp_path / "tel")) == p2
        monkeypatch.delenv("BST_TRACE_PATH")

        # dir hint: the per-process telemetry name
        trace.configure(buffer_bytes=1 << 20)
        trace.instant("io.read")
        out = trace.finalize(dir_hint=str(tmp_path / "tel"))
        assert out == str(tmp_path / "tel" / "trace-00000-of-00001.json")
        with open(out) as f:
            doc = json.load(f)
        assert doc["bst"]["schema"] == trace.SCHEMA


def _ev(ph, name, ts_s, tid=1, pid=0, **args):
    return {"name": name, "cat": name.split(".")[0], "ph": ph,
            "ts": ts_s * 1e6, "pid": pid, "tid": tid, "args": args}


def _synthetic_events():
    """Two per-block chains with KNOWN numbers. Block A (the critical
    path): kernel 0-1s, d2h 1-2s, write 1.5-3s, ends at 3.0s. Block B
    rides a second track and finishes by 0.9s; its category intervals
    are disjoint from A's, so every union below is a plain sum."""
    a, b = [0, 0, 0], [16, 0, 0]
    return [
        {"ph": "M", "name": "thread_name", "pid": 0, "tid": 1,
         "args": {"name": "writer-0"}},
        _ev("B", "fusion.kernel", 0.0, item=a),
        _ev("E", "fusion.kernel", 1.0, item=a),
        _ev("B", "fusion.kernel", 0.2, tid=2, item=b),
        _ev("E", "fusion.kernel", 0.5, tid=2, item=b),
        _ev("B", "fusion.d2h", 0.6, tid=2, item=b),
        _ev("E", "fusion.d2h", 0.7, tid=2, item=b),
        _ev("B", "fusion.write", 0.7, tid=2, item=b),
        _ev("E", "fusion.write", 0.9, tid=2, item=b),
        _ev("B", "fusion.d2h", 1.0, item=a),
        _ev("E", "fusion.d2h", 2.0, item=a),
        _ev("B", "fusion.write", 1.5, item=a),
        _ev("E", "fusion.write", 3.0, item=a),
    ]


class TestSyntheticReport:
    def test_known_overlap_and_decomposition(self):
        rep = build_report(_synthetic_events())
        fusion = rep["stages"]["fusion"]
        assert fusion["wall_s"] == 3.0
        assert fusion["compute_s"] == 1.0   # [0,1] u [0.2,0.5]
        assert fusion["d2h_s"] == pytest.approx(1.1)   # [0.6,0.7]+[1.0,2.0]
        assert fusion["write_s"] == pytest.approx(1.7)  # [0.7,0.9]+[1.5,3.0]
        ov = fusion["overlap"]["d2h_write"]
        assert ov["seconds"] == pytest.approx(0.5)   # [1.5,2.0]
        assert ov["pct_of_d2h"] == pytest.approx(45.5)   # 0.5/1.1
        assert ov["pct_of_write"] == pytest.approx(29.4)  # 0.5/1.7
        assert fusion["idle_s"] == 0.0  # busy union covers [0,3]

    def test_known_critical_path(self):
        rep = build_report(_synthetic_events(), top=3)
        cp = rep["critical_path"]
        assert cp["stage"] == "fusion"
        assert cp["item"] == [0, 0, 0]        # block A ends last (3.0s)
        assert cp["total_s"] == 3.0
        segs = [s["name"] for s in cp["segments"]]
        assert segs == ["fusion.kernel", "fusion.d2h", "fusion.write"]
        top = rep["top_blocking"]
        assert top[0]["name"] == "fusion.write"   # 1.5s
        assert top[0]["seconds"] == pytest.approx(1.5)

    def test_tracks_and_idle_gaps(self):
        rep = build_report(_synthetic_events())
        tracks = {t["name"]: t for t in rep["tracks"]}
        w = tracks["writer-0"]   # tid 1: [0,1] [1,2] [1.5,3] -> busy 3.0
        assert w["busy_s"] == 3.0 and w["util_pct"] == 100.0
        t2 = tracks["tid 2"]     # [0.2,0.5] [0.6,0.9]: one 0.1s gap
        assert t2["busy_s"] == pytest.approx(0.6)
        assert t2["largest_gaps"][0]["seconds"] == pytest.approx(0.1)

    def test_report_stable_under_event_reordering(self):
        evs = _synthetic_events()
        # interleave tracks differently: stable pairing is per (pid, tid,
        # name), so shuffling ACROSS series must not change the report
        reordered = ([e for e in evs if e.get("tid") == 2]
                     + [e for e in evs if e.get("tid") != 2])
        assert build_report(evs) == build_report(reordered)

    def test_unmatched_begin_dropped_not_invented(self):
        evs = _synthetic_events()[:-1]   # ring overflow tore an E off
        rep = build_report(evs)
        assert rep["intervals"] == 5
        assert "write_s" not in rep["stages"]["fusion"] or \
            rep["stages"]["fusion"]["write_s"] == pytest.approx(0.2)

    def test_render_names_the_numbers(self):
        txt = render_report(build_report(_synthetic_events()))
        assert "overlap d2h<->write: 0.500s" in txt
        assert "critical path [fusion item [0, 0, 0]]" in txt
        assert "top blocking segments:" in txt
        assert "fusion.write 1.500s" in txt


class TestMergeTraces:
    def _doc(self, pi, pc, events):
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "bst": {"schema": trace.SCHEMA, "process_index": pi,
                        "process_count": pc, "recorded": len(events),
                        "dropped": 0}}

    def test_barrier_alignment(self, tmp_path):
        # process 1's clock runs 4s AHEAD; the shared barrier exit is the
        # anchor that pulls its events back onto process 0's timeline
        p0 = [_ev("B", "barrier", 0.9, pid=0, stage="fusion"),
              _ev("E", "barrier", 1.0, pid=0, stage="fusion"),
              _ev("B", "fusion.kernel", 1.1, pid=0),
              _ev("E", "fusion.kernel", 1.6, pid=0)]
        p1 = [_ev("B", "barrier", 4.8, pid=1, stage="fusion"),
              _ev("E", "barrier", 5.0, pid=1, stage="fusion"),
              _ev("B", "fusion.kernel", 5.1, pid=1),
              _ev("E", "fusion.kernel", 5.4, pid=1)]
        for pi, evs in ((0, p0), (1, p1)):
            with open(tmp_path / trace.trace_name(pi, 2), "w") as f:
                json.dump(self._doc(pi, 2, evs), f)
        out = trace.merge_traces(str(tmp_path))
        with open(out) as f:
            doc = json.load(f)
        assert doc["bst"]["clock_offsets_us"]["1"] == pytest.approx(-4e6)
        k1 = [e for e in doc["traceEvents"]
              if e["pid"] == 1 and e["name"] == "fusion.kernel"]
        assert [e["ts"] for e in k1] == [pytest.approx(1.1e6),
                                         pytest.approx(1.4e6)]

    def test_alignment_survives_differential_overflow(self, tmp_path):
        # process 0's ring dropped its FIRST barrier; occurrences index
        # from the tail (newest events win overflow), so the surviving
        # last barriers still pair — and the merged doc sums the drop
        # counts so trace-report can flag the truncation
        p0 = [_ev("B", "barrier", 10.9, pid=0, stage="bst"),
              _ev("E", "barrier", 11.0, pid=0, stage="bst")]
        p1 = [_ev("B", "barrier", 4.9, pid=1, stage="bst"),
              _ev("E", "barrier", 5.0, pid=1, stage="bst"),
              _ev("B", "barrier", 14.9, pid=1, stage="bst"),
              _ev("E", "barrier", 15.0, pid=1, stage="bst")]
        for pi, evs, dropped in ((0, p0, 7), (1, p1, 0)):
            doc = self._doc(pi, 2, evs)
            doc["bst"]["dropped"] = dropped
            with open(tmp_path / trace.trace_name(pi, 2), "w") as f:
                json.dump(doc, f)
        out = trace.merge_traces(str(tmp_path))
        with open(out) as f:
            doc = json.load(f)
        # last barrier of p1 (15.0s) aligns to last of p0 (11.0s): -4s,
        # NOT the -(5-11)=+6s a head-indexed pairing would compute
        assert doc["bst"]["clock_offsets_us"]["1"] == pytest.approx(-4e6)
        assert doc["bst"]["dropped"] == 7
        assert doc["bst"]["recorded"] == 6
        assert doc["bst"]["unaligned_processes"] == []

    def test_unalignable_process_is_named(self, tmp_path):
        # process 1 recorded no barrier exits at all (single-host run, or
        # its whole ring overflowed past the last barrier): its events
        # merge unshifted and the metadata names it so telemetry-merge
        # can warn instead of silently presenting skewed clocks
        p0 = [_ev("B", "barrier", 0.9, pid=0, stage="bst"),
              _ev("E", "barrier", 1.0, pid=0, stage="bst")]
        p1 = [_ev("B", "fusion.kernel", 5.1, pid=1),
              _ev("E", "fusion.kernel", 5.4, pid=1)]
        for pi, evs in ((0, p0), (1, p1)):
            with open(tmp_path / trace.trace_name(pi, 2), "w") as f:
                json.dump(self._doc(pi, 2, evs), f)
        out = trace.merge_traces(str(tmp_path))
        with open(out) as f:
            doc = json.load(f)
        assert doc["bst"]["unaligned_processes"] == [1]
        assert doc["bst"]["clock_offsets_us"]["1"] == 0.0

    def test_empty_dir_returns_none(self, tmp_path):
        assert trace.merge_traces(str(tmp_path)) is None


@pytest.fixture()
def fused_project(tmp_path):
    """A prepared 2-tile fusion container + its project."""
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


class TestEndToEnd:
    def test_per_block_d2h_and_write_intervals(self, fused_project,
                                               tmp_path):
        # the per-block driver path: exactly one d2h and one write
        # interval PER OUTPUT BLOCK, each carrying its block offset
        from bigstitcher_spark_tpu.io.chunkstore import ChunkStore
        from bigstitcher_spark_tpu.io.container import read_container_meta
        from bigstitcher_spark_tpu.models.affine_fusion import fuse_volume
        from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
        from bigstitcher_spark_tpu.io.spimdata import SpimData

        proj, out = fused_project
        sd = SpimData.load(proj.xml_path)
        loader = ViewLoader(sd)
        store = ChunkStore.open(out)
        meta = read_container_meta(store)
        ds = store.open_dataset("0")
        trace.configure(buffer_bytes=8 << 20)
        stats = fuse_volume(
            sd, loader, sd.view_ids(), ds, meta.bbox,
            block_size=tuple(meta.block_size), block_scale=(1, 1, 1),
            fusion_type="AVG_BLEND", out_dtype="uint16",
            min_intensity=0, max_intensity=65535, zarr_ct=(0, 0),
            devices=1, device_resident=False,
        )
        snap = trace.snapshot()
        ivs, _ = build_intervals(trace.export(0, 1)["traceEvents"])
        n_blocks = stats.blocks - stats.skipped_empty
        assert n_blocks > 1
        for name in ("fusion.d2h", "fusion.write"):
            mine = [iv for iv in ivs if iv["name"] == name]
            assert len(mine) == n_blocks, name
            items = {tuple(iv["args"]["item"]) for iv in mine}
            assert len(items) == n_blocks   # one per DISTINCT block
            assert all(iv["args"]["bytes"] > 0 for iv in mine)
        ok, counts = _pairing_ok(
            [{"ph": e["ph"], "tid": e["tid"], "name": e["name"]}
             for e in snap])
        assert ok, counts

    def test_cli_trace_to_report(self, fused_project, tmp_path):
        from bigstitcher_spark_tpu.cli.main import cli

        _, out = fused_project
        tel = str(tmp_path / "tel")
        runner = CliRunner()
        r = runner.invoke(cli, [
            "affine-fusion", "-o", out, "--blockScale", "1,1,1",
            "--devices", "1", "--trace", "--telemetry-dir", tel,
        ], catch_exceptions=False)
        assert r.exit_code == 0, r.output
        assert not trace.enabled()   # finalized with the command

        # the trace archived next to the manifest, and the manifest
        # points at it
        tpath = os.path.join(tel, "trace-00000-of-00001.json")
        assert os.path.exists(tpath)
        with open(os.path.join(tel,
                               "manifest-00000-of-00001.json")) as f:
            assert json.load(f)["trace_file"] == os.path.basename(tpath)

        # Perfetto-loadable: valid JSON, B/E pairing, named tracks
        with open(tpath) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        ok, counts = _pairing_ok(evs)
        assert ok, counts
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in evs)
        assert any(e.get("name") == "fusion.write" for e in evs)

        # the report: decomposition + d2h<->write overlap + a critical
        # path, from the same directory the CLI points users at
        r = runner.invoke(cli, ["trace-report", tel],
                          catch_exceptions=False)
        assert r.exit_code == 0, r.output
        assert "d2h" in r.output and "write" in r.output
        assert "overlap d2h<->write:" in r.output
        assert "critical path [" in r.output
        events, meta = load_events(tel)
        rep = build_report(events, meta)
        assert rep["stages"]["fusion"]["d2h_s"] > 0
        assert rep["stages"]["fusion"]["write_s"] > 0
        assert rep["critical_path"] is not None

    def test_no_trace_flag_records_nothing(self, fused_project, tmp_path):
        # zero-overhead acceptance: same run WITHOUT --trace — span
        # aggregates fill as before, the flight recorder stays empty
        from bigstitcher_spark_tpu.cli.main import cli

        _, out = fused_project
        tel = str(tmp_path / "tel2")
        r = CliRunner().invoke(cli, [
            "affine-fusion", "-o", out, "--blockScale", "1,1,1",
            "--devices", "1", "--telemetry-dir", tel,
        ], catch_exceptions=False)
        assert r.exit_code == 0, r.output
        assert trace.stats()["recorded"] == 0
        assert not os.path.exists(
            os.path.join(tel, "trace-00000-of-00001.json"))
        with open(os.path.join(tel,
                               "manifest-00000-of-00001.json")) as f:
            man = json.load(f)
        assert "trace_file" not in man
        assert any(k.startswith("fusion.") for k in man["spans"])
