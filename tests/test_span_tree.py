"""One span primitive that records what a span is (ISSUE 25, part A), the
counts at the layer boundaries (part B) and the compile listener.

``profiling.span`` gives every span an id, the parent that was open where
it started and a self time; the context variable that holds the open span
crosses a ``CtxThreadPool`` hop; off, nothing is recorded and no context
variable is set; the ring's events carry ``id``/``parent`` through
``export``.
"""

import json
import re
import threading
import time

import numpy as np
import pytest

from bigstitcher_spark_tpu import profiling
from bigstitcher_spark_tpu.observe import compiles, metrics, trace
from bigstitcher_spark_tpu.utils.threads import CtxThreadPool


@pytest.fixture(autouse=True)
def _clean_state():
    trace.reset()
    profiling.enable(False)
    profiling.get().reset()
    yield
    trace.reset()
    profiling.enable(False)
    profiling.get().reset()


def _begins(snap):
    return {e["name"]: e for e in snap if e["ph"] == "B"}


class TestTree:
    def test_parent_and_root_across_a_pool_hop(self):
        trace.configure(buffer_bytes=1 << 20)
        seen = {}

        def pair(k):
            with profiling.span("stitching.refine.pair", item=k):
                cur = trace.CURRENT.get()
                seen[k] = (cur.id, cur.root, threading.get_ident())

        with profiling.span("stitching.stage"):
            stage = trace.CURRENT.get()
            with profiling.span("stitching.refine"):
                refine = trace.CURRENT.get()
                with CtxThreadPool(max_workers=2) as pool:
                    list(pool.map(pair, range(4)))
        assert trace.CURRENT.get() is None
        assert stage.root == stage.id and refine.root == stage.id
        # a pair refined on a pool thread has stitching.refine for parent
        # and the stage for root, whatever thread it ran on
        events = [e for e in trace.snapshot()
                  if e["name"] == "stitching.refine.pair"]
        assert len(events) == 8
        assert {e["parent"] for e in events} == {refine.id}
        assert all(root == stage.id for _i, root, _t in seen.values())
        assert any(t != threading.get_ident() for _i, _r, t in seen.values())
        # begin and end of one span share its id; ids are unique per span
        ids = [e["id"] for e in events if e["ph"] == "B"]
        assert len(set(ids)) == 4
        assert sorted(ids) == sorted(e["id"] for e in events
                                     if e["ph"] == "E")
        b = _begins(trace.snapshot())
        assert b["stitching.stage"]["parent"] == 0
        assert b["stitching.refine"]["parent"] == stage.id

    def test_a_bare_thread_starts_a_tree_of_its_own(self):
        trace.configure(buffer_bytes=1 << 20)

        def work():
            with profiling.span("io.prefetch"):
                pass

        with profiling.span("fusion.stage"):
            t = threading.Thread(target=work)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        assert _begins(trace.snapshot())["io.prefetch"]["parent"] == 0

    def test_instants_hang_under_the_open_span(self):
        trace.configure(buffer_bytes=1 << 20)
        trace.instant("io.read", nbytes=3)
        with profiling.span("fusion.prefetch"):
            me = trace.CURRENT.get().id
            trace.instant("io.read", nbytes=4)
        first, second = [e for e in trace.snapshot() if e["ph"] == "i"]
        assert first["parent"] == 0 and second["parent"] == me
        assert first["id"] != second["id"]

    def test_self_time_with_overlapping_children_on_two_threads(self):
        profiling.enable(True)
        gate = threading.Barrier(2, timeout=10)

        def child(delay, length):
            gate.wait()
            time.sleep(delay)
            with profiling.span("stitching.refine.pair"):
                time.sleep(length)

        with profiling.span("stitching.refine"):
            t0 = time.perf_counter()
            # [0.00, 0.12] and [0.06, 0.18]: union 0.18, sum 0.24
            with CtxThreadPool(max_workers=2) as pool:
                futs = [pool.submit(child, 0.0, 0.12),
                        pool.submit(child, 0.06, 0.12)]
                for f in futs:
                    f.result(timeout=10)
            time.sleep(0.05)        # the parent's own work
            total = time.perf_counter() - t0
        st = profiling.get().stats()
        pair, parent = st["stitching.refine.pair"], st["stitching.refine"]
        assert pair.count == 2 and pair.total_s == pytest.approx(0.24,
                                                                 abs=0.04)
        assert pair.self_s == pytest.approx(pair.total_s)   # leaves
        # the union counts once: self = total - 0.18, not total - 0.24
        assert parent.total_s == pytest.approx(total, abs=0.02)
        assert parent.self_s == pytest.approx(parent.total_s - 0.18,
                                              abs=0.04)
        assert parent.self_s > parent.total_s - 0.23

    def test_self_seconds_clips_a_child_that_outlives_its_parent(self):
        assert profiling._self_seconds(0.0, 10.0, []) == 10.0
        assert profiling._self_seconds(
            0.0, 10.0, [(1.0, 3.0), (2.0, 4.0), (9.0, 15.0)]) == \
            pytest.approx(10.0 - 3.0 - 1.0)

    def test_off_records_nothing_and_sets_no_context_variable(self):
        assert not trace.enabled() and not profiling.get().enabled
        with profiling.span("fusion.kernel", item=(0, 0, 0), nbytes=8):
            assert trace.CURRENT.get() is None
        assert trace.stats()["recorded"] == 0
        assert profiling.get().stats() == {}

    def test_as_a_decorator_it_opens_one_span_a_call(self):
        profiling.enable(True)

        @profiling.span("fusion.stage")
        def stage(x):
            """doc kept"""
            return x + 1

        assert stage(1) == 2 and stage(2) == 3
        assert stage.__doc__ == "doc kept"
        assert profiling.get().stats()["fusion.stage"].count == 2


class TestExport:
    def test_ids_and_parents_ride_through_export(self, tmp_path):
        trace.configure(buffer_bytes=1 << 20, path=str(tmp_path / "t.json"))
        with profiling.span("fusion.stage"):
            with profiling.span("fusion.kernel", item=[0, 0, 0]):
                trace.instant("io.read", nbytes=5)
        doc = trace.export(0, 1)
        assert doc["bst"]["schema"] == "bst-trace/2"
        evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
        assert all({"id", "parent"} <= set(e["args"]) for e in evs)
        stage = next(e for e in evs if e["name"] == "fusion.stage")
        kernel = next(e for e in evs if e["name"] == "fusion.kernel")
        inst = next(e for e in evs if e["ph"] == "i")
        assert stage["args"]["parent"] == 0
        assert kernel["args"]["parent"] == stage["args"]["id"]
        assert inst["args"]["parent"] == kernel["args"]["id"]
        assert kernel["args"]["item"] == [0, 0, 0]
        # and the tree comes back out of the written file
        from bigstitcher_spark_tpu.analysis.tracereport import (
            analyze, render_report,
        )
        rep = analyze(trace.finalize())
        paths = [r["path"] for r in rep["span_tree"]]
        assert paths == [["fusion.stage"], ["fusion.stage", "fusion.kernel"]]
        root = rep["span_tree"][0]
        assert 0 <= root["self_s"] <= root["total_s"]
        assert "span tree" in render_report(rep)
        json.loads(json.dumps(doc))


class TestCompileListener:
    def test_one_backend_compile_for_a_fresh_shape_none_for_a_repeat(self):
        import jax

        compiles.listen()
        compiles.listen()           # registered once, however often asked
        reg = metrics.get_registry()
        key = 'bst_jax_compile_events_total{phase="backend_compile"}'
        sec = 'bst_jax_compile_seconds_total{phase="backend_compile"}'
        fn = jax.jit(lambda x: (x * 3.0 + 1.0).sum())
        x = np.ones((7, 13), np.float32)    # no program of its own
        trace.configure(buffer_bytes=1 << 20)
        before = reg.snapshot()
        with profiling.span("stitching.kernel"):
            fn(x).block_until_ready()
        fresh = reg.snapshot_delta(before)
        assert fresh[key] == 1 and fresh[sec] > 0
        assert fresh['bst_jax_compile_events_total{phase="lower"}'] == 1
        marks = [e for e in trace.snapshot() if e["name"] == "jax.compile"]
        # which step compiled: the span that was open is the stage
        assert {m["stage"] for m in marks} == {"stitching.kernel"}
        assert {m["item"][0] for m in marks} >= {"lower", "backend_compile"}
        assert not any(m["item"][0] == "trace" for m in marks)
        before = reg.snapshot()
        fn(x).block_until_ready()
        again = reg.snapshot_delta(before)
        assert again[key] == 0 and again[sec] == 0
        assert compiles.seconds_by_phase()["backend_compile"] > 0


class TestCountsAtTheBoundaries:
    def test_heartbeat_times_items(self):
        from bigstitcher_spark_tpu.observe import progress

        reg = metrics.get_registry()
        before = reg.snapshot()
        hb = progress.Heartbeat("span-tree-test", 3)
        hb.tick(seconds=0.25)
        hb.tick(2, seconds=0.5)
        hb.tick()                   # untimed: counted, not observed
        d = reg.snapshot_delta(before)
        assert d['bst_stage_items_done_total{stage="span-tree-test"}'] == 4
        assert d['bst_stage_item_seconds{stage="span-tree-test"}'] == \
            {"count": 3, "sum": pytest.approx(1.25)}

    def test_retry_loop_times_each_block(self):
        from bigstitcher_spark_tpu.parallel.retry import run_with_retry

        reg = metrics.get_registry()
        before = reg.snapshot()
        run_with_retry([1, 2, 3], lambda it: time.sleep(0.01),
                       label="span-tree-blocks", verbose=False)
        d = reg.snapshot_delta(before)
        h = d['bst_stage_item_seconds{stage="span-tree-blocks"}']
        assert h["count"] == 3 and 0.03 <= h["sum"] < 1.0

    def test_chunk_io_counts_seconds_beside_bytes(self, tmp_path):
        from bigstitcher_spark_tpu.io.chunkcache import get_cache
        from bigstitcher_spark_tpu.io.chunkstore import (
            ChunkStore, StorageFormat,
        )

        store = ChunkStore.create(str(tmp_path / "c.n5"), StorageFormat.N5)
        ds = store.create_dataset("a", (32, 32, 16), (16, 16, 16), "uint16",
                                  compression="zstd")
        reg = metrics.get_registry()
        before = reg.snapshot()
        data = np.arange(32 * 32 * 16, dtype=np.uint16).reshape(32, 32, 16)
        ds.write(data, (0, 0, 0))
        get_cache().clear()
        assert np.array_equal(ds.read((0, 0, 0), (32, 32, 16)), data)
        assert np.array_equal(ds.read((0, 0, 0), (32, 32, 16)), data)
        d = reg.snapshot_delta(before)
        wrote = {k: v for k, v in d.items()
                 if k.startswith("bst_io_write_seconds_total")}
        read = {k: v for k, v in d.items()
                if k.startswith("bst_io_read_seconds_total")}
        assert sum(wrote.values()) > 0
        assert sum(read.values()) > 0
        # a hit in the decoded LRU has bytes and no seconds
        assert all(v == 0 for k, v in read.items() if 'path="cache"' in k)
        assert sum(v for k, v in d.items()
                   if k.startswith("bst_io_read_bytes_total")) >= \
            2 * data.nbytes


class TestPerBlockDriverReadsSideBySide:
    """The per-block affine driver reads a block's views on its pool
    (``io_threads``): the same bytes stored as one after another, and
    every read's ``fusion.prefetch`` span under the stage's tree though
    it ran on another thread."""

    @pytest.mark.parametrize("out_dtype", ["float32", "uint16"])
    def test_same_bytes_and_one_tree(self, tmp_path, out_dtype):
        from bigstitcher_spark_tpu.io.chunkstore import (
            ChunkStore, StorageFormat,
        )
        from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
        from bigstitcher_spark_tpu.io.spimdata import SpimData
        from bigstitcher_spark_tpu.models.affine_fusion import fuse_volume
        from bigstitcher_spark_tpu.utils.geometry import (
            Interval, transformed_interval,
        )
        from bigstitcher_spark_tpu.utils.testdata import (
            make_synthetic_project,
        )

        proj = make_synthetic_project(str(tmp_path / "p"), n_tiles=(2, 2, 1),
                                      jitter=0.0, seed=5)
        sd = SpimData.load(proj.xml_path)
        views = sd.view_ids()
        bbox = None
        for v in views:
            b = transformed_interval(sd.model(v),
                                     Interval.from_shape(sd.view_size(v)))
            bbox = b if bbox is None else bbox.union(b)
        stored, snaps = {}, {}
        for threads in (1, 4):
            st = ChunkStore.create(str(tmp_path / f"t{threads}.n5"),
                                   StorageFormat.N5)
            ds = st.create_dataset("f", bbox.shape, (64, 64, 32), out_dtype)
            trace.reset()
            trace.configure(buffer_bytes=1 << 22)
            profiling.enable(True)
            stats = fuse_volume(
                sd, ViewLoader(sd), views, ds, bbox, block_size=(64, 64, 32),
                block_scale=(1, 1, 1), out_dtype=out_dtype,
                min_intensity=0.0, max_intensity=65535.0,
                device_resident=False, devices=1, io_threads=threads)
            assert stats.voxels == bbox.num_elements
            stored[threads] = ds.read_full()
            snaps[threads] = trace.snapshot()
        assert stored[4].any()
        np.testing.assert_array_equal(stored[1], stored[4])
        for threads, snap in snaps.items():
            stage = next(e for e in snap
                         if e["name"] == "fusion.stage" and e["ph"] == "B")
            reads = [e for e in snap
                     if e["name"] == "fusion.prefetch" and e["ph"] == "B"]
            parent = {e["id"]: e["parent"] for e in snap if e["ph"] == "B"}

            def root(i):
                while parent[i]:
                    i = parent[i]
                return i

            assert reads and all(root(e["id"]) == stage["id"] for e in reads)
        # a block that two or more views cover is read off the main thread
        stage = next(e for e in snaps[4]
                     if e["name"] == "fusion.stage" and e["ph"] == "B")
        assert any(e["tid"] != stage["tid"] for e in snaps[4]
                   if e["name"] == "fusion.prefetch")


class TestDetectionTree:
    """``detect_project``, the one entry of ``bst detect-interestpoints``:
    every span of the pass hangs under ``detection.stage`` whatever thread
    it ran on, the counters count one a block and the points kept, and
    the named children cover the root."""

    def test_spans_under_the_stage_and_counts_a_block(self, tmp_path):
        from bigstitcher_spark_tpu.models.detection import (
            DetectionParams, detect_project,
        )
        from bigstitcher_spark_tpu.utils.testdata import (
            make_synthetic_project,
        )

        proj = make_synthetic_project(str(tmp_path / "p"), n_tiles=(2, 1, 1),
                                      tile_size=(64, 64, 32), overlap=16,
                                      jitter=0.0, seed=2,
                                      n_beads_per_tile=15)
        reg = metrics.get_registry()
        before = reg.snapshot()
        trace.configure(buffer_bytes=1 << 22)
        profiling.enable(True)
        dets = detect_project(proj.xml_path, DetectionParams(
            downsample_xy=1, downsample_z=1, block_size=(32, 32, 16)),
            progress=False)
        counted = reg.snapshot_delta(before)
        snap = trace.snapshot()
        begins = [e for e in snap if e["ph"] == "B"]
        parent = {e["id"]: e["parent"] for e in begins}

        def root(i):
            while parent[i]:
                i = parent[i]
            return i

        stage = [e for e in begins if e["name"] == "detection.stage"]
        assert len(stage) == 1 and stage[0]["parent"] == 0
        blocks = 2 * 2 * 2 * 2      # two views of 2 x 2 x 2 blocks
        want = {"detection.plan": 1, "detection.minmax": 2,
                "detection.read": blocks, "detection.consume": blocks,
                "detection.save": 1, "spimdata.load": 1,
                "spimdata.save": 1}
        for name, n in want.items():
            got = [e for e in begins if e["name"] == name]
            assert len(got) == n, name
            assert all(root(e["id"]) == stage[0]["id"] for e in got), name
        assert {e["item"] for e in begins
                if e["name"] == "detection.read"} == set(range(blocks))
        assert any(e["tid"] != stage[0]["tid"] for e in begins
                   if e["name"] == "detection.read")
        assert counted["bst_detection_blocks_whole_total"] == blocks
        assert counted["bst_detection_blocks_truncated_total"] == 0
        assert counted["bst_detection_points_total"] == \
            sum(len(d.points) for d in dets) > 0
        st = profiling.get().stats()["detection.stage"]
        assert st.self_s < 0.05 * st.total_s


class TestNames:
    def test_every_new_name_is_declared_once(self):
        from bigstitcher_spark_tpu.observe import metric_names as mn

        for span in ("stitching.stage", "stitching.plan", "stitching.pack",
                     "detection.stage", "detection.plan", "detection.minmax",
                     "detection.read", "detection.consume", "detection.save",
                     "stitching.refine.pair", "stitching.store",
                     "spimdata.load", "spimdata.save", "fusion.stage",
                     "fusion.plan", "fusion.h2d", "jax.compile"):
            assert span in mn.SPANS
        for m in ("bst_io_read_seconds_total", "bst_io_write_seconds_total",
                  "bst_stage_item_seconds", "bst_stitching_pairs_total",
                  "bst_fusion_voxels_total", "bst_jax_compile_events_total",
                  "bst_jax_compile_seconds_total",
                  "bst_detection_blocks_whole_total",
                  "bst_detection_blocks_truncated_total",
                  "bst_detection_points_total"):
            assert m in mn.METRICS
        for m in ("bst_pair_busy_ms_total", "bst_pair_device_util_pct",
                  "bst_pair_proc_busy_ms_total", "bst_pair_proc_util_pct"):
            assert "host time inside" in mn.METRICS[m]
            assert not re.search(r"device[- ]busy milliseconds|"
                                 r"device-utilization", mn.METRICS[m])

    def test_there_is_one_span_primitive(self):
        assert not hasattr(trace, "span")
