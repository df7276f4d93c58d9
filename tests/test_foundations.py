"""Foundations: geometry, grid, chunk store, SpimData XML round-trip."""

import numpy as np
import pytest

from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat
from bigstitcher_spark_tpu.io.dataset_io import (
    ViewLoader,
    best_mipmap_level,
    mipmap_transform,
)
from bigstitcher_spark_tpu.io.spimdata import SpimData, ViewId
from bigstitcher_spark_tpu.utils.geometry import (
    Interval,
    affine_from_flat,
    apply_affine,
    concatenate,
    concatenate_all,
    invert_affine,
    scale_affine,
    transformed_interval,
    translation_affine,
)
from bigstitcher_spark_tpu.utils.grid import create_grid


class TestGeometry:
    def test_interval_basics(self):
        a = Interval((0, 0, 0), (9, 19, 29))
        assert a.shape == (10, 20, 30)
        assert a.num_elements == 6000
        b = Interval.from_shape((5, 5, 5), (8, 18, 28))
        assert a.overlaps(b)
        inter = a.intersect(b)
        assert inter.min == (8, 18, 28) and inter.max == (9, 19, 29)
        assert not a.overlaps(Interval((10, 0, 0), (12, 5, 5)))
        assert a.expand(2).min == (-2, -2, -2)

    def test_affine_compose_invert(self):
        t = translation_affine((5, -3, 2))
        s = scale_affine((2, 2, 4))
        # concatenate(a, b): b first
        m = concatenate(t, s)
        p = apply_affine(m, np.array([1.0, 1.0, 1.0]))
        np.testing.assert_allclose(p, [7, -1, 6])
        minv = invert_affine(m)
        np.testing.assert_allclose(
            apply_affine(minv, p), [1, 1, 1], atol=1e-12
        )

    def test_chain_order_outermost_first(self):
        # chain [T, S]: S applied first (innermost = calibration at list end)
        t = translation_affine((10, 0, 0))
        s = scale_affine((2, 1, 1))
        m = concatenate_all([t, s])
        np.testing.assert_allclose(apply_affine(m, np.array([3.0, 0, 0])), [16, 0, 0])

    def test_transformed_interval(self):
        box = Interval((0, 0, 0), (9, 9, 9))
        out = transformed_interval(translation_affine((2.5, 0, -1)), box)
        assert out.min == (2, 0, -1) and out.max == (12, 9, 8)


class TestGrid:
    def test_grid_cover_and_alignment(self):
        blocks = create_grid((100, 50, 30), (64, 64, 32), (32, 32, 16))
        # covers exactly
        total = sum(np.prod(b.size) for b in blocks)
        assert total == 100 * 50 * 30
        # offsets aligned to storage blocks
        for b in blocks:
            assert all(o % s == 0 for o, s in zip(b.offset, (32, 32, 16)))
            assert b.grid_pos == tuple(o // s for o, s in zip(b.offset, (32, 32, 16)))
        assert len(blocks) == 2 * 1 * 1

    def test_grid_rejects_misaligned(self):
        with pytest.raises(ValueError):
            create_grid((10, 10, 10), (48, 48, 48), (32, 32, 32))


class TestChunkStore:
    def test_n5_roundtrip(self, tmp_path):
        store = ChunkStore.create(str(tmp_path / "a.n5"), StorageFormat.N5)
        ds = store.create_dataset("g/data", (40, 30, 20), (16, 16, 16), "uint16")
        block = np.arange(16 * 16 * 16, dtype=np.uint16).reshape(16, 16, 16)
        ds.write(block, (16, 0, 0))
        back = store.open_dataset("g/data").read((16, 0, 0), (16, 16, 16))
        np.testing.assert_array_equal(back, block)
        assert store.open_dataset("g/data").shape == (40, 30, 20)

    def test_n5_attributes_nested(self, tmp_path):
        store = ChunkStore.create(str(tmp_path / "a.n5"), StorageFormat.N5)
        store.set_attribute("", "Bigstitcher-Spark/NumChannels", 3)
        store.set_attribute("", "Bigstitcher-Spark/Boundingbox_min", [0, 0, 0])
        assert store.get_attribute("", "Bigstitcher-Spark/NumChannels") == 3
        # reopen detects format
        store2 = ChunkStore.open(str(tmp_path / "a.n5"))
        assert store2.format == StorageFormat.N5
        assert store2.get_attribute("", "Bigstitcher-Spark/Boundingbox_min") == [0, 0, 0]

    def test_zarr_axis_reversal(self, tmp_path):
        store = ChunkStore.create(str(tmp_path / "a.zarr"), StorageFormat.ZARR)
        # logical xyzct 5-D, on-disk tczyx
        ds = store.create_dataset("0", (20, 10, 5, 2, 1), (8, 8, 4, 1, 1), "uint8")
        data = np.random.default_rng(0).integers(0, 255, (8, 8, 4, 1, 1), dtype=np.uint8)
        ds.write(data, (8, 0, 0, 1, 0))
        back = store.open_dataset("0").read((8, 0, 0, 1, 0), (8, 8, 4, 1, 1))
        np.testing.assert_array_equal(back, data)
        # on-disk zarr shape must be reversed (t,c,z,y,x)
        import json, os
        zarray = json.load(open(os.path.join(str(tmp_path / "a.zarr"), "0", ".zarray")))
        assert zarray["shape"] == [1, 2, 5, 10, 20]


class TestSpimData:
    def test_synthetic_roundtrip(self, synthetic_project):
        sd = SpimData.load(synthetic_project.xml_path)
        assert len(sd.setups) == 2
        assert sd.timepoints == [0]
        views = sd.view_ids()
        assert views == [ViewId(0, 0), ViewId(0, 1)]
        # model = nominal translation (grid) ∘ identity calibration
        m = sd.model(ViewId(0, 1))
        np.testing.assert_allclose(
            m[:, 3], synthetic_project.nominal_offsets[1], atol=1e-9
        )
        # save → load again, identical models
        sd.save(synthetic_project.xml_path)
        sd2 = SpimData.load(synthetic_project.xml_path)
        for v in views:
            np.testing.assert_allclose(sd.model(v), sd2.model(v))
        assert sd2.setups[1].attributes["tile"] == 1

    def test_view_loader(self, synthetic_project):
        sd = SpimData.load(synthetic_project.xml_path)
        loader = ViewLoader(sd)
        ds = loader.open(ViewId(0, 0))
        assert ds.shape == (96, 96, 48)
        img = ds.read_full()
        assert img.dtype == np.uint16
        assert img.max() > 500  # beads present
        # halo over-read pads with zeros
        block = loader.read_block(ViewId(0, 0), 0, (-8, 0, 0), (16, 16, 16))
        assert block[:8].max() == 0 and block[8:].max() > 0

    def test_stitching_results_roundtrip(self, synthetic_project, tmp_path):
        from bigstitcher_spark_tpu.io.spimdata import PairwiseStitchingResult
        from bigstitcher_spark_tpu.utils.geometry import translation_affine

        sd = SpimData.load(synthetic_project.xml_path)
        res = PairwiseStitchingResult(
            views_a=(ViewId(0, 0),), views_b=(ViewId(0, 1),),
            transform=translation_affine((1.5, -2.25, 0.75)),
            correlation=0.87, hash=123.5,
            bbox=Interval((0, 0, 0), (9, 9, 9)),
        )
        sd.stitching_results[res.pair_key] = res
        p = str(tmp_path / "out.xml")
        sd.save(p)
        sd2 = SpimData.load(p)
        r2 = sd2.stitching_results[res.pair_key]
        np.testing.assert_allclose(r2.transform, res.transform)
        assert r2.correlation == pytest.approx(0.87)
        assert r2.hash == pytest.approx(123.5)
        assert r2.bbox == res.bbox

    def test_a_local_save_is_whole_or_not_at_all(self, synthetic_project,
                                                 monkeypatch):
        """Every rank of a multi-process stage saves the project while
        others may be reading it (ROADMAP C17): the XML is written beside
        its place and moved in. A serialisation that fails half way leaves
        the old file whole and nothing beside it."""
        import os
        import xml.etree.ElementTree as ET

        path = synthetic_project.xml_path
        sd = SpimData.load(path)
        with open(path, "rb") as f:
            before = f.read()
        listing = sorted(os.listdir(os.path.dirname(path)))
        seen = []

        def half_way(self, target, *args, **kwargs):
            seen.append(target.name)
            target.write("<SpimData")
            target.flush()
            assert os.path.exists(target.name)
            raise OSError("disk full")

        with monkeypatch.context() as m:
            m.setattr(ET.ElementTree, "write", half_way)
            with pytest.raises(OSError, match="disk full"):
                sd.save(path)
        # never written in place, and the half-written file is gone
        assert seen and seen[0] != path
        assert os.path.dirname(seen[0]) == os.path.dirname(path)
        assert sorted(os.listdir(os.path.dirname(path))) == listing
        with open(path, "rb") as f:
            assert f.read() == before
        sd.timepoints = [0, 1]
        sd.save(path)
        assert sorted(os.listdir(os.path.dirname(path))) == listing
        assert SpimData.load(path).timepoints == [0, 1]

    def test_two_writers_with_one_pid_and_thread_id_do_not_share_a_file(
            self, synthetic_project, monkeypatch):
        """Ranks on different hosts over one shared store can have the same
        pid and the same main-thread id (containers of one image): the
        temporary's name must not be made of those. The second writer runs
        while the first has its temporary open."""
        import os
        import threading
        import xml.etree.ElementTree as ET

        path = synthetic_project.xml_path
        first, second = SpimData.load(path), SpimData.load(path)
        first.timepoints, second.timepoints = [0, 1], [0, 1, 2]
        listing = sorted(os.listdir(os.path.dirname(path)))
        monkeypatch.setattr(os, "getpid", lambda: 4242)
        monkeypatch.setattr(threading, "get_ident", lambda: 1)
        write = ET.ElementTree.write
        names = []

        def both(self, target, *args, **kwargs):
            names.append(target.name)
            assert os.path.exists(target.name)
            if len(names) == 1:
                second.save(path)
                # the other rank's whole file is in place, ours still beside
                assert SpimData.load(path).timepoints == [0, 1, 2]
                assert os.path.exists(target.name)
            return write(self, target, *args, **kwargs)

        monkeypatch.setattr(ET.ElementTree, "write", both)
        first.save(path)
        assert len(names) == 2 and names[0] != names[1]
        assert SpimData.load(path).timepoints == [0, 1]
        assert sorted(os.listdir(os.path.dirname(path))) == listing


class TestMipmap:
    def test_mipmap_transform(self):
        m = mipmap_transform((2, 2, 1))
        np.testing.assert_allclose(
            apply_affine(m, np.array([0.0, 0, 0])), [0.5, 0.5, 0]
        )

    def test_best_level(self):
        factors = [[1, 1, 1], [2, 2, 1], [4, 4, 2]]
        assert best_mipmap_level(factors, (1, 1, 1)) == 0
        assert best_mipmap_level(factors, (2, 2, 2)) == 1
        assert best_mipmap_level(factors, (4, 4, 4)) == 2
        assert best_mipmap_level(factors, (3.9, 4, 4)) == 1


def test_bzip2_xz_codecs(tmp_path):
    """bzip2 (N5+zarr) and xz (N5) codecs round-trip (N5Util.java:82-105)."""
    import numpy as np

    from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat

    data = np.arange(16 * 16 * 8, dtype=np.uint16).reshape(16, 16, 8)
    for fmt, comps in ((StorageFormat.N5, ("bzip2", "xz")),
                       (StorageFormat.ZARR, ("bzip2",))):
        for comp in comps:
            store = ChunkStore.create(
                str(tmp_path / f"{fmt.value}_{comp}"), fmt)
            ds = store.create_dataset("d", data.shape, (8, 8, 8), "uint16",
                                      compression=comp)
            ds.write(data, (0, 0, 0))
            assert (store.open_dataset("d").read_full() == data).all()
