"""Native N5 block codec (native/blockio.cpp via ctypes): round trips and
bidirectional interop with the tensorstore N5 driver — the independent-decoder
check that guards the on-disk contract."""

import os

import numpy as np
import pytest

from bigstitcher_spark_tpu.io import native_blockio


def test_roundtrip_dtypes(tmp_path):
    rng = np.random.default_rng(0)
    for dtype, gen in (
        ("uint8", lambda s: rng.integers(0, 255, s).astype(np.uint8)),
        ("uint16", lambda s: rng.integers(0, 65535, s).astype(np.uint16)),
        ("float32", lambda s: rng.normal(size=s).astype(np.float32)),
        ("float64", lambda s: rng.normal(size=s)),
    ):
        for comp in ("zstd", "raw"):
            data = gen((17, 9, 5))
            p = str(tmp_path / f"{dtype}_{comp}" / "0" / "0" / "0")
            native_blockio.write_block(p, data, compression=comp)
            back = native_blockio.read_block(p, dtype, (17, 9, 5),
                                             compression=comp)
            np.testing.assert_array_equal(back, data)


def test_missing_block_returns_none(tmp_path):
    assert native_blockio.read_block(
        str(tmp_path / "nope"), np.uint16, (4, 4, 4)) is None


def test_interop_with_tensorstore(tmp_path):
    from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat

    rng = np.random.default_rng(1)
    store = ChunkStore.create(str(tmp_path / "t.n5"), StorageFormat.N5)
    ds = store.create_dataset("ds", (40, 30, 20), (16, 16, 16), "uint16")
    data = rng.integers(0, 65535, (40, 30, 20)).astype(np.uint16)

    # native writes (through Dataset.write fast path) -> tensorstore reads
    for ox in range(0, 40, 16):
        for oy in range(0, 30, 16):
            for oz in range(0, 20, 16):
                ds.write(data[ox:ox + 16, oy:oy + 16, oz:oz + 16],
                         (ox, oy, oz))
    np.testing.assert_array_equal(store.open_dataset("ds").read_full(), data)

    # tensorstore writes -> native reads
    os.environ["BST_NATIVE_IO"] = "0"
    try:
        ds2 = store.create_dataset("ds2", (16, 16, 16), (16, 16, 16), "uint16")
        ds2.write(data[:16, :16, :16], (0, 0, 0))
    finally:
        os.environ["BST_NATIVE_IO"] = "1"
    back = native_blockio.read_block(
        str(tmp_path / "t.n5" / "ds2" / "0" / "0" / "0"), np.uint16,
        (16, 16, 16))
    np.testing.assert_array_equal(back, data[:16, :16, :16])


def test_unaligned_write_falls_back(tmp_path):
    """Non-block-aligned writes must still work (tensorstore path)."""
    from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat

    store = ChunkStore.create(str(tmp_path / "t.n5"), StorageFormat.N5)
    ds = store.create_dataset("ds", (32, 32, 32), (16, 16, 16), "uint16")
    data = np.arange(8 * 8 * 8, dtype=np.uint16).reshape(8, 8, 8)
    ds.write(data, (4, 4, 4))
    np.testing.assert_array_equal(ds.read((4, 4, 4), (8, 8, 8)), data)


class TestNativeZarrChunks:
    def test_round_trip_via_tensorstore(self, tmp_path):
        """Native zarr chunk writes must read back exactly through a fresh
        tensorstore open: zstd + raw codecs, edge chunks, 5-D slots."""
        import numpy as np

        from bigstitcher_spark_tpu.io import native_blockio
        from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat

        st = ChunkStore.create(str(tmp_path / "z.zarr"), StorageFormat.ZARR)
        ds = st.create_dataset("0", (130, 96, 40, 2, 2), (64, 64, 32, 1, 1),
                               "uint16")
        rng = np.random.default_rng(0)
        vol = rng.integers(0, 60000, (130, 96, 40), dtype=np.uint16)
        ds.write(vol[..., None, None], (0, 0, 0, 1, 0))
        ds2 = ChunkStore.open(str(tmp_path / "z.zarr")).open_dataset("0")
        got = np.asarray(ds2.read((0, 0, 0, 1, 0), (130, 96, 40, 1, 1)))
        np.testing.assert_array_equal(got[..., 0, 0], vol)
        assert np.asarray(ds2.read((0, 0, 0, 0, 0),
                                   (130, 96, 40, 1, 1))).max() == 0
        raw_ds = st.create_dataset("raw", (50, 40, 30), (32, 32, 16),
                                   "float32", compression="raw")
        v2 = rng.random((50, 40, 30)).astype(np.float32)
        raw_ds.write(v2, (0, 0, 0))
        got2 = ChunkStore.open(str(tmp_path / "z.zarr")
                               ).open_dataset("raw").read_full()
        np.testing.assert_array_equal(got2, v2)

    def test_native_matches_tensorstore_bytes_decoded(self, tmp_path):
        """A chunk written natively and one written by tensorstore must
        decode to the same values (codec parity, not byte equality)."""
        import os

        import numpy as np

        from bigstitcher_spark_tpu.io import native_blockio
        from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat

        rng = np.random.default_rng(3)
        v = rng.integers(0, 4000, (32, 24, 16), dtype=np.uint16)
        outs = {}
        for label, env in (("native", "1"), ("ts", "0")):
            os.environ["BST_NATIVE_IO"] = env
            try:
                st = ChunkStore.create(str(tmp_path / f"{label}.zarr"),
                                       StorageFormat.ZARR)
                ds = st.create_dataset("0", v.shape, (32, 24, 16), "uint16")
                ds.write(v, (0, 0, 0))
            finally:
                os.environ["BST_NATIVE_IO"] = "1"
            outs[label] = ChunkStore.open(
                str(tmp_path / f"{label}.zarr")).open_dataset("0").read_full()
        np.testing.assert_array_equal(outs["native"], outs["ts"])
        np.testing.assert_array_equal(outs["native"], v)


class TestLz4Codec:
    """N5 lz4 (lz4-java LZ4Block framing — the reference's Lz4Compression,
    util/N5Util.java:87-88): tensorstore has no n5 lz4 codec, so these
    datasets are served entirely by the native path."""

    pytestmark = pytest.mark.skipif(
        not native_blockio.has_lz4(), reason="liblz4 not available")

    def test_block_roundtrip(self, tmp_path):
        rng = np.random.RandomState(3)
        data = (rng.rand(40, 24, 16) * 500).astype(np.uint16)
        p = str(tmp_path / "ds" / "0" / "0" / "0")
        native_blockio.write_block(p, data, compression="lz4")
        back = native_blockio.read_block(p, np.uint16, (40, 24, 16),
                                         compression="lz4")
        np.testing.assert_array_equal(data, back)

    def test_frame_format_is_lz4block(self, tmp_path):
        """Independent check of the on-disk layout: N5 big-endian header,
        then lz4-java frames (magic, token, LE lengths, xxhash32 of the raw
        chunk) terminated by an empty frame — decodable without our code
        when the payload chunk is stored RAW (incompressible data)."""
        import struct

        rng = np.random.RandomState(7)
        # random bytes are incompressible -> stored with method RAW (0x10)
        data = rng.randint(0, 2**16, (8, 8, 4)).astype(np.uint16)
        p = str(tmp_path / "b")
        native_blockio.write_block(p, data, compression="lz4")
        raw = open(p, "rb").read()
        mode, ndim = struct.unpack(">HH", raw[:4])
        assert (mode, ndim) == (0, 3)
        dims = struct.unpack(">3I", raw[4:16])
        assert dims == (8, 8, 4)
        frame = raw[16:]
        assert frame[:8] == b"LZ4Block"
        token = frame[8]
        method = token & 0xF0
        clen, rawlen, check = struct.unpack("<iii", frame[9:21])
        assert rawlen == data.nbytes
        assert method in (0x10, 0x20)
        if method == 0x10:  # stored raw: payload is the big-endian elements
            assert clen == rawlen
            payload = np.frombuffer(frame[21:21 + clen], ">u2")
            np.testing.assert_array_equal(
                payload.astype(np.uint16),
                np.asfortranarray(data).ravel(order="F"))
        # terminator frame closes the stream
        term = frame[21 + clen:]
        assert term[:8] == b"LZ4Block"
        assert struct.unpack("<ii", term[9:17]) == (0, 0)

    def test_chunkstore_dataset_roundtrip(self, tmp_path):
        from bigstitcher_spark_tpu.io.chunkstore import (
            ChunkStore, StorageFormat,
        )

        store = ChunkStore.create(str(tmp_path / "c.n5"), StorageFormat.N5)
        ds = store.create_dataset("vol", (64, 48, 32), (32, 32, 32),
                                  "uint16", compression="lz4")
        rng = np.random.RandomState(11)
        data = (rng.rand(64, 48, 32) * 900).astype(np.uint16)
        for ox in (0, 32):
            for oy in (0, 32):
                ds.write(data[ox:ox + 32, oy:oy + min(32, 48 - oy)],
                         (ox, oy, 0))
        # reopen cold: geometry + data come purely from the native path
        ds2 = ChunkStore.open(str(tmp_path / "c.n5")).open_dataset("vol")
        assert ds2.dtype == np.uint16
        assert ds2.shape == (64, 48, 32)
        assert ds2.block_size == (32, 32, 32)
        np.testing.assert_array_equal(ds2.read_full(), data)
        np.testing.assert_array_equal(ds2.read((16, 8, 4), (20, 20, 20)),
                                      data[16:36, 8:28, 4:24])


class TestBuildOnDemand:
    """libblockio.so is a gitignored build product: a clean checkout must
    build it from native/blockio.cpp, rebuild it when the source is newer
    (never load a stale library), and fail loudly — not fall back — when
    the build breaks."""

    SRC = native_blockio._SRC_DIR

    def _fresh(self, monkeypatch, tmp_path, source: str):
        d = tmp_path / "native"
        d.mkdir()
        (d / "Makefile").write_text(
            open(os.path.join(self.SRC, "Makefile")).read())
        (d / "blockio.cpp").write_text(source)
        monkeypatch.setattr(native_blockio, "_LIB", None)
        monkeypatch.setattr(native_blockio, "_SRC_DIR", str(d))
        return d

    def test_builds_when_missing_and_rebuilds_when_stale(self, monkeypatch,
                                                         tmp_path):
        d = self._fresh(monkeypatch, tmp_path, open(
            os.path.join(self.SRC, "blockio.cpp")).read())
        assert not native_blockio.loaded()
        native_blockio._load()
        so = d / "libblockio.so"
        assert so.exists() and native_blockio.loaded()
        built = so.stat().st_mtime_ns
        # a source newer than the library forces a rebuild on next load
        os.utime(d / "blockio.cpp", ns=(built + 10**9, built + 10**9))
        monkeypatch.setattr(native_blockio, "_LIB", None)
        native_blockio._load()
        assert so.stat().st_mtime_ns > built
        assert not list(d.glob("*.tmp"))

    def test_broken_build_raises(self, monkeypatch, tmp_path):
        self._fresh(monkeypatch, tmp_path, "this is not C++\n")
        with pytest.raises(RuntimeError, match="BST_NATIVE_IO=0"):
            native_blockio._load()
        assert not native_blockio.loaded()
