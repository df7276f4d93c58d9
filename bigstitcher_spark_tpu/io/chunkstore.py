"""Chunked-array storage layer: N5, ZARR (v2 / OME-ZARR), HDF5.

TPU-native replacement for the reference's L1 (n5/n5-zarr/n5-hdf5 writers,
util/N5Util.java:45-105): tensorstore does the chunk IO (async, C codecs),
h5py covers HDF5 (local-only, same restriction as the reference's
CreateFusionContainer.java:141-145).

All public APIs use **xyz-first logical axis order** (N5/imglib2 convention —
first axis fastest). For the zarr driver, whose on-disk shape is C-order
(e.g. OME-NGFF ``[t,c,z,y,x]``), the wrapper reverses axes at the boundary so
callers never see driver-specific order. Group attributes are plain JSON files
(``attributes.json`` / ``.zattrs``) manipulated directly, with N5-style nested
key paths (``setAttribute("/", "a/b", v)`` -> ``{"a": {"b": v}}``).
"""

from __future__ import annotations

import enum
import json
import os
import shutil
import time
from dataclasses import dataclass
from typing import Any, Sequence

import numpy as np
import tensorstore as ts

from . import chunkcache, uris
from .. import config, profiling
from ..observe import events as _events
from ..observe import metrics as _metrics
from ..observe import trace as _trace

# remote-object-store traffic, counted SEPARATELY from the per-impl io
# counters above: these are the bytes that actually crossed the network
# (or the fake-S3 loopback), the denominator every warm-cache / prefetch
# claim in bench measure_cloud and scripts/cloud_smoke.sh is checked
# against ("warm rerun reads 0 remote bytes" is asserted on these)
_REMOTE_READ_BYTES = _metrics.counter("bst_io_remote_read_bytes_total")
_REMOTE_WRITE_BYTES = _metrics.counter("bst_io_remote_write_bytes_total")
_PREFETCH_BYTES = _metrics.counter("bst_io_prefetch_bytes_total")
_UPLOAD_INFLIGHT = _metrics.gauge("bst_io_upload_inflight")

# per-run pin folded into remote cache signatures (BST_REMOTE_CACHE=run):
# bumping it orphans every remote-keyed cache entry at once, the coarse
# invalidation lever for "another writer may have touched the bucket"
_REMOTE_PIN = [0]


def remote_pin() -> int:
    return _REMOTE_PIN[0]


def bump_remote_pin() -> int:
    """Start a new remote-cache coherence window: every cached remote
    chunk keyed under the old pin becomes unreachable (and ages out of
    the LRU). The serve daemon calls this at each job start; a one-shot
    CLI process is a single window (pin 0) its whole life."""
    _REMOTE_PIN[0] += 1
    return _REMOTE_PIN[0]

# one (bytes, chunk-ops, seconds) counter triple per (op, path-taken) —
# cached so the hot path pays one dict lookup + a few lock'd adds per box
# read/write, which also records WHICH implementation served it (native
# codec vs tensorstore vs h5py), the tuning signal for the native-IO fast
# paths
_IO_COUNTERS: dict[tuple[str, str], tuple] = {}


def _record_io(op: str, via: str, nbytes: int, dataset: str,
               seconds: float | None = None) -> None:
    """``seconds``: what the fetch + decode of a read that missed the
    decoded LRU, or the encode + store of a write, took on this thread
    (a cache hit has none)."""
    pair = _IO_COUNTERS.get((op, via))
    if pair is None:
        # literal series names per op branch so every metric string is
        # declared in observe/metric_names.py (the metric-name lint check
        # bans constructed names — a typo'd op would otherwise mint a
        # silent zero-valued series)
        if op == "read":
            pair = (_metrics.counter("bst_io_read_bytes_total", path=via),
                    _metrics.counter("bst_io_read_ops_total", path=via),
                    _metrics.counter("bst_io_read_seconds_total", path=via))
        else:
            pair = (_metrics.counter("bst_io_write_bytes_total", path=via),
                    _metrics.counter("bst_io_write_ops_total", path=via),
                    _metrics.counter("bst_io_write_seconds_total",
                                     path=via))
        _IO_COUNTERS[(op, via)] = pair
    pair[0].inc(int(nbytes))
    pair[1].inc()
    if seconds is not None:
        pair[2].inc(seconds)
    if _trace.enabled():
        # timeline marks with byte payload (literal names per branch —
        # the span-name lint check bans constructed names)
        if op == "read":
            _trace.instant("io.read", stage=via, nbytes=int(nbytes))
        else:
            _trace.instant("io.write", stage=via, nbytes=int(nbytes))
    if _events.enabled():
        _events.emit(f"io.{op}", path=via, bytes=int(nbytes),
                     dataset=dataset)

# streaming stage-DAG hooks (dag/stream.StreamRegistry): installed only
# while a pipeline run has streamed edges registered, None otherwise, so
# one list-load guards every hot path. The registry gates consumer reads
# on producer block completion, accounts handoff-vs-container bytes, and
# publishes producer writes into the block exchange.
_DAG_HOOKS: list = [None]


def set_dag_hooks(hooks) -> None:
    """Install (or with None remove) the streaming-DAG read/write hooks —
    called by dag.stream when the first edge registers / the last one
    unregisters."""
    _DAG_HOOKS[0] = hooks


# one shared Context so every open in this process sees the same caches and
# the same in-process ``memory://`` store (tensorstore scopes the memory
# kvstore to a Context; without sharing, each open would get an empty store)
_TS_CONTEXT: list = [None]


def ts_context():
    if _TS_CONTEXT[0] is None:
        _TS_CONTEXT[0] = ts.Context()
    return _TS_CONTEXT[0]


class StorageFormat(str, enum.Enum):
    N5 = "N5"
    ZARR = "ZARR"
    HDF5 = "HDF5"


_N5_DTYPES = {
    "uint8", "uint16", "uint32", "uint64",
    "int8", "int16", "int32", "int64",
    "float32", "float64",
}

_ZARR_DTYPE = {
    "uint8": "|u1", "uint16": "<u2", "uint32": "<u4", "uint64": "<u8",
    "int8": "|i1", "int16": "<i2", "int32": "<i4", "int64": "<i8",
    "float32": "<f4", "float64": "<f8",
}


def _split_level(name: str, level: int | None):
    """Compression specs may carry the reference's --compressionLevel inline
    as ``name:level`` (e.g. ``zstd:7``) so the spelling passes unchanged
    through every layer that forwards a compression string."""
    if ":" in name:
        name, lv = name.split(":", 1)
        if level is None:
            level = int(lv)
    return name, level


def _n5_compression(name: str, level: int | None = None) -> dict:
    """N5 codec factory (reference surface: Lz4/Gzip/Zstd/Blosc/Bzip2/Xz/Raw,
    util/N5Util.java:82-105). ``level`` is the reference's
    --compressionLevel (codec-specific meaning). lz4 has no tensorstore n5
    codec — create_dataset/open_dataset route it through the native-only
    path (io.native_blockio LZ4Block codec)."""
    name, level = _split_level(name.lower(), level)
    if name == "lz4":
        return {"type": "lz4",
                "blockSize": 65536 if level is None else int(level)}
    if name == "zstd":
        return {"type": "zstd"} if level is None else {
            "type": "zstd", "level": int(level)}
    if name == "gzip":
        return {"type": "gzip"} if level is None else {
            "type": "gzip", "level": int(level)}
    if name == "raw":
        return {"type": "raw"}
    if name == "blosc":
        return {"type": "blosc", "cname": "zstd",
                "clevel": 3 if level is None else int(level), "shuffle": 1}
    if name == "bzip2":
        return {"type": "bzip2"} if level is None else {
            "type": "bzip2", "blockSize": int(level)}
    if name == "xz":
        return {"type": "xz"} if level is None else {
            "type": "xz", "preset": int(level)}
    raise ValueError(f"unsupported n5 compression: {name}")


def _zarr_compressor(name: str, level: int | None = None) -> dict | None:
    name, level = _split_level(name.lower(), level)
    if name == "zstd":
        return {"id": "zstd", "level": 3 if level is None else int(level)}
    if name == "gzip":
        return {"id": "zlib", "level": 5 if level is None else int(level)}
    if name == "blosc":
        return {"id": "blosc", "cname": "zstd",
                "clevel": 3 if level is None else int(level), "shuffle": 1}
    if name == "bzip2":
        return {"id": "bz2", "level": 5 if level is None else int(level)}
    if name == "raw":
        return None
    raise ValueError(f"unsupported zarr compression: {name}")


_DECODE_POOL = None


def _decode_pool():
    """Shared long-lived pool for native chunk decodes (the foreign calls
    release the GIL): callers' build/prefetch threads issue reads from
    their own pools, so a per-read executor would pay create/join overhead
    and fan out to ~64 transient threads."""
    global _DECODE_POOL
    if _DECODE_POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        # raw executor on purpose: decode workers run GIL-releasing
        # foreign calls only — they never read config or poll cancel,
        # and the pool outlives any one job's context
        _DECODE_POOL = ThreadPoolExecutor(max_workers=8,  # bst-lint: off=thread-spawn
                                          thread_name_prefix="n5decode")
    return _DECODE_POOL


@dataclass
class Dataset:
    """A chunked array presented in xyz-first logical order.

    ``_ts is None`` marks a NATIVE-ONLY dataset (N5 codecs tensorstore has
    no driver for — lz4): geometry comes from attributes.json and all IO
    goes through the in-repo codec (io.native_blockio)."""

    store: "ChunkStore"
    path: str
    _ts: Any  # tensorstore.TensorStore, h5py.Dataset, or None (native-only)
    reversed_axes: bool  # True when on-disk order is C (zarr/hdf5)

    def _n5_attrs(self) -> dict:
        attrs = self._meta_file_cached("attributes.json")
        if not attrs or "dimensions" not in attrs:
            raise ValueError(f"{self.path}: no N5 dataset attributes")
        return attrs

    @property
    def shape(self) -> tuple[int, ...]:
        if self._ts is None:
            return tuple(int(v) for v in self._n5_attrs()["dimensions"])
        s = tuple(int(v) for v in self._ts.shape)
        return s[::-1] if self.reversed_axes else s

    @property
    def block_size(self) -> tuple[int, ...]:
        if self._ts is None:
            return tuple(int(v) for v in self._n5_attrs()["blockSize"])
        if hasattr(self._ts, "chunk_layout"):
            c = self._ts.chunk_layout.read_chunk.shape
        else:  # h5py
            c = self._ts.chunks
        c = tuple(int(v) for v in c)
        return c[::-1] if self.reversed_axes else c

    @property
    def dtype(self) -> np.dtype:
        if self._ts is None:
            return np.dtype(self._n5_attrs()["dataType"])
        return np.dtype(self._ts.dtype.numpy_dtype if hasattr(self._ts.dtype, "numpy_dtype") else self._ts.dtype)

    def _sel(self, offset: Sequence[int], shape: Sequence[int]):
        idx = tuple(slice(int(o), int(o) + int(s)) for o, s in zip(offset, shape))
        return idx[::-1] if self.reversed_axes else idx

    # -- decoded-chunk cache plumbing (io.chunkcache) ----------------------

    def _cache_key(self) -> tuple:
        root = getattr(self.store, "root", None)
        if root is None:
            root = getattr(self.store, "path", None)
        return (root, self.path.strip("/"))

    def _cacheable(self) -> bool:
        """Process-coherent stores always participate: local filesystems,
        in-process ``memory://`` roots, and single-process HDF5. Remote
        object stores (s3/gs) participate under ``BST_REMOTE_CACHE=run``
        (the default) with a run-pinned signature — see ``_cache_sig`` —
        while ``off`` restores the historical bypass bit-identically."""
        store = self.store
        if store is None:
            return False
        if getattr(store, "format", None) == StorageFormat.HDF5:
            return True
        if (getattr(store, "is_local", False)
                or str(getattr(store, "root", "")).startswith("memory://")):
            return True
        return (getattr(store, "is_remote_object", False)
                and config.get_str("BST_REMOTE_CACHE") == "run")

    def _cache_sig(self):
        """Metadata signature folded into cache keys. Local stores use the
        metadata file's (mtime_ns, size) — the same identity
        ``_meta_file_cached`` uses — so an out-of-band recreate at this
        path orphans the old entries. Remote object stores fold the
        per-run pin plus the metadata object's content hash/size instead
        (one conditional GET per open, memoized per pin): this process's
        own writes still invalidate precisely via the generation bumps,
        and ``bump_remote_pin`` bounds the external-writer coherence
        window."""
        store = self.store
        if getattr(store, "is_remote_object", False):
            return self._remote_cache_sig()
        if not getattr(store, "is_local", False) or not hasattr(store, "_kvpath"):
            return None
        name = ("attributes.json"
                if getattr(store, "format", None) == StorageFormat.N5
                else ".zarray")
        try:
            st = os.stat(os.path.join(store._kvpath(self.path), name))
            return (st.st_mtime_ns, st.st_size)
        except OSError:
            return None

    def _remote_cache_sig(self):
        """Remote signature ("remote", pin, md5-of-metadata, size), fetched
        once per (dataset instance, pin) — re-opened datasets re-read it,
        so a REPLACED remote dataset (new .zarray/attributes.json bytes)
        never collides with stale cached chunks."""
        pin = remote_pin()
        memo = getattr(self, "_remote_sig_memo", None)
        if memo is not None and memo[0] == pin:
            return memo[1]
        name = ("attributes.json"
                if getattr(self.store, "format", None) == StorageFormat.N5
                else ".zarray")
        rel = f"{self.path.strip('/')}/{name}" if self.path.strip("/") else name
        try:
            raw = self.store._read_obj(rel)
        except Exception:
            raw = None
        if raw is None:
            sig = None  # unreadable metadata: share nothing across readers
        else:
            import hashlib

            sig = ("remote", pin, hashlib.md5(raw).hexdigest(), len(raw))
        self._remote_sig_memo = (pin, sig)
        return sig

    def _cached_read(self, offset: Sequence[int],
                     shape: Sequence[int]) -> np.ndarray | None:
        """Assemble a box from cached decoded chunks, decoding only the
        misses. Returns None when ineligible (out-of-bounds box,
        unchunked dataset, no usable decode route) — the caller then runs
        the exact pre-cache read path."""
        try:
            block = self.block_size
        except Exception:
            return None
        if not block or any(int(b) <= 0 for b in block):
            return None
        dims = self.shape
        ndim = len(dims)
        off = [int(o) for o in offset]
        shp = [int(s) for s in shape]
        if len(off) != ndim or len(shp) != ndim:
            return None
        if any(o < 0 or s <= 0 or o + s > dims[d]
               for d, (o, s) in enumerate(zip(off, shp))):
            return None
        cc = chunkcache.get_cache()
        dkey = self._cache_key()
        sig = self._cache_sig()
        out = np.empty(tuple(shp), self.dtype)
        import itertools

        grids = [range(off[d] // block[d],
                       (off[d] + shp[d] - 1) // block[d] + 1)
                 for d in range(ndim)]
        copied = {"cache": 0}
        miss_s = None

        def fill(pos, chunk) -> int:
            lo = [pos[d] * block[d] for d in range(ndim)]
            src = tuple(
                slice(max(off[d] - lo[d], 0),
                      min(off[d] + shp[d] - lo[d], chunk.shape[d]))
                for d in range(ndim))
            dst = tuple(
                slice(max(lo[d] - off[d], 0),
                      max(lo[d] - off[d], 0) + (src[d].stop - src[d].start))
                for d in range(ndim))
            out[dst] = chunk[src]
            return int(out[dst].nbytes)

        misses = []
        for pos in itertools.product(*grids):
            chunk = cc.get((dkey, sig, pos))
            if chunk is None:
                misses.append(pos)
            else:
                copied["cache"] += fill(pos, chunk)
        if misses:
            t0 = time.perf_counter()
            got = self._read_chunks(misses)
            if got is None:
                return None  # no decode route: fall back (and re-read hits)
            via, chunks = got
            nb = 0
            for pos, chunk in zip(misses, chunks):
                cc.put((dkey, sig, pos), chunk)
                nb += fill(pos, chunk)
            copied[via] = copied.get(via, 0) + nb
            miss_s = time.perf_counter() - t0
        hooks = _DAG_HOOKS[0]
        for via, nb in copied.items():
            if nb:
                _record_io("read", via, nb, self.path,
                           None if via == "cache" else miss_s)
                if hooks is not None:
                    hooks.account_read(self, via, nb)
        return out

    def _read_chunks(self, positions):
        """Decode whole chunks (clipped to the array bounds, logical
        xyz-first orientation, absent chunks zero-filled). Returns
        (via, [chunk, ...]) aligned with ``positions``, or None when no
        decode route applies."""
        block = self.block_size
        dims = self.shape
        ndim = len(dims)

        def extent(pos):
            return tuple(min(block[d], dims[d] - pos[d] * block[d])
                         for d in range(ndim))

        ctype = self._native_n5_eligible()
        if ctype is not None:
            from . import native_blockio

            root = self.store._kvpath(self.path)

            def read_one(pos):
                path = os.path.join(root, *[str(p) for p in pos])
                blk = native_blockio.read_block(path, self.dtype, block,
                                                compression=ctype)
                ext = extent(pos)
                if blk is None:
                    return np.zeros(ext, self.dtype)
                if tuple(blk.shape) != ext:
                    # stored chunk dims may be full-size at the array edge
                    clipped = np.zeros(ext, self.dtype)
                    sl = tuple(slice(0, min(blk.shape[d], ext[d]))
                               for d in range(ndim))
                    clipped[sl] = blk[sl]
                    return clipped
                return blk

            if len(positions) > 1:
                return "native", list(_decode_pool().map(read_one, positions))
            return "native", [read_one(positions[0])]
        if self._ts is None:
            return None
        sels = []
        for pos in positions:
            lo = [pos[d] * block[d] for d in range(ndim)]
            sels.append(self._sel(lo, extent(pos)))
        rev = tuple(range(ndim))[::-1]
        if hasattr(self._ts, "read"):
            # issue every chunk read before resolving any: tensorstore
            # overlaps the decodes, so a miss burst costs one round of IO
            futs = [self._ts[sel].read() for sel in sels]
            chunks = [np.asarray(f.result()) for f in futs]
            via = "tensorstore"
            if getattr(self.store, "is_remote_object", False):
                _REMOTE_READ_BYTES.inc(sum(int(c.nbytes) for c in chunks))
        else:
            chunks = [np.asarray(self._ts[sel]) for sel in sels]
            via = "h5py"
        if self.reversed_axes:
            chunks = [c.transpose(rev) for c in chunks]
        return via, chunks

    def _invalidate_box(self, offset: Sequence[int],
                        shape: Sequence[int]) -> None:
        """Drop the cached chunks a written box covers (and bump the
        dataset generation device-side caches key on)."""
        try:
            block = self.block_size
        except Exception:
            chunkcache.get_cache().invalidate(self._cache_key())
            return
        if not block or any(int(b) <= 0 for b in block):
            chunkcache.get_cache().invalidate(self._cache_key())
            return
        import itertools

        grids = [range(int(offset[d]) // block[d],
                       (int(offset[d]) + int(shape[d]) - 1) // block[d] + 1)
                 for d in range(len(block))]
        chunkcache.get_cache().invalidate(self._cache_key(),
                                          itertools.product(*grids))

    def prefetch_box(self, offset: Sequence[int],
                     shape: Sequence[int]) -> list:
        """Decode the chunks a FUTURE read of this box will need into the
        decoded LRU, off the consumer's critical path (io/prefetch.py
        workers call this). Bypasses the DAG read gate (a non-blocking
        ``box_ready`` probe skips unpublished streamed blocks instead of
        waiting on them) and records no read-path io counters — the
        prefetcher attributes its own traffic. Returns the
        ``[(cache_key, nbytes), ...]`` it inserted (empty when everything
        was already resident or the dataset is ineligible)."""
        if not (chunkcache.enabled() and self._cacheable()):
            return []
        hooks = _DAG_HOOKS[0]
        if hooks is not None:
            ready = getattr(hooks, "box_ready", None)
            if ready is not None and not ready(self, offset, shape):
                return []
        try:
            block = self.block_size
            dims = self.shape
        except Exception:
            return []
        if not block or any(int(b) <= 0 for b in block):
            return []
        ndim = len(dims)
        off = [int(o) for o in offset]
        shp = [int(s) for s in shape]
        if len(off) != ndim or len(shp) != ndim:
            return []
        if any(o < 0 or s <= 0 or o + s > dims[d]
               for d, (o, s) in enumerate(zip(off, shp))):
            return []
        if self._native_n5_eligible() is None and (
                self._ts is None or not hasattr(self._ts, "read")):
            return []  # h5py handles are not thread-safe: never prefetch
        cc = chunkcache.get_cache()
        dkey = self._cache_key()
        sig = self._cache_sig()
        import itertools

        grids = [range(off[d] // block[d],
                       (off[d] + shp[d] - 1) // block[d] + 1)
                 for d in range(ndim)]
        misses = [pos for pos in itertools.product(*grids)
                  if not cc.peek((dkey, sig, pos))]
        if not misses:
            return []
        itemsize = np.dtype(self.dtype).itemsize
        est = sum(int(np.prod([min(block[d], dims[d] - p[d] * block[d])
                               for d in range(ndim)])) * itemsize
                  for p in misses)
        nbytes = 0
        inserted = []
        with profiling.span("io.prefetch", item=self.path, nbytes=est):
            got = self._read_chunks(misses)
            if got is None:
                return []
            _via, chunks = got
            for pos, chunk in zip(misses, chunks):
                key = (dkey, sig, pos)
                cc.put(key, chunk, record_miss=False)
                inserted.append((key, int(chunk.nbytes)))
                nbytes += int(chunk.nbytes)
        _PREFETCH_BYTES.inc(nbytes)
        return inserted

    def read(self, offset: Sequence[int], shape: Sequence[int]) -> np.ndarray:
        """Read a box (xyz-first offset/shape) into a numpy array (xyz-first)."""
        hooks = _DAG_HOOKS[0]
        if hooks is not None:
            # streaming pipelines: a consumer stage's read of a streamed
            # edge blocks here until the producer has written the covering
            # blocks (or finished); everyone else passes straight through
            hooks.gate(self, offset, shape)
        if chunkcache.enabled() and self._cacheable():
            cached = self._cached_read(offset, shape)
            if cached is not None:
                return cached
        t0 = time.perf_counter()
        native = self._native_read(offset, shape)
        if native is not None:
            _record_io("read", "native", native.nbytes, self.path,
                       time.perf_counter() - t0)
            if hooks is not None:
                hooks.account_read(self, "native", native.nbytes)
            return native
        if self._ts is None:
            raise ValueError(
                f"{self.path}: native-only dataset (lz4) — read box "
                f"{offset}+{shape} must lie inside the array bounds")
        sel = self._sel(offset, shape)
        if hasattr(self._ts, "read"):
            data = self._ts[sel].read().result()
            via = "tensorstore"
            if getattr(self.store, "is_remote_object", False):
                _REMOTE_READ_BYTES.inc(int(np.asarray(data).nbytes))
        else:
            data = self._ts[sel]
            via = "h5py"
        data = np.asarray(data)
        _record_io("read", via, data.nbytes, self.path,
                   time.perf_counter() - t0)
        if hooks is not None:
            hooks.account_read(self, via, data.nbytes)
        return data.transpose(tuple(range(data.ndim))[::-1]) if self.reversed_axes else data

    def read_device(self, offset: Sequence[int], shape: Sequence[int]):
        """Serve a read as a DEVICE array straight from a streaming
        pipeline's HBM handoff cache (dag/stream.py): zero D2H, zero
        container decode. Returns None whenever that tier cannot serve
        the whole box — callers fall back to :meth:`read`."""
        hooks = _DAG_HOOKS[0]
        if hooks is None:
            return None
        fn = getattr(hooks, "device_read", None)
        if fn is None:
            return None
        return fn(self, offset, shape)

    def _native_read(self, offset: Sequence[int],
                     shape: Sequence[int]) -> np.ndarray | None:
        """N5 + zstd/raw local read via the native codec: chunk files decode
        through GIL-free foreign calls (threads genuinely overlap), and the
        per-chunk decode avoids tensorstore's extra assembly copies (~25%
        faster even single-threaded). Returns None when ineligible."""
        ctype = self._native_n5_eligible()
        if ctype is None:
            return None
        from . import native_blockio

        block = self.block_size
        dims = self.shape
        ndim = len(dims)
        off = [int(o) for o in offset]
        shp = [int(s) for s in shape]
        if any(o < 0 or o + s > dims[d] or s <= 0
               for d, (o, s) in enumerate(zip(off, shp))):
            return None
        out = np.zeros(tuple(shp), self.dtype)
        root = self.store._kvpath(self.path)
        grids = [range(off[d] // block[d], (off[d] + shp[d] - 1) // block[d] + 1)
                 for d in range(ndim)]
        import itertools

        def read_one(pos):
            path = os.path.join(root, *[str(p) for p in pos])
            lo = [pos[d] * block[d] for d in range(ndim)]
            src_lo = [max(off[d] - lo[d], 0) for d in range(ndim)]
            dst_off = [max(lo[d] - off[d], 0) for d in range(ndim)]
            copy = [min(off[d] + shp[d], lo[d] + block[d])
                    - max(off[d], lo[d]) for d in range(ndim)]
            if any(c <= 0 for c in copy):
                return
            # decode straight into the output box: the big-endian swap
            # fuses with the strided write (absent chunk = fill zeros)
            native_blockio.read_block_region(
                path, out, dst_off, src_lo, copy, compression=ctype)

        positions = list(itertools.product(*grids))
        if len(positions) > 1:
            list(_decode_pool().map(read_one, positions))
        else:
            read_one(positions[0])
        return out

    def write(self, data: np.ndarray, offset: Sequence[int]) -> None:
        """Write a numpy array (xyz-first) at an xyz-first offset.

        Block-aligned N5 and zarr writes take the native codec fast path
        (GIL-free strided copy + zstd encode + file write,
        io.native_blockio) when available."""
        shape = data.shape
        try:
            self._write_impl(data, offset)
        finally:
            # drop exactly the cached chunks this box covers (finally: a
            # partially-applied failed write must not leave stale entries)
            self._invalidate_box(offset, shape)
        hooks = _DAG_HOOKS[0]
        if hooks is not None:
            # streaming pipelines: publish the completed block (coverage,
            # write-through handoff, backpressure) — AFTER the invalidation
            # above so the handoff's cache entries survive it
            hooks.on_write(self, data, offset)

    def write_device(self, dev, offset: Sequence[int]) -> bool:
        """Publish a DEVICE-resident block to a streaming pipeline's HBM
        handoff cache (dag/stream.py) instead of draining it to host.
        Returns True when the block was accepted device-resident — the
        caller skips the fetch and the host :meth:`write` entirely;
        False means the block must take the ordinary host write path."""
        hooks = _DAG_HOOKS[0]
        if hooks is None:
            return False
        fn = getattr(hooks, "on_write_device", None)
        if fn is None:
            return False
        return bool(fn(self, dev, offset))

    def _write_impl(self, data: np.ndarray, offset: Sequence[int]) -> None:
        t0 = time.perf_counter()
        if (self._native_write(data, offset)
                or self._native_write_zarr(data, offset)):
            _record_io("write", "native", data.nbytes, self.path,
                       time.perf_counter() - t0)
            return
        if self._ts is None:
            raise ValueError(
                f"{self.path}: native-only dataset (lz4) — writes must "
                "be block-aligned and dtype-matched")
        if self._multipart_write(data, offset):
            return
        sel = self._sel(offset, data.shape)
        if self.reversed_axes:
            data = data.transpose(tuple(range(data.ndim))[::-1])
        if hasattr(self._ts, "read"):
            self._ts[sel].write(np.ascontiguousarray(data)).result()
            via = "tensorstore"
            if getattr(self.store, "is_remote_object", False):
                _REMOTE_WRITE_BYTES.inc(int(data.nbytes))
        else:
            self._ts[sel] = data
            via = "h5py"
        _record_io("write", via, data.nbytes, self.path,
                   time.perf_counter() - t0)

    def _multipart_write(self, data: np.ndarray,
                         offset: Sequence[int]) -> bool:
        """Remote direct writes: split a multi-chunk box along storage-chunk
        boundaries and push the per-chunk puts through a bounded concurrent
        pool with retry/backoff (parallel/retry.py) instead of one
        serialized tensorstore write — each part touches exactly one chunk,
        so concurrent parts never contend and a retried part re-puts its
        whole object (no partial chunk is ever visible). Returns False
        (caller takes the ordinary single-write path) for non-remote
        stores, ``BST_UPLOAD_THREADS<=1``, or single-chunk boxes."""
        if not getattr(self.store, "is_remote_object", False):
            return False
        threads = config.get_int("BST_UPLOAD_THREADS")
        if threads <= 1 or not hasattr(self._ts, "read"):
            return False
        try:
            block = self.block_size
            dims = self.shape
        except Exception:
            return False
        ndim = data.ndim
        if len(block) != ndim or any(int(b) <= 0 for b in block):
            return False
        off = [int(o) for o in offset]
        import itertools

        grids = [range(off[d] // block[d],
                       (off[d] + data.shape[d] - 1) // block[d] + 1)
                 for d in range(ndim)]
        positions = list(itertools.product(*grids))
        if len(positions) <= 1:
            return False
        rev = tuple(range(ndim))[::-1]
        parts = []
        for pos in positions:
            lo = [max(off[d], pos[d] * block[d]) for d in range(ndim)]
            hi = [min(off[d] + data.shape[d], (pos[d] + 1) * block[d],
                      dims[d]) for d in range(ndim)]
            if any(hi[d] <= lo[d] for d in range(ndim)):
                continue
            src = tuple(slice(lo[d] - off[d], hi[d] - off[d])
                        for d in range(ndim))
            parts.append((lo, data[src]))

        def put_one(item):
            lo, part = item
            psel = self._sel(lo, part.shape)
            pdata = part.transpose(rev) if self.reversed_axes else part
            _UPLOAD_INFLIGHT.inc()
            try:
                with profiling.span("io.upload", item=self.path,
                                    nbytes=int(part.nbytes)):
                    _upload_one(self, psel, np.ascontiguousarray(pdata))
            finally:
                _UPLOAD_INFLIGHT.inc(-1)

        from ..parallel.retry import run_with_retry

        t0 = time.perf_counter()
        run_with_retry(parts, put_one, max_retries=4, delay_s=0.25,
                       label="upload", verbose=False,
                       threads=min(int(threads), len(parts)))
        _record_io("write", "tensorstore", data.nbytes, self.path,
                   time.perf_counter() - t0)
        _REMOTE_WRITE_BYTES.inc(int(data.nbytes))
        return True

    def _native_n5_eligible(self) -> str | None:
        """Shared native-codec eligibility gate for N5 reads AND writes:
        local N5 store, zstd/raw (or, with liblz4 present, lz4) codec,
        ``BST_NATIVE_IO`` on. Returns the compression type, or None when
        the tensorstore path must be used."""
        if (self.reversed_axes or self.store is None
                or getattr(self.store, "format", None) != StorageFormat.N5
                or not getattr(self.store, "is_local", False)
                or not config.get_bool("BST_NATIVE_IO")):
            return None
        comp = (self._meta_file_cached("attributes.json")
                or {}).get("compression", {})
        ctype = comp.get("type", "zstd")
        from . import native_blockio

        if ctype == "lz4":
            return "lz4" if native_blockio.has_lz4() else None
        if ctype not in ("zstd", "raw"):
            return None
        return ctype

    def _native_write(self, data: np.ndarray, offset: Sequence[int]) -> bool:
        """N5 + zstd/raw + block-aligned box -> write chunk files natively.
        Returns False when ineligible (caller falls back to tensorstore)."""
        ctype = self._native_n5_eligible()
        if ctype is None:
            return False
        comp = (self._meta_file_cached("attributes.json")
                or {}).get("compression", {})
        from . import native_blockio

        block = self.block_size
        dims = self.shape
        if data.dtype != self.dtype:
            return False
        for d in range(data.ndim):
            o, s = int(offset[d]), int(data.shape[d])
            if o % block[d] != 0 or s <= 0 or o + s > dims[d]:
                return False
            # box must end on a storage-block boundary or the array edge
            if (o + s) % block[d] != 0 and (o + s) != dims[d]:
                return False
        # a compute block may span several storage blocks (blockScale > 1):
        # split per storage block, each an exact full/edge chunk file
        if any(int(data.shape[d]) > block[d] for d in range(data.ndim)):
            grid = [range(0, int(data.shape[d]), block[d])
                    for d in range(data.ndim)]
            import itertools

            for corner in itertools.product(*grid):
                sub = data[tuple(
                    slice(c, min(c + block[d], data.shape[d]))
                    for d, c in enumerate(corner))]
                off = [int(offset[d]) + c for d, c in enumerate(corner)]
                if not self._native_write(np.ascontiguousarray(sub), off):
                    return False
            return True
        pos = [int(offset[d]) // block[d] for d in range(data.ndim)]
        path = os.path.join(self.store._kvpath(self.path),
                            *[str(p) for p in pos])
        if ctype == "lz4":  # the level slot carries the LZ4Block blockSize
            level = int(comp.get("blockSize", 65536))
        else:
            level = int(comp.get("level", 3)) or 3
        native_blockio.write_block(path, data, compression=ctype, level=level)
        return True

    def _meta_file_cached(self, name: str):
        """Parse a per-dataset metadata file, cached against its
        (mtime_ns, size) signature — recreating the dataset at the same
        path invalidates the cache (ADVICE r4: a plain first-access cache
        could drive the native codec with stale codec/fill metadata)."""
        if not hasattr(self, "_meta_cache"):
            self._meta_cache: dict = {}
        p = os.path.join(self.store._kvpath(self.path), name)
        try:
            st = os.stat(p)
            sig = (st.st_mtime_ns, st.st_size)
        except OSError:
            sig = None
        ent = self._meta_cache.get(name)
        if ent is not None and ent[0] == sig:
            return ent[1]
        meta = None
        if sig is not None:
            try:
                with open(p) as f:
                    meta = json.load(f)
            except (OSError, ValueError):
                meta = None
        self._meta_cache[name] = (sig, meta)
        return meta

    def _zarr_meta(self) -> dict | None:
        return self._meta_file_cached(".zarray")

    def _native_write_zarr(self, data: np.ndarray, offset: Sequence[int]) -> bool:
        """zarr v2 + zstd/raw + chunk-aligned box -> write chunk files
        natively: the C side walks the transposed (disk-order) strides, so no
        Python-side transpose copy happens. Returns False when ineligible."""
        if (not self.reversed_axes or self.store is None
                or getattr(self.store, "format", None) != StorageFormat.ZARR
                or not getattr(self.store, "is_local", False)
                or not config.get_bool("BST_NATIVE_IO")):
            return False
        from . import native_blockio

        meta = self._zarr_meta()
        if (meta is None or meta.get("order") != "C"
                or meta.get("dimension_separator", ".") != "."
                or meta.get("filters")):
            return False
        comp = meta.get("compressor")
        if comp is None:
            ctype, level = "raw", 0
        elif comp.get("id") == "zstd":
            ctype, level = "zstd", int(comp.get("level", 3))
        else:
            return False
        if data.dtype != self.dtype or np.dtype(meta["dtype"]).byteorder == ">":
            return False
        fill = meta.get("fill_value") or 0
        block = self.block_size
        dims = self.shape
        for d in range(data.ndim):
            o, s = int(offset[d]), int(data.shape[d])
            if o % block[d] != 0 or s <= 0:
                return False
            if (o + s) != dims[d] and (o + s) % block[d] != 0:
                return False  # box must end on a chunk (or array) boundary
        import itertools

        root = self.store._kvpath(self.path)
        grid = [range(0, int(data.shape[d]), block[d])
                for d in range(data.ndim)]
        for corner in itertools.product(*grid):
            sub = data[tuple(slice(c, min(c + block[d], data.shape[d]))
                             for d, c in enumerate(corner))]
            pos = [(int(offset[d]) + c) // block[d]
                   for d, c in enumerate(corner)]
            name = ".".join(str(p) for p in reversed(pos))
            rev = tuple(range(sub.ndim))[::-1]
            native_blockio.write_zarr_chunk(
                os.path.join(root, name), sub.transpose(rev),
                tuple(reversed(block)), compression=ctype, level=level,
                fill_value=fill,
            )
        return True

    def read_full(self) -> np.ndarray:
        return self.read((0,) * len(self.shape), self.shape)


def _upload_one(ds: "Dataset", sel, part: np.ndarray) -> None:
    """One multipart upload part — module-level so tests can inject
    transient put failures (tests/test_tiered_io.py monkeypatches this)."""
    ds._ts[sel].write(part).result()


class ChunkStore:
    """A root N5/ZARR container on a local path or cloud URI.

    Roots may be plain paths or ``s3://bucket/…``, ``gs://bucket/…``,
    ``memory://…`` URIs (the reference's URITools/N5Util URI routing,
    util/N5Util.java:47-80); tensorstore kvstore drivers do the transport."""

    def __init__(self, root: str | os.PathLike, fmt: StorageFormat):
        self.is_local = not uris.has_scheme(root)
        self.root = uris.strip_file_scheme(root) if self.is_local else str(root)
        # remote OBJECT stores (network round trip per chunk) as opposed to
        # merely non-local roots like memory:// — the tiered-IO engine keys
        # prefetch/remote-cache/multipart eligibility on this
        self.is_remote_object = str(self.root).startswith(("s3://", "gs://"))
        self.format = StorageFormat(fmt)
        if self.format == StorageFormat.HDF5:
            raise ValueError("use Hdf5Store for HDF5")
        self._kv = None

    def _kvstore(self):
        """Root-level tensorstore KvStore (non-local roots)."""
        if self._kv is None:
            self._kv = ts.KvStore.open(
                uris.kvstore_spec(self.root), context=ts_context()).result()
        return self._kv

    # -- raw object IO (attribute files, markers) --------------------------

    def _read_obj(self, rel: str) -> bytes | None:
        if self.is_local:
            p = os.path.join(self.root, rel)
            if not os.path.exists(p):
                return None
            with open(p, "rb") as f:
                return f.read()
        r = self._kvstore().read(rel).result()
        return bytes(r.value) if r.state == "value" else None

    def _write_obj(self, rel: str, data: bytes) -> None:
        if self.is_local:
            p = os.path.join(self.root, rel)
            os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
            with open(p, "wb") as f:
                f.write(data)
            return
        self._kvstore().write(rel, data).result()

    # -- creation ----------------------------------------------------------

    @staticmethod
    def create(root: str | os.PathLike, fmt: StorageFormat) -> "ChunkStore":
        fmt = StorageFormat(fmt)
        store = ChunkStore(root, fmt)
        if store.is_local:
            os.makedirs(store.root, exist_ok=True)
        if fmt == StorageFormat.N5:
            store._merge_json("attributes.json", {"n5": "2.5.1"})
        else:
            store._merge_json(".zgroup", {"zarr_format": 2})
        return store

    @staticmethod
    def open(root: str | os.PathLike) -> "ChunkStore":
        root = str(root)
        probe = ChunkStore(root, StorageFormat.N5)
        if probe._read_obj("attributes.json") is not None:
            return probe
        if (probe._read_obj(".zgroup") is not None
                or probe._read_obj(".zattrs") is not None):
            return ChunkStore(root, StorageFormat.ZARR)
        # guess by extension
        if root.rstrip("/").endswith((".zarr", ".ome.zarr")):
            return ChunkStore(root, StorageFormat.ZARR)
        return probe

    # -- attributes --------------------------------------------------------

    def _attr_rel(self, group: str) -> str:
        name = "attributes.json" if self.format == StorageFormat.N5 else ".zattrs"
        g = group.strip("/")
        return f"{g}/{name}" if g else name

    def _merge_json(self, rel: str, updates: dict) -> None:
        raw = self._read_obj(rel)
        current: dict = json.loads(raw) if raw else {}
        current.update(updates)
        self._write_obj(rel, json.dumps(
            current, indent=0, default=_json_default).encode())

    def get_attributes(self, group: str = "") -> dict:
        raw = self._read_obj(self._attr_rel(group))
        return json.loads(raw) if raw else {}

    def set_attribute(self, group: str, key_path: str, value: Any) -> None:
        """N5-style nested attribute: key path split on '/'."""
        attrs = self.get_attributes(group)
        keys = [k for k in key_path.split("/") if k]
        node = attrs
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = value
        self._write_obj(self._attr_rel(group), json.dumps(
            attrs, indent=0, default=_json_default).encode())

    def get_attribute(self, group: str, key_path: str, default: Any = None) -> Any:
        node: Any = self.get_attributes(group)
        for k in [k for k in key_path.split("/") if k]:
            if not isinstance(node, dict) or k not in node:
                return default
            node = node[k]
        return node

    # -- datasets ----------------------------------------------------------

    def _kvpath(self, path: str) -> str:
        """Local filesystem path of a sub-path (local roots only)."""
        return os.path.join(self.root, path.strip("/"))

    def _dataset_kvstore(self, path: str) -> dict:
        return uris.kvstore_spec(self.root, path.strip("/"))

    def create_dataset(
        self,
        path: str,
        shape: Sequence[int],
        block_size: Sequence[int],
        dtype: str | np.dtype,
        compression: str = "zstd",
        delete_existing: bool = False,
        compression_level: int | None = None,
    ) -> Dataset:
        """Create a chunked dataset. ``shape``/``block_size`` xyz-first."""
        chunkcache.get_cache().invalidate_prefix(self.root, path)
        dtype = np.dtype(dtype).name
        if dtype not in _N5_DTYPES:
            raise ValueError(f"unsupported dtype {dtype}")
        shape = tuple(int(v) for v in shape)
        block = tuple(min(int(b), int(s)) if int(s) > 0 else int(b)
                      for b, s in zip(block_size, shape))
        if self.format == StorageFormat.N5:
            comp = _n5_compression(compression, compression_level)
            if comp["type"] == "lz4":
                # tensorstore's n5 driver has no lz4 codec: create the
                # dataset metadata directly and serve IO through the
                # native LZ4Block codec (reference parity with
                # util/N5Util.java:87-88)
                from . import native_blockio

                if not (self.is_local and native_blockio.has_lz4()
                        and config.get_bool("BST_NATIVE_IO")):
                    raise ValueError(
                        "lz4 N5 datasets need a local store and the native "
                        "codec (liblz4, BST_NATIVE_IO enabled)")
                if delete_existing:
                    self.remove(path)
                elif self.is_dataset(path):
                    raise ValueError(f"{path} already exists")
                self._write_obj(
                    self._attr_rel(path.strip("/")),
                    json.dumps({
                        "dimensions": list(shape),
                        "blockSize": list(block),
                        "dataType": dtype,
                        "compression": comp,
                    }, indent=0).encode())
                return Dataset(self, path, None, reversed_axes=False)
            spec = {
                "driver": "n5",
                "kvstore": self._dataset_kvstore(path),
                "metadata": {
                    "dimensions": list(shape),
                    "blockSize": list(block),
                    "dataType": dtype,
                    "compression": comp,
                },
                "create": True,
                "delete_existing": delete_existing,
            }
            arr = ts.open(spec, context=ts_context()).result()
            return Dataset(self, path, arr, reversed_axes=False)
        else:
            meta: dict[str, Any] = {
                "shape": list(shape[::-1]),
                "chunks": list(block[::-1]),
                "dtype": _ZARR_DTYPE[dtype],
                "compressor": _zarr_compressor(compression, compression_level),
            }
            spec = {
                "driver": "zarr",
                "kvstore": self._dataset_kvstore(path),
                "metadata": meta,
                "create": True,
                "delete_existing": delete_existing,
            }
            arr = ts.open(spec, context=ts_context()).result()
            return Dataset(self, path, arr, reversed_axes=True)

    def open_dataset(self, path: str) -> Dataset:
        if self.format == StorageFormat.N5:
            spec = {
                "driver": "n5",
                "kvstore": self._dataset_kvstore(path),
                "open": True,
            }
            try:
                arr = ts.open(spec, context=ts_context()).result()
            except ValueError as e:
                # tensorstore has no n5 lz4 codec: sniff the metadata only
                # on failure (no extra read on the happy path, and remote
                # stores get the clear message too) and serve the dataset
                # natively when possible
                ctype = self.get_attribute(path.strip("/"),
                                           "compression/type")
                if ctype != "lz4":
                    raise
                from . import native_blockio

                native_ok = config.get_bool("BST_NATIVE_IO")
                if self.is_local and native_blockio.has_lz4() and native_ok:
                    return Dataset(self, path, None, reversed_axes=False)
                raise ValueError(
                    f"{path}: lz4-compressed N5 needs the native codec on "
                    f"a local store (liblz4 loaded: "
                    f"{native_blockio.has_lz4()}, local: {self.is_local}, "
                    f"BST_NATIVE_IO enabled: {config.get_bool('BST_NATIVE_IO')})"
                ) from e
            return Dataset(self, path, arr, reversed_axes=False)
        spec = {
            "driver": "zarr",
            "kvstore": self._dataset_kvstore(path),
            "open": True,
        }
        return Dataset(self, path, ts.open(spec, context=ts_context()).result(),
                       reversed_axes=True)

    def is_dataset(self, path: str) -> bool:
        p = path.strip("/")
        if self.format == StorageFormat.N5:
            raw = self._read_obj(f"{p}/attributes.json" if p else "attributes.json")
            return raw is not None and "dimensions" in json.loads(raw)
        return self._read_obj(f"{p}/.zarray" if p else ".zarray") is not None

    def exists(self, path: str) -> bool:
        if self.is_local:
            return os.path.exists(self._kvpath(path))
        p = path.strip("/")
        kv = self._kvstore()
        # metadata-only presence checks: exact key, then any key under p/
        if kv.list(ts.KvStore.KeyRange(p, p + "\x00")).result():
            return True
        keys = kv.list(ts.KvStore.KeyRange(p + "/", p + "0")).result()
        return len(keys) > 0

    def remove(self, path: str = "") -> None:
        chunkcache.get_cache().invalidate_prefix(self.root, path)
        if self.is_local:
            p = self._kvpath(path) if path else self.root
            if os.path.exists(p):
                shutil.rmtree(p)
            return
        kv = self._kvstore()
        p = path.strip("/")
        if p:
            kv.delete_range(ts.KvStore.KeyRange(p + "/", p + "0")).result()
            kv.write(p, None).result()  # delete exact key if present
        else:
            kv.delete_range(ts.KvStore.KeyRange()).result()

    def list_children(self, path: str = "") -> list[str]:
        if self.is_local:
            p = self._kvpath(path)
            if not os.path.isdir(p):
                return []
            return sorted(
                d for d in os.listdir(p) if os.path.isdir(os.path.join(p, d))
            )
        p = path.strip("/")
        prefix = p + "/" if p else ""
        keys = self._kvstore().list(
            ts.KvStore.KeyRange(prefix, prefix[:-1] + "0" if prefix else "")
        ).result()
        kids = set()
        for k in keys:
            rest = k.decode()[len(prefix):]
            if "/" in rest:
                kids.add(rest.split("/", 1)[0])
        return sorted(kids)

    def make_group(self, path: str) -> None:
        if self.is_local:
            p = self._kvpath(path)
            os.makedirs(p, exist_ok=True)
        if self.format == StorageFormat.ZARR:
            rel = f"{path.strip('/')}/.zgroup"
            if self._read_obj(rel) is None:
                self._write_obj(rel, json.dumps({"zarr_format": 2}).encode())


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON serializable: {type(o)}")


class Hdf5Store:
    """Minimal HDF5 store (local-only, single process — the reference keeps the
    same restriction via a process-wide shared writer, N5Util.java:45-64)."""

    def __init__(self, path: str | os.PathLike, mode: str = "a"):
        import h5py

        if uris.has_scheme(path):
            raise ValueError(
                "HDF5 containers are local-only (the reference has the same "
                f"restriction, CreateFusionContainer.java:141-145): {path}")
        self.path = uris.strip_file_scheme(path)
        self.format = StorageFormat.HDF5
        self.is_local = True
        self._f = h5py.File(self.path, mode)

    def create_dataset(
        self,
        path: str,
        shape: Sequence[int],
        block_size: Sequence[int],
        dtype: str | np.dtype,
        compression: str = "gzip",
        delete_existing: bool = False,
    ) -> Dataset:
        shape = tuple(int(v) for v in shape)
        block = tuple(min(int(b), int(s)) for b, s in zip(block_size, shape))
        chunkcache.get_cache().invalidate_prefix(self.path, path)
        if delete_existing and path in self._f:
            del self._f[path]
        kw = {}
        compression, level = _split_level(compression, None)
        if compression not in ("raw", "gzip"):
            raise ValueError(
                f"HDF5 store supports only gzip/raw compression, got {compression!r}"
            )
        if compression != "raw":
            kw["compression"] = "gzip"
            if level is not None:
                kw["compression_opts"] = int(level)
        d = self._f.create_dataset(
            path, shape=shape[::-1], chunks=block[::-1], dtype=np.dtype(dtype), **kw
        )
        return Dataset(self, path, d, reversed_axes=True)

    def open_dataset(self, path: str) -> Dataset:
        return Dataset(self, path, self._f[path], reversed_axes=True)

    def put_array(self, path: str, data: np.ndarray) -> None:
        """Store a small auxiliary array verbatim (no axis reversal) — BDV
        ``s{XX}/resolutions`` / ``subdivisions`` tables."""
        if path in self._f:
            del self._f[path]
        self._f.create_dataset(path, data=data)

    def get_array(self, path: str) -> np.ndarray | None:
        if path not in self._f:
            return None
        return np.asarray(self._f[path])

    def exists(self, path: str) -> bool:
        return path.strip("/") in self._f

    def is_dataset(self, path: str) -> bool:
        import h5py

        return isinstance(self._f.get(path.strip("/")), h5py.Dataset)

    def set_attribute(self, group: str, key_path: str, value: Any) -> None:
        g = self._f.require_group(group or "/")
        g.attrs[key_path] = json.dumps(value) if isinstance(value, (dict, list)) else value

    def get_attribute(self, group: str, key_path: str, default: Any = None) -> Any:
        g = self._f.get(group or "/")
        if g is None or key_path not in g.attrs:
            return default
        v = g.attrs[key_path]
        if isinstance(v, (bytes, str)):
            try:
                return json.loads(v)
            except (json.JSONDecodeError, TypeError):
                return v
        return v

    def close(self):
        self._f.close()
