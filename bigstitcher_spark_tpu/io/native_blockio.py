"""ctypes binding for the native N5 block codec (native/blockio.cpp).

ctypes foreign calls release the GIL, so a Python thread pool over
``write_block``/``read_block`` encodes (zstd) and writes chunks truly in
parallel — the role the reference fills with prebuilt codec JNI libs
(N5Util.java:82-105, SURVEY.md §2.3). ``libblockio.so`` is a build product
(gitignored): the first use builds it from ``native/blockio.cpp`` with the
machine's ``make``/``g++``/``libzstd`` and rebuilds it whenever the source
is newer, so the loaded library always exports everything this module
binds. A build or load failure raises — the codec is on by default
(``BST_NATIVE_IO``), and silently reading and writing through the slower
path is not what the user asked for; ``BST_NATIVE_IO=0`` is how to run
without it.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_LIB = None

_SRC_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                        os.pardir, "native"))

COMPRESSION = {"raw": 0, "zstd": 1, "lz4": 2}


def _stale(so: str) -> bool:
    """Whether the library is missing or older than its source."""
    try:
        return os.path.getmtime(so) < os.path.getmtime(
            os.path.join(_SRC_DIR, "blockio.cpp"))
    except OSError:
        return True


def _load():
    """The bound library, built first if missing or stale. Unlocked on
    purpose: threads racing the first use each run ``make`` (which compiles
    to a temp name and renames, so nobody maps a half-written file) and
    bind the same library — idempotent, and no lock is held across the
    child process."""
    global _LIB
    if _LIB is not None:
        return _LIB
    so = os.path.join(_SRC_DIR, "libblockio.so")
    if _stale(so):
        proc = subprocess.run(["make", "-C", _SRC_DIR], capture_output=True,
                              text=True, timeout=300)
        if proc.returncode != 0:
            raise RuntimeError(
                f"building {so} failed (make rc={proc.returncode}; "
                f"set BST_NATIVE_IO=0 to run without the native codec):\n"
                f"{proc.stdout}{proc.stderr}")
    lib = ctypes.CDLL(so)
    lib.n5_encode_bound.restype = ctypes.c_int64
    lib.n5_encode_bound.argtypes = [ctypes.c_int64, ctypes.c_int32]
    lib.n5_write_block_file.restype = ctypes.c_int64
    lib.n5_write_block_file.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_int32, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32,
    ]
    lib.n5_read_block_file.restype = ctypes.c_int64
    lib.n5_read_block_file.argtypes = [
        ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.n5_read_block_region.restype = ctypes.c_int64
    lib.n5_read_block_region.argtypes = [
        ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.lz4_available.restype = ctypes.c_int32
    lib.lz4_available.argtypes = []
    lib.zarr_write_chunk_file.restype = ctypes.c_int64
    lib.zarr_write_chunk_file.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_int32, ctypes.c_int32,
    ]
    _LIB = lib
    return _LIB


def loaded() -> bool:
    """Whether this process has loaded the codec (i.e. some read or write
    went through it) — what run manifests report; never triggers a
    build."""
    return _LIB is not None


def has_lz4() -> bool:
    """True when liblz4 loads at runtime, i.e. the native codec can serve
    N5 lz4 (LZ4Block) chunks. Reference codec surface parity:
    util/N5Util.java:87-88."""
    return bool(_load().lz4_available())


def write_zarr_chunk(
    chunk_path: str,
    data: np.ndarray,
    chunk_shape: tuple[int, ...],
    compression: str = "zstd",
    level: int = 3,
    fill_value=0,
) -> None:
    """Write one zarr v2 chunk file from a strided DISK-ORDER view.

    ``data``'s axes must already be in on-disk (C) order — callers pass a
    transposed numpy VIEW (no copy; the C side walks the strides). Chunks
    shorter than ``chunk_shape`` (array edge) are padded with
    ``fill_value``."""
    lib = _load()
    ndim = data.ndim
    strides = (ctypes.c_int64 * ndim)(*data.strides)
    src_dims = (ctypes.c_uint32 * ndim)(*data.shape)
    chk_dims = (ctypes.c_uint32 * ndim)(*chunk_shape)
    fill = np.asarray(fill_value or 0, dtype=data.dtype).tobytes()
    got = lib.zarr_write_chunk_file(
        chunk_path.encode(), data.ctypes.data_as(ctypes.c_void_p),
        data.dtype.itemsize, strides, src_dims, chk_dims, ndim,
        ctypes.c_char_p(fill), COMPRESSION[compression], level,
    )
    if got < 0:
        raise IOError(f"zarr_write_chunk_file({chunk_path}) failed: {got}")


def write_block(
    block_path: str,
    data: np.ndarray,
    compression: str = "zstd",
    level: int = 3,
) -> None:
    """Encode ``data`` (xyz-first logical order) as an N5 block file.

    ``data`` axes follow the store convention (first axis fastest on disk),
    so the buffer handed to C must be Fortran-contiguous w.r.t. that order.
    """
    lib = _load()
    arr = np.asfortranarray(data)
    dims = (ctypes.c_uint32 * arr.ndim)(*arr.shape)
    got = lib.n5_write_block_file(
        block_path.encode(), arr.ctypes.data_as(ctypes.c_void_p),
        arr.dtype.itemsize, dims, arr.ndim, arr.size,
        COMPRESSION[compression], level,
    )
    if got < 0:
        raise IOError(f"n5_write_block_file({block_path}) failed: {got}")


def read_block_region(
    block_path: str,
    dst: np.ndarray,
    dst_offset: tuple[int, ...],
    src_lo: tuple[int, ...],
    copy_dims: tuple[int, ...],
    compression: str = "zstd",
) -> int | None:
    """Decode one N5 block file and copy ``copy_dims`` voxels starting at
    ``src_lo`` (in-chunk coords) directly into ``dst[dst_offset...]`` —
    the big-endian swap fuses with the strided write (one pass; no
    intermediate chunk array, no numpy assembly copy). Returns elements
    copied, or None if the file is absent."""
    lib = _load()
    ndim = dst.ndim
    es = dst.dtype.itemsize
    base = dst.ctypes.data + sum(
        int(dst_offset[d]) * dst.strides[d] for d in range(ndim))
    dstr = (ctypes.c_int64 * ndim)(*dst.strides)
    lo = (ctypes.c_uint32 * ndim)(*[int(v) for v in src_lo])
    cd = (ctypes.c_uint32 * ndim)(*[int(v) for v in copy_dims])
    dims = (ctypes.c_uint32 * 16)()
    nd = ctypes.c_int32()
    got = lib.n5_read_block_region(
        block_path.encode(), es, COMPRESSION[compression], ndim, lo, cd,
        ctypes.c_void_p(base), dstr, dims, ctypes.byref(nd))
    if got == -7:
        return None
    if got < 0:
        raise IOError(f"n5_read_block_region({block_path}) failed: {got}")
    return int(got)


def read_block(
    block_path: str,
    dtype: np.dtype,
    max_shape: tuple[int, ...],
    compression: str = "zstd",
) -> np.ndarray | None:
    """Decode one N5 block file -> xyz-first array, or None if absent."""
    lib = _load()
    dtype = np.dtype(dtype)
    cap = int(np.prod(max_shape)) * dtype.itemsize
    out = np.empty(int(np.prod(max_shape)), dtype=dtype)
    dims = (ctypes.c_uint32 * 16)()
    ndim = ctypes.c_int32()
    got = lib.n5_read_block_file(
        block_path.encode(), dtype.itemsize, COMPRESSION[compression],
        out.ctypes.data_as(ctypes.c_void_p), cap, dims, ctypes.byref(ndim),
    )
    if got == -7:
        return None
    if got < 0:
        raise IOError(f"n5_read_block_file({block_path}) failed: {got}")
    shape = tuple(int(dims[d]) for d in range(ndim.value))
    return out[: int(np.prod(shape))].reshape(shape, order="F")
