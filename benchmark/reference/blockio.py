"""Chunk formats, written and read without the program: zstd through
ctypes (no ``zstandard`` module is installed), N5 blocks out, zarr v2
chunks back in. The benchmark's fixture goes to disk through this file and
its comparison reads the program's output through it, so neither side of
``correct`` passes through the code under test."""

from __future__ import annotations

import ctypes
import json
import os
import struct

import numpy as np

_Z = ctypes.CDLL("libzstd.so.1")
_Z.ZSTD_compressBound.restype = ctypes.c_size_t
_Z.ZSTD_compressBound.argtypes = [ctypes.c_size_t]
_Z.ZSTD_compress.restype = ctypes.c_size_t
_Z.ZSTD_compress.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                             ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int]
_Z.ZSTD_decompress.restype = ctypes.c_size_t
_Z.ZSTD_decompress.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                               ctypes.c_void_p, ctypes.c_size_t]
_Z.ZSTD_isError.restype = ctypes.c_uint
_Z.ZSTD_isError.argtypes = [ctypes.c_size_t]

ZSTD_LEVEL = 3  # the codec's own default, as n5-zstd writes it


def zstd_compress(raw: bytes) -> bytes:
    cap = _Z.ZSTD_compressBound(len(raw))
    dst = ctypes.create_string_buffer(cap)
    n = _Z.ZSTD_compress(dst, cap, raw, len(raw), ZSTD_LEVEL)
    if _Z.ZSTD_isError(n):
        raise OSError("ZSTD_compress failed")
    return dst.raw[:n]


def zstd_decompress(comp: bytes, raw_size: int) -> bytes:
    dst = ctypes.create_string_buffer(raw_size)
    n = _Z.ZSTD_decompress(dst, raw_size, comp, len(comp))
    if _Z.ZSTD_isError(n) or n != raw_size:
        raise OSError(f"ZSTD_decompress gave {n} of {raw_size} bytes")
    return dst.raw


def write_json(path: str, doc: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)


def n5_dataset_attrs(dims, block, factors) -> dict:
    return {"blockSize": list(block), "dimensions": list(dims),
            "compression": {"type": "zstd", "level": ZSTD_LEVEL},
            "dataType": "uint16", "downsamplingFactors": list(factors)}


def write_n5_block(ds_dir: str, grid_pos, zyx: np.ndarray) -> int:
    """One N5 block. ``zyx`` is the block indexed [z, y, x] in C order,
    which is N5's x-fastest payload order. Returns the bytes written."""
    bz, by, bx = zyx.shape
    head = struct.pack(">HHIII", 0, 3, bx, by, bz)
    body = zstd_compress(zyx.astype(">u2", copy=False).tobytes())
    path = os.path.join(ds_dir, *(str(int(g)) for g in grid_pos))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(head)
        f.write(body)
    return len(head) + len(body)


class ZarrArray:
    """Read-only view of one zarr v2 array on local disk (zstd or no
    compressor, C order) — what the fusion stage writes."""

    def __init__(self, path: str):
        with open(os.path.join(path, ".zarray")) as f:
            meta = json.load(f)
        comp = meta.get("compressor")
        if meta["zarr_format"] != 2 or meta["order"] != "C" or (
                comp is not None and comp["id"] != "zstd") \
                or meta.get("filters"):
            raise ValueError(f"{path}: not a plain zstd zarr v2 array: "
                             f"{meta}")
        self.path = path
        self.shape = tuple(meta["shape"])
        self.chunks = tuple(meta["chunks"])
        self.dtype = np.dtype(meta["dtype"])
        self.fill = meta.get("fill_value") or 0
        self.sep = meta.get("dimension_separator", ".")
        self.compressed = comp is not None

    def chunk_path(self, idx) -> str:
        return os.path.join(self.path, self.sep.join(str(i) for i in idx))

    def read_chunk(self, idx) -> np.ndarray | None:
        """The stored chunk at grid index ``idx``, or None if no file."""
        try:
            with open(self.chunk_path(idx), "rb") as f:
                buf = f.read()
        except FileNotFoundError:
            return None
        n = int(np.prod(self.chunks)) * self.dtype.itemsize
        raw = zstd_decompress(buf, n) if self.compressed else buf
        return np.frombuffer(raw, self.dtype).reshape(self.chunks)

    def read(self, lo, hi) -> np.ndarray:
        """Box [lo, hi) in the array's own (C-order) axes; chunks with no
        file read as the fill value."""
        out = np.full([h - l for l, h in zip(lo, hi)], self.fill, self.dtype)
        first = [l // c for l, c in zip(lo, self.chunks)]
        last = [(h - 1) // c for h, c in zip(hi, self.chunks)]
        for idx in np.ndindex(*[b - a + 1 for a, b in zip(first, last)]):
            idx = tuple(a + i for a, i in zip(first, idx))
            chunk = self.read_chunk(idx)
            if chunk is None:
                continue
            c0 = [i * c for i, c in zip(idx, self.chunks)]
            src, dst = [], []
            for d in range(len(lo)):
                a = max(lo[d], c0[d])
                b = min(hi[d], c0[d] + self.chunks[d], self.shape[d])
                src.append(slice(a - c0[d], b - c0[d]))
                dst.append(slice(a - lo[d], b - lo[d]))
            out[tuple(dst)] = chunk[tuple(src)]
        return out

    def stored_chunks(self) -> int:
        n = 0
        for _root, _dirs, files in os.walk(self.path):
            n += sum(1 for f in files if not f.startswith("."))
        return n
