"""Interest points read back from what ``detect`` stored, without the
program: the project XML's ``ViewInterestPoints`` say which views hold
points of a label and where, ``interestpoints.n5`` beside the XML holds
them (mvrecon's ``InterestPointsN5``: per view and label
``interestpoints/loc`` float64 [3, N], N in the group's ``numPoints``
where the datasets are padded). N5 blocks are parsed here: a big-endian
header (mode, dimensions, block size) then the payload, raw, gzip or zstd
(``blockio.py``'s)."""

from __future__ import annotations

import gzip
import json
import os
import struct
import xml.etree.ElementTree as ET

import numpy as np

from . import blockio


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def read_block(ds_dir: str, grid_pos, attrs: dict) -> np.ndarray | None:
    """One N5 block as stored, (x-fastest) flat, in the dataset's type;
    None where no block was written (N5 reads it as zeros)."""
    path = os.path.join(ds_dir, *(str(int(g)) for g in grid_pos))
    if not os.path.exists(path):
        return None
    with open(path, "rb") as f:
        raw = f.read()
    mode, ndim = struct.unpack(">HH", raw[:4])
    dims = struct.unpack(f">{ndim}I", raw[4:4 + 4 * ndim])
    at = 4 + 4 * ndim
    if mode == 1:           # varlength: the element count follows
        (count,) = struct.unpack(">I", raw[at:at + 4])
        at += 4
    else:
        count = int(np.prod(dims))
    dtype = np.dtype(attrs["dataType"]).newbyteorder(">")
    body = raw[at:]
    kind = attrs.get("compression", {}).get("type", "raw")
    if kind == "zstd":
        body = blockio.zstd_decompress(body, count * dtype.itemsize)
    elif kind == "gzip":
        body = gzip.decompress(body)
    elif kind != "raw":
        raise ValueError(f"no decoder for N5 compression {kind!r}")
    return np.frombuffer(body, dtype, count).astype(dtype.newbyteorder("="))


def read_locs(group: str) -> np.ndarray:
    """(N, 3) float64 locations of one ``<view>/<label>`` group."""
    base = os.path.join(group, "interestpoints")
    ds = os.path.join(base, "loc")
    attrs = _json(os.path.join(ds, "attributes.json"))
    n_stored = int(attrs["dimensions"][1])
    step = int(attrs["blockSize"][1])
    n = int(_json(os.path.join(base, "attributes.json")).get("numPoints",
                                                             n_stored))
    parts = []
    for j in range(-(-n_stored // step)):
        block = read_block(ds, (0, j), attrs)
        parts.append(np.zeros((min(step, n_stored - j * step), 3))
                     if block is None else block.reshape(-1, 3))
    locs = np.concatenate(parts) if parts else np.zeros((0, 3))
    return locs[:n].astype(np.float64)


def stored_points(xml: str, label: str) -> dict[int, np.ndarray]:
    """{setup: (N, 3) full-resolution view pixels} of every view the saved
    project registers points of ``label`` for (timepoint 0)."""
    root = ET.parse(xml).getroot()
    store = os.path.join(os.path.dirname(os.path.abspath(xml)),
                         "interestpoints.n5")
    out = {}
    for el in root.iter("ViewInterestPointsFile"):
        if el.get("label") == label and int(el.get("timepoint")) == 0:
            out[int(el.get("setup"))] = read_locs(
                os.path.join(store, (el.text or "").strip()))
    return out
