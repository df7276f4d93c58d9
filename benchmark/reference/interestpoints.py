"""The interest points a multi-view acquisition carries after ``detect`` and
``match``, made from the configuration without the program, and written as
the state those stages leave: ``interestpoints.n5`` beside a project XML.

One point a bead and view where the bead's centre lies inside the view's
image: the bead's true centre in the view's pixels plus a localisation
error N(0, ``localisation_sigma_px``) a coordinate. One correspondence for
every pair of views that both hold the bead (what RANSAC would keep),
stored on both sides as the matcher stores them. Everything is drawn from
the fixture's ``geometry_seed``: the points belong to the configuration,
as the beads do, so every ``--seed`` fits the same grids.

On disk (mvrecon's ``InterestPointsN5``, what ``io/interestpoints.py`` and
the GUI read): per view and label ``interestpoints/id`` uint64 [1, N],
``interestpoints/loc`` float64 [3, N], ``correspondences/data`` uint64
[3, M] of (own id, other id, code) with ``idMap`` {"tp,setup,label": code}.
Blocks are raw, one a dataset. The project XML is written into the work
directory with an absolute image loader path to the cached fixture's
``dataset.n5``: the fixture directory is never written to.
"""

from __future__ import annotations

import os
import struct
import xml.etree.ElementTree as ET

import numpy as np

from . import blockio
from .fixture import Acquisition, _write_xml, invert


def make_points(acq: Acquisition, spec: dict) -> list[dict]:
    """Per view: ``ids`` (N,) uint64, ``locs`` (N,3) float64 view pixels,
    ``beads`` (N,) the bead each point sits on, and ``corrs``: rows of
    (own id, other view, other id)."""
    seed = int(acq.p["geometry_seed"])
    size = np.asarray(acq.size, np.float64)
    views = []
    for v, model in enumerate(acq.image_models):
        inv = invert(model)
        centres = acq.beads @ inv[:, :3].T + inv[:, 3]
        held = np.flatnonzero(np.all((centres >= 0)
                                     & (centres <= size - 1), axis=1))
        rng = np.random.default_rng([seed, 0x1B7, v])
        locs = centres[held] + rng.normal(
            0.0, float(spec["localisation_sigma_px"]), (len(held), 3))
        views.append({"ids": np.arange(len(held), dtype=np.uint64),
                      "locs": locs, "beads": held, "corrs": []})
    for a in range(len(views)):
        for b in range(len(views)):
            if a == b:
                continue
            _both, ia, ib = np.intersect1d(views[a]["beads"],
                                           views[b]["beads"],
                                           return_indices=True)
            views[a]["corrs"] += [(int(i), b, int(j))
                                  for i, j in zip(ia, ib)]
    return views


def _write_raw_block(ds_dir: str, rows: np.ndarray, dtype: str) -> None:
    """One raw N5 block holding the whole (components, points) array;
    N5's payload runs the first dimension fastest."""
    dims = [int(rows.shape[0]), int(rows.shape[1])]
    blockio.write_json(os.path.join(ds_dir, "attributes.json"), {
        "blockSize": dims, "compression": {"type": "raw"},
        "dataType": dtype, "dimensions": dims})
    big = {"uint64": ">u8", "float64": ">f8"}[dtype]
    path = os.path.join(ds_dir, "0", "0")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(struct.pack(">HHII", 0, 2, *dims))
        f.write(np.ascontiguousarray(rows.T).astype(big).tobytes())


def group(view: int, label: str) -> str:
    return f"tpId_0_viewSetupId_{view}/{label}"


def write_store(root: str, views: list[dict], label: str) -> None:
    blockio.write_json(os.path.join(root, "attributes.json"),
                       {"n5": "2.5.1"})
    for v, pts in enumerate(views):
        base = os.path.join(root, group(v, label))
        n = len(pts["ids"])
        ip = os.path.join(base, "interestpoints")
        blockio.write_json(os.path.join(ip, "attributes.json"), {
            "pointcloud": "1.0.0", "type": "list", "numPoints": n})
        # an empty list is stored as one zero row, as the program pads it
        ids = pts["ids"].reshape(1, -1) if n else np.zeros((1, 1), np.uint64)
        locs = pts["locs"].T if n else np.zeros((3, 1))
        _write_raw_block(os.path.join(ip, "id"), ids, "uint64")
        _write_raw_block(os.path.join(ip, "loc"), locs, "float64")
        id_map: dict[str, int] = {}
        rows = np.zeros((3, max(len(pts["corrs"]), 1)), np.uint64)
        for i, (own, other, other_id) in enumerate(pts["corrs"]):
            code = id_map.setdefault(f"0,{other},{label}", len(id_map))
            rows[:, i] = (own, other_id, code)
        co = os.path.join(base, "correspondences")
        blockio.write_json(os.path.join(co, "attributes.json"), {
            "correspondences": "1.0.0", "idMap": id_map})
        _write_raw_block(os.path.join(co, "data"), rows, "uint64")


def write_project(acq: Acquisition, spec: dict, fixture_dir: str,
                  out_dir: str) -> str:
    """``interestpoints.n5`` and the project XML that names it, in
    ``out_dir``; the XML's image loader points at the fixture's
    ``dataset.n5``. Returns the XML's path."""
    label = spec["label"]
    views = make_points(acq, spec)
    write_store(os.path.join(out_dir, "interestpoints.n5"), views, label)
    xml = os.path.join(out_dir, "registered-interestpoints.xml")
    _write_xml(xml, acq, acq.registered)
    tree = ET.parse(xml)
    loader = tree.getroot().find("SequenceDescription/ImageLoader/n5")
    loader.set("type", "absolute")
    loader.text = os.path.join(os.path.abspath(fixture_dir), "dataset.n5")
    vip = tree.getroot().find("ViewInterestPoints")
    for v in range(len(views)):
        el = ET.SubElement(vip, "ViewInterestPointsFile", timepoint="0",
                           setup=str(v), label=label,
                           params="benchmark/reference/interestpoints.py")
        el.text = group(v, label)
    tree.write(xml, encoding="unicode", xml_declaration=True)
    return xml
