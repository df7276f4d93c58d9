"""Stage adapter: interest-point-driven non-rigid fusion, container in to
container out.

A pass is what ``bst create-fusion-container`` and ``bst nonrigid-fusion``
do for one part of the acquisition: a new OME-ZARR container over the
part's bounding box, then ``models.nonrigid_fusion.fuse_nonrigid_project``,
the one function ``cli/fusion_tools.nonrigid_fusion_cmd`` calls once it has
opened its container (views, unique points, level-0 dataset, the fusion).
The interest points are the configuration's (``reference/interestpoints.py``),
written once a run, in set-up, into the work directory beside a project XML
whose image loader points at the cached fixture. The comparison is
``stages/fuse.py``'s over ``reference/nonrigid.py``.
"""

from __future__ import annotations

import os

import numpy as np

from ..reference import interestpoints, nonrigid
from ..reference.fixture import invert
from . import fuse

_NAMES = {"fuse_mean_abs_diff": "nonrigid_mean_abs_diff",
          "fuse_max_abs_diff": "nonrigid_max_abs_diff"}


def _named(numbers: dict) -> dict:
    return {_NAMES.get(k, k): v for k, v in numbers.items()}


class Stage(fuse.Stage):

    def __init__(self, job: dict):
        super().__init__(job)
        self.spec = job["config"]["interest_points"]
        self.xml = None         # written by the first pass: set-up
        self._unique = None

    # ------------------------------------------------------ the timed path

    def run_pass(self, index: int) -> dict:
        # first, so that a program without the entry ends the run here,
        # before anything is written
        from bigstitcher_spark_tpu.models.nonrigid_fusion import (
            fuse_nonrigid_project,
        )

        from bigstitcher_spark_tpu.io.chunkcache import get_cache
        from bigstitcher_spark_tpu.io.chunkstore import StorageFormat
        from bigstitcher_spark_tpu.io.container import (
            create_fusion_container, open_container, read_container_meta,
        )
        from bigstitcher_spark_tpu.io.spimdata import SpimData
        from bigstitcher_spark_tpu.models.affine_fusion import BlendParams
        from bigstitcher_spark_tpu.observe import progress
        from bigstitcher_spark_tpu.utils.geometry import Interval

        if self.xml is None:
            self.xml = interestpoints.write_project(
                self.acq, self.spec, self.job["fixture_dir"],
                self.job["work_dir"])
        # as a stage process starts: nothing decoded, nothing on the device
        get_cache().clear()
        progress.reset_records()
        out = os.path.join(self.job["work_dir"], f"pass{index}.ome.zarr")
        opt = self.opt
        create_fusion_container(
            out, StorageFormat.ZARR, self.xml, 1, 1,
            Interval([int(v) for v in self.lo],
                     [int(v) - 1 for v in self.hi]),
            data_type="uint16", block_size=self.block,
            downsamplings=[[1, 1, 1]], compression="zstd",
            min_intensity=opt["min_intensity"],
            max_intensity=opt["max_intensity"])
        store = open_container(out)
        meta = read_container_meta(store)
        sd = SpimData.load(meta.input_xml)
        voxels = fuse_nonrigid_project(
            store, meta, sd, sd.view_ids(), list(opt["labels"]),
            float(opt["cpd"]), float(opt["alpha"]), opt["fusion_type"],
            BlendParams(border=(0.0, 0.0, 0.0),
                        range=tuple(float(v)
                                    for v in opt["blending_range"])),
            tuple(opt["block_scale"]), devices=self.job["chips"])
        paths = [r.get("path") for r in progress.records()
                 if r.get("stage") == "nonrigid-fusion"]
        return {"work": float(voxels), "out": out,
                "array": os.path.join(
                    out, meta.mr_infos[0][0].dataset.strip("/")),
                "path": paths[-1] if paths else None}

    # ------------------------------------------------------ the comparison

    def _reference(self, lo, shp, precision: str = "float64"):
        if self._unique is None:    # the same for every block and pass
            self._unique = nonrigid.unique_points(
                interestpoints.make_points(self.acq, self.spec),
                self.acq.registered)
        cb = np.array(self.compute_block)
        compute_lo = self.lo + (np.asarray(lo) - self.lo) // cb * cb
        return nonrigid.fuse_box(
            self.acq, self._unique, lo, shp, compute_lo, self.compute_block,
            float(self.opt["cpd"]), float(self.opt["alpha"]),
            float(self.opt["blending_range"][0]), precision)

    def check(self, passes: list[dict]) -> dict:
        return _named(super().check(passes))

    def control(self) -> dict:
        return _named(super().control())

    # --------------------------------------------------- the kernels' work

    def kernel_calls(self, passes: list[dict]) -> list[dict]:
        """One call a compute block that a view reaches: its voxels, the
        voxels of each such view's source box (what the block's corners
        span in the view's pixels, a pixel of margin, clipped to the
        image), the block's shape and its control grid's — reckoned from
        the geometry, not from what the driver did."""
        cpd = float(self.opt["cpd"])
        grid = [int(np.ceil(b / cpd)) + 3 for b in self.compute_block]
        size = np.asarray(self.acq.size)
        calls = []
        for lo, shp in self._grid(self.compute_block):
            corners = lo + np.array([[x, y, z] for x in (0, shp[0])
                                     for y in (0, shp[1])
                                     for z in (0, shp[2])], np.float64)
            patches = []
            for m in self.acq.registered:
                inv = invert(m)
                p = corners @ inv[:, :3].T + inv[:, 3]
                box = np.minimum(np.ceil(p.max(0)) + 2, size) \
                    - np.maximum(np.floor(p.min(0)) - 1, 0)
                if np.all(box > 0):
                    patches.append(int(np.prod(box)))
            if patches:
                calls.append({"voxels": int(np.prod(shp)),
                              "patches": patches,
                              "block": list(self.compute_block),
                              "grid": grid})
        return calls * len(passes)
