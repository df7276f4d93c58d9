"""Stage adapter: affine fusion, container in to container out.

A pass is what ``bst create-fusion-container`` and ``bst affine-fusion``
do for one part of the acquisition: a new OME-ZARR container over the
part's bounding box, then ``models.affine_fusion.fuse_volume`` with the
arguments ``cli/fusion_tools.affine_fusion_cmd`` builds. The comparison
reads stored chunks back with ``reference.blockio`` and holds them against
``reference.fusion`` over source voxels made again from the seed.
"""

from __future__ import annotations

import os

import numpy as np

from ..reference import blockio, fusion


# the least summed blend weight at which a voxel is compared: under it the
# float32 cosine ramp (1 + cos(pi - x)) is a few steps of 6e-8 or nought
WEIGHT_FLOOR = 1e-5


class Stage:
    unit_scale = 1e-6   # work is counted in voxels, reported in Mvox

    def __init__(self, job: dict):
        self.job = job
        self.acq = job["acq"]
        opt = job["traffic"]["options"]
        self.opt = opt
        self.block = tuple(opt["block_size"])
        self.compute_block = tuple(
            b * s for b, s in zip(self.block, opt["block_scale"]))
        part = job["cell"]["part"]
        lo = self.acq.bbox_min + np.array(part["offset_blocks"]) \
            * self.compute_block
        hi = np.minimum(lo + np.array(part["size_blocks"])
                        * self.compute_block, self.acq.bbox_max + 1)
        self.lo, self.hi = lo, hi
        self.xml = os.path.join(job["fixture_dir"], "registered.xml")
        self._covered = None
        self._refs = {}

    # ------------------------------------------------------ the timed path

    def run_pass(self, index: int) -> dict:
        from bigstitcher_spark_tpu.io.chunkcache import get_cache
        from bigstitcher_spark_tpu.io.chunkstore import StorageFormat
        from bigstitcher_spark_tpu.io.container import (
            create_fusion_container, open_container, read_container_meta,
        )
        from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
        from bigstitcher_spark_tpu.io.spimdata import SpimData
        from bigstitcher_spark_tpu.models.affine_fusion import (
            BlendParams, fuse_volume,
        )
        from bigstitcher_spark_tpu.observe import progress
        from bigstitcher_spark_tpu.utils.geometry import Interval

        # as a stage process starts: nothing decoded, nothing on the device
        # (the tile cache is the composite driver's; no cell may take it)
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
        loader = ViewLoader(sd)
        mr = meta.mr_infos[0]
        ds = store.open_dataset(mr[0].dataset.strip("/"))
        stats = fuse_volume(
            sd, loader, sd.view_ids(), ds, meta.bbox,
            block_size=tuple(meta.block_size),
            block_scale=tuple(opt["block_scale"]),
            fusion_type=opt["fusion_type"],
            blend=BlendParams(border=(0.0, 0.0, 0.0),
                              range=tuple(float(v)
                                          for v in opt["blending_range"])),
            anisotropy_factor=float("nan"), out_dtype=meta.data_type,
            min_intensity=meta.min_intensity,
            max_intensity=meta.max_intensity, masks=False,
            mask_offset=(0.0, 0.0, 0.0), zarr_ct=(0, 0), coefficients=None,
            devices=self.job["chips"], io_threads=4, pyramid=None)
        paths = [r.get("path") for r in progress.records()
                 if r.get("stage") == "affine-fusion"]
        return {"work": float(stats.voxels), "out": out,
                "array": os.path.join(out, mr[0].dataset.strip("/")),
                "path": paths[-1] if paths else None}

    def release(self) -> None:
        from bigstitcher_spark_tpu.io.chunkcache import get_cache

        get_cache().clear()

    # ------------------------------------------------------ the comparison

    def _grid(self, block):
        """The part cut into ``block``-sized boxes: (world lo, shape)."""
        n = [-(-int(h - l) // b) for l, h, b in zip(self.lo, self.hi, block)]
        for g in np.ndindex(*n):
            lo = self.lo + np.array(g) * block
            yield lo, tuple(int(v) for v in np.minimum(block, self.hi - lo))

    def _views_at(self, lo, shape) -> int:
        """How many views hold one of the block's corners or its centre."""
        pts = np.array([[x, y, z] for x in (0, shape[0] - 1)
                        for y in (0, shape[1] - 1)
                        for z in (0, shape[2] - 1)]
                       + [[s // 2 for s in shape]], np.float64) + lo
        n = 0
        for m in self.acq.registered:
            inv = fusion.invert(m)
            p = pts @ inv[:, :3].T + inv[:, 3]
            n += bool(np.any(np.all((p >= 0) & (p <= np.array(self.acq.size)
                                                - 1), axis=1)))
        return n

    def _sample(self, k: int, n: int) -> list:
        """Pass ``k``'s sample of ``n`` covered container blocks, from the
        seed; the first is one where most views blend."""
        if self._covered is None:   # the same for every pass
            blocks = [(lo, shp, self._views_at(lo, shp))
                      for lo, shp in self._grid(self.block)]
            self._covered = [b for b in blocks if b[2] > 0]
        covered = self._covered
        most = max(b[2] for b in covered)
        blended = [b for b in covered if b[2] == most]
        rng = np.random.default_rng([self.job["seed"], k, 0xB10C])
        picks = [blended[int(rng.integers(len(blended)))]]
        picks += [covered[int(i)] for i in rng.choice(
            len(covered), min(n - 1, len(covered)), replace=False)]
        return [(lo, shp) for lo, shp, _n in picks], len(covered)

    def _per_pass(self, n_passes: int) -> int:
        """``check_blocks`` over the window's passes, and never under two a
        pass: the one where most views blend and one drawn from all."""
        return max(2, int(self.opt["check_blocks"]) // n_passes)

    def _reference(self, lo, shp, precision: str = "float64"):
        return fusion.fuse_box(self.acq, lo, shp,
                               float(self.opt["blending_range"][0]),
                               precision)

    def reference(self, lo, shp):
        """``_reference`` of one container block in float64, kept once a
        run: a part holds a few distinct blocks and every pass draws its
        sample from them."""
        key = (tuple(int(v) for v in lo), tuple(int(v) for v in shp))
        ref = self._refs.get(key)
        if ref is None:
            ref = self._refs[key] = self._reference(lo, shp)
        return ref

    def _compare(self, pairs) -> dict:
        """Worst block of (got, ref, summed reference weight) triples."""
        means, worst, left_out, voxels = [], 0.0, 0, 0
        for got, ref, wsum in pairs:
            d = np.abs(got.astype(np.float64) - ref.astype(np.float64))
            # a voxel that only the last thousandths of a pixel of a view's
            # edge reach has a weight that float32 cannot tell from nought:
            # its value is 0/0 to rounding. Left out by a rule on the
            # reference's own weight, and counted
            thin = (wsum > 0) & (wsum < WEIGHT_FLOOR)
            left_out += int(thin.sum())
            voxels += d.size
            means.append(float(d[~thin].mean()))
            worst = max(worst, float(d[~thin].max()))
        return {"fuse_mean_abs_diff": max(means), "fuse_max_abs_diff": worst,
                "fuse_left_out_share": left_out / voxels,
                "checked_blocks": float(len(means))}

    def check(self, passes: list[dict]) -> dict:
        """Sampled blocks of every pass's stored output against the numpy
        fusion; every covered chunk of every pass has to be there."""
        per_pass = self._per_pass(len(passes))
        missing, pairs = 0, []
        for k, p in enumerate(passes):
            arr = blockio.ZarrArray(p["array"])
            sample, covered = self._sample(k, per_pass)
            missing += max(0, covered - arr.stored_chunks())
            for lo, shp in sample:
                o = (lo - self.lo).astype(int)
                got = arr.read([0, 0, o[2], o[1], o[0]],
                               [1, 1, o[2] + shp[2], o[1] + shp[1],
                                o[0] + shp[0]])[0, 0].transpose(2, 1, 0)
                pairs.append((got, *self.reference(lo, shp)))
        return {**self._compare(pairs), "fuse_missing_chunks": float(missing)}

    def control(self) -> dict:
        """The control: the reference in bfloat16 in the program's place,
        over the sample a window of one pass would check, judged as
        ``check`` judges the program: by its worst block."""
        pairs = []
        for lo, shp in self._sample(0, int(self.opt["check_blocks"]))[0]:
            ref, wsum = self._reference(lo, shp)
            pairs.append((self._reference(lo, shp, "bfloat16")[0], ref, wsum))
        got = self._compare(pairs)
        return {k: got[k] for k in ("fuse_mean_abs_diff", "fuse_max_abs_diff")}

    # --------------------------------------------------- the kernels' work

    def kernel_calls(self, passes: list[dict]) -> list[dict]:
        """One call a compute block: its voxels and the views that reach
        it — reckoned from the geometry, not from what the driver did."""
        calls = []
        for lo, shp in self._grid(self.compute_block):
            views = self._views_at(lo, shp)
            if views:
                calls.append({"voxels": int(np.prod(shp)), "views": views})
        return calls * len(passes)
