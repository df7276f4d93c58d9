"""Stage adapter: DoG interest-point detection, project XML in to project
XML and ``interestpoints.n5`` out.

A pass is what ``bst detect-interestpoints`` does with the traffic's
options: ``models.detection.detect_project`` over every view of the
two-channel project (``stages/stitch_grouped.py``'s, made in set-up by its
``xml_text``), into a directory of its own, so that no pass finds the
points of the one before; the decoded-chunk LRU cleared first, as a stage
process starts.

The comparison parses every pass's stored points itself
(``reference/n5points.py``) and holds them

- against ``reference/dog.py`` (float64) in ``check_blocks`` detection
  blocks drawn from the seed once a run, each block's reference computed
  once: reference points with no stored point within ``MATCH_PX`` are
  missing, stored points inside the block's core with no reference point
  within it are extra, and the worst distance of a matched pair is the
  reference error;
- against the specimen in every view: a bead no other bead comes within 4
  sigma of (detection pixels), inside the view's core (a halo from its
  border), has to have a stored point within ``TRUTH_PX``; the worst such
  distance is the truth error.

Distances are in full-resolution pixels. A maximum whose response lies
within ``MARGIN`` of the threshold (relative) is neither required nor
counted extra: float32 arithmetic may put it on either side. A stored
point whose maximum lies in a neighbouring block may sit up to a voxel and
a half inside this block's core (a fit that moved its base), so "inside"
leaves out ``SHRINK`` detection pixels at faces shared with another block;
faces on the view's border are kept whole. The control is the reference
with its blurs in bfloat16 in the program's place, over the same blocks.
"""

from __future__ import annotations

import os

import numpy as np

from ..reference import channels, dog, n5points
from ..reference.fixture import invert
from . import stitch_grouped

MATCH_PX = 0.5      # a stored point and a reference point are one
TRUTH_PX = 1.0      # a bead found
MARGIN = 1e-3       # of the threshold: the float32 band
SHRINK = 1.5        # detection px left out at a shared face
ISOLATED_SIGMAS = 4.0
BATCH = 8           # blocks a call on one chip: DetectionParams.batch_size
CANDIDATES = 4096   # the K strongest maxima a block that leave the device


def nearest(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Distance from each point of ``a`` to the nearest of ``b`` (inf
    where ``b`` is empty)."""
    out = np.full(len(a), np.inf)
    if len(b):
        for i in range(0, len(a), 512):
            d = a[i:i + 512, None, :] - b[None, :, :]
            out[i:i + 512] = np.sqrt((d * d).sum(-1).min(1))
    return out


class Stage:
    unit_scale = 1e-6   # work is counted in full-resolution voxels, Mvox
    # the pass reads stages/stitch_grouped.py's project, made as it makes it
    xml_text = stitch_grouped.Stage.xml_text

    def __init__(self, job: dict):
        self.job = job
        self.acq = job["acq"]
        self.opt = opt = job["traffic"]["options"]
        self.ch1 = channels.second_channel(
            self.acq.p, job["config"]["second_channel"], self.acq.seed)
        self._xml_text = None
        self.ds = (int(opt["downsample_xy"]),) * 2 \
            + (int(opt["downsample_z"]),)
        if self.ds not in self.acq.levels:
            raise ValueError(f"the reference reads the stored level the "
                             f"stage detects on; {self.ds} is not stored")
        self.level = self.acq.levels.index(self.ds)
        self.sigma = float(opt["sigma"])
        self.threshold = float(opt["threshold"])
        self.halo = dog.halo(self.sigma)
        self.block = np.array(opt["block_size"], np.int64)
        self.dims = np.array(self.acq.level_size(self.level), np.int64)
        self.n_setups = 2 * self.acq.n_views
        self._refs: dict = {}
        self._truth_beads: dict = {}

    def source(self, setup: int):
        """(acquisition, tile) of a setup: channel 1 follows channel 0."""
        n = self.acq.n_views
        return (self.acq, setup) if setup < n else (self.ch1, setup - n)

    def cores(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """A view's detection blocks, (lo, hi) on its detection grid."""
        n = -(-self.dims // self.block)
        return [(np.array(g) * self.block,
                 np.minimum((np.array(g) + 1) * self.block, self.dims))
                for g in np.ndindex(*n)]

    # ------------------------------------------------------ the timed path

    def run_pass(self, index: int) -> dict:
        # the one entry first: a program without it stops here, before
        # set-up makes the second channel
        from bigstitcher_spark_tpu.io.chunkcache import get_cache
        from bigstitcher_spark_tpu.models.detection import (
            DetectionParams, detect_project,
        )

        get_cache().clear()
        out = os.path.join(self.job["work_dir"], f"pass{index}")
        os.makedirs(out)
        xml = os.path.join(out, "dataset.xml")
        with open(xml, "w") as f:
            f.write(self.xml_text())
        o = self.opt
        params = DetectionParams(
            label=o["label"], sigma=self.sigma, threshold=self.threshold,
            downsample_xy=self.ds[0], downsample_z=self.ds[2],
            min_intensity=o["min_intensity"],
            max_intensity=o["max_intensity"],
            find_max=o["extrema"] in ("MAX", "BOTH"),
            find_min=o["extrema"] in ("MIN", "BOTH"),
            overlapping_only=bool(o["overlapping_only"]),
            localization=o["localization"],
            block_size=tuple(int(b) for b in self.block))
        detect_project(xml, params, progress=False, devices=self.job["chips"])
        return {"work": float(self.n_setups * np.prod(self.acq.size)),
                "out": xml, "path": None}

    def release(self) -> None:
        from bigstitcher_spark_tpu.io.chunkcache import get_cache

        get_cache().clear()

    # ------------------------------------------------------ the comparison

    def sample(self) -> list[tuple[int, int]]:
        """``check_blocks`` (setup, block) drawn from the seed, once a
        run."""
        keys = [(s, b) for s in range(self.n_setups)
                for b in range(len(self.cores()))]
        rng = np.random.default_rng([self.job["seed"], 0xD06])
        picks = rng.choice(len(keys), min(int(self.opt["check_blocks"]),
                                          len(keys)), replace=False)
        return [keys[int(i)] for i in sorted(picks)]

    def _detect(self, keys, precision: str) -> dict:
        """{(setup, block): {"pts": full-resolution points, "required"}}
        by the reference in ``precision``; a view's stored level is made
        once for all its blocks and dropped after them."""
        out = {}
        for setup in sorted({s for s, _b in keys}):
            acq, tile = self.source(setup)
            vol = dog.level_volume(acq, tile, self.level)
            top = len(acq.levels) - 1
            coarse = vol if top == self.level \
                else dog.level_volume(acq, tile, top)
            lo_i, hi_i = float(coarse.min()), float(coarse.max())
            del coarse
            for s, b in keys:
                if s != setup:
                    continue
                lo, hi = self.cores()[b]
                r = dog.detect_block(
                    dog.mirror_box(vol, lo - self.halo, hi + self.halo),
                    lo_i, hi_i, self.sigma, self.threshold, MARGIN,
                    precision)
                out[(s, b)] = {
                    "pts": dog.full_resolution(r["pos"] - self.halo + lo,
                                               self.ds),
                    "required": r["required"]}
        return out

    def reference(self) -> dict:
        """The float64 reference of the sampled blocks, kept once a run:
        the check and the control both hold against it."""
        if not self._refs:
            self._refs = self._detect(self.sample(), "float64")
        return self._refs

    def _inside(self, pts: np.ndarray, lo, hi) -> np.ndarray:
        """Which full-resolution points lie in the block's core, less
        ``SHRINK`` at faces shared with another block."""
        f = np.array(self.ds, np.float64)
        det = (pts - (f - 1) / 2) / f
        a = np.where(lo > 0, lo - 0.5 + SHRINK, -np.inf)
        b = np.where(hi < self.dims, hi - 0.5 - SHRINK, np.inf)
        return np.all((det >= a) & (det < b), axis=1)

    def _compare(self, points: dict, refs: dict) -> dict:
        missing, extra, err = 0, 0, 0.0
        for (setup, b), ref in refs.items():
            got = points.get(setup, np.zeros((0, 3)))
            d = nearest(ref["pts"], got)
            missing += int(np.sum(ref["required"] & (d > MATCH_PX)))
            if np.any(d <= MATCH_PX):
                err = max(err, float(d[d <= MATCH_PX].max()))
            lo, hi = self.cores()[b]
            inside = got[self._inside(got, lo, hi)]
            extra += int(np.sum(nearest(inside, ref["pts"]) > MATCH_PX))
        return {"detect_missing": float(missing),
                "detect_extra": float(extra), "detect_ref_err_px": err}

    def truth_beads(self, setup: int) -> np.ndarray:
        """Full-resolution positions in the view of the beads it has to
        find: isolated, inside its core."""
        acq, tile = self.source(setup)
        if tile not in self._truth_beads:
            inv = invert(acq.image_models[tile])
            centres = acq.beads @ inv[:, :3].T + inv[:, 3]
            f = np.array(self.ds, np.float64)
            det = (centres - (f - 1) / 2) / f
            alone = np.array([np.sort(np.sqrt(((det - p) ** 2).sum(1)))[1]
                              for p in det]) > ISOLATED_SIGMAS * self.sigma
            core = np.all((det >= self.halo)
                          & (det <= self.dims - 1 - self.halo), axis=1)
            self._truth_beads[tile] = centres[alone & core]
        return self._truth_beads[tile]

    def _truth(self, points: dict) -> dict:
        missing, err = 0, 0.0
        for setup in range(self.n_setups):
            d = nearest(self.truth_beads(setup),
                        points.get(setup, np.zeros((0, 3))))
            missing += int(np.sum(d > TRUTH_PX))
            if np.any(d <= TRUTH_PX):
                err = max(err, float(d[d <= TRUTH_PX].max()))
        return {"detect_truth_missing": float(missing),
                "detect_truth_err_px": err}

    def check(self, passes: list[dict]) -> dict:
        """Every pass's stored points, read back by this file's parser."""
        refs = self.reference()
        worst: dict = {}
        for p in passes:
            pts = n5points.stored_points(p["out"], self.opt["label"])
            for k, v in {**self._compare(pts, refs),
                         **self._truth(pts)}.items():
                worst[k] = max(worst.get(k, 0.0), v)
        worst["checked_blocks"] = float(len(refs) * len(passes))
        return worst

    def control(self) -> dict:
        """The control: the reference with its blurs in bfloat16 (values,
        taps, accumulation) in the program's place, in the same blocks."""
        refs = self.reference()
        points: dict = {}
        for (s, _b), r in self._detect(list(refs), "bfloat16").items():
            points[s] = np.concatenate([points.get(s, np.zeros((0, 3))),
                                        r["pts"]])
        return self._compare(points, refs)

    # --------------------------------------------------- the kernels' work

    def kernel_calls(self, passes: list[dict]) -> list[dict]:
        """Every view's blocks with halo, bucketed by shape, ``BATCH`` a
        call: reckoned from the geometry, not from what the program did."""
        shapes: dict[tuple, int] = {}
        for lo, hi in self.cores():
            shp = tuple(int(v) for v in hi - lo + 2 * self.halo)
            shapes[shp] = shapes.get(shp, 0) + self.n_setups
        calls = [{"blocks": min(BATCH, n - i), "shape": list(shp),
                  "itemsize": 2, "sigma": self.sigma,
                  "candidates": CANDIDATES}
                 for shp, n in sorted(shapes.items())
                 for i in range(0, n, BATCH)]
        return calls * len(passes)
