"""Stage adapter: pairwise stitching, project XML in to project XML out.

A pass is what ``bst stitching`` does: load the unregistered project,
``stitch_all_pairs`` -> ``filter_results`` -> ``store_results``, save the
XML. The comparison parses the stored ``StitchingResults`` itself and holds
each pair's shift against the generator's ground truth and against
``reference.pcm`` over crops made again from the seed.
"""

from __future__ import annotations

import os
import re
from concurrent.futures import ThreadPoolExecutor
import xml.etree.ElementTree as ET

import numpy as np

from ..reference import pcm

_NUM = re.compile(r"[-+]?(?:\d+\.?\d*|\.\d+)(?:[eE][-+]?\d+)?")


class Stage:
    unit_scale = 1.0    # work is counted and reported in pairs

    def __init__(self, job: dict):
        self.job = job
        self.acq = job["acq"]
        self.opt = job["traffic"]["options"]
        self.ds = tuple(int(v) for v in self.opt["downsampling"])
        self._refs: dict = {}
        self._xml_text = None

    def xml_text(self) -> str:
        """The unregistered project, its images named by absolute path:
        each pass saves into a project file of its own, beside none of the
        fixture's."""
        if self._xml_text is None:
            fixture = self.job["fixture_dir"]
            with open(os.path.join(fixture, "unregistered.xml")) as f:
                self._xml_text = f.read().replace(
                    '<n5 type="relative">dataset.n5</n5>',
                    '<n5 type="absolute">'
                    + os.path.join(fixture, "dataset.n5") + "</n5>")
        return self._xml_text

    # ------------------------------------------------------ the timed path

    def run_pass(self, index: int) -> dict:
        from bigstitcher_spark_tpu.io.chunkcache import get_cache
        from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
        from bigstitcher_spark_tpu.io.spimdata import SpimData
        from bigstitcher_spark_tpu.models.stitching import (
            StitchingParams, filter_results, stitch_all_pairs, store_results,
        )

        get_cache().clear()
        xml = os.path.join(self.job["work_dir"], f"pass{index}.xml")
        with open(xml, "w") as f:
            f.write(self.xml_text())
        opt = self.opt
        sd = SpimData.load(xml)
        loader = ViewLoader(sd)
        params = StitchingParams(
            downsampling=self.ds, peaks_to_check=int(opt["peaks_to_check"]),
            subpixel=bool(opt["subpixel"]), min_r=float(opt["min_r"]),
            max_r=1.0, channel_combine="AVERAGE",
            illum_combine="PICK_BRIGHTEST")
        results = stitch_all_pairs(sd, loader, sd.view_ids(), params,
                                   progress=False,
                                   devices=self.job["chips"])
        kept = filter_results(results, params, verbose=False)
        store_results(sd, kept, computed=results)
        sd.save(xml)
        return {"work": float(len(results)), "out": xml, "path": None}

    def release(self) -> None:
        from bigstitcher_spark_tpu.io.chunkcache import get_cache

        get_cache().clear()

    # ------------------------------------------------------ the comparison

    def pairs(self):
        """Every pair of tiles whose nominal boxes overlap, with the
        overlap's box in the nominal frame (inclusive max)."""
        noms = self.acq.nominal_offsets
        size = np.array(self.acq.size)
        for a in range(len(noms)):
            for b in range(a + 1, len(noms)):
                lo = np.maximum(noms[a], noms[b])
                hi = np.minimum(noms[a], noms[b]) + size - 1
                if np.all(hi >= lo):
                    yield a, b, lo.astype(np.int64), hi.astype(np.int64)

    def _crop(self, tile: int, lo, hi):
        """The overlap as the stage reads it: at the stored level whose
        factors are the downsampling, from the tile's own pixel grid (a
        level pixel x sits at full-resolution f*x + (f-1)/2, the BDV
        mipmap convention). Returns the crop and its origin in level px."""
        level = self.acq.levels.index(self.ds)
        f = np.array(self.ds)
        shape = -(-(hi - lo + 1) // f)
        p0 = np.round((lo - self.acq.nominal_offsets[tile] - (f - 1) / 2)
                      / f).astype(np.int64)
        return self.acq.region(tile, level, p0, p0 + shape
                               ).astype(np.float32), p0

    @staticmethod
    def stored(xml: str) -> dict:
        """{(setup a, setup b): (shift xyz, r)} from a project's
        StitchingResults, parsed here and not by the program."""
        out = {}
        for el in ET.parse(xml).getroot().find("StitchingResults"):
            a = int(el.get("views_a").split(";")[0].split(",")[1])
            b = int(el.get("views_b").split(";")[0].split(",")[1])
            flat = [float(v) for v in _NUM.findall(el.findtext("shift"))]
            out[(a, b)] = (np.array([flat[3], flat[7], flat[11]]),
                           float(_NUM.findall(el.findtext("correlation"))[0]))
        return out

    def reference(self, score_dtype=np.float64) -> dict:
        """Every pair by the numpy PCM; the pairs in threads (numpy and the
        FFT release the interpreter lock). Kept per scoring type: the
        check and the control both hold against the float64 one."""
        if score_dtype in self._refs:
            return self._refs[score_dtype]

        def one(pair):
            a, b, lo, hi = pair
            ca, pa = self._crop(a, lo, hi)
            cb, pb = self._crop(b, lo, hi)
            s, r = pcm.stitch_pair(
                ca, cb, n_peaks=int(self.opt["peaks_to_check"]),
                subpixel=bool(self.opt["subpixel"]), score_dtype=score_dtype)
            # level px back to full resolution; the crops' origins differ
            # from the nominal offsets by their rounding
            noms = self.acq.nominal_offsets
            return (a, b), (np.array(self.ds, np.float64) * (pb - pa + s)
                            - (noms[a] - noms[b]), r)

        with ThreadPoolExecutor(6) as pool:
            self._refs[score_dtype] = dict(pool.map(one, self.pairs()))
        return self._refs[score_dtype]

    def _truth(self, a: int, b: int) -> np.ndarray:
        t, n = self.acq.true_offsets, self.acq.nominal_offsets
        return (t[a] - n[a]) - (t[b] - n[b])

    def _compare(self, got: dict, ref: dict) -> dict:
        missing = sum(1 for k in ref if k not in got)
        both = [k for k in ref if k in got]
        return {
            "pair_missing": float(missing),
            "pair_truth_err_px": max(
                (float(np.abs(got[k][0] - self._truth(*k)).max())
                 for k in both), default=0.0),
            "pair_ref_err_px": max(
                (float(np.abs(got[k][0] - ref[k][0]).max()) for k in both),
                default=0.0),
            "pair_r_err": max((abs(got[k][1] - ref[k][1]) for k in both),
                              default=0.0),
        }

    def check(self, passes: list[dict]) -> dict:
        """Every pair of every pass. The reference is computed once: all
        passes read the same acquisition."""
        ref = self.reference()
        worst: dict = {}
        for p in passes:
            for k, v in self._compare(self.stored(p["out"]), ref).items():
                worst[k] = max(worst.get(k, 0.0), v)
        worst["checked_pairs"] = float(len(ref) * len(passes))
        return worst

    def control(self) -> dict:
        """The control: the reference scored in float32, in the program's
        place."""
        return self._compare(self.reference(np.float32), self.reference())

    # --------------------------------------------------- the kernels' work

    def kernel_calls(self, passes: list[dict]) -> list[dict]:
        """One PCM call a shape bucket (pairs whose crops pad to the same
        power-of-two box go together), as the stage documents."""
        buckets: dict[tuple, int] = {}
        for _a, _b, lo, hi in self.pairs():
            shape = -(-(hi - lo + 1) // np.array(self.ds))
            fft = tuple(1 << int(np.ceil(np.log2(max(int(s), 1))))
                        for s in shape)
            buckets[fft] = buckets.get(fft, 0) + 1
        calls = [{"fft_shape": list(k), "pairs": n,
                  "peaks": int(self.opt["peaks_to_check"])}
                 for k, n in sorted(buckets.items())]
        return calls * len(passes)
