"""The acquisition every cell reads, made from ``--seed`` without the program.

The configuration fixes where the views lie and what they look at
(``geometry_seed``: stage error, solved registrations, affine
perturbations, bead positions); ``--seed`` makes every voxel's noise. A
bead phantom seen by overlapping tiles (``kind: grid``) or by views rotated
about Y (``kind: multiview``), written as a BigStitcher project:
``dataset.n5`` (bdv.n5 layout, uint16, zstd, an s0 and a 2,2,1 level as a
resaved acquisition has) and the XMLs that describe it. Every N5 block is a
pure function of (seed, view, block index): integer noise from a Philox
stream of its own plus the beads that reach into it, so blocks are made in
parallel, in any order, and the comparison that decides ``correct`` makes
again exactly the source voxels it needs instead of reading them back
through the code under test.

Copied in purpose from ``bigstitcher_spark_tpu/utils/testdata.py`` (PERF.md
section 7 lists the original for a later PR): that one fills a global float
volume and draws float noise, 95 s for four 1024x1024x256 tiles; this one
stamps beads sparsely and draws one random byte a voxel.

Run as ``python -m benchmark.reference.fixture <params.json> <seed> <dir>``
it imports neither JAX nor the program, so a parent can start it before it
touches the chip.
"""

from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import blockio

BEAD_RADIUS_SIGMAS = 3.0


def _rot_y(deg: float) -> np.ndarray:
    c, s = np.cos(np.radians(deg)), np.sin(np.radians(deg))
    return np.array([[c, 0.0, s], [0.0, 1.0, 0.0], [-s, 0.0, c]])


def _affine(lin, t) -> np.ndarray:
    return np.hstack([np.asarray(lin, np.float64),
                      np.asarray(t, np.float64).reshape(3, 1)])


def invert(a) -> np.ndarray:
    inv = np.linalg.inv(a[:, :3])
    return _affine(inv, -inv @ a[:, 3])


class Acquisition:
    """Geometry, ground truth and voxels of one seeded acquisition."""

    def __init__(self, params: dict, seed: int):
        self.p = params
        self.seed = int(seed)
        self.size = tuple(int(v) for v in params["tile_size"])
        self.block = tuple(int(v) for v in params["block_size"])
        self.levels = [tuple(lv) for lv in params["levels"]]
        if self.levels not in ([(1, 1, 1)], [(1, 1, 1), (2, 2, 1)]) or any(
                s % 2 for s in self.size[:2]):
            raise ValueError("levels are s0 alone or s0 and 2,2,1 over even "
                             "XY sizes")
        # where the views lie and where the beads are belongs to the
        # configuration, not to the seed. Stage error and solved
        # registrations decide crop and source-box sizes, and the beads in
        # an overlap decide which peaks the stitching stage scores and how
        # far it climbs: drawn from the seed they gave every seed another
        # amount of work (PERF.md section 6: with the geometry one seed in
        # six ran 13 % slower a pass in grid1k.stitch, with the beads the
        # seeds still spanned 3 %, the same seed under 1 %) and a corner
        # overlap with a dozen beads now and then. The seed makes the noise
        # of every voxel, 2.1 GB of it
        geo = np.random.default_rng([int(params["geometry_seed"]), 0x6F1])
        rng = np.random.default_rng([int(params["geometry_seed"]), 0xBEAD])
        kind = params["kind"]
        self.calibration = np.eye(3)
        self.unregistered: list[np.ndarray] | None = None
        if kind == "grid":
            self._grid(geo)
        elif kind == "multiview":
            self._multiview(geo)
        else:
            raise ValueError(f"unknown fixture kind {kind!r}")
        self.n_views = len(self.registered)
        corners = np.array([[x, y, z] for x in (0, self.size[0] - 1)
                            for y in (0, self.size[1] - 1)
                            for z in (0, self.size[2] - 1)], np.float64)
        world = np.concatenate([corners @ m[:, :3].T + m[:, 3]
                                for m in self.registered])
        self.bbox_min = np.floor(world.min(0)).astype(np.int64)
        self.bbox_max = np.ceil(world.max(0)).astype(np.int64)
        n_beads = int(params["beads_per_tile"]) * self.n_views
        self.beads = rng.uniform(world.min(0), world.max(0), (n_beads, 3))
        # bead centres in each view's pixel space
        inverses = [invert(m) for m in self.image_models]
        self._centres = [self.beads @ inv[:, :3].T + inv[:, 3]
                         for inv in inverses]

    # ---------------------------------------------------------- geometry

    def _grid(self, rng) -> None:
        nx, ny, nz = (int(v) for v in self.p["tiles"])
        step = [s - int(self.p["overlap"]) for s in self.size]
        j = int(self.p["jitter"])
        self.true_offsets, self.nominal_offsets = [], []
        self.registered, self.unregistered = [], []
        for tz in range(nz):
            for ty in range(ny):
                for tx in range(nx):
                    true = np.array([tx * step[0], ty * step[1],
                                     tz * step[2]], np.float64) \
                        + rng.integers(0, 4, 3)
                    nominal = true + rng.integers(-j, j + 1, 3)
                    solved = true + rng.uniform(-0.3, 0.3, 3)
                    self.true_offsets.append(true)
                    self.nominal_offsets.append(nominal)
                    self.registered.append(_affine(np.eye(3), solved))
                    self.unregistered.append(_affine(np.eye(3), nominal))
        # where the voxels really are: the integer true offsets
        self.image_models = [_affine(np.eye(3), t) for t in self.true_offsets]

    def _multiview(self, rng) -> None:
        zc = float(self.p["z_calibration"])
        self.calibration = np.diag([1.0, 1.0, zc])
        centre_px = (np.array(self.size, np.float64) - 1) / 2
        extent = np.array(self.size, np.float64) * [1, 1, zc]
        centre_world = np.full(3, float(np.linalg.norm(extent)) / 2)
        eps = float(self.p["affine_perturbation"])
        self.registered = []
        for deg in self.p["angles_deg"]:
            lin = (np.eye(3) + rng.uniform(-eps, eps, (3, 3))) @ _rot_y(deg) \
                @ self.calibration
            t = centre_world + rng.uniform(-2, 2, 3) - lin @ centre_px
            self.registered.append(_affine(lin, t))
        self.image_models = self.registered

    def level_size(self, level: int):
        f = self.levels[level]
        return tuple(s // k for s, k in zip(self.size, f))

    # ------------------------------------------------------------ voxels

    def _s0_block(self, view: int, g) -> np.ndarray:
        """s0 block at grid position ``g``, indexed [z, y, x]."""
        lo = np.array([g[d] * self.block[d] for d in range(3)])
        shp = np.minimum(self.block, np.array(self.size) - lo)
        grid = [-(-s // b) for s, b in zip(self.size, self.block)]
        lin = (g[2] * grid[1] + g[1]) * grid[0] + g[0]
        rng = np.random.Generator(np.random.Philox(
            key=[self.seed, (view << 32) | lin]))
        b = np.frombuffer(rng.bytes(int(np.prod(shp))), np.uint8
                          ).reshape(shp[::-1])
        arr = (b & 15).astype(np.uint16)
        arr += b >> 4
        arr += np.uint16(self.p["background"])
        sigma = float(self.p["bead_sigma"])
        r = int(np.ceil(BEAD_RADIUS_SIGMAS * sigma))
        c = self._centres[view]
        near = np.all((c >= lo - r - 1) & (c <= lo + shp + r), axis=1)
        for p in c[near]:
            ip = np.round(p).astype(np.int64)
            a = np.maximum(ip - r, lo)
            e = np.minimum(ip + r + 1, lo + shp)
            if np.any(e <= a):
                continue
            gx, gy, gz = (np.exp(-((np.arange(a[d], e[d]) - p[d]) ** 2)
                                 / (2 * sigma ** 2)) for d in range(3))
            blob = float(self.p["bead_amplitude"]) \
                * gz[:, None, None] * gy[None, :, None] * gx[None, None, :]
            sl = tuple(slice(a[d] - lo[d], e[d] - lo[d]) for d in (2, 1, 0))
            arr[sl] += np.rint(blob).astype(np.uint16)
        return arr

    def block_zyx(self, view: int, level: int, g) -> np.ndarray:
        """Stored block of ``level`` at grid position ``g``, [z, y, x]."""
        if level == 0:
            return self._s0_block(view, g)
        fine = self.region(view, 0,
                           [g[0] * self.block[0] * 2, g[1] * self.block[1] * 2,
                            g[2] * self.block[2]],
                           [(g[0] + 1) * self.block[0] * 2,
                            (g[1] + 1) * self.block[1] * 2,
                            (g[2] + 1) * self.block[2]])
        return _mean_2x2(fine.transpose(2, 1, 0))

    def region(self, view: int, level: int, lo, hi) -> np.ndarray:
        """Voxels [lo, hi) of a view's level, indexed (x, y, z), clipped to
        the image."""
        size = self.level_size(level)
        lo = [max(0, int(v)) for v in lo]
        hi = [min(int(s), int(v)) for s, v in zip(size, hi)]
        out = np.zeros([h - l for l, h in zip(lo, hi)], np.uint16)
        first = [l // b for l, b in zip(lo, self.block)]
        last = [(h - 1) // b for h, b in zip(hi, self.block)]
        for i in np.ndindex(*[b - a + 1 for a, b in zip(first, last)]):
            g = [a + k for a, k in zip(first, i)]
            blk = self.block_zyx(view, level, g).transpose(2, 1, 0)
            b0 = [k * b for k, b in zip(g, self.block)]
            src, dst = [], []
            for d in range(3):
                a = max(lo[d], b0[d])
                e = min(hi[d], b0[d] + blk.shape[d])
                src.append(slice(a - b0[d], e - b0[d]))
                dst.append(slice(a - lo[d], e - lo[d]))
            out[tuple(dst)] = blk[tuple(src)]
        return out

    # ----------------------------------------------------------- writing

    def write(self, out_dir: str, threads: int | None = None) -> dict:
        """Write the project; returns byte counts. Blocks are made and
        compressed in threads (numpy and zstd release the interpreter
        lock), one 2,2,1 block with the four s0 blocks under it a task."""
        n5 = os.path.join(out_dir, "dataset.n5")
        blockio.write_json(os.path.join(n5, "attributes.json"),
                           {"n5": "2.5.1"})
        tasks = []
        for v in range(self.n_views):
            setup = os.path.join(n5, f"setup{v}")
            blockio.write_json(os.path.join(setup, "attributes.json"), {
                "downsamplingFactors": [list(f) for f in self.levels],
                "dataType": "uint16"})
            blockio.write_json(
                os.path.join(setup, "timepoint0", "attributes.json"),
                {"multiScale": True, "resolution": [1.0, 1.0, 1.0]})
            for lv, f in enumerate(self.levels):
                blockio.write_json(
                    os.path.join(setup, "timepoint0", f"s{lv}",
                                 "attributes.json"),
                    blockio.n5_dataset_attrs(self.level_size(lv),
                                             self.block, f))
            top = len(self.levels) - 1
            grid = [-(-s // b) for s, b in zip(self.level_size(top),
                                               self.block)]
            tasks += [(v, g) for g in np.ndindex(*grid)]

        def one(task) -> int:
            v, g = task
            base = os.path.join(n5, f"setup{v}", "timepoint0")
            if len(self.levels) == 1:
                return blockio.write_n5_block(os.path.join(base, "s0"), g,
                                              self._s0_block(v, g))
            n = 0
            fine = {}
            grid0 = [-(-s // b) for s, b in zip(self.size, self.block)]
            for dx in (0, 1):
                for dy in (0, 1):
                    g0 = (2 * g[0] + dx, 2 * g[1] + dy, g[2])
                    if g0[0] < grid0[0] and g0[1] < grid0[1]:
                        fine[g0] = self._s0_block(v, g0)
                        n += blockio.write_n5_block(
                            os.path.join(base, "s0"), g0, fine[g0])
            row = [np.concatenate([fine[k] for k in sorted(fine)
                                   if k[1] == gy], axis=2)
                   for gy in sorted({k[1] for k in fine})]
            return n + blockio.write_n5_block(
                os.path.join(base, "s1"), g,
                _mean_2x2(np.concatenate(row, axis=1)))

        with ThreadPoolExecutor(threads or min(12, os.cpu_count() or 1)
                                ) as pool:
            stored = sum(pool.map(one, tasks))
        _write_xml(os.path.join(out_dir, "registered.xml"), self,
                   self.registered)
        if self.unregistered is not None:
            _write_xml(os.path.join(out_dir, "unregistered.xml"), self,
                       self.unregistered)
        with open(os.path.join(out_dir, "truth.json"), "w") as f:
            json.dump({"seed": self.seed, "views": self.n_views,
                       "stored_bytes": stored,
                       "bbox_min": self.bbox_min.tolist(),
                       "bbox_max": self.bbox_max.tolist()}, f)
        return {"stored_bytes": stored}


def _mean_2x2(zyx: np.ndarray) -> np.ndarray:
    """The 2,2,1 level: round-half-up mean of each 2x2 in XY ([z, y, x])."""
    f = zyx.astype(np.uint32)
    s = f[:, 0::2, 0::2] + f[:, 1::2, 0::2] + f[:, 0::2, 1::2] \
        + f[:, 1::2, 1::2]
    return ((s + 2) >> 2).astype(np.uint16)


def _fmt(m) -> str:
    return " ".join(repr(float(v)) for v in np.asarray(m).reshape(-1))


def _write_xml(path: str, acq: Acquisition, models) -> None:
    cal_inv = np.linalg.inv(acq.calibration)
    setups, tiles, angles, regs = [], [], [], []
    grid = acq.p["kind"] == "grid"
    for v, m in enumerate(models):
        setups.append(
            f"<ViewSetup><id>{v}</id><name>view{v}</name>"
            f"<size>{acq.size[0]} {acq.size[1]} {acq.size[2]}</size>"
            "<voxelSize><unit>um</unit>"
            f"<size>{_fmt(np.diagonal(acq.calibration))}</size></voxelSize>"
            "<attributes><illumination>0</illumination><channel>0</channel>"
            f"<tile>{v if grid else 0}</tile>"
            f"<angle>{0 if grid else v}</angle></attributes></ViewSetup>")
        if grid:
            tiles.append(f"<Tile><id>{v}</id><name>{v}</name></Tile>")
        else:
            angles.append(f"<Angle><id>{v}</id><name>{v}</name></Angle>")
        outer = _affine(m[:, :3] @ cal_inv, m[:, 3])
        regs.append(
            f'<ViewRegistration timepoint="0" setup="{v}">'
            '<ViewTransform type="affine"><Name>registration</Name>'
            f"<affine>{_fmt(outer)}</affine></ViewTransform>"
            '<ViewTransform type="affine"><Name>calibration</Name>'
            f"<affine>{_fmt(_affine(acq.calibration, np.zeros(3)))}</affine>"
            "</ViewTransform></ViewRegistration>")
    tiles = tiles or ["<Tile><id>0</id><name>0</name></Tile>"]
    angles = angles or ["<Angle><id>0</id><name>0</name></Angle>"]
    doc = (
        "<?xml version='1.0' encoding='utf-8'?>\n"
        '<SpimData version="0.2"><BasePath type="relative">.</BasePath>'
        '<SequenceDescription><ImageLoader format="bdv.n5" version="1.0">'
        '<n5 type="relative">dataset.n5</n5></ImageLoader><ViewSetups>'
        + "".join(setups)
        + '<Attributes name="illumination"><Illumination><id>0</id>'
          "<name>0</name></Illumination></Attributes>"
          '<Attributes name="channel"><Channel><id>0</id><name>0</name>'
          "</Channel></Attributes>"
        + '<Attributes name="tile">' + "".join(tiles) + "</Attributes>"
        + '<Attributes name="angle">' + "".join(angles) + "</Attributes>"
        + '</ViewSetups><Timepoints type="pattern"><integerpattern>0'
          "</integerpattern></Timepoints><MissingViews /></SequenceDescription>"
          "<ViewRegistrations>" + "".join(regs) + "</ViewRegistrations>"
          "<ViewInterestPoints /><BoundingBoxes /><PointSpreadFunctions />"
          "<StitchingResults /><IntensityAdjustments /></SpimData>\n")
    with open(path, "w") as f:
        f.write(doc)


def main(argv) -> int:
    params_path, seed, out_dir = argv
    with open(params_path) as f:
        params = json.load(f)
    Acquisition(params, int(seed)).write(out_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
