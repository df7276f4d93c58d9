"""DoG detection against the plain float64 reference
(``benchmark/reference/dog.py``, which imports nothing of the program) on a
seeded bead acquisition at toy size, through both blur strategies; the
command and the stage's one entry writing the same bytes; the entry's span
tree and counters."""

import filecmp
import os
import shutil

import numpy as np
import pytest
from click.testing import CliRunner

PARAMS = {"kind": "grid", "geometry_seed": 0, "tiles": [2, 1, 1],
          "tile_size": [96, 64, 40], "overlap": 24, "jitter": 2,
          "beads_per_tile": 40, "bead_sigma": 1.8, "bead_amplitude": 3000,
          "background": 100, "block_size": [32, 32, 16],
          "levels": [[1, 1, 1], [2, 2, 1]]}
SEED = 2147483661


@pytest.fixture(scope="module")
def acquisition(tmp_path_factory):
    from benchmark.reference.fixture import Acquisition

    acq = Acquisition(PARAMS, SEED)
    root = str(tmp_path_factory.mktemp("beads"))
    acq.write(root, threads=2)
    return acq, root


def _reference(acq, tile, block):
    """Every block of the view's detection grid (the 2,2,1 level) by the
    reference: full-resolution points, whether each is required, and the
    detection-grid positions."""
    from benchmark.reference import dog

    vol = dog.level_volume(acq, tile, 1)
    h = dog.halo(1.8)
    dims = np.array(vol.shape)
    pts, req, det = [], [], []
    for g in np.ndindex(*(-(-dims // block))):
        lo = np.array(g) * block
        hi = np.minimum(lo + block, dims)
        r = dog.detect_block(dog.mirror_box(vol, lo - h, hi + h),
                             float(vol.min()), float(vol.max()), 1.8, 0.008,
                             1e-3)
        det.append(r["pos"] - h + lo)
        pts.append(dog.full_resolution(det[-1], (2, 2, 1)))
        req.append(r["required"])
    return np.concatenate(pts), np.concatenate(req), np.concatenate(det)


def _nearest(a, b):
    if not len(b):
        return np.full(len(a), np.inf), np.zeros(len(a), int)
    d = np.sqrt(((a[:, None, :] - b[None, :, :]) ** 2).sum(-1))
    return d.min(1), d.argmin(1)


@pytest.mark.parametrize("blur,block", [("fft", (24, 16, 20)),
                                        ("gemm", (16, 24, 20))])
def test_points_equal_the_float64_reference(acquisition, monkeypatch, blur,
                                            block):
    """The same points at the same positions, to 1e-3 full-resolution
    px, whichever way the program blurs; the grids put block boundaries
    through the views, and points lie at them and at the views' borders,
    where the halo is the image's mirror."""
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.detection import (
        DetectionParams, detect_interest_points,
    )

    acq, root = acquisition
    # a shape of its own, so the kernel is traced under this strategy
    monkeypatch.setenv("BST_DOG_BLUR", blur)
    sd = SpimData.load(os.path.join(root, "unregistered.xml"))
    dets = detect_interest_points(sd, ViewLoader(sd), sd.view_ids(),
                                  DetectionParams(block_size=block),
                                  progress=False)
    block = np.array(block)
    near_face = near_border = 0
    for det in dets:
        ref, required, ref_det = _reference(acq, det.view.setup, block)
        got = det.points
        assert len(got) >= 20
        d, _ = _nearest(ref, got)
        assert np.all(d[required] < 1e-3), d[required].max()
        d_back, i = _nearest(got, ref)
        assert np.all(d_back < 1e-3), d_back.max()
        assert len(got) == len(ref)
        dims = np.array(acq.level_size(1))
        at = ref_det[i]
        off_face = np.abs(((at + 0.5) % block) - 0.5)
        near_face += int(np.sum(np.any((off_face < 2) & (at > 2)
                                       & (at < dims - 3), axis=1)))
        near_border += int(np.sum(np.any((at < 8) | (at > dims - 9),
                                         axis=1)))
    assert near_face > 0 and near_border > 0


@pytest.fixture()
def two_copies(acquisition, tmp_path):
    _acq, root = acquisition
    copies = []
    for name in ("command", "entry"):
        shutil.copytree(root, str(tmp_path / name))
        copies.append(str(tmp_path / name / "unregistered.xml"))
    return copies


def _tree(root):
    out = []
    for dp, _dirs, names in os.walk(root):
        out += [os.path.relpath(os.path.join(dp, n), root) for n in names]
    return sorted(out)


def test_the_command_and_the_one_entry_store_the_same_bytes(two_copies):
    from bigstitcher_spark_tpu.cli.main import cli
    from bigstitcher_spark_tpu.models.detection import (
        DetectionParams, detect_project,
    )

    by_cli, by_entry = two_copies
    res = CliRunner().invoke(cli, ["detect-interestpoints", "-x", by_cli,
                                   "--blockSize", "24,16,20"])
    assert res.exit_code == 0, res.output
    assert "detected" in res.output and "saved interest points" in res.output
    said = []
    dets = detect_project(by_entry, DetectionParams(block_size=(24, 16, 20)),
                          progress=False, log=said.append)
    assert said[-1] == "saved interest points 'beads' + XML"
    assert sum(len(d.points) for d in dets) > 40
    a, b = (os.path.join(os.path.dirname(x), "interestpoints.n5")
            for x in (by_cli, by_entry))
    names = _tree(a)
    assert names == _tree(b) and any(n.endswith("loc/0/0") for n in names)
    _same, differ, errors = filecmp.cmpfiles(a, b, names, shallow=False)
    assert not differ and not errors
    with open(by_cli) as f, open(by_entry) as g:
        xml = f.read()
        assert xml == g.read()
    assert xml.count("<ViewInterestPointsFile") == 2


def test_dry_run_stores_nothing(two_copies):
    from bigstitcher_spark_tpu.models.detection import (
        DetectionParams, detect_project,
    )

    xml = two_copies[0]
    with open(xml) as f:
        before = f.read()
    said = []
    dets = detect_project(xml, DetectionParams(), dry_run=True,
                          progress=False, log=said.append)
    assert len(dets) == 2 and said[-1] == "dryRun: not saving"
    assert not os.path.exists(os.path.join(os.path.dirname(xml),
                                           "interestpoints.n5"))
    with open(xml) as f:
        assert f.read() == before
