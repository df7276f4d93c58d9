"""``multiview.nonrigid``: the cell rehearsed at toy size on XLA:CPU, its
control and planted faults through the run's own comparison, its kernel's
least work, and the interest points it writes read back by the program."""

import json

import numpy as np
import pytest

from bm_helpers import run_cell
from test_bm_faults import ALTERED_VOXELS, HALF_THE_BLOCKS, WRONG_DRIVER

CELL = "multiview.nonrigid"

NO_DEFORMATION = """
import numpy as np
import bigstitcher_spark_tpu.models.nonrigid_fusion as nf
def _identity(targets, view_world, origin, dims, *a, **k):
    grid = np.zeros((*dims, 12), np.float32)
    grid[..., 0] = grid[..., 5] = grid[..., 10] = 1.0
    return grid
nf.fit_control_grid = _identity
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_a_correct_line(trace):
    rc, line, err = run_cell(CELL, trace=trace, seed=2147483659)
    assert rc == 0, err[-3000:]
    assert line["rehearsal"] is True and line["correct"] is True, \
        line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["compared"]) == {
        "nonrigid_mean_abs_diff", "nonrigid_max_abs_diff",
        "fuse_missing_chunks", "path_mismatch"}
    names = set(line["metrics"])
    if trace:
        # what the program's spans give on any platform; the kernel's time
        # and its share of a roofline come from a chip's trace alone
        assert names >= {"nonrigid_plan_pct", "nonrigid_prefetch_pct",
                         "nonrigid_write_pct", "fuse_cache_hit_pct",
                         "fuse_pass_cv_pct", "fuse_compiles_in_window",
                         "traced_voxel_rate", "setup_warm_s"}
        assert line["metrics"]["fuse_compiles_in_window"]["value"] == 0
        assert "nonrigid_kernel_roofline" not in names
    else:
        assert names == {"voxel_rate", "setup_s"}
        assert line["metrics"]["voxel_rate"]["unit"] == "Mvox/s"


FAULTS = [(ALTERED_VOXELS, "nonrigid_mean_abs_diff"),
          (ALTERED_VOXELS, "nonrigid_max_abs_diff"),
          (NO_DEFORMATION, "nonrigid_mean_abs_diff"),
          (NO_DEFORMATION, "nonrigid_max_abs_diff"),
          (HALF_THE_BLOCKS, "fuse_missing_chunks"),
          (WRONG_DRIVER.replace('"sharded"', '"per-block"'),
           "path_mismatch")]


@pytest.mark.parametrize("fault,number", FAULTS, ids=[
    f"{name}-{n}" for name, (_f, n) in zip(
        ("altered-voxels", "altered-voxels", "identity-grid", "identity-grid",
         "half-the-blocks", "wrong-driver"), FAULTS)])
def test_a_broken_timed_path_is_not_correct(fault, number):
    rc, line, err = run_cell(CELL, prelude=fault)
    assert rc == 0, err[-3000:]
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"], line["compared"]


def test_the_control_is_not_correct_on_another_seed(capsys):
    from benchmark import run

    assert run.main(["--workload", CELL, "--seed", "3000000017",
                     "--control", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr()[0].strip().splitlines()[-1])
    assert line["control"] is True and line["correct"] is False


def test_least_work_of_one_compute_block():
    from benchmark.kernels import fuse_affine, nonrigid

    vox = 256 * 256 * 128
    box = 288 * 288 * 96     # a rotated view's source box of such a block
    call = {"voxels": vox, "patches": [box] * 4, "block": [256, 256, 128],
            "grid": [29, 29, 16]}
    flops, nbytes = nonrigid.ops_and_bytes(call)
    # the source boxes and the grids in, the block out
    assert nbytes == 4 * box * 2 + 4 * 29 * 29 * 16 * 12 * 4 + vox * 2
    assert nbytes == pytest.approx(83.1e6, rel=0.01)
    # the affine block's flops plus, a voxel and view, two affine
    # applications and three flops for each of 12 coefficients
    a_flops, _ = fuse_affine.ops_and_bytes({"voxels": vox, "views": 4})
    per = (flops - a_flops) / (vox * 4)
    assert 2 * 18 + 3 * 12 < per < 2 * 18 + 3 * 12 * 1.3
    assert nbytes / 819e9 > flops / 197e12      # the HBM bound binds
    # no view, no deformation work
    assert nonrigid.ops_and_bytes({**call, "patches": []}) == \
        (4 * vox, 2 * vox)


def test_the_adapter_reckons_its_calls_from_the_geometry():
    """Two central compute blocks a pass, four views each; a source box is
    a block's 8.4 Mvox seen through the view's z calibration of 4 (2.4 M
    pixels along the view's axes, 5.2 M rotated by 45 degrees): what the
    roofline reader is given."""
    from benchmark import run

    stage = run.build_stage(run.load_cell(CELL, rehearse=False), "", "", 5, 1)
    calls = stage.kernel_calls([{}, {}])
    assert len(calls) == 4 and calls[0] == calls[2]
    for call in calls[:2]:
        assert call["voxels"] == 256 * 256 * 128
        assert call["block"] == [256, 256, 128]
        assert call["grid"] == [29, 29, 16]
        assert len(call["patches"]) == 4
        assert all(0.25 * call["voxels"] < p < 0.75 * call["voxels"]
                   for p in call["patches"])
    flops, nbytes = __import__(
        "benchmark.kernels.nonrigid", fromlist=["x"]).ops_and_bytes(calls[0])
    assert nbytes == pytest.approx(49.7e6, rel=0.01)
    assert nbytes / 819e9 > flops / 197e12


def test_the_interest_points_written_are_read_back_by_the_program(tmp_path):
    from benchmark import run
    from benchmark.reference import interestpoints
    from bigstitcher_spark_tpu.io.interestpoints import InterestPointStore
    from bigstitcher_spark_tpu.io.spimdata import SpimData, ViewId

    job = run.load_cell(CELL, rehearse=True)
    stage = run.build_stage(job, str(tmp_path / "fixture"), str(tmp_path),
                            5, 1)
    xml = interestpoints.write_project(stage.acq, stage.spec,
                                       str(tmp_path / "fixture"),
                                       str(tmp_path))
    sd = SpimData.load(xml)
    assert sd.resolve_loader_path() == str(tmp_path / "fixture" /
                                           "dataset.n5")
    store = InterestPointStore.for_project(sd)
    views = interestpoints.make_points(stage.acq, stage.spec)
    assert len(views) == 4 and sum(len(p["corrs"]) for p in views) > 100
    for v, pts in enumerate(views):
        view = ViewId(0, v)
        assert list(sd.interest_points[view]) == ["beads"]
        ids, locs = store.load_points(view, "beads")
        assert ids.dtype == np.uint64 and np.array_equal(ids, pts["ids"])
        assert np.array_equal(locs, pts["locs"])    # float64, bit for bit
        got = [(c.id, c.other_view.setup, c.other_label, c.other_id)
               for c in store.load_correspondences(view, "beads")]
        assert got == [(a, b, "beads", c) for a, b, c in pts["corrs"]]
    # the same specimen for every seed: the points belong to the
    # configuration, as the beads do
    other = run.build_stage(job, "", "", 6, 1)
    again = interestpoints.make_points(other.acq, other.spec)
    assert all(np.array_equal(a["locs"], b["locs"])
               for a, b in zip(views, again))
