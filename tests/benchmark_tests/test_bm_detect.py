"""``grid1k-2ch.detect``: the cell rehearsed at toy size on XLA:CPU, its
control and planted faults through the run's own comparison, a program
without the stage's one entry, the parser of the stored points and the
pieces of the plain reference."""

import json
import os

import numpy as np
import pytest

from bm_helpers import ROOT, run_cell

CELL = "grid1k-2ch.detect"
COMPARED = {"detect_missing", "detect_extra", "detect_ref_err_px",
            "detect_truth_missing", "detect_truth_err_px"}

A_BLOCK_DROPPED = """
import bigstitcher_spark_tpu.models.detection as d
_plan = d._plan_blocks
def _first_block_of_each_view_left_out(*a, **k):
    boxes, jobs = _plan(*a, **k)
    first = {}
    return boxes, [j for j in jobs if first.setdefault(j.view_idx, j) is not j]
d._plan_blocks = _first_block_of_each_view_left_out
"""

ONE_DETECTION_PIXEL_OFF = """
import numpy as np
import bigstitcher_spark_tpu.models.detection as d
_affine = d.apply_affine
d.apply_affine = lambda t, p: _affine(t, p) + np.array([t[0, 0], 0.0, 0.0])
"""

HALF_PIXEL_LEFT_OUT = """
import numpy as np
import bigstitcher_spark_tpu.models.detection as d
d.mipmap_transform = lambda f: np.hstack([np.diag(np.asarray(f, float)),
                                          np.zeros((3, 1))])
"""

NO_ONE_ENTRY = """
import bigstitcher_spark_tpu.models.detection as d
del d.detect_project
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_a_correct_line(trace):
    rc, line, err = run_cell(CELL, trace=trace, seed=2147483659,
                             seconds=3.0)
    assert rc == 0, err[-3000:]
    assert line["rehearsal"] is True and line["correct"] is True, \
        line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["compared"]) == COMPARED
    names = set(line["metrics"])
    if trace:
        # every metric of the cell's list that needs no chip: the kernel's
        # time and share of a roofline, the idle share and the HBM peak
        # come from a chip's trace alone
        with open(os.path.join(ROOT, "benchmark", "cells",
                               CELL + ".json")) as f:
            listed = set(json.load(f)["per_layer"])
        assert listed - names == {
            "detect_kernel_ms", "detect_kernel_roofline",
            "detect_device_idle_pct", "detect_hbm_peak_GB"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        # every block's candidates fit: 0.0, not left out
        assert m["detect_truncated_pct"] == 0.0
        for span in ("minmax", "read", "consume", "save"):
            assert m[f"detect_{span}_pct"] > 0, span
        assert m["detect_compiles_in_window"] == 0
    else:
        assert names == {"voxel_rate", "setup_s"}
        assert line["metrics"]["voxel_rate"]["unit"] == "Mvox/s"


FAULTS = {"a-block-of-each-view-dropped": (A_BLOCK_DROPPED,
                                           "detect_truth_missing"),
          "one-detection-pixel-off": (ONE_DETECTION_PIXEL_OFF,
                                      "detect_missing"),
          # a level voxel p placed at f p, not f p + (f - 1) / 2: half a
          # full-resolution pixel off in x and y, every bead still found
          "mipmap-half-pixel-left-out": (HALF_PIXEL_LEFT_OUT,
                                         "detect_truth_err_px")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault):
    prelude, number = FAULTS[fault]
    rc, line, err = run_cell(CELL, prelude=prelude)
    assert rc == 0, err[-3000:]
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"], line["compared"]
    if fault == "one-detection-pixel-off":
        # every point two full-resolution pixels off in x: none matched
        # to the reference, each one extra, every bead missed
        assert line["compared"]["detect_extra"]["value"] > 0
        assert line["compared"]["detect_truth_missing"]["value"] > 0


def test_a_program_without_the_one_entry_fails_at_once():
    """The parent of this cell's PR lacks ``detect_project``: the run
    stops at set-up's pass with an error, and prints no line."""
    rc, line, err = run_cell(CELL, prelude=NO_ONE_ENTRY)
    assert rc != 0 and line is None
    assert "detect_project" in err
    assert "window" not in err


def test_the_control_is_not_correct_on_another_seed(capsys):
    from benchmark import run

    assert run.main(["--workload", CELL, "--seed", "3000000017",
                     "--control", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr()[0].strip().splitlines()[-1])
    assert line["control"] is True and line["correct"] is False
    c = line["compared"]
    assert set(c) == {"detect_missing", "detect_extra", "detect_ref_err_px"}
    assert c["detect_ref_err_px"]["value"] > 3 * c["detect_ref_err_px"][
        "limit"]


def test_stored_points_are_read_back_as_the_program_wrote_them(tmp_path):
    """The cell's own parser against the program's writer: an empty view,
    one of a few points, one over a storage block of points."""
    from benchmark.reference import n5points
    from bigstitcher_spark_tpu.io.interestpoints import (
        BLOCK, InterestPointStore, register_points_in_xml,
    )
    from bigstitcher_spark_tpu.io.spimdata import SpimData, ViewId
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    proj = make_synthetic_project(str(tmp_path / "p"), n_tiles=(3, 1, 1),
                                  tile_size=(32, 32, 16), overlap=8,
                                  n_beads_per_tile=2, seed=1)
    sd = SpimData.load(proj.xml_path)
    store = InterestPointStore.for_project(sd)
    rng = np.random.default_rng(3)
    want = {0: np.zeros((0, 3)), 1: rng.normal(size=(7, 3)) * 100,
            2: rng.normal(size=(BLOCK + 5, 3)) * 1000}
    for setup, pts in want.items():
        grp = store.save_points(ViewId(0, setup), "beads", pts)
        register_points_in_xml(sd, ViewId(0, setup), "beads", "", grp)
    sd.save(proj.xml_path)
    got = n5points.stored_points(proj.xml_path, "beads")
    assert sorted(got) == [0, 1, 2]
    for setup, pts in want.items():
        assert got[setup].shape == pts.shape
        assert np.array_equal(got[setup], pts)
    assert n5points.stored_points(proj.xml_path, "other") == {}


def test_the_reference_pieces():
    from benchmark.reference import dog

    # bfloat16: 8 bits of significand, ties to even
    x = np.array([1.0, 1.0 + 2 ** -8, 1.0 + 3 * 2 ** -8, 3.14159, -2.5e-3],
                 np.float32)
    assert np.array_equal(dog.bfloat16(x), np.array(
        [1.0, 1.0, 1.0 + 2 ** -6, 3.140625, -0.00250244140625], np.float32))
    # the mirror leaves out the edge voxel, and reaches past either end
    v = np.arange(5)[:, None, None] * np.ones((1, 2, 2), int)
    assert dog.mirror_box(v, (-3, 0, 0), (8, 2, 2))[:, 0, 0].tolist() == \
        [3, 2, 1, 0, 1, 2, 3, 4, 3, 2, 1]
    # strict maxima only: a plateau of two is none
    d = np.zeros((7, 7, 7))
    d[3, 3, 3] = 1.0
    d[2, 2, 4] = d[2, 2, 5] = 0.5
    core = (slice(1, 6),) * 3
    assert dog.maxima(d, core, 0.1).tolist() == [[3, 3, 3]]
    # the fit finds a paraboloid's top to rounding, moving its base
    g = np.indices((9, 9, 9)).transpose(1, 2, 3, 0) - np.array([4.7, 3.2,
                                                                 4.0])
    para = -(g ** 2).sum(-1)
    pos, val = dog.localize(para, np.array([[4, 4, 4]]))
    assert np.allclose(pos, [[4.7, 3.2, 4.0]]) and np.allclose(val, 0.0)
    assert dog.halo(1.8) == 8 and len(dog.taps(1.8)) == 13
    assert np.allclose(dog.full_resolution(np.array([[0.0, 1.0, 2.0]]),
                                           (2, 2, 1)), [[0.5, 2.5, 2.0]])


def test_the_kernel_counts_taps_not_gemm_bands():
    from benchmark.kernels import dog

    call = {"blocks": 8, "shape": [528, 528, 144], "itemsize": 2,
            "sigma": 1.8, "candidates": 4096}
    flops, nbytes = dog.ops_and_bytes(call)
    vox = 528 * 528 * 144
    assert flops == 8 * vox * (6 * 13 + 6 * 15 + 2 + 26)
    assert nbytes == 8 * (2 * vox + 4096 * 29)
    # on a v5e the HBM bound binds
    assert nbytes / 819e9 > flops / 197e12
