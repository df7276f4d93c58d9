"""``grid1k-2ch.stitch``: the cell rehearsed at toy size on XLA:CPU, its
control and planted faults through the run's own comparison, the second
channel it makes beside a cached fixture it leaves untouched, the plain
reference of the group's image and the reader kind it brings."""

import hashlib
import json
import os

import numpy as np
import pytest

from bm_helpers import ROOT, run_cell
from test_bm_faults import ALTERED_SHIFT, HALF_THE_PAIRS, ONE_LEVEL_PIXEL_OFF

CELL = "grid1k-2ch.stitch"

ONE_VIEW_A_SIDE = """
import bigstitcher_spark_tpu.models.stitching as st
_build = st.build_groups
st.build_groups = lambda sd, views: [
    st.ViewGroup(g.timepoint, g.angle, g.tile, g.views[:1])
    for g in _build(sd, views)]
"""

A_CHANNEL_LEFT_OUT = """
import bigstitcher_spark_tpu.models.stitching as st
st._aggregate = lambda sd, crops, group, params: crops[group.views[0]]
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_a_correct_line(trace):
    rc, line, err = run_cell(CELL, trace=trace, seed=2147483659)
    assert rc == 0, err[-3000:]
    assert line["rehearsal"] is True and line["correct"] is True, \
        line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["compared"]) == {"pair_missing", "pair_truth_err_px",
                                     "pair_ref_err_px", "pair_r_err"}
    names = set(line["metrics"])
    if trace:
        # every metric of the cell's list that needs no chip: the kernel's
        # time and share of a roofline, the idle share and the HBM peak
        # come from a chip's trace alone
        with open(os.path.join(ROOT, "benchmark", "cells",
                               CELL + ".json")) as f:
            listed = set(json.load(f)["per_layer"])
        assert listed - names == {"pcm_kernel_ms", "pcm_roofline",
                                  "pair_device_idle_pct", "pair_hbm_peak_GB"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        # no pair by the device scorer, and the counter moved: 0.0, not
        # left out; the combination is a part of the extract that holds it
        assert m["pair_device_scored_pct"] == 0.0
        assert 0 < m["pair_aggregate_pct"] < m["pair_extract_pct"]
        assert m["pair_pack_pct"] > 0
        assert m["pair_compiles_in_window"] == 0
    else:
        assert names == {"pair_rate", "setup_s"}
        assert line["metrics"]["pair_rate"]["unit"] == "pairs/s"


FAULTS = {"altered-shift": (ALTERED_SHIFT, "pair_ref_err_px"),
          "a-peak-one-level-pixel-off": (ONE_LEVEL_PIXEL_OFF,
                                         "pair_truth_err_px"),
          "half-the-pairs": (HALF_THE_PAIRS, "pair_missing"),
          "one-view-a-side": (ONE_VIEW_A_SIDE, "pair_missing"),
          "a-channel-left-out": (A_CHANNEL_LEFT_OUT, "pair_r_err")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault):
    prelude, number = FAULTS[fault]
    rc, line, err = run_cell(CELL, prelude=prelude)
    assert rc == 0, err[-3000:]
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"], line["compared"]
    if fault == "one-view-a-side":
        # every pair was computed and stored, none for whole groups (the
        # number is the worst pass's)
        assert c["value"] == 6


def test_the_control_is_not_correct_on_another_seed(capsys):
    from benchmark import run

    assert run.main(["--workload", CELL, "--seed", "3000000017",
                     "--control", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr()[0].strip().splitlines()[-1])
    assert line["control"] is True and line["correct"] is False
    assert all(line["compared"][k]["value"] > line["compared"][k]["limit"]
               for k in ("pair_ref_err_px", "pair_r_err"))


def digest(root: str) -> dict:
    """{relative path: (sha256, mtime_ns)} of every file under ``root``."""
    out = {}
    for dp, _dirs, names in os.walk(root):
        for name in names:
            path = os.path.join(dp, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = (
                    hashlib.sha256(f.read()).hexdigest(),
                    os.stat(path).st_mtime_ns)
    return out


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The toy one-channel fixture on disk as the harness caches it, the
    adapter over it with a work directory of its own, and what set-up and
    one pass left: (stage, the pass, the fixture's digest before)."""
    from benchmark import run

    job = run.load_cell(CELL, rehearse=True)
    fixture = str(tmp_path_factory.mktemp("fixture"))
    work = str(tmp_path_factory.mktemp("work"))
    stage = run.build_stage(job, fixture, work, 77, 1)
    stage.acq.write(fixture, threads=2)
    before = digest(fixture)
    stage.run_pass(-1)
    return stage, stage.run_pass(0), before


def test_a_run_leaves_the_cached_fixture_as_it_was(toy):
    from benchmark import fixtures

    stage, out, before = toy
    fixture, work = stage.job["fixture_dir"], stage.job["work_dir"]
    assert digest(fixture) == before
    assert sorted(os.listdir(fixture)) == sorted(
        {p.split(os.sep)[0] for p in before})
    # channel 0 is read where the cache keeps it, channel 1 where set-up
    # made it, in the run's own directory and not in the cache: the run's
    # container is links, channel 1's setups numbered after channel 0's
    n5 = os.path.join(work, "dataset.n5")
    second = os.path.join(work, "channel1")
    assert not os.path.realpath(second).startswith(
        os.path.realpath(fixtures.CACHE) + os.sep)
    for v in range(4):
        for at, root in ((0, fixture), (4, second)):
            link = os.path.join(n5, f"setup{at + v}")
            assert os.path.islink(link)
            assert os.path.realpath(link) == os.path.realpath(
                os.path.join(root, "dataset.n5", f"setup{v}"))
    assert out["work"] == 6 and os.path.dirname(out["out"]) == work


def test_the_second_channel_written_is_read_back_by_the_program(toy):
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData, ViewId
    from bigstitcher_spark_tpu.models.stitching import (
        build_groups, plan_pairs,
    )

    stage, out, _before = toy
    sd = SpimData.load(out["out"])
    n = stage.acq.n_views
    assert sorted(sd.setups) == list(range(2 * n)) and n == 4
    assert sorted(sd.attributes["channel"]) == [0, 1]
    for v in range(n):
        a, b = sd.setups[v].attributes, sd.setups[n + v].attributes
        assert (a["channel"], b["channel"]) == (0, 1)
        assert a["illumination"] == b["illumination"] == 0
        assert a["tile"] == b["tile"] == v
        assert np.array_equal(sd.model(ViewId(0, v)),
                              sd.model(ViewId(0, n + v)))
    groups = build_groups(sd, sd.view_ids())
    assert [tuple(v.setup for v in g.views) for g in groups] == \
        [(v, n + v) for v in range(n)]
    assert len(plan_pairs(sd, groups)) == 6
    # both levels of both channels of a tile, as stored, are the
    # generator's
    loader = ViewLoader(sd)
    for level in (0, 1):
        size = stage.ch1.level_size(level)
        for setup, acq in ((2, stage.acq), (n + 2, stage.ch1)):
            got = loader.read_block(ViewId(0, setup), level, (0, 0, 0), size)
            assert got.dtype == np.uint16
            assert np.array_equal(got, acq.region(2, level, (0, 0, 0), size))


def test_the_second_channel_is_the_configuration_s_and_the_seed_s(toy):
    from benchmark.reference import channels
    from benchmark.reference.fixture import Acquisition

    stage, _out, _before = toy
    ch1 = stage.ch1
    spec = stage.job["config"]["second_channel"]
    box = ((10, 20, 3), (75, 90, 40))
    a = ch1.region(1, 0, *box)
    again = channels.second_channel(stage.acq.p, spec, 77)
    assert np.array_equal(a, again.region(1, 0, *box))
    other = channels.second_channel(stage.acq.p, spec, 78)
    assert not np.array_equal(a, other.region(1, 0, *box))
    # the specimen is the configuration's: the first channel's beads,
    # where they are, under the same stage error, for every seed
    assert np.array_equal(ch1.beads, stage.acq.beads)
    assert np.array_equal(ch1.beads, other.beads)
    assert all(np.array_equal(x, y) for x, y in zip(
        ch1.true_offsets + ch1.nominal_offsets,
        stage.acq.true_offsets + stage.acq.nominal_offsets))
    # its own background and amplitude, keyed apart from every --seed
    first = stage.acq.region(1, 0, *box)
    assert not np.array_equal(a, first)
    assert int(a.min()) >= spec["background"] > int(first.min())
    assert int(a.max()) < int(first.max())
    assert channels.channel_seed(77) > 2 ** 33
    # the noise alone (the same generators with no bead lit): 5 bits a
    # voxel in each channel, and the two streams know nothing of each other
    dark0 = Acquisition({**stage.acq.p, "bead_amplitude": 0}, 77)
    dark1 = channels.second_channel(
        stage.acq.p, {**spec, "bead_amplitude": 0}, 77)
    n0 = dark0.region(1, 0, *box).astype(np.int64) \
        - stage.acq.p["background"]
    n1 = dark1.region(1, 0, *box).astype(np.int64) - spec["background"]
    assert n0.min() == n1.min() == 0 and n0.max() == n1.max() == 30
    assert np.all(n1 + spec["background"] <= a)     # beads only add
    assert abs(np.corrcoef(n0.ravel(), n1.ravel())[0, 1]) < 0.02


def test_the_reference_crop_is_the_group_s_image(toy):
    from benchmark.reference import aggregate

    stage, _out, _before = toy
    a_, _b, lo, hi = next(stage.pairs())
    crop, p0 = stage._crop(a_, lo, hi)
    level = stage.acq.levels.index(stage.ds)
    both = [c.region(a_, level, p0, p0 + np.array(crop.shape))
            for c in (stage.acq, stage.ch1)]
    assert crop.dtype == np.float64
    assert np.array_equal(crop * 2, both[0].astype(np.int64) + both[1])
    # the plain aggregator: channels within an illumination first, then
    # the illuminations; whichever order the views come in
    dim, bright = both[0][:8, :8, :4], both[0][:8, :8, :4] + 7
    other = both[1][:8, :8, :4]
    views = [(1, 0, bright), (0, 1, other), (0, 0, dim), (1, 1, other)]
    want = (bright.astype(np.float64) + other) / 2
    for order in (views, views[::-1]):
        assert np.array_equal(aggregate.group_image(order), want)
    assert np.array_equal(
        aggregate.group_image(views, "PICK_BRIGHTEST", "AVERAGE"),
        (bright.astype(np.float64)
         + (dim if dim.sum() > other.sum() else other)) / 2)
    assert aggregate.group_image([(0, 0, dim)]).dtype == np.float64
    with pytest.raises(ValueError):
        aggregate.group_image(views, "MEDIAN")


def test_a_result_counts_only_for_whole_groups(toy, tmp_path):
    stage, out, _before = toy
    got = stage.stored(out["out"])
    assert sorted(got) == sorted((a, b) for a, b, _lo, _hi in stage.pairs())
    with open(out["out"]) as f:
        doc = f.read()
    assert 'views_a="0,0;0,4"' in doc
    # the same results named for one view a side, or for the channels of
    # two tiles: none counts
    for bad in ('views_a="0,0"', 'views_a="0,0;0,5"'):
        path = str(tmp_path / "bad.xml")
        with open(path, "w") as f:
            f.write(doc.replace('views_a="0,0;0,4"', bad))
        assert sorted(stage.stored(path)) == sorted(
            k for k in got if k[0] != 0)


def test_series_share_tells_a_counter_s_labels_apart():
    from benchmark.readers import counter_ratio, series_share

    p = {"part": 'pairs{scorer="device"}', "rest": 'pairs{scorer="host"}'}
    ctx = {"counters": {'pairs{scorer="device"}': 3,
                        'pairs{scorer="host"}': 1, "pairs_other": 9}}
    assert series_share.read(ctx, p) == 75.0
    # a program that has the counter and never took the path reads 0.0
    assert series_share.read({"counters": {'pairs{scorer="device"}': 0,
                                           'pairs{scorer="host"}': 6}},
                             p) == 0.0
    assert series_share.read({"counters": {'pairs{scorer="host"}': 6}},
                             p) == 0.0
    assert series_share.read({"counters": {}}, p) is None
    # counter_ratio sums over the labels: it cannot give this share
    assert counter_ratio.read(ctx, {"part": "pairs", "rest": "pairs"}) == 50.0
