"""``bst stitching`` with its default grouping on a seeded two-channel toy
grid (the ``grid1k-2ch`` configuration at its rehearsal size), against the
plain reference: ``benchmark/reference/aggregate.py`` over the two
channels' crops made again from the seed, then ``reference/pcm.py``. What
``_aggregate`` now records (the span ``stitching.aggregate``,
``bst_stitching_groups_total{combine}``) is counted beside what the data
picks downstream of it (the pack route, the scorer), on both sides of the
selection: a two-channel project takes ``average`` / ``float`` / ``host``,
a one-channel project ``single`` / ``stored`` / ``device``. And recording
changed no arithmetic: shift and r are, bit for bit, what the parent's
uninstrumented ``_aggregate`` gives."""


import numpy as np
import pytest
from click.testing import CliRunner

from bigstitcher_spark_tpu import profiling
from bigstitcher_spark_tpu.cli.main import cli
from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
from bigstitcher_spark_tpu.io.spimdata import SpimData
from bigstitcher_spark_tpu.models import stitching as st
from bigstitcher_spark_tpu.models.stitching import (
    StitchingParams, stitch_all_pairs,
)

CELLS = {"two-channel": "grid1k-2ch.stitch", "one-channel": "grid1k.stitch"}
# which way each selection falls: groups, pack route, scorer
TAKES = {"two-channel": ("average", "float", "host"),
         "one-channel": ("single", "stored", "device")}


@pytest.fixture(scope="module", params=sorted(CELLS))
def toy(request, tmp_path_factory):
    """(which, the cell's stage adapter at toy size, the unregistered
    project's XML text): the fixture on disk, and for two channels the
    second one beside it."""
    from benchmark import run

    job = run.load_cell(CELLS[request.param], rehearse=True)
    fixture = str(tmp_path_factory.mktemp("fixture"))
    work = str(tmp_path_factory.mktemp("work"))
    stage = run.build_stage(job, fixture, work, 41, 1)
    stage.acq.write(fixture, threads=2)
    return request.param, stage, stage.xml_text()


def counted() -> dict:
    return {"groups": {k: c.value for k, c in st._GROUPS.items()},
            "pack": {k: c.value for k, c in st._PACK_BUCKETS.items()},
            "pairs": {k: c.value for k, c in st._REFINE_PAIRS.items()}}


def since(base: dict) -> dict:
    return {g: {k: n - base[g][k] for k, n in series.items() if n - base[g][k]}
            for g, series in counted().items()}


def project(toy, tmp_path) -> str:
    xml = str(tmp_path / "project.xml")
    with open(xml, "w") as f:
        f.write(toy[2])
    return xml


def test_the_command_against_the_plain_reference(toy, tmp_path):
    which, stage, _text = toy
    xml = project(toy, tmp_path)
    base = counted()
    profiling.enable(True)
    profiling.get().reset()
    try:
        result = CliRunner().invoke(cli, ["stitching", "-x", xml])
        spans = profiling.get().stats()
    finally:
        profiling.enable(False)
    assert result.exit_code == 0, result.output
    assert "6/6 pairs pass filters" in result.output
    # every group pair, parsed from the saved XML by the adapter, against
    # the reference and the ground truth, to the cell's own limits
    numbers = stage._compare(stage.stored(xml), stage.reference())
    assert numbers["pair_missing"] == 0
    for name, limit in stage.job["cell"]["limits"].items():
        assert numbers[name] <= limit, (name, numbers)
    # 6 pairs: 12 sides aggregated, each inside its pair's extract
    groups, pack, scorer = TAKES[which]
    got = since(base)
    assert got["groups"] == {groups: 12}
    assert list(got["pack"]) == [pack] and got["pack"][pack] >= 1
    assert got["pairs"] == {scorer: 6}
    assert spans["stitching.aggregate"].count == 12
    assert spans["stitching.extract"].count == 6
    assert spans["stitching.aggregate"].total_s \
        <= spans["stitching.extract"].total_s
    assert spans["stitching.pack"].count == got["pack"][pack]


def parents_aggregate(sd, crops, group, params):
    """``_aggregate`` as the parent commit (3f8d2d5) has it, copied: no
    span, no counter."""
    def combine(imgs, how):
        if len(imgs) == 1:
            return imgs[0]
        if how == "AVERAGE":
            return np.mean(np.asarray(imgs, np.float32), axis=0)
        if how == "PICK_BRIGHTEST":
            return imgs[int(np.argmax([np.sum(i, dtype=np.float64)
                                       for i in imgs]))]
        raise ValueError(f"unknown aggregation {how}")

    by_illum = {}
    for v in group.views:
        illum = sd.setups[v.setup].attributes.get("illumination", 0)
        by_illum.setdefault(illum, []).append(crops[v])
    per_illum = [combine(imgs, params.channel_combine)
                 for _, imgs in sorted(by_illum.items())]
    return combine(per_illum, params.illum_combine)


def test_shift_and_r_are_the_parent_s_bit_for_bit(toy, tmp_path, monkeypatch):
    sd = SpimData.load(project(toy, tmp_path))
    loader = ViewLoader(sd)
    params = StitchingParams()

    def run():
        return [(r.pair_key, r.transform.tobytes(), r.correlation)
                for r in stitch_all_pairs(sd, loader, sd.view_ids(), params,
                                          progress=False, devices=1)]

    now = run()
    base = counted()
    monkeypatch.setattr(st, "_aggregate", parents_aggregate)
    assert run() == now and len(now) == 6
    assert since(base)["groups"] == {}      # the copy counts nothing


@pytest.mark.parametrize("combine,label", [
    (("AVERAGE", "PICK_BRIGHTEST"), "average"),
    (("PICK_BRIGHTEST", "AVERAGE"), "average"),
    (("PICK_BRIGHTEST", "PICK_BRIGHTEST"), "brightest")])
def test_a_group_is_counted_by_what_came_of_it(combine, label):
    """Two channels under each of two illuminations: a mean anywhere on
    the way makes the image a computed float32 one, picks alone hand on a
    stored image; either way the group counts once, and the image is the
    plain reference's."""
    from benchmark.reference import aggregate

    from bigstitcher_spark_tpu.io.spimdata import ViewId, ViewSetup

    rng = np.random.default_rng(3)
    sd = SpimData()
    crops, views = {}, []
    for setup, (illum, channel) in enumerate(
            [(0, 0), (0, 1), (1, 0), (1, 1)]):
        sd.setups[setup] = ViewSetup(
            id=setup, name=str(setup), size=(8, 8, 4),
            attributes={"illumination": illum, "channel": channel,
                        "tile": 0, "angle": 0})
        crops[ViewId(0, setup)] = rng.integers(
            0, 65536, (8, 8, 4), dtype=np.uint16)
        views.append((illum, channel, crops[ViewId(0, setup)]))
    group = st.ViewGroup(0, 0, 0, tuple(sorted(crops)))
    base = counted()
    got = st._aggregate(sd, crops, group, StitchingParams(
        channel_combine=combine[0], illum_combine=combine[1]))
    assert since(base)["groups"] == {label: 1}
    assert got.dtype == (np.float32 if label == "average" else np.uint16)
    assert np.array_equal(got, aggregate.group_image(views, *combine))
