"""Phase-correlation stitching: kernel golden tests + ground-truth recovery
on the synthetic tiled project (reference: SparkPairwiseStitching; the
synthetic grid with known true/nominal offsets replaces the S3 fixture)."""

import numpy as np
import jax.numpy as jnp
import pytest
from click.testing import CliRunner

from bigstitcher_spark_tpu.cli.main import cli
from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
from bigstitcher_spark_tpu.io.spimdata import SpimData
from bigstitcher_spark_tpu.models.stitching import (
    StitchingParams,
    build_groups,
    plan_pairs,
    stitch_all_pairs,
)
from bigstitcher_spark_tpu.ops.phasecorr import pad_to, stitch_crops


def _smooth_noise(shape, seed=0, sigma=2.0):
    from scipy.ndimage import gaussian_filter

    rng = np.random.default_rng(seed)
    return gaussian_filter(
        rng.normal(100, 20, shape).astype(np.float32), sigma
    )


def test_kernel_integer_shift():
    base = _smooth_noise((80, 80, 40))
    d = np.array([5, -3, 2])
    a = base[10:58, 10:58, 8:32]
    b = base[10 - d[0]:58 - d[0], 10 - d[1]:58 - d[1], 8 - d[2]:32 - d[2]]
    P = (64, 64, 32)
    s, r = stitch_crops(pad_to(a, P), pad_to(b, P),
                        jnp.array(a.shape, jnp.int32),
                        jnp.array(b.shape, jnp.int32))
    assert np.allclose(np.asarray(s), d, atol=0.3)
    assert float(r) > 0.95


def test_kernel_subpixel_shift():
    from scipy.ndimage import shift as ndshift

    base = _smooth_noise((80, 80, 40))
    d = np.array([2.3, -1.7, 0.5])
    a = base[10:58, 10:58, 8:32]
    b = ndshift(base, d, order=3)[10:58, 10:58, 8:32]
    P = (64, 64, 32)
    s, r = stitch_crops(pad_to(a, P), pad_to(b, P),
                        jnp.array(a.shape, jnp.int32),
                        jnp.array(b.shape, jnp.int32))
    assert np.allclose(np.asarray(s), d, atol=0.35)


def test_kernel_rejects_noise():
    a = _smooth_noise((48, 48, 24), seed=1)
    b = _smooth_noise((48, 48, 24), seed=2)
    P = (64, 64, 32)
    s, r = stitch_crops(pad_to(a, P), pad_to(b, P),
                        jnp.array(a.shape, jnp.int32),
                        jnp.array(b.shape, jnp.int32))
    assert float(r) < 0.5


@pytest.fixture(scope="module")
def stitch_project(tmp_path_factory):
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    return make_synthetic_project(
        str(tmp_path_factory.mktemp("stitch") / "proj"),
        n_tiles=(2, 2, 1), tile_size=(96, 96, 48), overlap=28,
        jitter=3.0, seed=3, n_beads_per_tile=60,
    )


def test_pair_planning(stitch_project):
    sd = SpimData.load(stitch_project.xml_path)
    groups = build_groups(sd, sd.view_ids())
    assert len(groups) == 4  # 2x2 tiles, 1 channel
    pairs = plan_pairs(sd, groups)
    # 4 edge-adjacent + 2 diagonal corner overlaps
    assert len(pairs) >= 4


def test_stitching_recovers_ground_truth(stitch_project):
    proj = stitch_project
    sd = SpimData.load(proj.xml_path)
    loader = ViewLoader(sd)
    results = stitch_all_pairs(sd, loader, sd.view_ids(),
                               StitchingParams(downsampling=(1, 1, 1)))
    assert len(results) >= 4
    checked = 0
    for res in results:
        sa = res.views_a[0].setup
        sb = res.views_b[0].setup
        e_a = proj.true_offsets[sa] - proj.nominal_offsets[sa]
        e_b = proj.true_offsets[sb] - proj.nominal_offsets[sb]
        expected = e_a - e_b  # c_A - c_B convention
        shift = res.transform[:, 3]
        if res.correlation > 0.5:  # diagonal corner overlaps may be tiny
            np.testing.assert_allclose(shift, expected, atol=0.75)
            checked += 1
    assert checked >= 4


def test_uint16_transport_is_bit_identical():
    """The lossless h2d downcast (integral float32 crops sent as uint16,
    cast back on device) must produce exactly the same peaks, and must
    not engage for fractional crops (channel averages)."""
    from bigstitcher_spark_tpu.ops.phasecorr import as_uint16_lossless
    from bigstitcher_spark_tpu.ops.phasecorr import pcm_peaks_batch

    rng = np.random.RandomState(1)
    crop = rng.randint(0, 60000, (2, 16, 64, 64)).astype(np.float32)
    ext = np.tile(np.array([16, 64, 64], np.int32), (2, 1))
    pk_f = np.asarray(pcm_peaks_batch(jnp.asarray(crop), jnp.asarray(crop),
                                      jnp.asarray(ext), jnp.asarray(ext),
                                      5, 0.25))
    t = as_uint16_lossless(crop)
    assert t is not None and t.dtype == np.uint16
    pk_u = np.asarray(pcm_peaks_batch(jnp.asarray(t), jnp.asarray(t),
                                      jnp.asarray(ext), jnp.asarray(ext),
                                      5, 0.25))
    np.testing.assert_array_equal(pk_f, pk_u)
    assert as_uint16_lossless(crop + 0.5) is None      # fractional
    assert as_uint16_lossless(crop - 1e6) is None      # negative
    assert as_uint16_lossless(crop + 1e6) is None      # out of range


# ---------------------------------------------------------------------------
# stored crops keep their type from the read to the upload (PR 33)
# ---------------------------------------------------------------------------


def _float32_reads(monkeypatch):
    """The route every crop took before PR 33: float32 from the read on.
    From there on the program computes what it computed then, so its crops
    and results are the values the stored-dtype route has to reproduce."""
    real = ViewLoader.read_block

    def read_block(self, *args, **kwargs):
        return real(self, *args, **kwargs).astype(np.float32)

    monkeypatch.setattr(ViewLoader, "read_block", read_block)


def _pack_counts():
    from bigstitcher_spark_tpu.models import stitching as st

    return {path: c.value for path, c in st._PACK_BUCKETS.items()}


def _packed_since(base):
    return {path: n - base[path] for path, n in _pack_counts().items()}


def _bare_jobs(crops):
    from bigstitcher_spark_tpu.models.stitching import _PairJob

    return [_PairJob(None, None, None, a, b, None, None, None)
            for a, b in crops]


@pytest.mark.parametrize("shapes", [
    [((16, 32, 32), (16, 32, 32))],
    [((11, 30, 32), (16, 25, 17)), ((16, 32, 1), (9, 32, 32)),
     ((1, 1, 1), (13, 31, 29))],
], ids=["full", "ragged"])
def test_a_stored_bucket_uploads_the_lossless_cast_s_bytes(shapes):
    """Copied straight into a zeroed uint16 stack, a bucket of uint16 crops
    is byte for byte what pad_to + stack + as_uint16_lossless made of the
    same crops as float32."""
    from bigstitcher_spark_tpu.models.stitching import _dispatch_bucket
    from bigstitcher_spark_tpu.ops.phasecorr import as_uint16_lossless

    rng = np.random.default_rng(7)
    crops = [tuple(rng.integers(0, 65536, shp, dtype=np.uint16)
                   for shp in pair) for pair in shapes]
    shp = (16, 32, 32)
    base = _pack_counts()
    _peaks, stacks = _dispatch_bucket(_bare_jobs(crops), shp,
                                      StitchingParams())
    assert _packed_since(base) == {"stored": 1, "cast": 0, "float": 0}
    for side in (0, 1):
        want = as_uint16_lossless(np.stack(
            [pad_to(pair[side].astype(np.float32), shp) for pair in crops]))
        got = np.asarray(stacks[side])
        assert got.dtype == want.dtype == np.uint16
        assert got.tobytes() == want.tobytes()
        np.testing.assert_array_equal(
            np.asarray(stacks[2 + side]),
            [pair[side].shape for pair in crops])


@pytest.mark.parametrize("kind,path", [
    ("whole", "cast"), ("fractional", "float"), ("mixed", "cast"),
    ("negative", "float")])
def test_a_float32_bucket_is_checked_as_before(kind, path):
    """Crops that arrive float32 (or one of a pair does) take pad, stack
    and the lossless check: uint16 across the link where every value is
    whole, float32 and no resident stacks where one is not."""
    from bigstitcher_spark_tpu.models.stitching import _dispatch_bucket

    rng = np.random.default_rng(8)
    a = rng.integers(0, 65536, (12, 32, 20), dtype=np.uint16)
    b = rng.integers(0, 65536, (16, 27, 32)).astype(np.float32)
    if kind == "fractional":
        b[3, 4, 5] += 0.5
    elif kind == "negative":
        b[0, 0, 0] = -1.0
    elif kind == "whole":
        a = a.astype(np.float32)
    shp = (16, 32, 32)
    base = _pack_counts()
    peaks, stacks = _dispatch_bucket(_bare_jobs([(a, b)]), shp,
                                     StitchingParams())
    assert _packed_since(base)[path] == 1
    assert sum(_packed_since(base).values()) == 1
    assert np.asarray(peaks).shape == (1, 5, 3)
    if path == "float":
        assert stacks is None
    else:
        assert np.asarray(stacks[0]).dtype == np.uint16
        np.testing.assert_array_equal(np.asarray(stacks[1])[0],
                                      pad_to(b, shp))


@pytest.fixture(scope="module")
def two_channel_project(tmp_path_factory):
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    return make_synthetic_project(
        str(tmp_path_factory.mktemp("stitch2c") / "proj"),
        n_tiles=(2, 1, 1), tile_size=(64, 64, 32), overlap=24,
        jitter=2.0, seed=6, n_channels=2, n_beads_per_tile=60,
        downsampling_factors=((1, 1, 1), (2, 2, 1)),
    )


# (project, params) -> the crops' dtype and the pack path a bucket takes
_EXTRACT_CASES = {
    "one-view-s0": ("stitch_project", dict(downsampling=(1, 1, 1)),
                    np.uint16, "stored"),
    "one-view-stored-level": (
        "two_channel_project",
        dict(downsampling=(2, 2, 1), channel_combine="PICK_BRIGHTEST"),
        np.uint16, "stored"),
    "pick-brightest": (
        "two_channel_project",
        dict(downsampling=(1, 1, 1), channel_combine="PICK_BRIGHTEST"),
        np.uint16, "stored"),
    "average-group": ("two_channel_project", dict(downsampling=(1, 1, 1)),
                      np.float32, "float"),
    "residual-downsample": ("stitch_project", dict(downsampling=(2, 2, 1)),
                            np.float32, "float"),
    "residual-over-stored-level": (
        "two_channel_project",
        dict(downsampling=(4, 2, 1), channel_combine="PICK_BRIGHTEST"),
        np.float32, "float"),
}


@pytest.fixture(params=sorted(_EXTRACT_CASES))
def extract_case(request):
    project, kw, dtype, path = _EXTRACT_CASES[request.param]
    proj = request.getfixturevalue(project)
    sd = SpimData.load(proj.xml_path)
    return sd, ViewLoader(sd), StitchingParams(**kw), dtype, path


def test_extract_hands_on_the_stored_type_unless_it_computes(
        extract_case, monkeypatch):
    """One image a group at a stored level stays the uint16 array the
    loader read; an AVERAGE over channels or a residual downsample gives
    float32, with the values the float32-from-the-read route gives."""
    from bigstitcher_spark_tpu.models.stitching import _extract_pair_job

    sd, loader, params, dtype, _path = extract_case
    pairs = plan_pairs(sd, build_groups(sd, sd.view_ids()))
    jobs = [_extract_pair_job(sd, loader, *p, params) for p in pairs]
    _float32_reads(monkeypatch)
    before = [_extract_pair_job(sd, loader, *p, params) for p in pairs]
    assert jobs and len(jobs) == len(before)
    for job, old in zip(jobs, before):
        for crop, old_crop in ((job.crop_a, old.crop_a),
                               (job.crop_b, old.crop_b)):
            assert crop.dtype == dtype and old_crop.dtype == np.float32
            np.testing.assert_array_equal(crop, old_crop)
        assert job.residual_ds == old.residual_ds
        np.testing.assert_array_equal(job.p0_delta, old.p0_delta)


def test_stitching_gives_the_float32_route_s_bits(extract_case, monkeypatch):
    """transform and correlation of every pair, bit for bit, whichever
    type the crops travel in; the counter says which path packed them."""
    sd, loader, params, _dtype, path = extract_case

    def run():
        base = _pack_counts()
        res = stitch_all_pairs(sd, loader, sd.view_ids(), params,
                               progress=False, devices=1)
        return sorted(res, key=lambda r: r.pair_key), _packed_since(base)

    now, packed = run()
    assert packed[path] >= 1 and sum(packed.values()) == packed[path]
    _float32_reads(monkeypatch)
    before, packed_before = run()
    # float32 crops of whole numbers pass the lossless check: same bytes
    old_path = "cast" if path == "stored" else path
    assert packed_before[old_path] == packed[path]
    assert sum(packed_before.values()) == packed[path]
    assert len(now) == len(before) >= 1
    for n, o in zip(now, before):
        assert n.pair_key == o.pair_key and n.correlation == o.correlation
        np.testing.assert_array_equal(n.transform, o.transform)


def test_a_type_float32_cannot_hold_is_rounded_at_the_read(
        stitch_project, monkeypatch):
    """int32 or float64 voxels become float32 once, where they are read,
    so that the PCM and the host scorer see the same numbers."""
    from bigstitcher_spark_tpu.models.stitching import _extract_pair_job

    real = ViewLoader.read_block
    monkeypatch.setattr(
        ViewLoader, "read_block",
        lambda self, *a, **k: real(self, *a, **k).astype(np.float64) + 1e-9)
    sd = SpimData.load(stitch_project.xml_path)
    pair = plan_pairs(sd, build_groups(sd, sd.view_ids()))[0]
    job = _extract_pair_job(sd, ViewLoader(sd), *pair,
                            StitchingParams(downsampling=(1, 1, 1)))
    assert job.crop_a.dtype == job.crop_b.dtype == np.float32


def test_segmented_pipeline_matches_single_segment(stitch_project):
    """A tiny inflight_bytes budget forces one segment per chunk (max
    round-trips); results must be identical to the default single-segment
    run — the segmentation is a scheduling choice, not a math change.
    Pinned to one device: with the mesh spread each device drains its own
    segments, so the global sync count stops being the budget's signal."""
    from bigstitcher_spark_tpu import profiling

    proj = stitch_project
    sd = SpimData.load(proj.xml_path)
    loader = ViewLoader(sd)

    def run_counting_segments(params):
        profiling.enable(True)
        profiling.get().reset()
        try:
            res = stitch_all_pairs(sd, loader, sd.view_ids(), params,
                                   devices=1)
        finally:
            profiling.enable(False)
        segs = profiling.get().stats()["stitching.kernel_sync"].count
        return res, segs

    one, segs_one = run_counting_segments(
        StitchingParams(downsampling=(1, 1, 1)))
    many, segs_many = run_counting_segments(
        StitchingParams(downsampling=(1, 1, 1), inflight_bytes=1))
    # the scheduling must actually differ, or this test compares a run
    # against itself
    assert segs_many > segs_one >= 1
    assert len(one) == len(many)
    key = lambda r: r.pair_key
    for a, b in zip(sorted(one, key=key), sorted(many, key=key)):
        assert key(a) == key(b)
        np.testing.assert_allclose(a.transform, b.transform, atol=1e-12)
        np.testing.assert_allclose(a.correlation, b.correlation, atol=1e-12)


def test_stitching_downsampled_still_recovers(stitch_project):
    proj = stitch_project
    sd = SpimData.load(proj.xml_path)
    loader = ViewLoader(sd)
    results = stitch_all_pairs(sd, loader, sd.view_ids(),
                               StitchingParams(downsampling=(2, 2, 1)))
    good = 0
    for res in results:
        sa, sb = res.views_a[0].setup, res.views_b[0].setup
        expected = ((proj.true_offsets[sa] - proj.nominal_offsets[sa])
                    - (proj.true_offsets[sb] - proj.nominal_offsets[sb]))
        if res.correlation > 0.5:
            np.testing.assert_allclose(res.transform[:, 3], expected, atol=1.5)
            good += 1
    assert good >= 4


def test_stitching_reads_stored_mipmap_level(tmp_path):
    """With a stored 2,2,1 level and ds=2,2,1 the crops come from s1
    (residual 1,1,1) and ground truth is still recovered."""
    from unittest import mock

    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    proj = make_synthetic_project(
        str(tmp_path / "proj"), n_tiles=(2, 1, 1), tile_size=(96, 96, 48),
        overlap=28, jitter=3.0, seed=5,
        downsampling_factors=((1, 1, 1), (2, 2, 1)),
    )
    sd = SpimData.load(proj.xml_path)
    loader = ViewLoader(sd)
    levels_read = []
    orig = ViewLoader.read_block

    def spy(self, view, level, offset, shape, pad_value=0.0):
        levels_read.append(level)
        return orig(self, view, level, offset, shape, pad_value)

    with mock.patch.object(ViewLoader, "read_block", spy):
        results = stitch_all_pairs(sd, loader, sd.view_ids(),
                                   StitchingParams(downsampling=(2, 2, 1)))
    assert levels_read and all(lv == 1 for lv in levels_read)
    (res,) = results
    sa, sb = res.views_a[0].setup, res.views_b[0].setup
    expected = ((proj.true_offsets[sa] - proj.nominal_offsets[sa])
                - (proj.true_offsets[sb] - proj.nominal_offsets[sb]))
    np.testing.assert_allclose(res.transform[:, 3], expected, atol=1.5)


def test_stitching_cli_writes_results(stitch_project):
    runner = CliRunner()
    res = runner.invoke(cli, [
        "stitching", "-x", stitch_project.xml_path, "-ds", "1,1,1",
    ], catch_exceptions=False)
    assert res.exit_code == 0, res.output
    sd = SpimData.load(stitch_project.xml_path)
    assert len(sd.stitching_results) >= 4
    for res_ in sd.stitching_results.values():
        assert res_.hash != 0.0
        assert res_.correlation > 0.3


class TestNonEqualTransformPath:
    """Rendered-overlap stitching when linear parts differ
    (computeStitchingNonEqualTransformations role,
    SparkPairwiseStitching.java:259-267): one tile registered with a small
    z-rotation, content generated with a known world translation error —
    the rendered path must recover that error (VERDICT r3 item 5)."""

    @pytest.fixture(scope="class")
    def rotated_project(self, tmp_path_factory):
        from scipy.ndimage import affine_transform

        from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat
        from bigstitcher_spark_tpu.io.dataset_io import create_bdv_view_datasets
        from bigstitcher_spark_tpu.io.spimdata import (
            AttributeEntity, ImageLoader, SpimData as SD, ViewId, ViewSetup,
            ViewTransform,
        )
        from bigstitcher_spark_tpu.utils.geometry import translation_affine
        from bigstitcher_spark_tpu.utils.testdata import make_bead_volume

        out = tmp_path_factory.mktemp("rotproj")
        world, _ = make_bead_volume((120, 96, 40), n_beads=160, seed=21)
        tile_size = (72, 96, 40)
        theta = np.deg2rad(3.0)
        rot = np.array([[np.cos(theta), -np.sin(theta), 0.0],
                        [np.sin(theta), np.cos(theta), 0.0],
                        [0.0, 0.0, 1.0]])
        t_b = np.array([44.0, 0.0, 0.0])
        err = np.array([2.0, -1.0, 1.0])  # world error baked into B's content

        # view A: identity registration, exact content
        img_a = world[:tile_size[0], :, :]
        # view B content sampled at M_B_true(p) = rot @ p + t_b + err
        img_b = affine_transform(world, rot, offset=t_b + err,
                                 output_shape=tile_size, order=1)
        noise = np.random.default_rng(3).normal(0, 4.0, tile_size)

        store = ChunkStore.create(str(out / "dataset.n5"), StorageFormat.N5)
        sd = SD()
        sd.image_loader = ImageLoader(format="bdv.n5", path="dataset.n5")
        sd.timepoints = [0]
        sd.attributes["illumination"][0] = AttributeEntity(0, "0")
        sd.attributes["angle"][0] = AttributeEntity(0, "0")
        sd.attributes["channel"][0] = AttributeEntity(0, "0")
        for tid in (0, 1):
            sd.attributes["tile"][tid] = AttributeEntity(tid, str(tid))
        for sid, img in ((0, img_a), (1, img_b)):
            sd.setups[sid] = ViewSetup(
                id=sid, name=f"tile{sid}", size=tile_size,
                attributes={"illumination": 0, "channel": 0, "tile": sid,
                            "angle": 0})
            ds = create_bdv_view_datasets(store, sid, 0, tile_size,
                                          (32, 32, 16), "uint16")
            arr = np.clip(img + noise, 0, 65535).astype(np.uint16)
            ds[0].write(arr, (0, 0, 0))
        sd.registrations[ViewId(0, 0)] = [
            ViewTransform("identity", translation_affine((0, 0, 0)))]
        m_b = np.hstack([rot, t_b.reshape(3, 1)])
        sd.registrations[ViewId(0, 1)] = [ViewTransform("rigid", m_b)]
        xml = str(out / "dataset.xml")
        sd.save(xml)
        return xml, err

    def test_rendered_path_recovers_known_error(self, rotated_project):
        xml, err = rotated_project
        sd = SpimData.load(xml)
        loader = ViewLoader(sd)
        from bigstitcher_spark_tpu.models.stitching import _extract_pair_job

        groups = build_groups(sd, sd.view_ids())
        pairs = plan_pairs(sd, groups)
        assert len(pairs) == 1
        job = _extract_pair_job(sd, loader, *pairs[0],
                                StitchingParams(downsampling=(1, 1, 1)))
        assert job is not None and job.linear is None, \
            "rotation must route to the rendered (non-equal-transform) path"
        results = stitch_all_pairs(sd, loader, sd.view_ids(),
                                   StitchingParams(downsampling=(1, 1, 1)))
        assert len(results) == 1
        res = results[0]
        assert res.correlation > 0.5
        # rendered A(w)=W(w), rendered B(w)=W(w+err): expected S = -err
        # (c_A - c_B convention, same as the equal-transform tests above)
        np.testing.assert_allclose(res.transform[:, 3], -err, atol=1.0)

    def test_rendered_path_downsampled(self, rotated_project):
        xml, err = rotated_project
        sd = SpimData.load(xml)
        loader = ViewLoader(sd)
        results = stitch_all_pairs(sd, loader, sd.view_ids(),
                                   StitchingParams(downsampling=(2, 2, 1)))
        assert len(results) == 1
        assert results[0].correlation > 0.5
        np.testing.assert_allclose(results[0].transform[:, 3], -err, atol=2.0)


class TestResultFilters:
    """Link filters (FilteredStitchingResults: Correlation, AbsoluteShift,
    ShiftMagnitude — SparkPairwiseStitching.java:347-382)."""

    @staticmethod
    def _mk(shift, r):
        from bigstitcher_spark_tpu.io.spimdata import (
            PairwiseStitchingResult, ViewId,
        )
        from bigstitcher_spark_tpu.utils.geometry import translation_affine

        return PairwiseStitchingResult(
            views_a=(ViewId(0, 0),), views_b=(ViewId(0, 1),),
            transform=translation_affine(shift), correlation=r, hash=0.5)

    def test_min_r_filter(self):
        from bigstitcher_spark_tpu.models.stitching import filter_results

        res = [self._mk((1, 0, 0), 0.9), self._mk((2, 0, 0), 0.2)]
        kept = filter_results(res, StitchingParams(min_r=0.5))
        assert len(kept) == 1 and kept[0].correlation == 0.9

    def test_max_shift_filters(self):
        from bigstitcher_spark_tpu.models.stitching import filter_results

        res = [self._mk((1.0, 1.0, 0.0), 0.9),
               self._mk((11.0, 0.0, 0.0), 0.9),  # per-axis only (norm 11 < 12)
               self._mk((8.0, 8.0, 8.0), 0.9)]   # magnitude only (8*sqrt3 > 12)
        kept = filter_results(
            res, StitchingParams(max_shift=(10.0, 10.0, 10.0),
                                 max_shift_total=12.0))
        assert len(kept) == 1
        assert tuple(kept[0].transform[:, 3]) == (1.0, 1.0, 0.0)
