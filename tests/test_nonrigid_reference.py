"""The program's non-rigid fusion against the plain reference
(``benchmark/reference/nonrigid.py``: numpy float64, nothing of the
program) on seeded rotated toys with the configuration's interest points:
four views with points about every block, and two views with so few
beads that blocks fall back to the mean translation or the identity.
Unique points, vertex models, fused voxels."""

import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = (32, 32, 16)
SCALE = (2, 2, 1)
CPD, ALPHA, BLEND = 10.0, 1.0, 40.0


TOYS = {
    # points about every block: every grid is a least-squares fit
    "four-views": {},
    # two views at right angles and four beads, two of them seen by both:
    # every grid has under four points, the mean translation
    "two-views-two-beads": {"angles_deg": [0, 90], "beads_per_tile": 2},
    # no bead, no point near any block: every grid is the identity
    "two-views-no-bead": {"angles_deg": [0, 90], "beads_per_tile": 0},
}
POINTS = {"four-views": (20, 10**6), "two-views-two-beads": (1, 3),
          "two-views-no-bead": (0, 0)}


@pytest.fixture(scope="module", params=sorted(TOYS))
def toy(request, tmp_path_factory):
    """The multiview-nonrigid configuration at its rehearsal size, on disk
    with its interest points."""
    from benchmark.reference import interestpoints
    from benchmark.reference.fixture import Acquisition

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "multiview-nonrigid.json")) as f:
        config = json.load(f)
    acq = Acquisition({**config["fixture"], **config["rehearsal_fixture"],
                       **TOYS[request.param]}, 2147483659)
    spec = config["interest_points"]
    root = str(tmp_path_factory.mktemp("nonrigid"))
    acq.write(root, threads=2)
    xml = interestpoints.write_project(acq, spec, root, root)
    return {"name": request.param, "acq": acq, "spec": spec, "root": root,
            "xml": xml, "points": interestpoints.make_points(acq, spec)}


@pytest.fixture(scope="module")
def fused(toy):
    """The toy fused by the program over its middle."""
    from bigstitcher_spark_tpu.io.chunkstore import StorageFormat
    from bigstitcher_spark_tpu.io.container import (
        create_fusion_container, open_container, read_container_meta,
    )
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.interestpoints import InterestPointStore
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.affine_fusion import BlendParams
    from bigstitcher_spark_tpu.models.nonrigid_fusion import (
        build_unique_points, fuse_nonrigid_volume,
    )
    from bigstitcher_spark_tpu.utils.geometry import Interval

    acq = toy["acq"]
    cb = np.array(BLOCK) * SCALE
    lo = acq.bbox_min + np.array([0, 0, 3]) * cb
    hi = np.minimum(lo + np.array([2, 2, 1]) * cb, acq.bbox_max + 1)
    out = os.path.join(toy["root"], "fused.ome.zarr")
    create_fusion_container(
        out, StorageFormat.ZARR, toy["xml"], 1, 1,
        Interval([int(v) for v in lo], [int(v) - 1 for v in hi]),
        data_type="uint16", block_size=BLOCK, downsamplings=[[1, 1, 1]],
        compression="zstd", min_intensity=0.0, max_intensity=65535.0)
    store = open_container(out)
    meta = read_container_meta(store)
    sd = SpimData.load(meta.input_xml)
    unique = build_unique_points(sd, InterestPointStore.for_project(sd),
                                 sd.view_ids(), ["beads"])
    ds = store.open_dataset(meta.mr_infos[0][0].dataset.strip("/"))
    stats = fuse_nonrigid_volume(
        sd, ViewLoader(sd), sd.view_ids(), unique, ds, meta.bbox,
        block_size=BLOCK, block_scale=SCALE, cpd=CPD, alpha=ALPHA,
        fusion_type="AVG_BLEND",
        blend=BlendParams(border=(0.0, 0.0, 0.0), range=(BLEND,) * 3),
        out_dtype="uint16", min_intensity=0.0, max_intensity=65535.0,
        zarr_ct=(0, 0), devices=1)
    got = np.asarray(ds.read_full())[..., 0, 0]
    return {"lo": lo, "hi": hi, "got": got, "stats": stats, "sd": sd,
            "unique": unique}


def _reference(toy, fused, deform):
    """The whole fused part by the reference, a compute block at a time,
    with the reference's own summed weights."""
    from benchmark.reference import nonrigid

    acq = toy["acq"]
    unique = nonrigid.unique_points(toy["points"], acq.registered)
    cb = np.array(BLOCK) * SCALE
    lo, hi = fused["lo"], fused["hi"]
    ref = np.zeros(hi - lo, np.uint16)
    wsum = np.zeros(hi - lo)
    for g in np.ndindex(*[-(-int(h - l) // int(b))
                          for l, h, b in zip(lo, hi, cb)]):
        blo = lo + np.array(g) * cb
        shp = tuple(int(v) for v in np.minimum(cb, hi - blo))
        sl = tuple(slice(int(a - l), int(a - l) + s)
                   for a, l, s in zip(blo, lo, shp))
        ref[sl], wsum[sl] = nonrigid.fuse_box(
            acq, unique, blo, shp, blo, tuple(cb), CPD, ALPHA, BLEND,
            deform=deform, threads=2)
    return ref, wsum


def test_unique_points_agree(toy, fused):
    from benchmark.reference import nonrigid

    ref = nonrigid.unique_points(toy["points"], toy["acq"].registered)
    for v, (targets, view_world) in zip(fused["sd"].view_ids(), ref):
        mine = np.hstack([fused["unique"].targets[v],
                          fused["unique"].view_world[v]])
        theirs = np.hstack([targets, view_world])
        assert len(mine) == len(theirs)
        # the toy fits what its name says: least squares, the mean
        # translation (under four points), the identity (none)
        assert POINTS[toy["name"]][0] <= len(mine) <= POINTS[toy["name"]][1]
        # the same groups in another order; float64 on both sides, the
        # locations read back from disk bit for bit
        order = lambda a: a[np.lexsort(a.T[::-1])]      # noqa: E731
        np.testing.assert_allclose(order(mine), order(theirs), rtol=0,
                                   atol=1e-9)


@pytest.mark.parametrize("n_points,thickness", [
    (0, None), (2, None), (40, None), (4, 0.0), (5, 1e-3), (6, 0.05)])
def test_vertex_models_agree_with_least_squares_by_qr(n_points, thickness):
    """The program's normal equations against the reference's QR over the
    weighted rows, vertex by vertex. The program ships float32: a
    coefficient of magnitude m carries m * 6e-8 and the normal equations
    a little more; translations of well-spread points are under 300
    (vertices up to 150 px from the origin), so 3e-5 holds them and the
    linear part to 2e-6. With a ``thickness`` the points lie that many px
    about one plane: across it only the regulariser (0.0) or the
    localisation error over the thickness (1e-3: coefficients of a hundred
    and translations of thousands) decides the model, and both sides have
    to decide it alike, to the same share of the magnitude."""
    from benchmark.reference import nonrigid
    from bigstitcher_spark_tpu.ops.nonrigid import fit_control_grid

    rng = np.random.default_rng(n_points)
    targets = rng.uniform(20, 130, (n_points, 3))
    if thickness is not None:
        targets[:, 2] = 60.0 + thickness * rng.normal(size=n_points)
    view_world = targets + 1.5 * np.sin(targets / 25.0) \
        + rng.normal(0, 0.1, targets.shape)
    origin, dims = np.array([10.0, 20.0, 30.0]), (9, 8, 7)
    grid = fit_control_grid(targets, view_world, origin, dims, CPD, ALPHA)
    vertices = origin + np.indices(dims).reshape(3, -1).T * CPD
    ref = nonrigid.vertex_models(targets, view_world, vertices, ALPHA)
    got = grid.reshape(-1, 3, 4).astype(np.float64)
    np.testing.assert_allclose(
        got[:, :, :3], ref[:, :, :3], rtol=0,
        atol=2e-6 * max(1.0, np.abs(ref[:, :, :3]).max()))
    np.testing.assert_allclose(
        got[:, :, 3], ref[:, :, 3], rtol=0,
        atol=1e-7 * max(300.0, np.abs(ref[:, :, 3]).max()))
    if n_points >= 4:   # and the models are not the identity
        assert np.abs(ref - nonrigid.IDENTITY).max() > 0.01


def _differences(got, ref, wsum):
    d = np.abs(got.astype(np.float64) - ref.astype(np.float64))
    # as the benchmark's comparison: a voxel that only the last thousandths
    # of a pixel of a view's edge reach has a weight float32 cannot tell
    # from nought
    kept = ~((wsum > 0) & (wsum < 1e-5))
    return float(d[kept].mean()), float(d[kept].max())


def test_fused_voxels_equal_the_reference_to_rounding(toy, fused):
    """Tolerance: the program computes coordinates in float32 (world
    coordinates of a few hundred through coefficients of the same size:
    1e-4 px), so a fused value moves by under 0.1 grey level beside a bead
    and a voxel's uint16 may round the other way: max 1. How many do is the
    mean: 0.0013 on XLA:CPU at this size, held under 0.01; the identity in
    the grids' place reads a hundred times that (next test)."""
    assert fused["stats"].voxels == fused["got"].size
    ref, wsum = _reference(toy, fused, deform=True)
    assert (wsum > 0).mean() > 0.5
    mean, worst = _differences(fused["got"], ref, wsum)
    assert worst <= 1.0, (mean, worst)
    assert mean < 0.01, (mean, worst)


def test_leaving_the_deformation_out_is_outside_the_tolerance(toy, fused):
    ref, wsum = _reference(toy, fused, deform=False)
    mean, worst = _differences(fused["got"], ref, wsum)
    if toy["name"] == "two-views-no-bead":
        # no point, no deformation: the affine fusion of the same voxels
        assert worst <= 1.0 and mean < 0.01, (mean, worst)
    else:
        assert worst > 10.0 and mean > 0.1, (mean, worst)
