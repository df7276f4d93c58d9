"""The non-rigid driver (ISSUE 28): the chunked fit against the fit it
replaced, the one entry ``bst nonrigid-fusion`` and the benchmark share,
the fits a batch ahead of the device, and the span tree and counters of a
pass."""

import json
import os
import threading
import time

import numpy as np
import pytest
from click.testing import CliRunner

from bigstitcher_spark_tpu import profiling
from bigstitcher_spark_tpu.observe import metrics, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = (32, 32, 16)


def _old_fit(targets, view_world, grid_origin, grid_dims, spacing,
             alpha=1.0, reg_eps=1e-6):
    """``ops/nonrigid.fit_control_grid`` as it was before ISSUE 28, less
    its float32 cast: every vertex at once, three-operand einsums."""
    gx, gy, gz = grid_dims
    G = gx * gy * gz
    m = len(targets)
    idx = np.indices((gx, gy, gz)).reshape(3, -1).T
    verts = grid_origin + idx * spacing
    out = np.zeros((G, 3, 4))
    out[:, :, :3] = np.eye(3)
    if m == 0:
        return out
    if m < 4:
        out[:, :, 3] = (view_world - targets).mean(axis=0)
        return out
    d = np.linalg.norm(verts[:, None, :] - targets[None, :, :], axis=2)
    w = 1.0 / (d**alpha + 0.5)
    pc = targets[None, :, :] - verts[:, None, :]
    qc = view_world[None, :, :] - verts[:, None, :]
    ph = np.concatenate([pc, np.ones((G, m, 1))], axis=2)
    A = np.einsum("gm,gmi,gmj->gij", w, ph, ph)
    B = np.einsum("gm,gmi,gmk->gik", w, ph, qc)
    lam = reg_eps * w.sum(axis=1)[:, None, None]
    x_id = np.zeros((4, 3))
    x_id[:3, :3] = np.eye(3)
    sol = np.linalg.solve(A + lam * np.eye(4), B + lam * x_id)
    lin = np.swapaxes(sol[:, :3, :], 1, 2)
    out[:, :, :3] = lin
    out[:, :, 3] = sol[:, 3, :] + verts - np.einsum("gij,gj->gi", lin, verts)
    return out


@pytest.mark.parametrize("alpha", [1.0, 2.0])
@pytest.mark.parametrize("m", [0, 1, 3, 4, 30, 120])
def test_the_fit_equals_the_fit_it_replaced(m, alpha):
    """Float64 on both sides, world coordinates of a thousand as in the
    cell: the chunked batched products sum in another order than the
    einsums did, which moves a translation of a thousand by 1e-12. The
    grid spans two chunks of vertices and a part of one. m = 0 and m < 4
    are the fallbacks."""
    from bigstitcher_spark_tpu.ops import nonrigid

    rng = np.random.default_rng([m, int(alpha)])
    targets = rng.uniform(1000, 1200, (m, 3))
    view_world = targets + rng.normal(0, 0.3, (m, 3)) * [1, 1, 4]
    origin, dims = np.array([990.0, 1010.0, 1030.0]), (12, 11, 9)
    assert 2 * nonrigid._FIT_CHUNK < np.prod(dims) < 3 * nonrigid._FIT_CHUNK
    new = nonrigid.fit_vertex_models(targets, view_world, origin, dims, 10.0,
                                     alpha)
    old = _old_fit(targets, view_world, origin, dims, 10.0, alpha)
    assert new.dtype == np.float64 and new.shape == old.shape
    np.testing.assert_allclose(new, old, rtol=0, atol=1e-9)
    grid = nonrigid.fit_control_grid(targets, view_world, origin, dims, 10.0,
                                     alpha)
    assert grid.dtype == np.float32 and grid.shape == (*dims, 12)
    np.testing.assert_array_equal(
        grid, new.reshape(*dims, 12).astype(np.float32))
    if m >= 4:
        assert np.abs(new[:, :, 3]).max() > 0.01    # not the identity


# ------------------------------------------------------------ a toy on disk

@pytest.fixture(scope="module")
def project(tmp_path_factory):
    """The multiview-nonrigid configuration at its rehearsal size with its
    interest points, and the box the benchmark's rehearsal fuses."""
    from benchmark.reference import interestpoints
    from benchmark.reference.fixture import Acquisition

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "multiview-nonrigid.json")) as f:
        config = json.load(f)
    acq = Acquisition({**config["fixture"], **config["rehearsal_fixture"]}, 7)
    root = str(tmp_path_factory.mktemp("driver"))
    acq.write(root, threads=2)
    xml = interestpoints.write_project(acq, config["interest_points"], root,
                                       root)
    cb = np.array(BLOCK) * (2, 2, 1)
    lo = acq.bbox_min + np.array([0, 0, 3]) * cb
    hi = np.minimum(lo + np.array([2, 2, 1]) * cb, acq.bbox_max + 1)
    return {"root": root, "xml": xml, "lo": lo, "hi": hi}


def _old_unique_points(sd, store, views, labels):
    """``build_unique_points`` as it was before ISSUE 28: dictionaries and
    a loop a correspondence. Returns (targets, view_world) by view."""
    from bigstitcher_spark_tpu.utils.geometry import apply_affine

    keys, index, world = [], {}, {}
    vset = set(views)

    def load(view, label):
        k = (view, label)
        if k not in world:
            ids, locs = store.load_points(view, label)
            w = apply_affine(sd.model(view), locs) if len(locs) else locs
            world[k] = dict(zip(ids.astype(int).tolist(), w))
        return world[k]

    def key_id(k):
        if k not in index:
            index[k] = len(keys)
            keys.append(k)
        return index[k]

    parent = []

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    edges = []
    for v in views:
        for label in labels:
            if label not in sd.interest_points.get(v, {}):
                continue
            mine = load(v, label)
            for c in store.load_correspondences(v, label):
                if c.other_view not in vset:
                    continue
                theirs = load(c.other_view, c.other_label)
                if c.id not in mine or c.other_id not in theirs:
                    continue
                edges.append(((v, label, c.id),
                              (c.other_view, c.other_label, c.other_id)))
    for a, b in edges:
        ia, ib = key_id(a), key_id(b)
        while len(parent) < len(keys):
            parent.append(len(parent))
        ra, rb = find(ia), find(ib)
        if ra != rb:
            parent[ra] = rb
    groups = {}
    for i, k in enumerate(keys):
        groups.setdefault(find(i), []).append(k)
    targets = {v: [] for v in views}
    vw = {v: [] for v in views}
    for members in groups.values():
        pos = np.array([world[(v, lab)][i] for v, lab, i in members])
        tgt = pos.mean(axis=0)
        for (v, _lab, _i), p in zip(members, pos):
            targets[v].append(tgt)
            vw[v].append(p)
    return ({v: np.array(t).reshape(-1, 3) for v, t in targets.items()},
            {v: np.array(t).reshape(-1, 3) for v, t in vw.items()})


@pytest.mark.parametrize("setups", [(0, 1, 2, 3), (0, 2), (3,)])
def test_unique_points_equal_the_loop_they_replaced(project, setups):
    """The same rows in the same order, bit for bit: over all four views,
    over two of them (correspondences to the others are left out) and
    over one (none is left)."""
    from bigstitcher_spark_tpu.io.interestpoints import InterestPointStore
    from bigstitcher_spark_tpu.io.spimdata import SpimData, ViewId
    from bigstitcher_spark_tpu.models.nonrigid_fusion import (
        build_unique_points,
    )

    sd = SpimData.load(project["xml"])
    store = InterestPointStore.for_project(sd)
    views = [ViewId(0, s) for s in setups]
    new = build_unique_points(sd, store, views, ["beads", "absent"])
    targets, view_world = _old_unique_points(sd, store, views,
                                             ["beads", "absent"])
    assert set(new.targets) == set(new.view_world) == set(views)
    for v in views:
        assert new.targets[v].shape == targets[v].shape
        assert len(targets[v]) > (100 if len(setups) > 1 else -1)
        assert np.array_equal(new.targets[v], targets[v])
        assert np.array_equal(new.view_world[v], view_world[v])


def _container(project, name, xml=None):
    from bigstitcher_spark_tpu.io.chunkstore import StorageFormat
    from bigstitcher_spark_tpu.io.container import create_fusion_container
    from bigstitcher_spark_tpu.utils.geometry import Interval

    out = os.path.join(project["root"], name)
    create_fusion_container(
        out, StorageFormat.ZARR, xml or project["xml"], 1, 1,
        Interval([int(v) for v in project["lo"]],
                 [int(v) - 1 for v in project["hi"]]),
        data_type="uint16", block_size=BLOCK, downsamplings=[[1, 1, 1]],
        compression="zstd", min_intensity=0.0, max_intensity=65535.0)
    return out


def _fuse_directly(out, devices=1, block_scale=(2, 2, 1)):
    """``fuse_nonrigid_volume`` with the arguments the click callback gave
    it before ISSUE 28 moved them into ``fuse_nonrigid_project`` (it left
    ``devices`` at None: every local device)."""
    from bigstitcher_spark_tpu.io.container import (
        open_container, read_container_meta,
    )
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.interestpoints import InterestPointStore
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.models.affine_fusion import BlendParams
    from bigstitcher_spark_tpu.models.nonrigid_fusion import (
        build_unique_points, fuse_nonrigid_volume,
    )

    store = open_container(out)
    meta = read_container_meta(store)
    sd = SpimData.load(meta.input_xml)
    views = sd.view_ids()
    unique = build_unique_points(sd, InterestPointStore.for_project(sd),
                                 views, ["beads"])
    ds = store.open_dataset(meta.mr_infos[0][0].dataset.strip("/"))
    return fuse_nonrigid_volume(
        sd, ViewLoader(sd), views, unique, ds, meta.bbox,
        block_size=tuple(meta.block_size), block_scale=block_scale,
        cpd=10.0, alpha=1.0, fusion_type="AVG_BLEND",
        blend=BlendParams(border=(0.0, 0.0, 0.0), range=(40.0, 40.0, 40.0)),
        anisotropy_factor=float("nan"), out_dtype=meta.data_type,
        min_intensity=meta.min_intensity, max_intensity=meta.max_intensity,
        zarr_ct=(0, 0), devices=devices)


def _stored(out) -> dict:
    """Every chunk file of the container's level 0, byte for byte."""
    base = os.path.join(out, "0")
    return {os.path.relpath(os.path.join(dp, f), base):
            open(os.path.join(dp, f), "rb").read()
            for dp, _d, fs in os.walk(base) for f in fs
            if not f.startswith(".")}


@pytest.fixture(scope="module")
def direct(project):
    out = _container(project, "direct-call.ome.zarr")
    stats = _fuse_directly(out)
    return {"out": out, "stats": stats, "chunks": _stored(out)}


# ------------------------------------------------------------ the one entry

def test_the_command_stores_what_the_direct_call_stores(project, direct):
    """Container mode: ``bst nonrigid-fusion -o <container>`` through
    ``fuse_nonrigid_project``, against ``fuse_nonrigid_volume`` called as
    the old callback called it: the same chunks, byte for byte."""
    from bigstitcher_spark_tpu.cli.main import cli

    out = _container(project, "command.ome.zarr")
    r = CliRunner().invoke(cli, ["nonrigid-fusion", "-o", out],
                           catch_exceptions=False)
    assert r.exit_code == 0, r.output
    assert "nonrigid fusing channel 0 timepoint 0: 4 views" in r.output
    assert f"done, {direct['stats'].voxels} voxels" in r.output
    got = _stored(out)
    assert len(got) == len(direct["chunks"]) > 4
    assert got == direct["chunks"]


def test_direct_output_mode_stores_what_the_direct_call_stores(project):
    """Direct-output mode makes its own container over the named bounding
    box (128^3 blocks, the command's fixed choice), then takes the same
    entry: held against a direct call into a container made alike."""
    import xml.etree.ElementTree as ET

    from bigstitcher_spark_tpu.cli.main import cli
    from bigstitcher_spark_tpu.io.chunkstore import StorageFormat
    from bigstitcher_spark_tpu.io.container import create_fusion_container
    from bigstitcher_spark_tpu.utils.geometry import Interval

    xml = os.path.join(project["root"], "boxed.xml")
    tree = ET.parse(project["xml"])
    box = ET.SubElement(tree.getroot().find("BoundingBoxes"),
                        "BoundingBoxDefinition", name="middle")
    lo, hi = project["lo"], project["lo"] + 64
    ET.SubElement(box, "min").text = " ".join(str(int(v)) for v in lo)
    ET.SubElement(box, "max").text = " ".join(str(int(v) - 1) for v in hi)
    tree.write(xml, encoding="unicode", xml_declaration=True)

    out = os.path.join(project["root"], "direct-output.ome.zarr")
    r = CliRunner().invoke(cli, [
        "nonrigid-fusion", "-o", out, "-x", xml, "-p", "UINT16", "-s",
        "ZARR", "-b", "middle", "--minIntensity", "0", "--maxIntensity",
        "65535", "--blockScale", "1,1,1"], catch_exceptions=False)
    assert r.exit_code == 0, r.output
    assert "direct output: created container" in r.output

    twin = os.path.join(project["root"], "direct-output-twin.ome.zarr")
    create_fusion_container(
        twin, StorageFormat.ZARR, xml, 1, 1,
        Interval([int(v) for v in lo], [int(v) - 1 for v in hi]),
        data_type="uint16", block_size=(128, 128, 128),
        downsamplings=[[1, 1, 1]], compression="zstd", min_intensity=0.0,
        max_intensity=65535.0)
    _fuse_directly(twin, devices=None, block_scale=(1, 1, 1))
    got, want = _stored(out), _stored(twin)
    assert len(got) == len(want) >= 1 and got == want


def test_the_sharded_mesh_stores_the_same_blocks(project, direct):
    """Two blocks a batch on two devices, staged at the batch's largest
    patch shape: the same chunks as a block a batch."""
    out = _container(project, "two-devices.ome.zarr")
    stats = _fuse_directly(out, devices=2)
    assert stats.voxels == direct["stats"].voxels
    assert stats.blocks == direct["stats"].blocks
    assert _stored(out) == direct["chunks"]


# ---------------------------------------------- the fits leave the hot path

def test_fits_run_a_batch_ahead_and_side_by_side(project, monkeypatch):
    """No fit before the stage has its work list; the first block's views
    are fitted at once on several threads; every later block's fits begin
    before the block before it has been fetched from the device."""
    from bigstitcher_spark_tpu.models import nonrigid_fusion as nf

    fit = nf.fit_control_grid
    lock = threading.Lock()
    running, most, fits = [0], [0], []

    def slow_fit(*a, **k):
        with lock:
            running[0] += 1
            most[0] = max(most[0], running[0])
        t0 = time.perf_counter()
        time.sleep(0.05)
        try:
            return fit(*a, **k)
        finally:
            with lock:
                running[0] -= 1
                fits.append((t0, time.perf_counter()))

    monkeypatch.setattr(nf, "fit_control_grid", slow_fit)
    trace.configure(buffer_bytes=8 << 20)
    try:
        out = _container(project, "ahead.ome.zarr")
        _fuse_directly(out)
        snap = trace.snapshot()
    finally:
        trace.reset()
    assert most[0] >= 2, "a block's views are fitted side by side"
    begins = {e["id"]: e for e in snap if e["ph"] == "B"}
    ends = {e["id"]: e["ts"] for e in snap if e["ph"] == "E"}
    plans = sorted((e["ts"], ends[i]) for i, e in begins.items()
                   if e["name"] == "nonrigid.plan")
    d2h = sorted(ends[i] for i, e in begins.items()
                 if e["name"] == "nonrigid.d2h")
    assert len(plans) == len(d2h) == 4
    # block k+1 is planned before block k's outputs have been fetched
    for k in range(len(plans) - 1):
        assert plans[k + 1][0] < d2h[k], (k, plans, d2h)
    assert len(fits) == 16      # four views a block, each fitted once


# ------------------------------------------------- the tree and the counters

@pytest.fixture()
def recorded(tmp_path):
    trace.reset()
    profiling.enable(True)
    profiling.get().reset()
    trace.configure(buffer_bytes=8 << 20, path=str(tmp_path / "trace.json"))
    yield
    trace.reset()
    profiling.enable(False)
    profiling.get().reset()


def test_the_span_tree_of_a_pass_has_no_hole(project, direct, recorded):
    """One pass after a warm one: every span of the pass hangs under
    ``nonrigid.stage``, fits under their block's plan, and the named
    children cover the root (99 % on the chip at the cell's size, PERF.md
    section 5; here a toy pass of a second on XLA:CPU, where the root's
    own few milliseconds of geometry are a larger share)."""
    out = _container(project, "tree.ome.zarr")
    reg = metrics.get_registry()
    before = reg.snapshot()
    stats = _fuse_directly(out)
    d = reg.snapshot_delta(before)
    snap = trace.snapshot()
    begins = {e["id"]: e for e in snap if e["ph"] == "B"}
    by_name: dict = {}
    for e in begins.values():
        by_name.setdefault(e["name"], []).append(e)
    root, = by_name["nonrigid.stage"]
    assert root["parent"] == 0
    n = stats.blocks - stats.skipped_empty
    assert n == 4
    for name, count in (("nonrigid.plan", n), ("nonrigid.fit", 4 * n),
                        ("nonrigid.prefetch", 4 * n), ("nonrigid.h2d", n),
                        ("nonrigid.kernel", 2 * n), ("nonrigid.d2h", n),
                        ("nonrigid.write", n)):
        assert len(by_name[name]) == count, name
        for e in by_name[name]:     # whatever thread it ran on
            up = e
            while up["parent"]:
                up = begins[up["parent"]]
            assert up["id"] == root["id"], name
    assert {begins[e["parent"]]["name"] for e in by_name["nonrigid.fit"]} \
        == {"nonrigid.plan"}
    # the unique points are joined before the stage, beside its tree
    assert "nonrigid.unique_points" in by_name
    assert by_name["nonrigid.unique_points"][0]["parent"] == 0
    st = profiling.get().stats()
    stage = st["nonrigid.stage"]
    assert stage.count == 1
    assert stage.self_s <= 0.1 * stage.total_s, (stage.self_s, stage.total_s)
    # counts at the boundary, as the blocks complete
    assert d["bst_fusion_voxels_total"] == stats.voxels
    assert d['bst_fusion_blocks_total{kernel="nonrigid"}'] == n
    assert d["bst_nonrigid_control_points_total"] > 4 * n * 100
    assert 0 < d["bst_nonrigid_fit_seconds_total"] <= \
        st["nonrigid.fit"].total_s
    # and the report prints it as a tree
    from bigstitcher_spark_tpu.analysis.tracereport import analyze

    paths = {tuple(r["path"]) for r in analyze(trace.finalize())["span_tree"]}
    assert ("nonrigid.stage",) in paths
    assert any(p[0] == "nonrigid.stage" and p[-2:] == ("nonrigid.plan",
                                                       "nonrigid.fit")
               for p in paths)


def test_every_new_name_is_declared_once():
    from bigstitcher_spark_tpu.observe import metric_names as mn

    for span in ("nonrigid.stage", "nonrigid.unique_points", "nonrigid.plan",
                 "nonrigid.fit", "nonrigid.prefetch", "nonrigid.h2d",
                 "nonrigid.kernel", "nonrigid.d2h", "nonrigid.write"):
        assert span in mn.SPANS
    for m in ("bst_nonrigid_control_points_total",
              "bst_nonrigid_fit_seconds_total", "bst_fusion_blocks_total"):
        assert m in mn.METRICS
