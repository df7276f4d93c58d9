"""The rest of a run with the timed path broken underneath: ``correct``
has to come out false, once for each fault a cell can have. And the
control — the reference in the next lower precision, in the program's
place — has to come out not correct through the same comparison."""

import json
import os

import numpy as np
import pytest

from bm_helpers import ROOT, run_cell

ALTERED_VOXELS = """
import bigstitcher_spark_tpu.ops.fusion as F
_orig = F._convert_intensity_expr
def _off_by_three(block, *a, **k):
    return _orig(block + 3.0, *a, **k)
F._convert_intensity_expr = _off_by_three
import jax
F.convert_intensity = jax.jit(_off_by_three, static_argnames=("out_dtype",))
"""

HALF_THE_BLOCKS = """
import bigstitcher_spark_tpu.io.chunkstore as cs
_write = cs.Dataset.write
def _every_other(self, data, offset, *a, **k):
    if "ome.zarr" in str(getattr(self.store, "root", "")) \\
            and (sum(int(o) for o in offset[:3]) // 64) % 2:
        return None
    return _write(self, data, offset, *a, **k)
cs.Dataset.write = _every_other
"""

ALTERED_SHIFT = """
import bigstitcher_spark_tpu.models.stitching as st
_refine = st.refine_peaks
def _nudged(*a, **k):
    shift, r = _refine(*a, **k)
    return shift + 0.4, r
st.refine_peaks = _nudged
"""

HALF_THE_PAIRS = """
import bigstitcher_spark_tpu.models.stitching as st
_plan = st.plan_pairs
st.plan_pairs = lambda sd, groups: _plan(sd, groups)[::2]
"""

WRONG_DRIVER = """
from benchmark import files
_cell = files.cell
files.cell = lambda name: {**_cell(name), "rehearsal": {
    **_cell(name)["rehearsal"], "expect_path": "sharded"}}
"""

ONE_LEVEL_PIXEL_OFF = """
import bigstitcher_spark_tpu.models.stitching as st
_refine = st.refine_peaks
def _next_peak(*a, **k):
    shift, r = _refine(*a, **k)
    shift = shift.copy()
    shift[..., 0] += 1.0
    return shift, r
st.refine_peaks = _next_peak
"""

FAULTS = [
    ("multiview.fuse", 1, ALTERED_VOXELS, "fuse_mean_abs_diff"),
    ("multiview.fuse", 1, ALTERED_VOXELS, "fuse_max_abs_diff"),
    ("multiview.fuse", 1, HALF_THE_BLOCKS, "fuse_missing_chunks"),
    ("multiview.fuse", 1, WRONG_DRIVER, "path_mismatch"),
    ("grid1k.stitch", 1, ALTERED_SHIFT, "pair_ref_err_px"),
    ("grid1k.stitch", 1, ONE_LEVEL_PIXEL_OFF, "pair_truth_err_px"),
    ("grid1k.stitch", 1, HALF_THE_PAIRS, "pair_missing"),
]


@pytest.mark.parametrize("workload,devices,fault,number", FAULTS,
                         ids=[f"{w}-{n}" for w, _d, _f, n in FAULTS])
def test_a_broken_timed_path_is_not_correct(workload, devices, fault,
                                            number):
    rc, line, err = run_cell(workload, devices=devices, prelude=fault)
    assert rc == 0, err[-3000:]
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"], line["compared"]


CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_is_not_correct(workload, capsys):
    """The lower-precision reference in the program's place goes through
    the run's own comparison, against the cell's own limits."""
    from benchmark import run

    assert run.main(["--workload", workload, "--seed", "77", "--control",
                     "--rehearse"]) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert line["control"] is True and line["correct"] is False
    over = [k for k, c in line["compared"].items()
            if c["value"] > 3 * c["limit"]]
    assert over, line["compared"]
    assert err.strip().splitlines()[-1].startswith("compared ")


def test_a_run_brings_a_number_for_every_limit():
    from benchmark import run

    compared, correct = run.judge({"a": 1.0, "b": 0.0}, {"a": 1.0, "b": 0.0})
    assert correct and compared["a"] == {"value": 1.0, "limit": 1.0}
    assert run.judge({"a": 1.5, "b": 0.0}, {"a": 1.0, "b": 0.0})[1] is False
    with pytest.raises(KeyError):
        run.judge({"a": 1.0}, {"a": 1.0, "b": 0.0})
    assert run.judge({"a": 2.0}, {"a": 1.0, "b": 0.0}, whole=False) == \
        ({"a": {"value": 2.0, "limit": 1.0}}, False)


@pytest.fixture(scope="module")
def toy_stitch(tmp_path_factory):
    """The toy grid1k fixture on disk and the stitching adapter over it."""
    from benchmark import run

    job = run.load_cell("grid1k.stitch", rehearse=True)
    root = str(tmp_path_factory.mktemp("toy"))
    stage = run.build_stage(job, root, root, 77, 1)
    stage.acq.write(root, threads=2)
    return stage


def test_fixture_blocks_are_a_function_of_the_seed(toy_stitch):
    stage = toy_stitch
    from benchmark.reference.fixture import Acquisition

    again = Acquisition(stage.acq.p, 77)
    other = Acquisition(stage.acq.p, 78)
    a = stage.acq.region(1, 0, (10, 20, 3), (75, 90, 40))
    assert np.array_equal(a, again.region(1, 0, (10, 20, 3), (75, 90, 40)))
    assert not np.array_equal(a, other.region(1, 0, (10, 20, 3),
                                              (75, 90, 40)))
    # the 2,2,1 level is the rounded mean of the level under it
    fine = stage.acq.region(1, 0, (0, 0, 0), (64, 64, 16)).astype(np.uint32)
    mean = (fine[0::2, 0::2] + fine[1::2, 0::2] + fine[0::2, 1::2]
            + fine[1::2, 1::2] + 2) >> 2
    assert np.array_equal(stage.acq.region(1, 1, (0, 0, 0), (32, 32, 16)),
                          mean)
    assert os.path.exists(os.path.join(stage.job["fixture_dir"],
                                       "unregistered.xml"))


def test_each_pass_starts_with_an_empty_chunk_cache(toy_stitch, monkeypatch):
    """As a stage process starts: what pass one decoded, pass two decodes
    again (PR 22's window re-read 81.5 % from the LRU). Which of the
    consumer and the read-ahead pool decodes a chunk first varies from run
    to run; that every chunk a pass reads was decoded in that pass, by one
    or the other, does not."""
    from bigstitcher_spark_tpu.io.chunkcache import get_cache

    stage = toy_stitch
    cache = get_cache()
    decoded, read = [], []
    get, put = cache.get, cache.put

    def counting_get(key):
        read[-1].add(key)
        return get(key)

    def counting_put(key, arr, *a, **k):
        decoded[-1].add(key)
        return put(key, arr, *a, **k)

    monkeypatch.setattr(cache, "get", counting_get)
    monkeypatch.setattr(cache, "put", counting_put)
    for i in range(3):
        decoded.append(set())
        read.append(set())
        out = stage.run_pass(i)
    assert out["work"] == 6
    assert read[0] and read[1] == read[0] and read[2] == read[0]
    assert all(r <= d for r, d in zip(read, decoded)), \
        [len(r - d) for r, d in zip(read, decoded)]
    got = stage.stored(out["out"])
    assert sorted(got) == sorted((a, b) for a, b, _lo, _hi in stage.pairs())
