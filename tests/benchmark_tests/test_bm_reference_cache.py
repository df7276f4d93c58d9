"""The fusion cells' comparison computes each distinct checked block's
float64 reference once a run, and reads the same numbers as it did when it
computed one anew for every sampled block of every pass."""

import pytest

CELLS = ["multiview.fuse", "multiview.nonrigid"]


class _Forgets(dict):
    """A reference store that keeps nothing: every block computed anew."""

    def __setitem__(self, key, value):
        pass


@pytest.fixture(scope="module", params=CELLS)
def toy_window(request, tmp_path_factory):
    """A cell's stage adapter at toy size over its fixture on disk, and a
    window of eight passes made of two stored outputs."""
    from benchmark import run

    job = run.load_cell(request.param, rehearse=True)
    root = str(tmp_path_factory.mktemp("toy"))
    stage = run.build_stage(job, root, root, 77, 1)
    stage.acq.write(root, threads=2)
    done = [stage.run_pass(i) for i in range(2)]
    return job, root, done * 4


def _counted(stage):
    """Count the stage's float64 reference computations."""
    calls = []
    compute = stage._reference

    def counting(lo, shp, precision="float64"):
        calls.append((tuple(int(v) for v in lo), tuple(shp), precision))
        return compute(lo, shp, precision)

    stage._reference = counting
    return calls


def test_each_checked_block_is_computed_once_a_run(toy_window):
    from benchmark import run

    job, root, passes = toy_window
    kept = run.build_stage(job, root, root, 77, 1)
    kept_calls = _counted(kept)
    numbers = kept.check(passes)

    anew = run.build_stage(job, root, root, 77, 1)
    anew._refs = _Forgets()
    anew_calls = _counted(anew)
    assert anew.check(passes) == numbers

    per_pass = kept._per_pass(len(passes))
    picks = [(tuple(int(v) for v in lo), tuple(shp))
             for k in range(len(passes))
             for lo, shp in kept._sample(k, per_pass)[0]]
    distinct = set(picks)
    assert len(anew_calls) == len(picks)
    assert {c[:2] for c in kept_calls} == distinct
    assert len(kept_calls) == len(distinct) < len(picks)
    assert all(c[2] == "float64" for c in kept_calls)
    # the window still reads back every pass's stored output
    assert numbers["checked_blocks"] == len(picks)
