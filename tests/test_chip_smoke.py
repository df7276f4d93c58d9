"""chip_smoke.py's contract, as far as a CPU-only machine can hold it to it:
without the rehearsal argument it must FAIL here and name the missing TPU;
the rehearsal drives the same stages and checks at toy size; the parent
process stays off jax; and the compile cache is placed from outside or at
one fixed in-checkout path."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (stdlib-only parent module)


def _run(args, cwd=REPO, env=None, timeout=600):
    e = dict(os.environ)
    e.pop("XLA_FLAGS", None)   # one CPU device: the stages' own default
    e.update(env or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=timeout)


def _result_lines(stdout: str) -> list[str]:
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


def test_without_a_tpu_it_fails_and_says_so(tmp_path):
    r = _run([SMOKE, "--workdir", str(tmp_path / "w")])
    assert r.returncode != 0
    assert "no TPU" in r.stdout and "'cpu'" in r.stdout
    assert not _result_lines(r.stdout)


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path))
    assert r.returncode != 0
    assert not _result_lines(r.stdout)


def test_parent_imports_neither_jax_nor_the_package():
    r = _run(["-c", "import sys, chip_smoke; "
              "bad = [m for m in sys.modules if m == 'jax' or "
              "m.startswith(('jax.', 'bigstitcher_spark_tpu'))]; "
              "assert not bad, bad"])
    assert r.returncode == 0, r.stderr


def test_the_smoke_imports_no_module_bench():
    """Its numpy fusion is its own: nothing at the repo's root is a
    second yardstick the smoke leans on."""
    import ast

    with open(SMOKE) as f:
        tree = ast.parse(f.read())
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names}
    imported |= {(n.module or "").split(".")[0] for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)}
    assert "bench" not in imported


@pytest.mark.parametrize("config", ["grid1k", "multiview-affine"])
def test_the_smokes_reference_agrees_with_the_benchmarks(config, tmp_path):
    """Two numpy fusions that share no code: the smoke's (float32, voxels
    through the program's loader from a project on disk) and the cells'
    (float64, voxels from the seeded generator). Corner, centre and far
    edge of the bounding box, as the smoke samples them, for a translation
    grid and for views under general affines. Left out as in the cells'
    ``correct``: voxels whose summed blend weight is under 1e-5, where the
    float32 cosine ramp cancels to nought."""
    import numpy as np

    from benchmark.reference.fixture import Acquisition
    from benchmark.reference.fusion import fuse_box
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.utils.geometry import Interval

    with open(os.path.join(REPO, "benchmark", "configs",
                           f"{config}.json")) as f:
        cfg = json.load(f)
    acq = Acquisition({**cfg["fixture"], **cfg["rehearsal_fixture"]}, 7)
    acq.write(str(tmp_path), threads=2)
    sd = SpimData.load(str(tmp_path / "registered.xml"))
    loader = ViewLoader(sd)
    shape = np.array([48, 48, 24])
    covered = 0
    for lo in (acq.bbox_min, (acq.bbox_min + acq.bbox_max - shape) // 2,
               acq.bbox_max + 1 - shape):
        box = Interval.from_shape(tuple(shape), tuple(int(v) for v in lo))
        got = chip_smoke.reference_fuse_block(sd, loader, sd.view_ids(), box)
        want, weight = fuse_box(acq, lo, tuple(shape))
        diff = np.abs(got.astype(np.int64) - want.astype(np.int64))
        assert diff.mean() < 1.0           # what the smoke holds a block to
        sound = ~((weight > 0) & (weight < 1e-5))
        assert diff[sound].max() <= 1      # a rounding step, float32 / 64
        assert diff[sound].mean() < 1e-3
        covered += int((weight > 0).sum())
        assert got.std() > 0
    assert covered > shape.prod()          # the boxes are not empty space


def test_rehearsal_runs_every_stage_and_check_at_toy_size(tmp_path):
    # with the compile cache on (the suite runs without) and placed from
    # outside, so the rerun-adds-nothing check counts real entries
    cache = str(tmp_path / "cache")
    r = _run([SMOKE, "--rehearsal", "--workdir", str(tmp_path / "w")],
             env={"JAX_ENABLE_COMPILATION_CACHE": "true",
                  "JAX_COMPILATION_CACHE_DIR": cache})
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    assert f'"dir": "{cache}", "placed_by": "JAX_COMPILATION_CACHE_DIR"' \
        in r.stdout
    assert len(os.listdir(cache)) > 10
    assert "REHEARSAL" in r.stdout
    last = json.loads(r.stdout.splitlines()[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    for stage in ("resave", "stitching", "solver-stitching",
                  "detect-interestpoints", "match-interestpoints",
                  "solver-ip", "affine-fusion", "affine-fusion-rerun",
                  "verify"):
        assert f"] {stage}: " in r.stdout, stage
    assert '"entries_added_by_rerun": 0' in r.stdout
    assert not (tmp_path / "w").exists()   # it cleans up after itself


class TestDeviceProof:
    """check_device_proof is what turns a manifest into a verdict."""

    def _stage(self, name="affine-fusion", **over):
        rec = {"stage": name, "wall_s": 1.0,
               "device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                          "device_count": 4},
               "retry_rounds": 0, "blocks_failed": 0, "redispatch": 0,
               "budget_windows": {"stats": 4}, "native_codec": True,
               "fusion_driver": "sharded",
               "drain_rows_per_device": {0: 5, 1: 5, 2: 5, 3: 5}}
        rec.update(over)
        return rec

    def test_clean_four_chip_run_passes(self):
        stages = [self._stage(),
                  # a 2x2 grid's PCM is three shape buckets: three tasks
                  self._stage("stitching", fusion_driver=None,
                              drain_rows_per_device=None,
                              dispatch_per_device={0: 1, 1: 1, 2: 1}),
                  self._stage("match-interestpoints", fusion_driver=None,
                              drain_rows_per_device=None,
                              dispatch_per_device={0: 1, 1: 1, 2: 2, 3: 2})]
        assert chip_smoke.check_device_proof(stages, rehearsal=False) == []

    @pytest.mark.parametrize("over,needle", [
        ({"device": {"platform": "cpu", "device_count": 1}}, "not tpu"),
        ({"device": {"error": "RuntimeError('no backend')"}},
         "no device inventory"),
        ({"budget_windows": {"stats": 3, "fallback": 1}}, "memory_stats"),
        ({"native_codec": False}, "native codec"),
        ({"redispatch": 1}, "retries happened"),
        ({"retry_rounds": 2}, "retries happened"),
        ({"drain_rows_per_device": {0: 9, 1: 1, 2: 0}}, "did no work"),
        ({"fusion_driver": "per-block"}, "driver ran"),
    ])
    def test_each_degraded_run_is_named(self, over, needle):
        bad = chip_smoke.check_device_proof([self._stage(**over)],
                                            rehearsal=False)
        assert any(needle in b for b in bad), bad

    def test_a_pair_stage_without_a_recorded_window_is_named(self):
        bad = chip_smoke.check_device_proof(
            [self._stage("stitching", fusion_driver=None,
                         drain_rows_per_device=None, budget_windows={},
                         dispatch_per_device={0: 1, 1: 1, 2: 1})],
            rehearsal=False)
        assert any("no dispatch window" in b for b in bad), bad

    def test_a_solve_placed_off_the_device_is_named(self):
        ok = self._stage("solver-ip", fusion_driver=None, budget_windows={},
                         native_codec=False, drain_rows_per_device=None,
                         solver_backend="device")
        assert chip_smoke.check_device_proof([ok], rehearsal=False) == []
        bad = chip_smoke.check_device_proof(
            [dict(ok, solver_backend="numpy")], rehearsal=False)
        assert any("relaxation ran on 'numpy'" in b for b in bad), bad

    def test_idle_chip_in_a_pair_stage_is_named(self):
        bad = chip_smoke.check_device_proof(
            [self._stage("match-interestpoints", fusion_driver=None,
                         drain_rows_per_device=None,
                         dispatch_per_device={0: 3, 1: 3})], rehearsal=False)
        assert any("devices [2, 3] did no work" in b for b in bad), bad


class TestCompileCachePlacement:
    PRINT = ("import bigstitcher_spark_tpu, jax; "
             "print(jax.config.jax_compilation_cache_dir)")

    def test_env_placement_is_left_alone(self, tmp_path):
        where = str(tmp_path / "placed-from-outside")
        r = _run(["-c", self.PRINT],
                 env={"JAX_COMPILATION_CACHE_DIR": where})
        assert r.returncode == 0, r.stderr
        assert r.stdout.strip() == where

    def test_unset_resolves_one_fixed_gitignored_checkout_path(self,
                                                               tmp_path):
        env = {"JAX_COMPILATION_CACHE_DIR": "", "PYTHONPATH": REPO}
        a = _run(["-c", self.PRINT], env=env)
        b = _run(["-c", self.PRINT], env=env, cwd=str(tmp_path))
        assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
        assert a.stdout.strip() == b.stdout.strip() \
            == os.path.join(REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
