"""Device-side global solvers (ops/solve.py): exact-parity suite.

The contract of the device-solver PR: the jit-compiled relaxation — one
``lax.while_loop`` per ``relax()`` call — tracks the numpy reference path
through the mpicbg convergence state (same iteration count, same error
history to ≤1e-6 documented tolerance, in practice ~1e-12 relative), the
iterative drop-worst-link loop removes the IDENTICAL link sequence, a
masked-link re-solve is bitwise equal to a rebuilt-link-list solve, the
psum-sharded layout is bitwise equal to the single-device one, repeated
solves hit warm compile buckets, and the relax inner loop performs zero
per-iteration host transfers (trace-asserted). The intensity coefficient
CG gets the same treatment against the dense normal-equations solve.
"""

import numpy as np
import pytest

from bigstitcher_spark_tpu import config, profiling
from bigstitcher_spark_tpu.io.spimdata import ViewId
from bigstitcher_spark_tpu.models import solver as S
from bigstitcher_spark_tpu.models.intensity import smoothness_pairs
from bigstitcher_spark_tpu.observe import metrics as _metrics, trace
from bigstitcher_spark_tpu.ops import models as M
from bigstitcher_spark_tpu.ops.intensity import (
    match_stats,
    solve_intensity_coefficients,
)


@pytest.fixture(autouse=True)
def _clean_trace_state():
    trace.reset()
    profiling.enable(False)
    profiling.get().reset()
    yield
    trace.reset()
    profiling.enable(False)
    profiling.get().reset()


def _graph(n=(4, 3), jitter=3.0, seed=0, tile=(100, 100, 50), step=80.0):
    """Synthetic tile-grid link graph: truth-consistent 8-corner links
    (the stitching-source shape) with jittered nominal positions."""
    rng = np.random.default_rng(seed)
    tiles = [(ViewId(0, i),) for i in range(n[0] * n[1])]
    truth = {i: np.array([(i % n[0]) * step, (i // n[0]) * step, 0.0])
             for i in range(len(tiles))}
    nom = {i: truth[i] + (rng.uniform(-jitter, jitter, 3) if i else 0.0)
           for i in truth}
    corners = np.array([[x, y, z] for x in (0, tile[0]) for y in (0, tile[1])
                        for z in (0, tile[2])], float)
    links = []
    for i in range(len(tiles)):
        for j in (i + 1, i + n[0]):
            if j >= len(tiles):
                continue
            if j == i + 1 and (i % n[0]) == n[0] - 1:
                continue
            shift = (truth[i] - nom[i]) - (truth[j] - nom[j])
            links.append(S.MatchLink(tiles[i], tiles[j], corners,
                                     corners + shift, np.full(8, 0.9)))
    return tiles, links


def _assert_same_result(a: S.SolveResult, b: S.SolveResult,
                        rtol=1e-9, atol=1e-9, exact=False):
    assert a.iterations == b.iterations
    if exact:
        np.testing.assert_array_equal(a.history, b.history)
    else:
        np.testing.assert_allclose(a.history, b.history, rtol=rtol,
                                   atol=atol)
    assert set(a.corrections) == set(b.corrections)
    for k in a.corrections:
        if exact:
            np.testing.assert_array_equal(a.corrections[k],
                                          b.corrections[k])
        else:
            np.testing.assert_allclose(a.corrections[k], b.corrections[k],
                                       rtol=1e-7, atol=atol)
    assert set(a.link_errors) == set(b.link_errors)
    for k in a.link_errors:
        np.testing.assert_allclose(a.link_errors[k], b.link_errors[k],
                                   rtol=1e-7, atol=atol)


# ------------------------------------------------------- relax parity


class TestRelaxParity:
    COMBOS = [
        (M.TRANSLATION, M.NONE),
        (M.RIGID, M.NONE),
        (M.AFFINE, M.NONE),
        (M.AFFINE, M.RIGID),
        (M.RIGID, M.TRANSLATION),
        (M.TRANSLATION, M.IDENTITY),
    ]

    @pytest.mark.parametrize("model,reg", COMBOS)
    def test_device_matches_numpy(self, model, reg):
        tiles, links = _graph()
        fixed = {tiles[0]}
        pn = S.SolverParams(model=model, regularization=reg,
                            backend="numpy")
        pd = S.SolverParams(model=model, regularization=reg,
                            backend="device")
        rn = S.relax(links, tiles, fixed, pn)
        rd = S.relax(links, tiles, fixed, pd)
        # same compiled-convergence semantics: identical sweep count and
        # an error history that tracks to f64 noise (documented ≤1e-6)
        _assert_same_result(rn, rd, rtol=1e-9, atol=1e-9)

    def test_knob_selects_backend(self, monkeypatch):
        params = S.SolverParams()
        assert S._resolve_backend(params) == "device"
        monkeypatch.setenv("BST_SOLVE_DEVICE", "0")
        assert S._resolve_backend(params) == "numpy"
        # explicit params win over the knob
        assert S._resolve_backend(
            S.SolverParams(backend="device")) == "device"
        with config.overrides({"BST_SOLVE_DEVICE": True}):
            assert S._resolve_backend(params) == "device"

    def test_tpu_places_the_f64_lu_solve_on_the_host(self, monkeypatch,
                                                     capsys):
        """XLA:TPU has no float64 LuDecomposition (measured, PR 21): the
        AFFINE fit is placed on the numpy solver by rule and says so; the
        models that compile stay on the device; an explicit request is
        honoured."""
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert S._resolve_backend(S.SolverParams(model=M.AFFINE)) == "numpy"
        assert "float64 LU" in capsys.readouterr().out
        assert S._resolve_backend(S.SolverParams(
            model=M.RIGID, regularization=M.AFFINE)) == "numpy"
        capsys.readouterr()
        for model in (M.TRANSLATION, M.RIGID):
            assert S._resolve_backend(
                S.SolverParams(model=model)) == "device"
        assert S._resolve_backend(S.SolverParams(
            model=M.RIGID, regularization=M.AFFINE, lam=0.0)) == "device"
        assert S._resolve_backend(S.SolverParams(
            model=M.AFFINE, backend="device")) == "device"
        assert capsys.readouterr().out == ""

    def test_empty_links_identity(self):
        tiles, _ = _graph(n=(2, 1))
        res = S.relax([], tiles, {tiles[0]},
                      S.SolverParams(backend="device"))
        for k in tiles:
            np.testing.assert_array_equal(res.corrections[k][:, :3],
                                          np.eye(3))
        assert res.iterations == 0


class TestIterativeParity:
    def _bad_graph(self):
        tiles, links = _graph()
        corners = links[0].p
        links.append(S.MatchLink(tiles[0], tiles[5], corners,
                                 corners + np.array([80.0, -60.0, 40.0]),
                                 np.full(8, 0.8)))
        return tiles, links

    def test_drops_identical_link_sequence(self):
        tiles, links = self._bad_graph()
        fixed = {tiles[0]}
        pn = S.SolverParams(model=M.TRANSLATION,
                            method="ONE_ROUND_ITERATIVE", backend="numpy")
        pd = S.SolverParams(model=M.TRANSLATION,
                            method="ONE_ROUND_ITERATIVE", backend="device")
        rn = S.solve_iterative(links, tiles, fixed, pn, verbose=False)
        rd = S.solve_iterative(links, tiles, fixed, pd, verbose=False)
        assert len(rn.removed_links) >= 1
        assert rn.removed_links == rd.removed_links
        for k in rn.corrections:
            np.testing.assert_allclose(rd.corrections[k],
                                       rn.corrections[k], rtol=1e-7,
                                       atol=1e-9)

    def test_dropped_links_metric(self):
        tiles, links = self._bad_graph()
        c = _metrics.counter("bst_solve_links_dropped_total")
        before = c.value
        S.solve_iterative(links, tiles, {tiles[0]},
                          S.SolverParams(model=M.TRANSLATION,
                                         method="ONE_ROUND_ITERATIVE",
                                         backend="device"), verbose=False)
        assert c.value >= before + 1

    def test_masked_resolve_equals_rebuilt(self):
        """Re-entering the compiled fn with a zeroed link-weight mask must
        equal rebuilding the link list from scratch BITWISE — the property
        that lets solve_iterative skip per-drop re-traces."""
        tiles, links = self._bad_graph()
        fixed = {tiles[0]}
        pd = S.SolverParams(model=M.TRANSLATION, backend="device")
        state = S._DeviceRelax(links, tiles, fixed, pd)
        mask = np.ones(len(links))
        mask[-1] = 0.0
        masked = state.solve(mask)
        rebuilt = S.relax(links[:-1], tiles, fixed, pd)
        _assert_same_result(masked, rebuilt, exact=True)


class TestShardedParity:
    def test_sharded_equals_single_device_bitwise(self):
        """Rows grouped by owner tile: per-tile segment moments accumulate
        entirely on one device in single-device row order, psum adds exact
        zeros — the collective layout changes NOTHING, bit for bit."""
        tiles, links = _graph(n=(6, 4))
        fixed = {tiles[0]}
        pd = S.SolverParams(model=M.AFFINE, regularization=M.RIGID,
                            backend="device")
        single = S.relax(links, tiles, fixed, pd)
        with config.overrides({"BST_SOLVE_SHARD": 1}):
            sharded = S.relax(links, tiles, fixed, pd)
        _assert_same_result(single, sharded, exact=True)

    def test_shard_threshold_respected(self):
        tiles, links = _graph(n=(3, 2))
        pd = S.SolverParams(backend="device")
        with config.overrides({"BST_SOLVE_SHARD": 10 ** 9}):
            st = S._DeviceRelax(links, tiles, {tiles[0]}, pd)
            assert st.problem.n_shards == 1
        with config.overrides({"BST_SOLVE_SHARD": 1}):
            st = S._DeviceRelax(links, tiles, {tiles[0]}, pd)
            assert st.problem.n_shards > 1
        with config.overrides({"BST_SOLVE_SHARD": 0}):
            st = S._DeviceRelax(links, tiles, {tiles[0]}, pd)
            assert st.problem.n_shards == 1


class TestCompileBuckets:
    def test_warm_hit_on_repeat(self):
        tiles, links = _graph(seed=7)
        pd = S.SolverParams(model=M.RIGID, backend="device")
        warm = _metrics.counter("bst_compiled_fn_warm_hits_total")
        S.relax(links, tiles, {tiles[0]}, pd)
        before = warm.value
        # same shape bucket (same grid) — must hit the warm compiled fn
        S.relax(links, tiles, {tiles[0]}, pd)
        assert warm.value > before

    def test_iterative_resolves_share_one_bucket(self):
        """The drop-worst-link loop re-enters ONE compiled fn: every
        re-solve after the first is a warm hit."""
        tiles, links = _graph()
        corners = links[0].p
        links.append(S.MatchLink(tiles[0], tiles[5], corners,
                                 corners + np.array([80.0, -60.0, 40.0]),
                                 np.full(8, 0.8)))
        warm = _metrics.counter("bst_compiled_fn_warm_hits_total")
        cold = _metrics.counter("bst_compiled_fn_cold_builds_total")
        pd = S.SolverParams(model=M.TRANSLATION,
                            method="ONE_ROUND_ITERATIVE", backend="device")
        S.solve_iterative(links, tiles, {tiles[0]}, pd, verbose=False)
        w0, c0 = warm.value, cold.value
        res = S.solve_iterative(links, tiles, {tiles[0]}, pd,
                                verbose=False)
        assert len(res.removed_links) >= 1  # ≥2 relax calls ran
        assert cold.value == c0             # zero new compile buckets
        assert warm.value >= w0 + 2


class TestSingleWhileLoop:
    def test_one_relax_span_many_iterations(self):
        """The acceptance trace assertion: a relax() that iterates N ≫ 1
        times records exactly ONE solve.relax span (one compiled
        while_loop call) and one solve.reduce fetch — no per-iteration
        host round trips on the solver hot path."""
        trace.configure(buffer_bytes=1 << 20)
        tiles, links = _graph()
        pd = S.SolverParams(model=M.TRANSLATION, regularization=M.IDENTITY,
                            backend="device")
        res = S.relax(links, tiles, {tiles[0]}, pd)
        assert res.iterations > 10  # genuinely iterative solve
        snap = trace.snapshot()
        relax_b = [e for e in snap if e["name"] == "solve.relax"
                   and e["ph"] == "B"]
        reduce_b = [e for e in snap if e["name"] == "solve.reduce"
                    and e["ph"] == "B"]
        assert len(relax_b) == 1
        assert len(reduce_b) == 1
        # nothing else on the timeline: the solve never touches the mesh
        # drain or per-pair dispatch machinery mid-iteration
        other = {e["name"] for e in snap
                 if e["name"] not in ("solve.relax", "solve.reduce")}
        assert not other, other

    def test_iteration_metric_counts_sweeps(self):
        tiles, links = _graph()
        c = _metrics.counter("bst_solve_iterations_total")
        before = c.value
        res = S.relax(links, tiles, {tiles[0]},
                      S.SolverParams(backend="device"))
        assert c.value == before + res.iterations
        ms = _metrics.counter("bst_solve_device_ms_total", stage="relax")
        assert ms.value > 0


# ------------------------------------------------------- warm start


class TestDirectTranslations:
    def _dense_reference(self, links, index, fixed_idx, T):
        A = np.zeros((T, T))
        B = np.zeros((T, 3))
        for lk in links:
            ia, ib = index[lk.key_a], index[lk.key_b]
            wsum = float(lk.w.sum())
            s = ((lk.q - lk.p) * lk.w[:, None]).sum(0) / max(wsum, 1e-12)
            A[ia, ia] += wsum; A[ib, ib] += wsum
            A[ia, ib] -= wsum; A[ib, ia] -= wsum
            B[ia] += wsum * s; B[ib] -= wsum * s
        anchor = fixed_idx if len(fixed_idx) else np.arange(1)
        A[anchor, :] = 0.0
        A[anchor, anchor] = 1.0
        B[anchor] = 0.0
        iso = np.diag(A) == 0
        A[iso, iso] = 1.0
        return np.linalg.solve(A, B)

    def test_sparse_assembly_matches_dense(self):
        tiles, links = _graph(n=(5, 4), seed=3)
        index = {k: i for i, k in enumerate(tiles)}
        T = len(tiles)
        for fixed_idx in (np.array([0]), np.array([2, 7]),
                          np.array([], int)):
            sparse = S._direct_translations(links, index, fixed_idx, T)
            dense = self._dense_reference(links, index, fixed_idx, T)
            np.testing.assert_allclose(sparse, dense, rtol=1e-9, atol=1e-9)

    def test_isolated_tiles_stay_at_zero(self):
        tiles, links = _graph(n=(2, 1), seed=4)
        tiles = tiles + [(ViewId(0, 99),)]  # no links touch it
        index = {k: i for i, k in enumerate(tiles)}
        out = S._direct_translations(links, index, np.array([0]),
                                     len(tiles))
        np.testing.assert_array_equal(out[-1], 0.0)

    def test_no_dense_tt_allocation(self, monkeypatch):
        """The O(T²) guard: the warm start must never build a (T, T)
        ndarray again (the million-tile motivation of the rework)."""
        tiles, links = _graph(n=(6, 5), seed=5)
        index = {k: i for i, k in enumerate(tiles)}
        T = len(tiles)
        real_zeros = np.zeros

        def guarded(shape, *a, **k):
            if isinstance(shape, tuple) and len(shape) == 2 \
                    and shape[0] == T and shape[1] == T:
                raise AssertionError("dense (T,T) allocation in warm start")
            return real_zeros(shape, *a, **k)

        monkeypatch.setattr(np, "zeros", guarded)
        S._direct_translations(links, index, np.array([0]), T)


# ------------------------------------------------------- intensity CG


class TestIntensityDevice:
    def _system(self, seed=0, n_views=3, dims=(4, 4, 4), n_matches=300):
        rng = np.random.default_rng(seed)
        ncell = int(np.prod(dims))
        C = ncell * n_views
        matches = []
        for _ in range(n_matches):
            ca, cb = rng.integers(0, C, 2)
            if ca == cb:
                continue
            x = rng.uniform(100, 1000, 50)
            a, b = rng.uniform(0.8, 1.2), rng.uniform(-20, 20)
            y = a * x + b + rng.normal(0, 5, 50)
            matches.append((int(ca), int(cb),
                            *match_stats(x / 500, y / 500)))
        return C, matches, smoothness_pairs(dims, n_views)

    def test_cg_matches_dense_solve(self):
        C, matches, smooth = self._system()
        dense = solve_intensity_coefficients(C, matches, 0.1,
                                             smooth_pairs=smooth,
                                             backend="numpy")
        dev = solve_intensity_coefficients(C, matches, 0.1,
                                           smooth_pairs=smooth,
                                           backend="device")
        # documented tolerance: CG converges to the direct solve ≤1e-6
        np.testing.assert_allclose(dev, dense, rtol=1e-6, atol=1e-6)

    def test_sharded_matches_single(self):
        C, matches, smooth = self._system(seed=1)
        dev = solve_intensity_coefficients(C, matches, 0.1,
                                           smooth_pairs=smooth,
                                           backend="device")
        with config.overrides({"BST_SOLVE_SHARD": 1}):
            sh = solve_intensity_coefficients(C, matches, 0.1,
                                              smooth_pairs=smooth,
                                              backend="device")
        np.testing.assert_allclose(sh, dev, rtol=1e-8, atol=1e-8)

    def test_unmatched_cells_solve_to_identity(self):
        out = solve_intensity_coefficients(16, [], 0.1, backend="device")
        np.testing.assert_allclose(out[:, 0], 1.0)
        np.testing.assert_allclose(out[:, 1], 0.0)

    def test_device_metrics_and_spans(self):
        trace.configure(buffer_bytes=1 << 20)
        C, matches, smooth = self._system(seed=2, n_matches=100)
        ms = _metrics.counter("bst_solve_device_ms_total",
                              stage="intensity")
        before = ms.value
        solve_intensity_coefficients(C, matches, 0.1, smooth_pairs=smooth,
                                     backend="device")
        assert ms.value > before
        names = [e["name"] for e in trace.snapshot() if e["ph"] == "B"]
        assert names.count("solve.relax") == 1
        assert names.count("solve.reduce") == 1


class TestSmoothnessPairs:
    def _reference_loop(self, dims, n_views):
        ncell = int(np.prod(dims))
        smooth = []
        strides = (dims[1] * dims[2], dims[2], 1)
        for vi in range(n_views):
            b = vi * ncell
            for cx in range(dims[0]):
                for cy in range(dims[1]):
                    for cz in range(dims[2]):
                        c = (cx * dims[1] + cy) * dims[2] + cz
                        for d, n_d in enumerate(dims):
                            if (c // strides[d]) % n_d + 1 < n_d:
                                smooth.append((b + c, b + c + strides[d]))
        return smooth

    @pytest.mark.parametrize("dims,n_views", [
        ((8, 8, 8), 2), ((3, 4, 5), 3), ((1, 1, 1), 2), ((2, 1, 3), 1),
    ])
    def test_same_pair_set_as_reference_loop(self, dims, n_views):
        new = smoothness_pairs(dims, n_views)
        old = self._reference_loop(dims, n_views)
        assert len(new) == len(old)
        assert set(map(tuple, new.tolist())) == set(old)


# ------------------------------------------------------- pipeline round


def test_registration_pipeline_detect_match_solve(tmp_path):
    """The dag/spec.py registration round: detect → match → solve as ONE
    streamed pipeline job, the solver barrier-gated on the matcher's
    stored correspondences, optimized registrations written to the XML."""
    from bigstitcher_spark_tpu.dag import (
        PipelineSpec,
        registration_spec,
        run_pipeline,
    )
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    proj = make_synthetic_project(
        str(tmp_path / "proj"), n_tiles=(2, 1, 1), tile_size=(80, 80, 40),
        overlap=28, jitter=2.0, seed=6, n_beads_per_tile=35,
    )
    d = registration_spec(proj.xml_path)
    # small-fixture matcher settings (the spec's defaults target real data)
    d["stages"][1]["args"] += ["--ransacMinNumInliers", "5",
                               "--ransacIterations", "2000"]
    spec = PipelineSpec.from_dict(d)
    res = run_pipeline(spec, workdir=str(tmp_path))
    assert res.ok, res.stages
    assert [s["state"] for s in res.stages] == ["done"] * 3
    sd = SpimData.load(proj.xml_path)
    chain = sd.registrations[ViewId(0, 1)]
    assert any("[ip]" in t.name for t in chain), [t.name for t in chain]
    # the solve recovered the jittered offset: both tiles end up on the
    # true grid up to the fixed tile's shared residual
    resid = {v.setup: sd.model(v)[:, 3] - proj.true_offsets[v.setup]
             for v in sd.view_ids()}
    np.testing.assert_allclose(resid[1], resid[0], atol=0.5)


def test_registration_spec_validates_and_inits(tmp_path):
    from click.testing import CliRunner

    from bigstitcher_spark_tpu.cli.main import cli
    from bigstitcher_spark_tpu.dag import PipelineSpec, registration_spec
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    proj = make_synthetic_project(
        str(tmp_path / "proj"), n_tiles=(2, 1, 1), tile_size=(48, 48, 24),
        overlap=16, seed=1, n_beads_per_tile=10,
    )
    spec = PipelineSpec.from_dict(registration_spec(proj.xml_path))
    by_id = {s.id: s for s in spec.stages}
    assert spec.barrier_parents(by_id["solve"]) == {"match"}
    assert spec.barrier_parents(by_id["match"]) == {"detect"}
    out = str(tmp_path / "reg.json")
    res = CliRunner().invoke(cli, [
        "pipeline", "init", out, "-x", proj.xml_path, "--registration",
        "--label", "beads"])
    assert res.exit_code == 0, res.output
    loaded = PipelineSpec.load(out)
    assert [s.tool for s in loaded.stages] == [
        "detect-interestpoints", "match-interestpoints", "solver"]
