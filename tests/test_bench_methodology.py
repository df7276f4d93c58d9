"""The bench's timing methodology is itself load-bearing evidence, so the
sync/drift primitives get their own tests: a silent regression here would
let kernel metrics measure dispatch latency instead of compute (see
bench._tiny_fetch)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from bigstitcher_spark_tpu import profiling


class TestDeviceSync:
    def test_returns_input_and_blocks(self):
        x = jnp.arange(8.0) * 2.0
        assert profiling.device_sync(x) is x
        np.testing.assert_allclose(np.asarray(x)[0], 0.0)

    def test_pytree_and_scalars(self):
        tree = {"a": jnp.ones((2, 3)), "b": (jnp.float32(3.0), "not-an-array")}
        assert profiling.device_sync(tree) is tree

    def test_empty_leaf_skipped(self):
        profiling.device_sync(jnp.zeros((0, 3)))  # must not raise


class TestTinyFetch:
    def test_syncs_first_nonempty_leaf(self):
        out = (jnp.zeros((0,)), jnp.arange(4))
        got = bench._tiny_fetch(out)  # returns the synced non-empty leaf
        np.testing.assert_array_equal(np.asarray(got), [0, 1, 2, 3])

    def test_raises_when_nothing_to_sync(self):
        with pytest.raises(ValueError, match="no non-empty array leaf"):
            bench._tiny_fetch((jnp.zeros((0,)), "x"))


class TestKernelRate:
    def test_measures_real_work(self):
        x = jax.device_put(np.random.rand(256, 256).astype(np.float32))
        f = jax.jit(lambda x: x @ x)
        bench._tiny_fetch(f(x))  # warm
        per = bench._kernel_rate(lambda: f(x), reps=5)
        assert per > 0
        # sanity ceiling: 5 reps of a 256^2 matmul cannot take a minute
        assert per < 60

    def test_noise_fallback_is_conservative(self):
        # a dispatch whose cost is far below timer noise must not produce
        # an absurd rate: the fallback keeps the k=reps total's constant
        x = jnp.float32(1.0)
        f = jax.jit(lambda x: x + 1)
        bench._tiny_fetch(f(x))
        per = bench._kernel_rate(lambda: f(x), reps=5)
        assert per >= 1e-9


class TestBaselineDrift:
    def _with_cache(self, monkeypatch, tmp_path, cache):
        p = tmp_path / "baseline.json"
        p.write_text(json.dumps(cache))
        monkeypatch.setattr(bench, "BASELINE_FILE", str(p))

    def test_same_key_drift_flagged(self, monkeypatch, tmp_path):
        self._with_cache(monkeypatch, tmp_path, {
            "dog": {"key": "k1", "previous_key": "k1",
                    "vox_per_sec": 100.0, "previous_vox_per_sec": 500.0}})
        flags = bench._baseline_drift_flags()
        assert flags["dog"]["ratio"] == pytest.approx(0.2)

    def test_fixture_change_not_misreported_as_drift(self, monkeypatch,
                                                     tmp_path):
        self._with_cache(monkeypatch, tmp_path, {
            "dog": {"key": "k2", "previous_key": "k1",
                    "vox_per_sec": 100.0, "previous_vox_per_sec": 500.0}})
        assert bench._baseline_drift_flags() == {}

    def test_small_drift_not_flagged(self, monkeypatch, tmp_path):
        self._with_cache(monkeypatch, tmp_path, {
            "dog": {"key": "k1", "previous_key": "k1",
                    "vox_per_sec": 120.0, "previous_vox_per_sec": 100.0}})
        assert bench._baseline_drift_flags() == {}

    def test_corrupt_cache_tolerated(self, monkeypatch, tmp_path):
        p = tmp_path / "baseline.json"
        p.write_text('{"dog": {"key": ')  # truncated
        monkeypatch.setattr(bench, "BASELINE_FILE", str(p))
        assert bench._baseline_cache_load() == {}
        assert bench._baseline_drift_flags() == {}
