"""Direct tests of the shared sharded work loop (parallel/mesh.py):
ordering, the early-dispatch device double-buffering, and its
interaction with the retry path — a consume failure in a batch whose
successor was already dispatched must still retry cleanly and deliver
every item's correct output exactly once to a successful consume.
Reference failure model: RetryTrackerSpark.java:28-61 (resubmit ≤5)."""

from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

from bigstitcher_spark_tpu.parallel.mesh import run_sharded_batches
from bigstitcher_spark_tpu.parallel.retry import RetryError


def _kernel(x):
    return x * 2.0


def _kernel_two_outputs(x):
    return x * 2.0, x + 1.0


class TestRunShardedBatches:
    def _run(self, n_items, consume, kernel=_kernel, per_dev=1):
        items = list(range(n_items))
        with ThreadPoolExecutor(4) as pool:
            run_sharded_batches(
                items,
                build=lambda it: (np.full((4,), float(it), np.float32),),
                kernel=jax.jit(kernel),
                consume=consume,
                n_dev=1,
                pool=pool,
                per_dev=per_dev,
            )

    def test_every_item_consumed_with_its_own_output(self):
        got = {}

        def consume(it, out):
            got[it] = np.asarray(out).copy()

        self._run(7, consume, per_dev=2)
        assert sorted(got) == list(range(7))
        for it, out in got.items():
            np.testing.assert_allclose(out, np.full((4,), 2.0 * it))

    def test_multi_output_kernels(self):
        got = {}

        def consume(it, a, b):
            got[it] = (np.asarray(a).copy(), np.asarray(b).copy())

        self._run(5, consume, kernel=_kernel_two_outputs, per_dev=2)
        for it, (a, b) in got.items():
            np.testing.assert_allclose(a, np.full((4,), 2.0 * it))
            np.testing.assert_allclose(b, np.full((4,), it + 1.0))

    def test_consume_failure_retries_without_duplicate_or_loss(self):
        # fail item 2's consume ONCE, on a run long enough that item 3's
        # batch has been early-dispatched by the time 2 drains: the retry
        # must re-run batch 2 only, and every item lands exactly once
        got = {}
        fails = {"n": 0}

        def consume(it, out):
            if it == 2 and fails["n"] == 0:
                fails["n"] += 1
                raise RuntimeError("transient write failure")
            assert it not in got, f"item {it} consumed twice"
            got[it] = np.asarray(out).copy()

        self._run(6, consume)
        assert sorted(got) == list(range(6))
        for it, out in got.items():
            np.testing.assert_allclose(out, np.full((4,), 2.0 * it))
        assert fails["n"] == 1

    def test_transient_build_failure_recovers(self):
        # whether the failing build is first hit by a neighbour's early
        # dispatch (swallowed, re-staged by its own batch) or by its own
        # batch (retried), every item must land exactly once with its data
        fails = {"n": 0}
        got = {}

        def build(it):
            if it == 3 and fails["n"] == 0:
                fails["n"] += 1
                raise RuntimeError("transient read failure")
            return (np.full((4,), float(it), np.float32),)

        def consume(it, out):
            assert it not in got
            got[it] = np.asarray(out).copy()

        items = list(range(6))
        with ThreadPoolExecutor(4) as pool:
            run_sharded_batches(items, build=build, kernel=jax.jit(_kernel),
                                consume=consume, n_dev=1, pool=pool)
        assert sorted(got) == items
        for it, out in got.items():
            np.testing.assert_allclose(out, np.full((4,), 2.0 * it))

    def test_persistent_failure_raises_retry_error(self):
        def consume(it, out):
            raise RuntimeError("disk full")

        with pytest.raises(RetryError):
            self._run(2, consume)


class TestInflightWindow:
    """The byte-budgeted dispatch window (BST_INFLIGHT_BYTES /
    utils.devicemem): the ledger must never exceed budget + one batch
    (the current batch always dispatches), a generous budget must let the
    loop run multiple batches ahead, and a starved budget must degrade to
    strict one-batch-at-a-time without losing items."""

    def _run(self, n_items, consume, build=None, per_dev=1):
        from bigstitcher_spark_tpu.utils import devicemem

        devicemem._HIGHWATER.set(0)
        devicemem._INFLIGHT.set(0)
        items = list(range(n_items))
        build = build or (
            lambda it: (np.full((1024,), float(it), np.float32),))
        with ThreadPoolExecutor(4) as pool:
            run_sharded_batches(
                items, build=build, kernel=jax.jit(_kernel), consume=consume,
                n_dev=1, pool=pool, per_dev=per_dev, workspace_mult=1.0,
            )
        return devicemem._HIGHWATER.value

    def test_highwater_never_exceeds_budget_plus_current(self, monkeypatch):
        batch_bytes = 1024 * 4                         # one item per batch
        monkeypatch.setenv("BST_EARLY_DISPATCH", "1")
        monkeypatch.setenv("BST_INFLIGHT_BYTES", str(2 * batch_bytes))
        got = {}
        import time

        def consume(it, out):
            time.sleep(0.02)   # give later builds time to stage
            got[it] = np.asarray(out).copy()

        hw = self._run(8, consume)
        assert sorted(got) == list(range(8))
        # budget (2 batches) + the always-dispatched current batch
        assert hw <= 3 * batch_bytes, hw

    def test_generous_budget_runs_ahead(self, monkeypatch):
        monkeypatch.setenv("BST_EARLY_DISPATCH", "1")
        monkeypatch.setenv("BST_INFLIGHT_BYTES", str(1 << 30))
        got = {}
        import time

        def consume(it, out):
            time.sleep(0.02)
            got[it] = np.asarray(out).copy()

        hw = self._run(8, consume)
        assert sorted(got) == list(range(8))
        for it, out in got.items():
            np.testing.assert_allclose(out, np.full((1024,), 2.0 * it))
        assert hw >= 2 * 1024 * 4, hw                  # >= 2 batches in flight

    def test_starved_budget_still_completes(self, monkeypatch):
        monkeypatch.setenv("BST_INFLIGHT_BYTES", "1")
        got = {}

        def consume(it, out):
            got[it] = np.asarray(out).copy()

        hw = self._run(6, consume, per_dev=2)
        assert sorted(got) == list(range(6))
        for it, out in got.items():
            np.testing.assert_allclose(out, np.full((1024,), 2.0 * it))

    def test_retry_restages_inside_window(self, monkeypatch):
        # a consume failure while successors are dispatched ahead must
        # retry cleanly: every item lands exactly once, ledger drains to 0
        monkeypatch.setenv("BST_EARLY_DISPATCH", "1")
        monkeypatch.setenv("BST_INFLIGHT_BYTES", str(1 << 30))
        from bigstitcher_spark_tpu.utils import devicemem

        fails = {"n": 0}
        got = {}
        import time

        def consume(it, out):
            time.sleep(0.01)
            if it == 2 and fails["n"] == 0:
                fails["n"] += 1
                raise RuntimeError("transient write failure")
            assert it not in got, f"item {it} consumed twice"
            got[it] = np.asarray(out).copy()

        self._run(8, consume)
        assert sorted(got) == list(range(8)) and fails["n"] == 1
        assert devicemem._INFLIGHT.value == 0


class TestPairScheduler:
    """The pair-work mesh scheduler (parallel/pairsched.py): cost-weighted
    placement balance, per-device in-flight windows, result ordering, and
    poisoned-device re-dispatch."""

    def test_cost_weighted_placement_bounded_spread(self):
        # a synthetic skewed bucket distribution (two huge buckets + a
        # long tail) must balance within the greedy-LPT bound:
        # max_load - min_load <= max single task cost
        from bigstitcher_spark_tpu.parallel.pairsched import (
            PairTask, assign_tasks,
        )

        rng = np.random.default_rng(3)
        costs = [1000.0, 700.0] + list(rng.integers(1, 60, 30).astype(float))
        tasks = [PairTask(index=i, cost=c) for i, c in enumerate(costs)]
        bins = assign_tasks(tasks, 4)
        loads = [sum(t.cost for t in b) for b in bins]
        assert max(loads) - min(loads) <= max(costs)
        placed = sorted(t.index for b in bins for t in b)
        assert placed == list(range(len(tasks)))  # exactly once each

    def test_zero_cost_tasks_still_spread(self):
        from bigstitcher_spark_tpu.parallel.pairsched import (
            PairTask, assign_tasks,
        )

        bins = assign_tasks([PairTask(index=i, cost=0.0) for i in range(8)], 8)
        assert all(len(b) == 1 for b in bins)

    def test_results_in_task_order_all_devices_used(self):
        import jax

        from bigstitcher_spark_tpu.parallel.pairsched import (
            PairTask, run_pair_tasks,
        )

        seen = set()

        def run(t):
            seen.add(str(jax.config.jax_default_device))
            return t.index * 2

        n = 24
        out = run_pair_tasks(
            [PairTask(index=i, cost=1.0 + i % 3) for i in range(n)],
            run, stage="sched-order-test")
        assert out == [2 * i for i in range(n)]
        assert len(seen) == len(jax.local_devices())

    def test_per_device_window_never_exceeds_budget(self, monkeypatch):
        # drain-mode: each device's dispatched-but-undrained bytes must
        # stay within its budget + segmentation slack (two half-budget
        # segments in flight)
        import threading

        from bigstitcher_spark_tpu.parallel.pairsched import (
            PairTask, run_pair_tasks,
        )

        nb = 1024
        budget = 4 * nb
        monkeypatch.setenv("BST_PAIR_INFLIGHT_BYTES", str(budget))
        lock = threading.Lock()
        cur: dict[str, int] = {}
        peak: dict[str, int] = {}

        def dispatch(t):
            name = threading.current_thread().name
            with lock:
                cur[name] = cur.get(name, 0) + nb
                peak[name] = max(peak.get(name, 0), cur[name])
            return t.index

        def drain(tasks, handles):
            name = threading.current_thread().name
            with lock:
                cur[name] = cur.get(name, 0) - nb * len(tasks)
            return [h * 3 for h in handles]

        n = 64
        out = run_pair_tasks(
            [PairTask(index=i, cost=1.0, nbytes=nb) for i in range(n)],
            dispatch, drain, stage="sched-window-test")
        assert out == [3 * i for i in range(n)]
        assert peak and max(peak.values()) <= budget + nb

    def test_pair_budget_splits_process_knob_across_workers(self,
                                                            monkeypatch):
        # BST_INFLIGHT_BYTES is process-wide: N workers split it;
        # BST_PAIR_INFLIGHT_BYTES is per device: taken verbatim
        from bigstitcher_spark_tpu.utils.devicemem import pair_budget

        monkeypatch.delenv("BST_PAIR_INFLIGHT_BYTES", raising=False)
        monkeypatch.setenv("BST_INFLIGHT_BYTES", "8000")
        assert pair_budget(None, 8) == (1000, "env")
        assert pair_budget(None, 1) == (8000, "env")
        monkeypatch.setenv("BST_PAIR_INFLIGHT_BYTES", "500")
        assert pair_budget(None, 8) == (500, "pair_env")

    def test_windows_record_the_budget_they_were_given(self, monkeypatch):
        # the manifest reads what each window was sized with, and from
        # where, off these series — not a value re-derived afterwards
        from bigstitcher_spark_tpu.observe import metrics
        from bigstitcher_spark_tpu.parallel.pairsched import (
            PairTask, run_pair_tasks,
        )
        from bigstitcher_spark_tpu.utils.devicemem import InflightWindow

        def run(source, budget_bytes=None):
            base = metrics.get_registry().snapshot()
            run_pair_tasks([PairTask(index=i) for i in range(8)],
                           lambda t: t.index, lambda ts, hs: list(hs),
                           stage="sched-record-test", n_devices=2,
                           budget_bytes=budget_bytes)
            d = metrics.get_registry().snapshot_delta(base)
            opened = {k.split('"')[1]: v for k, v in d.items() if v
                      and k.startswith("bst_inflight_windows_total")}
            assert opened == {source: 2}    # one window per worker
            return d[f'bst_inflight_budget_bytes{{source="{source}"}}']

        monkeypatch.delenv("BST_INFLIGHT_BYTES", raising=False)
        monkeypatch.setenv("BST_PAIR_INFLIGHT_BYTES", "500")
        assert run("pair_env") == 500
        monkeypatch.delenv("BST_PAIR_INFLIGHT_BYTES")
        monkeypatch.setenv("BST_INFLIGHT_BYTES", "8000")
        assert run("env") == 4000   # the process-wide knob, split in two
        assert run("caller", budget_bytes=77) == 77
        monkeypatch.delenv("BST_INFLIGHT_BYTES")
        base = metrics.get_registry().snapshot()
        w = InflightWindow()    # XLA:CPU reports no memory stats
        d = metrics.get_registry().snapshot_delta(base)
        assert d['bst_inflight_windows_total{source="fallback"}'] == 1
        assert d['bst_inflight_budget_bytes{source="fallback"}'] == w.budget

    def test_batched_drain_failure_isolates_to_offender(self):
        # a host-side error in a batched segment drain must fall back to
        # per-task drains: healthy neighbours keep their device results
        # (no recompute), only the offending task re-dispatches
        from bigstitcher_spark_tpu.parallel.pairsched import (
            PairTask, run_pair_tasks,
        )

        n_dispatch = {"n": 0}
        single_fails = {"n": 0}

        def dispatch(t):
            n_dispatch["n"] += 1
            return t.index

        def drain(tasks, handles):
            if len(tasks) > 1 and any(t.index == 3 for t in tasks):
                raise RuntimeError("bad pair in the batch")
            if (len(tasks) == 1 and tasks[0].index == 3
                    and single_fails["n"] == 0):
                single_fails["n"] += 1
                raise RuntimeError("bad pair, isolated")
            return [h * 2 for h in handles]

        n = 8
        out = run_pair_tasks(
            [PairTask(index=i, cost=1.0, nbytes=100) for i in range(n)],
            dispatch, drain, n_devices=1, stage="sched-drainfail-test")
        assert out == [2 * i for i in range(n)]
        # 8 originals + exactly ONE re-dispatch (task 3); the other 7
        # were salvaged from the failed segment without device recompute
        assert n_dispatch["n"] == n + 1
        assert single_fails["n"] == 1

    def test_multihost_partitions_pairs_processes_first(self, monkeypatch):
        # pairs split across PROCESSES first (cost-aware LPT), local
        # devices second; the allgather merge hands every rank the FULL
        # result list (simulate rank 1 by answering the gather with the
        # complementary slice's results)
        from bigstitcher_spark_tpu.parallel import distributed
        from bigstitcher_spark_tpu.parallel.pairsched import (
            PairTask, run_pair_tasks,
        )

        monkeypatch.setattr(distributed, "world", lambda: (0, 2))
        other = set(distributed.partition_indices_weighted(
            [1.0] * 7, 1, 2))

        def fake_gather(payload):
            assert payload[0] == "ok"
            return [payload, ("ok", {i: i * 10 for i in other})]

        monkeypatch.setattr(distributed, "allgather_object", fake_gather)
        out = run_pair_tasks(
            [PairTask(index=i, cost=1.0) for i in range(7)],
            lambda t: t.index * 10, stage="sched-mh-test", multihost=True)
        assert out == [i * 10 for i in range(7)]

    def test_poisoned_device_redispatches(self):
        # a device whose every call fails must degrade capacity, not kill
        # the run: its tasks re-dispatch onto the other devices
        import jax

        from bigstitcher_spark_tpu.observe import metrics
        from bigstitcher_spark_tpu.parallel.pairsched import (
            PairTask, run_pair_tasks,
        )

        if len(jax.local_devices()) < 2:
            pytest.skip("needs >= 2 devices")
        poisoned = jax.local_devices()[0]

        def run(t):
            if jax.config.jax_default_device == poisoned:
                raise RuntimeError("poisoned device call")
            return t.index

        ctr = metrics.counter("bst_pair_redispatch_total",
                              stage="sched-poison-test")
        before = ctr.value
        out = run_pair_tasks(
            [PairTask(index=i, cost=1.0) for i in range(16)],
            run, stage="sched-poison-test")
        assert out == list(range(16))
        assert ctr.value > before
