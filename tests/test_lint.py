"""Tier-1 gate for the AST invariant analyzer (``bst lint``) and the
runtime-config registry.

Three layers: (1) the live package must produce ZERO non-baselined
findings (and the baseline must not hide ops/models host-sync bugs);
(2) the analyzer itself is tested against fixture snippets with known
violations per check, a clean fixture, and suppression comments;
(3) doc drift — every ``BST_*`` name in README/WORKFLOW/PERF exists in
the config registry and vice versa."""

import os
import re
import shutil
import textwrap
from pathlib import Path

import pytest

from bigstitcher_spark_tpu import config
from bigstitcher_spark_tpu.analysis import (
    baseline_counts,
    default_baseline_path,
    default_root,
    load_baseline,
    new_findings,
    run_lint,
)

REPO = Path(__file__).resolve().parent.parent


def _write_tree(root: Path, files: dict[str, str]) -> Path:
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src).lstrip("\n"), encoding="utf-8")
    return root


# -- layer 1: the live package ---------------------------------------------


class TestPackageIsClean:
    def test_zero_new_findings(self):
        findings = run_lint(default_root())
        baseline = load_baseline(default_baseline_path())
        new = new_findings(findings, baseline)
        assert not new, "new bst-lint findings:\n" + "\n".join(
            f.render() for f in new)

    def test_baseline_hides_no_ops_models_host_sync(self):
        # the ISSUE's contract: host-sync findings in ops/ and models/
        # are FIXED, never baselined away
        baseline = load_baseline(default_baseline_path())
        bad = [k for k in baseline
               if k.startswith(("host-sync|ops/", "host-sync|models/"))]
        assert not bad, bad

    def test_inserted_violations_fail(self, tmp_path):
        # the enforcement proof: copy the package, insert a raw
        # os.environ["BST_X"] read and an unlocked mutation of a
        # lock-guarded dict, and the scan must produce new findings
        src = default_root()
        dst = tmp_path / "pkg"
        shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
            "__pycache__", "*.pyc"))
        uris = dst / "io" / "uris.py"
        uris.write_text(uris.read_text(encoding="utf-8") + (
            "\n\ndef _sneaky():\n"
            "    import os\n"
            "    return os.environ[\"BST_X\"]\n"), encoding="utf-8")
        progress = dst / "observe" / "progress.py"
        progress.write_text(progress.read_text(encoding="utf-8") + (
            "\n\ndef _unlocked_drop():\n"
            "    _records.clear()\n"), encoding="utf-8")
        solver = dst / "models" / "solver.py"
        solver.write_text(solver.read_text(encoding="utf-8") + (
            "\n\ndef _sneaky_spawn():\n"
            "    import threading\n"
            "    return threading.Thread(target=print)\n"), encoding="utf-8")
        client = dst / "serve" / "client.py"
        client.write_text(client.read_text(encoding="utf-8") + (
            "\n\ndef _sneaky_close(addr):\n"
            "    import socket\n"
            "    s = socket.create_connection(addr)\n"
            "    s.close()\n"), encoding="utf-8")
        findings = run_lint(dst)
        new = new_findings(findings, load_baseline(default_baseline_path()))
        checks = {f.check for f in new}
        assert "config-registry" in checks, [f.render() for f in new]
        assert "lock-discipline" in checks, [f.render() for f in new]
        assert "thread-spawn" in checks, [f.render() for f in new]
        assert "socket-hygiene" in checks, [f.render() for f in new]


# -- layer 2: the analyzer against known fixtures --------------------------


class TestHostSyncCheck:
    def test_known_violations(self, tmp_path):
        _write_tree(tmp_path, {"ops/mod.py": """
            import jax
            import jax.numpy as jnp
            import numpy as np


            def bad(x):
                y = jnp.sum(x)
                z = float(y)                      # line 8
                a = np.asarray(jnp.fft.rfftn(x))  # line 9
                if y > 0:                         # line 10
                    pass
                v = y.item()                      # line 12
                return z, a, v
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "host-sync"]
        assert sorted(f.line for f in fs) == [8, 9, 10, 12]

    def test_drain_points_are_clean(self, tmp_path):
        _write_tree(tmp_path, {"ops/mod.py": """
            import jax
            import jax.numpy as jnp
            import numpy as np


            def good(x):
                y = jnp.sum(x)
                z = float(jax.device_get(y))
                a = np.asarray(jax.device_get(jnp.fft.rfftn(x)))
                r = jnp.dot(x, x).block_until_ready()
                n = int(x.shape[0])          # .shape never syncs
                return z, a, n, np.asarray(r)
            """})
        assert [f for f in run_lint(tmp_path) if f.check == "host-sync"] == []

    def test_ops_kernel_results_are_sources(self, tmp_path):
        # the ADVICE r5 bug class: np.asarray on a kernel-layer result
        _write_tree(tmp_path, {"models/driver.py": """
            import numpy as np
            from ..ops import fusion as F


            def drive(p):
                fused, wsum = F.fuse_block(p)
                return np.asarray(fused), np.asarray(wsum)
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "host-sync"]
        assert len(fs) == 2 and all(f.line == 7 for f in fs)

    def test_outside_ops_models_not_scanned(self, tmp_path):
        _write_tree(tmp_path, {"cli/tool.py": """
            import jax.numpy as jnp


            def show(x):
                return float(jnp.sum(x))    # CLI boundary: fetch is fine
            """})
        assert [f for f in run_lint(tmp_path) if f.check == "host-sync"] == []


class TestLockDisciplineCheck:
    def test_unlocked_mutation(self, tmp_path):
        _write_tree(tmp_path, {"mod.py": """
            import threading

            _LOCK = threading.Lock()
            _STATE = {}


            def locked(k, v):
                with _LOCK:
                    _STATE[k] = v


            def unlocked(k, v):
                _STATE[k] = v               # line 13


            def drop_locked(k):
                _STATE.pop(k)               # *_locked: caller holds it
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "lock-discipline"]
        assert [f.line for f in fs] == [13]

    def test_instance_state(self, tmp_path):
        _write_tree(tmp_path, {"mod.py": """
            import threading


            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []        # __init__ is exempt

                def add(self, x):
                    with self._lock:
                        self._items.append(x)

                def sneak(self, x):
                    self._items.append(x)   # line 14
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "lock-discipline"]
        assert [f.line for f in fs] == [14]


class TestLockOrderCheck:
    def test_two_lock_inversion_is_a_cycle(self, tmp_path):
        # the old single-file A->B/B->A heuristic, now a graph cycle
        _write_tree(tmp_path, {"mod.py": """
            import threading

            LOCK_A = threading.Lock()
            LOCK_B = threading.Lock()


            def one():
                with LOCK_A:
                    with LOCK_B:
                        pass


            def two():
                with LOCK_B:
                    with LOCK_A:
                        pass
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "lock-order"]
        assert len(fs) == 1 and "potential deadlock" in fs[0].message
        assert "--graph lock-order" in fs[0].message

    def test_three_lock_interprocedural_cycle(self, tmp_path):
        # A->B and B->C are direct nestings; C->A only exists one call
        # level deep (three() calls take_a() under LOCK_C) — the planted
        # cycle the per-pair heuristic could never see
        _write_tree(tmp_path, {"mod.py": """
            import threading

            LOCK_A = threading.Lock()
            LOCK_B = threading.Lock()
            LOCK_C = threading.Lock()


            def one():
                with LOCK_A:
                    with LOCK_B:
                        pass


            def two():
                with LOCK_B:
                    with LOCK_C:
                        pass


            def three():
                with LOCK_C:
                    take_a()


            def take_a():
                with LOCK_A:
                    pass
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "lock-order"]
        assert len(fs) == 1, [f.render() for f in fs]
        assert "LOCK_A" in fs[0].message and "LOCK_C" in fs[0].message

    def test_one_way_ordering_is_clean(self, tmp_path):
        # a consistent global order (cache -> tier, never back) is the
        # live package's shape and must not be flagged
        _write_tree(tmp_path, {"mod.py": """
            import threading


            class Cache:
                def __init__(self):
                    self._lock = threading.Lock()

                def drop(self, tier):
                    with self._lock:
                        tier.keys()


            class Tier:
                def __init__(self):
                    self._lock = threading.Lock()

                def keys(self):
                    with self._lock:
                        return []
            """})
        assert [f for f in run_lint(tmp_path)
                if f.check == "lock-order"] == []

    def test_condition_aliases_to_its_lock(self, tmp_path):
        # Condition(self._lock) IS self._lock: entering the condition in
        # one method and the lock in another around the same second lock
        # inverts the order — one node, real 2-cycle
        _write_tree(tmp_path, {"mod.py": """
            import threading


            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cv = threading.Condition(self._lock)
                    self._side_lock = threading.Lock()

                def a(self):
                    with self._cv:
                        with self._side_lock:
                            pass

                def b(self):
                    with self._side_lock:
                        with self._lock:
                            pass
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "lock-order"]
        assert len(fs) == 1, [f.render() for f in fs]

    def test_dot_export_lists_edges(self, tmp_path):
        from bigstitcher_spark_tpu.analysis import (
            lock_graph_dot,
            parse_package,
        )

        _write_tree(tmp_path, {"mod.py": """
            import threading

            LOCK_A = threading.Lock()
            LOCK_B = threading.Lock()


            def one():
                with LOCK_A:
                    with LOCK_B:
                        pass
            """})
        ctxs, _sup, _err = parse_package(tmp_path)
        dot = lock_graph_dot(ctxs)
        assert dot.startswith("digraph lock_order")
        assert "LOCK_A" in dot and "->" in dot


class TestBlockingUnderLockCheck:
    def test_recv_and_queue_get_under_lock(self, tmp_path):
        _write_tree(tmp_path, {"mod.py": """
            import threading


            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = None

                def bad_recv(self, sock):
                    with self._lock:
                        data = sock.recv(4096)      # line 11
                    return data

                def bad_get(self):
                    with self._lock:
                        return self._q.get()        # line 16

                def ok_nowait(self):
                    with self._lock:
                        return self._q.get_nowait()

                def ok_outside(self, sock):
                    with self._lock:
                        pending = True
                    return sock.recv(4096)
            """})
        fs = [f for f in run_lint(tmp_path)
              if f.check == "blocking-under-lock"]
        assert sorted(f.line for f in fs) == [11, 16]

    def test_helper_one_call_deep(self, tmp_path):
        # the exchange.py shape: the blocking sendall hides one call
        # level down in a module helper, flagged at the call site
        _write_tree(tmp_path, {"mod.py": """
            import threading

            _LOCK = threading.Lock()


            def _send_line(sock, data):
                sock.sendall(data)


            def bad(sock, data):
                with _LOCK:
                    _send_line(sock, data)          # line 12
            """})
        fs = [f for f in run_lint(tmp_path)
              if f.check == "blocking-under-lock"]
        assert [f.line for f in fs] == [12]

    def test_long_sleep_and_subprocess(self, tmp_path):
        _write_tree(tmp_path, {"mod.py": """
            import subprocess
            import threading
            import time

            _LOCK = threading.Lock()


            def bad():
                with _LOCK:
                    time.sleep(5.0)                          # line 10
                    subprocess.run(["ls"], check=False)      # line 11


            def ok_tick():
                with _LOCK:
                    time.sleep(0.01)    # sub-threshold tick
            """})
        fs = [f for f in run_lint(tmp_path)
              if f.check == "blocking-under-lock"]
        assert sorted(f.line for f in fs) == [10, 11]


class TestThreadSpawnCheck:
    def test_raw_spawns_flagged(self, tmp_path):
        _write_tree(tmp_path, {"models/worker.py": """
            import threading
            from concurrent.futures import ThreadPoolExecutor


            def spawn(fn):
                t = threading.Thread(target=fn)     # line 6
                pool = ThreadPoolExecutor(4)        # line 7
                return t, pool
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "thread-spawn"]
        assert sorted(f.line for f in fs) == [6, 7]
        assert all("ctx" in f.message.lower() for f in fs)

    def test_utils_threads_is_the_sanctioned_home(self, tmp_path):
        _write_tree(tmp_path, {"utils/threads.py": """
            import threading


            def ctx_thread(fn, name=None):
                return threading.Thread(target=fn, name=name, daemon=True)
            """})
        assert [f for f in run_lint(tmp_path)
                if f.check == "thread-spawn"] == []

    def test_ctx_thread_calls_are_clean(self, tmp_path):
        _write_tree(tmp_path, {"dag/runner.py": """
            from ..utils.threads import ctx_thread


            def start(fn):
                return ctx_thread(fn, name="worker")
            """})
        assert [f for f in run_lint(tmp_path)
                if f.check == "thread-spawn"] == []


class TestCancelCoverageCheck:
    def test_poll_free_worker_loop_flagged(self, tmp_path):
        _write_tree(tmp_path, {"dag/pump.py": """
            from ..utils.threads import ctx_thread


            class Pump:
                def start(self):
                    ctx_thread(self._loop, name="pump")

                def _loop(self):
                    while True:                     # line 9
                        self.step()

                def step(self):
                    pass
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "cancel-coverage"]
        assert [f.line for f in fs] == [9]
        assert "cancel" in fs[0].message

    def test_stop_flag_poll_is_clean(self, tmp_path):
        _write_tree(tmp_path, {"serve/pump.py": """
            import threading
            from ..utils.threads import ctx_thread


            class Pump:
                def __init__(self):
                    self._stop = threading.Event()

                def start(self):
                    ctx_thread(self._loop, name="pump")

                def _loop(self):
                    while True:
                        if self._stop.is_set():
                            return
                        self.step()

                def step(self):
                    pass
            """})
        assert [f for f in run_lint(tmp_path)
                if f.check == "cancel-coverage"] == []

    def test_non_worker_and_out_of_scope_loops_clean(self, tmp_path):
        _write_tree(tmp_path, {
            # not a thread target: a main-thread convergence loop
            "models/solve.py": """
                def iterate(step):
                    while True:
                        if step():
                            break
                """,
            # a worker loop, but io/ is outside the policed dirs
            "io/pump.py": """
                from ..utils.threads import ctx_thread


                def start():
                    ctx_thread(_loop)


                def _loop():
                    while True:
                        pass
                """})
        assert [f for f in run_lint(tmp_path)
                if f.check == "cancel-coverage"] == []


class TestSocketHygieneCheck:
    def test_shutdown_less_close_flagged(self, tmp_path):
        _write_tree(tmp_path, {"net/conn.py": """
            import socket


            def leak(addr):
                s = socket.create_connection(addr)
                s.close()                           # line 6


            def clean(addr):
                s = socket.create_connection(addr)
                s.shutdown(socket.SHUT_RDWR)
                s.close()


            def helper_clean(addr):
                s = socket.create_connection(addr)
                _shutdown_close(s)


            def _shutdown_close(sock):
                sock.shutdown(socket.SHUT_RDWR)
                sock.close()
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "socket-hygiene"]
        assert [f.line for f in fs] == [6]
        assert "shutdown" in fs[0].message

    def test_accepted_conn_param_flagged(self, tmp_path):
        # the daemon/relay handler shape: the socket arrives as a
        # parameter, recognized by annotation or sock/conn naming
        _write_tree(tmp_path, {"net/handler.py": """
            import socket


            def handle(conn: socket.socket):
                f = conn.makefile("rb")
                f.close()
                conn.close()                        # line 7
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "socket-hygiene"]
        assert [f.line for f in fs] == [7]

    def test_listener_and_utils_exempt(self, tmp_path):
        _write_tree(tmp_path, {
            "net/server.py": """
                import socket


                def serve(port):
                    srv = socket.socket()
                    srv.bind(("", port))
                    srv.listen(4)
                    srv.close()     # listener: shutdown is meaningless
                """,
            "utils/sockets.py": """
                import socket


                def quick(addr):
                    s = socket.create_connection(addr)
                    s.close()       # utils/-level helper: exempt
                """})
        assert [f for f in run_lint(tmp_path)
                if f.check == "socket-hygiene"] == []


class TestConfigRegistryCheck:
    def test_raw_reads_flagged(self, tmp_path):
        _write_tree(tmp_path, {"mod.py": """
            import os


            def f():
                a = os.environ.get("BST_FOO")        # line 5
                b = os.environ["BST_BAR"]            # line 6
                c = os.getenv("HOME")                # non-BST: fine
                d = __import__("os").environ.get("BST_BAZ")  # line 8
                return a, b, c, d
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "config-registry"]
        assert sorted(f.line for f in fs) == [5, 6, 8]

    def test_undeclared_knob_flagged(self, tmp_path):
        _write_tree(tmp_path, {
            "config.py": """
                KNOBS = {}


                def _knob(name, kind, default, doc):
                    KNOBS[name] = (kind, default, doc)


                _knob("BST_REAL", "str", None, "declared")
                """,
            "mod.py": """
                from . import config


                def f():
                    return config.get_str("BST_TYPO")   # line 5
                """})
        fs = [f for f in run_lint(tmp_path) if f.check == "config-registry"]
        assert [f.line for f in fs] == [5]
        assert "BST_TYPO" in fs[0].message

    def test_config_py_itself_exempt(self, tmp_path):
        _write_tree(tmp_path, {"config.py": """
            import os


            def raw_value(name):
                return os.environ.get(name)
            """})
        assert [f for f in run_lint(tmp_path)
                if f.check == "config-registry"] == []


class TestEnvMutationCheck:
    def test_raw_env_mutation_in_serve_flagged(self, tmp_path):
        # the serve contract: a daemon job configuring itself by mutating
        # the process env would leak into every concurrent job — the check
        # points straight at config.overrides()
        _write_tree(tmp_path, {"serve/daemon.py": """
            import os


            def run_job(overrides):
                os.environ["BST_INFLIGHT_BYTES"] = "1000"       # line 5
                os.environ.setdefault("BST_PAIR_SHARD", "0")    # line 6
                os.environ.pop("BST_WRITE_THREADS", None)       # line 7
                del os.environ["BST_TILE_CACHE_BYTES"]          # line 8
                os.environ.update({"BST_NATIVE_IO": "1"})       # line 9
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "env-mutation"]
        assert sorted(f.line for f in fs) == [5, 6, 7, 8, 9]
        assert all("config.overrides" in f.message for f in fs)

    def test_config_py_not_exempt(self, tmp_path):
        # unlike config-registry, even the registry module may not WRITE
        _write_tree(tmp_path, {"config.py": """
            import os


            def bad(name, value):
                os.environ[name] = value     # dynamic name: not BST_-provable
                os.environ["BST_X"] = value  # line 6
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "env-mutation"]
        assert [f.line for f in fs] == [6]

    def test_reads_and_non_bst_writes_are_clean(self, tmp_path):
        _write_tree(tmp_path, {"config.py": """
            import os


            def fine():
                a = os.environ.get("BST_FOO")
                os.environ["JAX_PLATFORMS"] = "cpu"
                return a
            """})
        assert [f for f in run_lint(tmp_path)
                if f.check == "env-mutation"] == []


class TestMetricNameCheck:
    FILES = {
        "observe/metric_names.py": """
            METRICS = {
                "bst_good_total": "a declared counter",
            }
            """,
    }

    def test_unregistered_and_dynamic(self, tmp_path):
        _write_tree(tmp_path, {**self.FILES, "mod.py": """
            from observe import metrics as _metrics

            C = _metrics.counter("bst_good_total")
            D = _metrics.counter("bst_typo_total")     # line 4


            def g(name):
                return _metrics.gauge(name)            # line 8: dynamic
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "metric-name"]
        assert sorted(f.line for f in fs) == [4, 8]

    def test_duplicate_declaration(self, tmp_path):
        _write_tree(tmp_path, {"observe/metric_names.py": """
            METRICS = {
                "bst_twice_total": "one",
                "bst_twice_total": "two",
            }
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "metric-name"]
        assert len(fs) == 1 and "more than once" in fs[0].message


class TestSpanNameCheck:
    FILES = {
        "observe/metric_names.py": """
            SPANS = {
                "fusion.kernel": "a declared span",
            }
            """,
    }

    def test_unregistered_and_dynamic(self, tmp_path):
        _write_tree(tmp_path, {**self.FILES, "mod.py": """
            from observe import trace as _trace
            import profiling


            def f(stage):
                with profiling.span("fusion.kernel"):
                    pass
                with profiling.span("fusion.typo"):      # line 8
                    pass
                _trace.instant("stage." + stage)         # line 10: dynamic
                _trace.record("B", "fusion.missing")     # line 11
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "span-name"]
        assert sorted(f.line for f in fs) == [8, 10, 11]

    def test_duplicate_declaration(self, tmp_path):
        _write_tree(tmp_path, {"observe/metric_names.py": """
            SPANS = {
                "span.twice": "one",
                "span.twice": "two",
            }
            """})
        fs = [f for f in run_lint(tmp_path) if f.check == "span-name"]
        assert len(fs) == 1 and "more than once" in fs[0].message

    def test_declaring_modules_exempt(self, tmp_path):
        # trace.py/profiling.py manipulate names as data; only CALL sites
        # elsewhere are checked
        _write_tree(tmp_path, {**self.FILES, "observe/trace.py": """
            def span(name):
                return record("B", name)
            """, "profiling.py": """
            def span(name, dynamic=str):
                return dynamic(name)
            """})
        assert not [f for f in run_lint(tmp_path)
                    if f.check == "span-name"]


class TestSuppressionAndBaseline:
    def test_clean_fixture_zero_findings(self, tmp_path):
        _write_tree(tmp_path, {
            "ops/k.py": """
                import jax
                import jax.numpy as jnp


                def kernel(x):
                    return jnp.sum(x * 2.0)


                def drain(x):
                    return jax.device_get(kernel(x))
                """,
            "store.py": """
                import threading

                _LOCK = threading.Lock()
                _CACHE = {}


                def put(k, v):
                    with _LOCK:
                        _CACHE[k] = v
                """})
        assert run_lint(tmp_path) == []

    def test_suppression_same_line_and_line_above(self, tmp_path):
        _write_tree(tmp_path, {"mod.py": """
            import os


            def f():
                a = os.environ.get("BST_A")  # bst-lint: off=config-registry
                # bst-lint: off (reason documented here)
                b = os.environ.get("BST_B")
                c = os.environ.get("BST_C")  # wrong check name:
                # stays flagged
                return a, b, c
            """})
        fs = run_lint(tmp_path)
        assert [f.line for f in fs] == [8]

    def test_suppression_is_per_check(self, tmp_path):
        _write_tree(tmp_path, {"mod.py": """
            import os


            def f():
                return os.environ.get("BST_A")  # bst-lint: off=host-sync
            """})
        assert [f.check for f in run_lint(tmp_path)] == ["config-registry"]

    def test_baseline_counts_admit_legacy_only(self, tmp_path):
        _write_tree(tmp_path, {"mod.py": """
            import os


            def f():
                return os.environ.get("BST_A")
            """})
        fs = run_lint(tmp_path)
        assert len(fs) == 1
        baseline = baseline_counts(fs)
        assert new_findings(fs, baseline) == []
        # a second identical occurrence is NEW relative to count 1
        assert len(new_findings(fs + fs, baseline)) == 1


# -- layer 3: config registry behavior + doc drift -------------------------


class TestConfigRegistry:
    def test_call_time_reads(self, monkeypatch):
        monkeypatch.delenv("BST_CHUNK_CACHE_BYTES", raising=False)
        assert config.get_bytes("BST_CHUNK_CACHE_BYTES") == 1 << 30
        monkeypatch.setenv("BST_CHUNK_CACHE_BYTES", "2e9")
        assert config.get_bytes("BST_CHUNK_CACHE_BYTES") == int(2e9)
        assert config.source("BST_CHUNK_CACHE_BYTES") == "env"

    def test_bool_explicit_falsy_rule(self, monkeypatch):
        for raw, want in [("0", False), ("false", False), ("off", False),
                          ("no", False), ("1", True), ("true", True),
                          ("2", True)]:
            monkeypatch.setenv("BST_PAIR_SHARD", raw)
            assert config.get_bool("BST_PAIR_SHARD") is want, raw

    def test_unparseable_falls_back_to_default(self, monkeypatch):
        monkeypatch.setenv("BST_WRITE_THREADS", "not-a-number")
        assert config.get_int("BST_WRITE_THREADS") == 8
        assert config.source("BST_WRITE_THREADS") == "default"

    def test_undeclared_name_raises(self):
        with pytest.raises(KeyError):
            config.get("BST_NOT_A_KNOB")

    def test_uris_read_env_at_call_time(self, monkeypatch):
        # the io/uris.py import-time-snapshot bug: env set AFTER import
        # must be visible (and the setter must still override)
        from bigstitcher_spark_tpu.io import uris

        monkeypatch.setattr(uris, "_S3_REGION", [uris._UNSET])
        monkeypatch.setenv("BST_S3_REGION", "eu-central-1")
        assert uris.get_s3_region() == "eu-central-1"
        spec = uris.kvstore_spec("s3://bucket/root")
        assert spec["aws_region"] == "eu-central-1"
        uris.set_s3_region("us-west-2")
        assert uris.get_s3_region() == "us-west-2"
        uris.set_s3_region(None)    # explicit clear beats the env
        assert uris.get_s3_region() is None
        monkeypatch.setattr(uris, "_S3_ENDPOINT", [uris._UNSET])
        monkeypatch.setenv("BST_S3_ENDPOINT", "http://127.0.0.1:9000")
        assert uris.get_s3_endpoint() == "http://127.0.0.1:9000"

    def test_resolve_covers_every_knob(self):
        rows = config.resolve()
        assert {r["name"] for r in rows} == set(config.KNOBS)
        assert all(r["doc"] for r in rows)

    # where a knob of each class has to be read (a glob from the repo's
    # root) and what a read looks like there: a declared name nothing reads
    # is an option the documents promise and no code honours
    READERS = {
        "runtime": ("bigstitcher_spark_tpu/**/*.py",
                    r"\bget(?:_[a-z]+)?\(\s*[\"']{name}[\"']"),
        "wrapper": ("install", r"\b{name}\b"),
        "tests": ("tests/**/*.py",
                  r"(?:environ|getenv)[^\n]*[\"']{name}[\"']"),
    }

    @pytest.mark.parametrize("consumer", sorted(READERS))
    def test_every_knob_has_a_reader_where_its_consumer_says(self, consumer):
        assert {k.consumer for k in config.KNOBS.values()} == set(
            self.READERS)
        where, pattern = self.READERS[consumer]
        text = "\n".join(p.read_text(encoding="utf-8")
                         for p in REPO.glob(where)
                         if p != default_root() / "config.py")
        names = [n for n, k in config.KNOBS.items() if k.consumer == consumer]
        assert names
        unread = [n for n in names
                  if not re.search(pattern.format(name=n), text)]
        assert not unread, (
            f"declared {consumer!r} knobs nothing reads: {unread} — delete "
            f"the declaration or the class is wrong")


class TestDocDrift:
    DOCS = ("README.md", "WORKFLOW.md", "PERF.md")

    def _doc_names(self):
        names: set[str] = set()
        for doc in self.DOCS:
            text = (REPO / doc).read_text(encoding="utf-8")
            names |= set(re.findall(r"\bBST_[A-Z0-9_]+\b", text))
        return names

    def test_every_doc_name_is_declared(self):
        undeclared = self._doc_names() - set(config.KNOBS)
        assert not undeclared, (
            f"docs mention undeclared knobs: {sorted(undeclared)} — "
            f"declare them in bigstitcher_spark_tpu/config.py or fix "
            f"the docs")

    # a repository source file as a document names it: code, documents,
    # and the records in capitals at the root. What a run writes
    # (bst-trace.json, manifest-*.json) is not a source file
    CITED = (r"(?<![\w./-])((?:[\w.-]+/)*[\w.-]+\.(?:py|sh|md|cpp|toml)"
             r"|[A-Z_]+\.jsonl?)\b(?!/)")

    @pytest.mark.parametrize("doc", ["README.md", "WORKFLOW.md", "PARITY.md"])
    def test_every_cited_source_file_exists(self, doc):
        cited = set(re.findall(self.CITED,
                               (REPO / doc).read_text(encoding="utf-8")))
        assert len(cited) >= 10, cited
        gone = sorted(c for c in cited if not (REPO / c).exists()
                      and not (default_root() / c).exists())
        assert not gone, f"{doc} cites files that are not there: {gone}"

    def test_every_knob_is_documented(self):
        undocumented = set(config.KNOBS) - self._doc_names()
        assert not undocumented, (
            f"knobs missing from {self.DOCS}: {sorted(undocumented)} — "
            f"add them to the README configuration table")
