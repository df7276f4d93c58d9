"""Pod-scale observability fabric: the cross-host telemetry relay
(observe/relay.py) and its aggregated live plane.

Acceptance contract (ISSUE 15): with >=2 processes relayed into one
collector, mid-run the rank-0 /metrics serves host/process_index-labeled
series from every rank; /healthz returns 503 naming the silent host when
one rank's heartbeat stops and recovers on resume; and a cluster
trace-dump writes ONE barrier-aligned Perfetto file loadable by
`bst trace-report`. Backpressure: a deliberately slow or absent
collector must never block (or meaningfully slow) a producing rank —
the bounded queue drops and counts (`bst_relay_dropped_total`), and the
client reconnects cleanly after a collector restart. Relay off must be
zero-overhead.

Collectors bind ephemeral 127.0.0.1 ports; the end-to-end test runs two
REAL worker subprocesses through the `init_distributed` bring-up path.
"""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import click
import pytest
from click.testing import CliRunner

from bigstitcher_spark_tpu import profiling
from bigstitcher_spark_tpu.cli.main import cli
from bigstitcher_spark_tpu.observe import (
    events, history, httpexport, metrics, progress, relay, trace,
)
from bigstitcher_spark_tpu.serve import client as serve_client
from bigstitcher_spark_tpu.serve.daemon import Daemon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get(url: str, timeout: float = 10.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _cli_ok(runner, args):
    r = runner.invoke(cli, args, catch_exceptions=False)
    assert r.exit_code == 0, f"bst {' '.join(args)}\n{r.output}"
    return r


def _wait_for(cond, timeout=20.0, interval=0.05, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture()
def collector():
    col = relay.RelayCollector("127.0.0.1", 0).start()
    yield col
    col.stop()


def _mk_client(port, host, pi, pc=2, interval_s=0.1, **kw):
    return relay.RelayClient(f"127.0.0.1:{port}", host=host,
                             process_index=pi, process_count=pc,
                             interval_s=interval_s, **kw).start()


class _FakeRank:
    """A raw-socket push client driven line by line — the protocol-level
    test surface (silence, bye, malformed lines)."""

    def __init__(self, port, host="fake", pi=1, pc=2, pid=None):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=5)
        self.identity = {"host": host, "process_index": pi,
                         "process_count": pc}
        self.send({"t": "hello", **self.identity,
                   "pid": pid if pid is not None else os.getpid()})

    def send(self, msg: dict) -> None:
        self.sock.sendall((json.dumps(msg) + "\n").encode())

    def snap(self, **payload) -> None:
        self.send({"t": "snap", "payload": payload})

    def close(self) -> None:
        self.sock.close()


# -- backpressure / loss accounting (satellite) ------------------------------


class TestBackpressure:
    def test_absent_collector_never_blocks_producer(self):
        """No collector listening: every offer returns immediately, the
        bounded queue fills, and further messages drop and COUNT."""
        port = _free_port()   # nothing listens here
        c = relay.RelayClient(f"127.0.0.1:{port}", host="h", process_index=1,
                              process_count=2, interval_s=0.05,
                              queue_max=16)
        c.start()

        def drops():
            return (metrics.counter("bst_relay_dropped_total",
                                    reason="queue").value
                    + metrics.counter("bst_relay_dropped_total",
                                      reason="conn").value)

        try:
            d0 = drops()
            t0 = time.perf_counter()
            for i in range(5000):
                c.offer({"t": "event", "rec": {"type": "block.fail",
                                               "i": i}})
            dt = time.perf_counter() - t0
            # 5000 enqueue attempts against a 16-slot queue + a
            # connection-refused sender: pure put_nowait on this side,
            # far under a second even on a loaded CI host
            assert dt < 2.0, f"offer() blocked: {dt:.2f}s for 5000 msgs"
            # every message accounted as a drop (queue-full at offer
            # time, or dequeued and dropped as unconnectable)
            _wait_for(lambda: drops() - d0 >= 5000,
                      what="loss accounting of all 5000 messages")
        finally:
            c.stop()

    def test_slow_collector_never_blocks_producer(self):
        """A collector that accepts but never reads: the TCP buffer
        fills, the relay thread wedges in send — and the producing side
        still never blocks (drops count instead)."""
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(4)
        held = []
        stop = threading.Event()

        def hold():
            srv.settimeout(0.5)
            while not stop.is_set():
                try:
                    conn, _ = srv.accept()
                    held.append(conn)   # accepted, never read
                except OSError:
                    continue

        th = threading.Thread(target=hold, daemon=True)
        th.start()
        big = "x" * 65536
        c = relay.RelayClient(f"127.0.0.1:{srv.getsockname()[1]}",
                              host="h", process_index=1, process_count=2,
                              interval_s=0.02, queue_max=8)
        c.start()
        try:
            _wait_for(lambda: c.connected.is_set(), what="client connect")
            q0 = metrics.counter("bst_relay_dropped_total",
                                 reason="queue").value
            worst = 0.0
            for i in range(2000):
                t0 = time.perf_counter()
                c.offer({"t": "event", "rec": {"type": "block.fail",
                                               "blob": big}})
                worst = max(worst, time.perf_counter() - t0)
            assert worst < 0.5, f"a single offer stalled {worst:.2f}s"
            # the relay thread is wedged in send -> the BOUNDED QUEUE
            # fills -> the queue-full drop path specifically engages
            _wait_for(lambda: metrics.counter(
                "bst_relay_dropped_total", reason="queue").value > q0,
                what="bounded-queue drop accounting")
        finally:
            c.stop(timeout=2)
            stop.set()
            srv.close()
            for conn in held:
                conn.close()

    def test_clean_reconnect_after_collector_restart(self):
        col = relay.RelayCollector("127.0.0.1", 0).start()
        port = col.port
        c = _mk_client(port, "h", 1)
        try:
            _wait_for(lambda: any(r["connected"]
                                  for r in col.cluster_status()["ranks"]),
                      what="first connect")
            r0 = metrics.counter("bst_relay_reconnects_total").value
            col.stop()
            _wait_for(lambda: not c.connected.is_set(),
                      what="client notices the dead collector")
            # restart on the SAME port (SO_REUSEADDR)
            col = relay.RelayCollector("127.0.0.1", port).start()
            row = _wait_for(
                lambda: next((r for r in col.cluster_status()["ranks"]
                              if r["connected"]), None),
                what="reconnect")
            assert row["host"] == "h" and row["process_index"] == 1
            assert metrics.counter(
                "bst_relay_reconnects_total").value > r0
            # snapshots flow again on the new connection
            _wait_for(lambda: (next(
                (r for r in col.cluster_status()["ranks"]), {})
                .get("process")) is not None, what="fresh snapshot")
        finally:
            c.stop()
            col.stop()


# -- the aggregated plane ----------------------------------------------------


class TestClusterPlane:
    def test_labeled_metrics_cluster_rows_and_health(self, collector):
        """Acceptance core, in-process: two relayed ranks surface as
        host/process_index-labeled series on /metrics, rows on /cluster,
        and a healthy pod verdict on /healthz."""
        exp = httpexport.start(0)
        c1 = _mk_client(collector.port, "hostA", 0)
        c2 = _mk_client(collector.port, "hostB", 1)
        metrics.counter("bst_io_read_bytes_total", op="relay-test",
                        path="synthetic").inc(4242)
        try:
            series = re.compile(
                r'bst_io_read_bytes_total\{host="host[AB]",'
                r'process_index="[01]",op="relay-test",'
                r'path="synthetic"\} \d+')

            def scraped():
                code, body = _get(exp.url + "/metrics")
                return (code == 200
                        and 'host="hostA",process_index="0"' in body
                        and 'host="hostB",process_index="1"' in body
                        and series.search(body) and body)

            # a real workload series rode the relay, labeled per rank
            body = _wait_for(scraped, what="labeled series on /metrics")
            code, body = _get(exp.url + "/cluster")
            assert code == 200
            doc = json.loads(body)
            hosts = {(r["host"], r["process_index"])
                     for r in doc["ranks"]}
            assert hosts == {("hostA", 0), ("hostB", 1)}
            assert doc["collector"]["connected"] == 2
            code, body = _get(exp.url + "/healthz")
            assert code == 200
            assert json.loads(body)["cluster"]["ranks"] == 2
        finally:
            c1.stop()
            c2.stop()
            httpexport.stop()

    def test_silent_rank_flips_healthz_naming_host_and_recovers(
            self, collector, monkeypatch):
        """Acceptance: a rank whose heartbeat stops past
        BST_STALL_TIMEOUT_S -> 503 naming the host; resuming heartbeats
        recovers 200. A cleanly-finished (bye) rank never flags."""
        monkeypatch.setenv("BST_STALL_TIMEOUT_S", "1")
        exp = httpexport.start(0)
        live = _FakeRank(collector.port, host="silent-host", pi=1)
        finished = _FakeRank(collector.port, host="done-host", pi=0)
        try:
            live.snap()
            finished.snap()
            finished.send({"t": "bye"})
            finished.close()
            assert _get(exp.url + "/healthz")[0] == 200
            # go silent: no snaps past the timeout
            code, body = _wait_for(
                lambda: (lambda cb: cb if cb[0] == 503 else None)(
                    _get(exp.url + "/healthz")),
                what="503 on silence")
            doc = json.loads(body)
            silent = doc["cluster"]["silent_ranks"]
            assert [s["host"] for s in silent] == ["silent-host"]
            assert silent[0]["process_index"] == 1
            # the finished rank never reads as silent
            assert all(s["host"] != "done-host" for s in silent)
            # resume -> recovery
            live.snap()
            code, _ = _wait_for(
                lambda: (lambda cb: cb if cb[0] == 200 else None)(
                    _get(exp.url + "/healthz")),
                what="recovery on resume")
            assert code == 200
            # watchdog off releases any stall verdict entirely
            monkeypatch.setenv("BST_STALL_TIMEOUT_S", "0")
            time.sleep(1.2)
            assert _get(exp.url + "/healthz")[0] == 200
        finally:
            live.close()
            httpexport.stop()

    def test_warn_events_ride_the_relay(self, collector):
        c = _mk_client(collector.port, "hostE", 1)
        try:
            _wait_for(lambda: any(r["connected"] for r in
                                  collector.cluster_status()["ranks"]),
                      what="connect")
            events.emit("retry.round", stage="relay-test", round=1)
            events.emit("stage.progress", stage="x", done=1, total=2)
            row = _wait_for(
                lambda: next((r for r in
                              collector.cluster_status()["ranks"]
                              if "retry.round" in (r.get("events") or [])),
                             None),
                what="forwarded warn event")
            # per-block progress spam deliberately does NOT ride the
            # event path (it ships with the periodic snapshot instead)
            assert "stage.progress" not in row["events"]
        finally:
            c.stop()

    def test_progress_rides_the_snapshot(self, collector):
        c = _mk_client(collector.port, "hostP", 1)
        try:
            hb = progress.Heartbeat("relay-stage", total=4, every_s=0.0)
            hb.tick(2)
            row = _wait_for(
                lambda: next(
                    (r for r in collector.cluster_status()["ranks"]
                     if (r.get("progress") or {}).get("stage")
                     == "relay-stage"), None),
                what="progress in snapshot")
            assert row["progress"]["done"] == 2
            assert row["progress"]["total"] == 4
            hb.finish()
            _wait_for(
                lambda: (next(
                    (r for r in collector.cluster_status()["ranks"]), {})
                    .get("progress") or {}).get("finished"),
                what="finished progress row")
        finally:
            c.stop()

    def test_garbage_lines_do_not_kill_the_handler(self, collector):
        """The relay port is unauthenticated TCP: valid-JSON-but-not-
        object lines (and non-JSON noise) must be ignored, not crash
        the connection handler."""
        snaps0 = metrics.counter("bst_relay_recv_total",
                                 type="snap").value
        fr = _FakeRank(collector.port, host="noisy", pi=1)
        try:
            fr.sock.sendall(b"null\n[1]\n\"x\"\nnot json at all\n")
            fr.snap(marker=1)
            # the snap AFTER the garbage still processes on the same
            # (uncrashed) handler, and the rank stays connected
            _wait_for(lambda: metrics.counter(
                "bst_relay_recv_total", type="snap").value > snaps0,
                what="snap processed after garbage")
            row = next(r for r in collector.cluster_status()["ranks"]
                       if r["host"] == "noisy")
            assert row["connected"]
        finally:
            fr.close()

    def test_idle_read_timeout_keeps_connection(self, collector):
        """The client socket's timeout exists for the WRITER (a wedged
        sendall must eventually error); the reader idling past it — the
        collector is silent except for trace pulls — must NOT tear a
        healthy connection down and reconnect-flap."""
        c = _mk_client(collector.port, "idle-host", 1)
        try:
            _wait_for(lambda: c.connected.is_set(), what="connect")
            with c._sock_lock:
                s0 = c._sock
                s0.settimeout(0.1)   # idle-read timeouts fire fast now
                # half-open (no FIN/RST) peers are caught by keepalive
                # probes, not by read-timeout teardown
                assert s0.getsockopt(socket.SOL_SOCKET,
                                     socket.SO_KEEPALIVE) == 1
            r0 = metrics.counter("bst_relay_reconnects_total").value
            time.sleep(0.8)          # several timeout windows, all idle
            assert c.connected.is_set()
            with c._sock_lock:
                assert c._sock is s0, \
                    "an idle read timeout dropped a healthy connection"
            assert metrics.counter(
                "bst_relay_reconnects_total").value == r0
            row = next(r for r in collector.cluster_status()["ranks"]
                       if r["host"] == "idle-host")
            assert row["connected"]
            # the COLLECTOR side of the same mostly-idle connection
            # needs the keepalive hardening too: its handler blocks in
            # a plain read, so a no-FIN dead worker would otherwise
            # stay a phantom connected rank (stalling cluster dumps)
            # until TCP retransmission gives up
            with collector._lock:
                conn = next(r["conn"] for r in collector._ranks.values()
                            if r["host"] == "idle-host")
            assert conn.getsockopt(socket.SOL_SOCKET,
                                   socket.SO_KEEPALIVE) == 1
        finally:
            c.stop()

    def test_metrics_families_contiguous_and_typed(self, collector):
        """The aggregated /metrics must stay VALID Prometheus
        exposition: each metric family exactly once, contiguous, under
        a single TYPE comment — duplicate or split families are
        rejected by promtool/OpenMetrics parsers."""
        exp = httpexport.start(0)
        c1 = _mk_client(collector.port, "hostA", 0)
        c2 = _mk_client(collector.port, "hostB", 1)
        metrics.counter("bst_io_read_bytes_total", op="fmt-test",
                        path="synthetic").inc(1)
        try:
            def scraped():
                code, body = _get(exp.url + "/metrics")
                return (code == 200
                        and 'host="hostA",process_index="0"' in body
                        and 'host="hostB",process_index="1"' in body
                        and body)

            body = _wait_for(scraped, what="aggregated scrape")
            types = {}
            for line in body.splitlines():
                if line.startswith("# TYPE "):
                    _, _, name, typ = line.split()
                    assert name not in types, f"duplicate TYPE: {name}"
                    types[name] = typ

            def family(name):
                for suf in ("_bucket", "_sum", "_count"):
                    if (name.endswith(suf) and types.get(name[:-len(suf)])
                            in ("histogram", "summary")):
                        return name[:-len(suf)]
                return name

            closed, current = set(), None
            for line in body.splitlines():
                if not line or line.startswith("#"):
                    continue
                name = family(line.split("{", 1)[0].split(" ", 1)[0])
                if name != current:
                    assert name not in closed, \
                        f"family {name} split into separate groups"
                    if current is not None:
                        closed.add(current)
                    current = name
                assert name in types, f"series {name} lacks a TYPE line"
        finally:
            c1.stop()
            c2.stop()
            httpexport.stop()

    def test_colliding_identity_ranks_dedupe_in_metrics(self, collector):
        """Two ranks claiming the same (host, process_index) but
        different process_count occupy distinct collector rows; the
        merged /metrics must carry ONE labeled copy (the freshest), not
        duplicate identical-label samples."""
        a = _FakeRank(collector.port, host="dup-host", pi=0, pc=1)
        b = _FakeRank(collector.port, host="dup-host", pi=0, pc=2)
        snaps0 = metrics.counter("bst_relay_recv_total",
                                 type="snap").value
        try:
            a.snap(prom="# TYPE x_total counter\nx_total 1\n")
            _wait_for(lambda: metrics.counter(
                "bst_relay_recv_total", type="snap").value > snaps0,
                what="first colliding snap")
            time.sleep(0.02)   # strictly newer last_seen for b
            b.snap(prom="# TYPE x_total counter\nx_total 2\n")
            _wait_for(lambda: metrics.counter(
                "bst_relay_recv_total", type="snap").value > snaps0 + 1,
                what="second colliding snap")
            body = collector.metrics_render(
                metrics.get_registry().render_prometheus())
            lines = [l for l in body.splitlines()
                     if l.startswith('x_total{host="dup-host"')]
            assert lines == \
                ['x_total{host="dup-host",process_index="0"} 2']
            # an EVENT from the stale rank touches last_seen but must
            # not let its older snapshot win back the identity
            ev0 = metrics.counter("bst_relay_recv_total",
                                  type="event").value
            a.send({"t": "event", "rec": {"type": "retry.round"}})
            _wait_for(lambda: metrics.counter(
                "bst_relay_recv_total", type="event").value > ev0,
                what="stale rank's event")
            body = collector.metrics_render(
                metrics.get_registry().render_prometheus())
            lines = [l for l in body.splitlines()
                     if l.startswith('x_total{host="dup-host"')]
            assert lines == \
                ['x_total{host="dup-host",process_index="0"} 2']
        finally:
            a.close()
            b.close()

    def test_self_hosting_rank_ring_not_duplicated(self, collector,
                                                   tmp_path, monkeypatch):
        """A hosting rank that also pushes to itself over loopback
        (ensure_started) must contribute its flight-recorder ring ONCE
        to a cluster dump — the direct local export, not a second
        pulled copy of the same ring."""
        monkeypatch.setenv("BST_PROCESS_ID", "0")
        monkeypatch.setenv("BST_NUM_PROCESSES", "2")
        me = _mk_client(collector.port, socket.gethostname(), 0)
        other = _mk_client(collector.port, "other-host", 1)
        try:
            _wait_for(lambda: collector.cluster_status()["collector"]
                      ["connected"] == 2, what="both connected")
            with profiling.span("barrier", stage="self-dedup"):
                pass
            out = str(tmp_path / "self-dedup-trace.json")
            res = collector.cluster_trace_dump(out, timeout_s=10)
            # only the non-self rank was pulled; the local ring rode in
            # exactly once via the direct export
            assert res["local_ring"] and res["asked"] == 1
            assert res["ranks"] == 1 and res["missing"] == 0
            assert res["traces"] == 2, \
                "self rank's ring written twice into the merge"
        finally:
            me.stop()
            other.stop()

    def test_same_host_rank0_worker_still_pulled(self, collector,
                                                 tmp_path):
        """The self-ring dedup must identify the self-CONNECTION (pid),
        not the (host, process_index) pair: a separately-launched
        same-host worker claiming process_index 0 (identity-only rank
        against a daemon-hosted collector) is NOT this process's ring
        and must still be asked for its trace."""
        own = not trace.enabled()
        if own:
            trace.configure()
        fr = _FakeRank(collector.port, host=socket.gethostname(), pi=0,
                       pid=os.getpid() + 1)
        try:
            _wait_for(lambda: any(r["connected"] for r in
                                  collector.cluster_status()["ranks"]),
                      what="worker connect")
            out = str(tmp_path / "same-host-trace.json")
            res = collector.cluster_trace_dump(out, timeout_s=1.0)
            # the worker was ASKED (a fake rank never answers, so it
            # reports missing) instead of silently deduped away
            assert res["asked"] == 1 and res["missing"] == 1
            assert res["local_ring"] and res["traces"] == 1
        finally:
            fr.close()
            if own:
                trace.reset()

    def test_cluster_trace_dump_merges_and_loads(self, collector,
                                                 tmp_path):
        c1 = _mk_client(collector.port, "hostA", 0)
        c2 = _mk_client(collector.port, "hostB", 1)
        try:
            _wait_for(lambda: collector.cluster_status()["collector"]
                      ["connected"] == 2, what="both connected")
            with profiling.span("barrier", stage="relay-test"):
                pass
            out = str(tmp_path / "pod-trace.json")
            res = collector.cluster_trace_dump(out, timeout_s=10)
            assert res["path"] == out and os.path.exists(out)
            assert res["ranks"] == 2 and res["missing"] == 0
            from bigstitcher_spark_tpu.analysis.tracereport import (
                build_report, load_events,
            )
            evs, meta = load_events(out)
            build_report(evs, meta)   # must not raise
            doc = json.load(open(out))
            assert doc["bst"]["schema"] == "bst-merged-trace/1"
            # the recorder kept recording through the pull
            assert trace.stats()["enabled"]
        finally:
            c1.stop()
            c2.stop()


# -- daemon integration + CLI -------------------------------------------------


class TestDaemonCluster:
    @pytest.fixture()
    def daemon(self, tmp_path):
        d = Daemon(str(tmp_path / "bst.sock"), slots=1,
                   jobs_root=str(tmp_path / "jobs"), metrics_port=0,
                   relay="127.0.0.1:0").start()
        try:
            yield d
        finally:
            if not d.wait(timeout=0):
                d.shutdown(drain=False, wait=True)

    def test_daemon_hosts_collector_and_cli_cluster_surfaces(
            self, daemon, tmp_path):
        col = relay.collector()
        assert col is not None, "daemon did not host the collector"
        c = _mk_client(col.port, "worker-host", 1)
        runner = CliRunner()
        try:
            _wait_for(lambda: col.cluster_status()["collector"]
                      ["connected"] == 1, what="worker connect")
            # ping/status carry the collector summary
            pong = serve_client.ping(daemon.socket_path)
            assert pong["relay"] == f"127.0.0.1:{col.port}"
            st = serve_client.status(daemon.socket_path)
            assert st["relay"]["connected"] == 1
            # bst top --cluster over the socket AND over HTTP
            out = _cli_ok(runner, ["top", "--cluster", "--once",
                                   "--socket", daemon.socket_path]).output
            assert "worker-host" in out and "live" in out
            out = _cli_ok(runner, [
                "top", "--cluster", "--once",
                "--url", f"http://127.0.0.1:{daemon.metrics_port}"]).output
            assert "worker-host" in out
            # bst trace-dump --cluster -> merged file -> trace-report
            dump = str(tmp_path / "cluster-trace.json")
            out = _cli_ok(runner, ["trace-dump", "--cluster",
                                   "--socket", daemon.socket_path,
                                   "--out", dump]).output
            assert dump in out and "rank ring(s)" in out
            _cli_ok(runner, ["trace-report", dump])
            doc = json.load(open(dump))
            assert doc["bst"]["schema"] == "bst-merged-trace/1"
        finally:
            c.stop()

    def test_drain_releases_collector_address(self, tmp_path):
        d = Daemon(str(tmp_path / "a.sock"), slots=1,
                   jobs_root=str(tmp_path / "ja"), relay="127.0.0.1:0")
        d.start()
        port = relay.collector().port
        d.shutdown(drain=True, wait=True)
        assert relay.collector() is None
        # the address is free again for the next daemon
        d2 = Daemon(str(tmp_path / "b.sock"), slots=1,
                    jobs_root=str(tmp_path / "jb"),
                    relay=f"127.0.0.1:{port}")
        d2.start()
        try:
            assert relay.collector().port == port
        finally:
            d2.shutdown(drain=True, wait=True)

    def test_cluster_ops_without_collector_are_clean_errors(self,
                                                            tmp_path):
        d = Daemon(str(tmp_path / "bst.sock"), slots=1,
                   jobs_root=str(tmp_path / "jobs")).start()
        runner = CliRunner()
        try:
            r = runner.invoke(cli, ["top", "--cluster", "--once",
                                    "--socket", d.socket_path])
            assert r.exit_code != 0 and "no relay collector" in r.output
            r = runner.invoke(cli, ["trace-dump", "--cluster",
                                    "--socket", d.socket_path])
            assert r.exit_code != 0 and "no relay collector" in r.output
        finally:
            d.shutdown(drain=True, wait=True)


# -- end to end: real worker processes (acceptance) ---------------------------


_WORKER = """
import os, sys, time
from bigstitcher_spark_tpu.parallel.distributed import init_distributed

init_distributed()   # relay bring-up rides beside initialize
from bigstitcher_spark_tpu import profiling
from bigstitcher_spark_tpu.observe import metrics, progress, relay, trace

assert relay.client() is not None, "worker did not become a push client"
rank = int(os.environ["BST_PROCESS_ID"])
metrics.counter("bst_io_read_bytes_total", op="e2e",
                path="native").inc(1000 + rank)
hb = progress.Heartbeat("e2e-stage", total=1000, every_s=0.0)
print("WORKER-READY", flush=True)
while True:
    with profiling.span("barrier", stage="e2e"):
        hb.tick()
    time.sleep(0.05)
"""


class TestEndToEnd:
    def _spawn_worker(self, tmp_path, rank: int, port: int):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            "BST_TELEMETRY_RELAY": f"127.0.0.1:{port}",
            # identity-only rank id: no BST_COORDINATOR/NUM_PROCESSES,
            # so these are independent local processes, not a jax world
            "BST_PROCESS_ID": str(rank),
            "BST_RELAY_INTERVAL_S": "0.2",
        })
        env.pop("BST_NUM_PROCESSES", None)
        script = tmp_path / "worker.py"
        script.write_text(_WORKER)
        return subprocess.Popen([sys.executable, str(script)], env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT)

    def test_two_process_pod_plane(self, tmp_path, monkeypatch):
        """Acceptance, end to end with REAL processes: labeled /metrics
        from every rank mid-run, 503 naming the killed rank's host, 200
        again after it resumes, one merged cluster trace."""
        monkeypatch.setenv("BST_STALL_TIMEOUT_S", "2")
        col = relay.RelayCollector("127.0.0.1", 0).start()
        exp = httpexport.start(0)
        hostname = socket.gethostname()
        workers = {}
        try:
            for rank in (0, 1):
                workers[rank] = self._spawn_worker(tmp_path, rank,
                                                   col.port)

            def both_reporting():
                """Each rank's own workload counter, host/rank-labeled —
                NOT just any labeled line (the collector's self-row
                carries process_index=0 labels before worker 0's first
                counter-bearing snapshot lands)."""
                code, body = _get(exp.url + "/metrics")
                if code != 200:
                    return None
                for rank in (0, 1):
                    if not re.search(
                            rf'bst_io_read_bytes_total\{{'
                            rf'host="{hostname}",process_index="{rank}",'
                            rf'op="e2e",path="native"\}} {1000 + rank}',
                            body):
                        return None
                return body

            body = _wait_for(both_reporting, timeout=90,
                             what="labeled counters from both ranks")
            # rank 0 of a multi-process world tried to HOST the already-
            # owned address and fell back to pushing — both must be rows
            doc = json.loads(_get(exp.url + "/cluster")[1])
            assert {r["process_index"] for r in doc["ranks"]
                    if r["connected"]} == {0, 1}
            assert _get(exp.url + "/healthz")[0] == 200

            # kill rank 1 (no bye): its heartbeat stops -> 503 names it
            workers[1].kill()
            workers[1].wait(timeout=30)
            code, body = _wait_for(
                lambda: (lambda cb: cb if cb[0] == 503 else None)(
                    _get(exp.url + "/healthz")),
                timeout=30, what="503 after kill")
            silent = json.loads(body)["cluster"]["silent_ranks"]
            assert [(s["host"], s["process_index"]) for s in silent] == \
                [(hostname, 1)]

            # resume the rank -> pod health recovers
            workers[1] = self._spawn_worker(tmp_path, 1, col.port)
            _wait_for(
                lambda: _get(exp.url + "/healthz")[0] == 200,
                timeout=90, what="recovery after restart")

            # cluster flight-recorder pull: every rank's live ring folds
            # into ONE Perfetto file, mid-run, loadable by trace-report
            out = str(tmp_path / "pod-trace.json")
            res = col.cluster_trace_dump(out, timeout_s=30)
            assert res["ranks"] == 2 and res["missing"] == 0
            from bigstitcher_spark_tpu.analysis.tracereport import (
                build_report, load_events,
            )
            evs, meta = load_events(out)
            report = build_report(evs, meta)
            assert report   # renders
            doc = json.load(open(out))
            assert doc["bst"]["process_count"] >= 2
            names = {e.get("name") for e in doc["traceEvents"]}
            assert "barrier" in names   # the workers' recorded spans
        finally:
            for w in workers.values():
                if w.poll() is None:
                    w.kill()
                w.wait(timeout=30)
            httpexport.stop()
            col.stop()


# -- relay OFF: zero overhead, byte-identical --------------------------------


class TestRelayOff:
    def test_ensure_started_is_noop_without_knob(self, monkeypatch):
        monkeypatch.delenv("BST_TELEMETRY_RELAY", raising=False)
        assert relay.ensure_started() is None
        assert relay.client() is None and relay.collector() is None
        assert not events._taps, "no tap may be installed with relay off"

    def test_progress_latest_stays_off(self):
        hb = progress.Heartbeat("off-stage", total=2, every_s=0.0)
        hb.tick(2)
        hb.finish()
        assert progress.latest() is None

    def test_metrics_render_unchanged_without_collector(self):
        """No relay -> /metrics is exactly the local registry render
        (no cluster section, no host/process_index labels injected)."""
        exp = httpexport.start(0)
        try:
            code, body = _get(exp.url + "/metrics")
            assert code == 200
            assert "relay-aggregated" not in body
            assert 'host="' not in body
            assert 'process_index="' not in body
        finally:
            httpexport.stop()

    def test_broken_metrics_render_falls_back_to_local(self):
        """A metrics_render provider that raises OR returns a non-str
        must degrade the scrape to the host-local render, never cost
        /metrics a 500."""
        exp = httpexport.start(0)
        try:
            for bad in (lambda text: None,
                        lambda text: (_ for _ in ()).throw(RuntimeError)):
                httpexport.set_cluster_providers(metrics_render=bad)
                code, body = _get(exp.url + "/metrics")
                assert code == 200
                assert "bst_http_requests_total" in body
        finally:
            httpexport.clear_cluster_providers()
            httpexport.stop()

    def test_rank0_hosts_and_registers_itself(self, monkeypatch):
        """Knob-driven pod mode: the hosting rank 0 also pushes into
        its own collector over loopback, so /cluster and the pod
        verdict cover rank 0, not only ranks 1..N-1."""
        port = _free_port()
        monkeypatch.setenv("BST_TELEMETRY_RELAY", f"127.0.0.1:{port}")
        monkeypatch.setenv("BST_PROCESS_ID", "0")
        monkeypatch.setenv("BST_NUM_PROCESSES", "4")
        got = relay.ensure_started()
        try:
            assert isinstance(got, relay.RelayCollector)
            assert relay.client() is not None
            row = _wait_for(lambda: next(
                (r for r in got.cluster_status()["ranks"]
                 if r["connected"] and r["process_index"] == 0), None),
                what="rank-0 self row")
            assert row["host"] == socket.gethostname()
        finally:
            relay.stop()

    def test_rank0_host_fallback_when_address_owned(self, monkeypatch,
                                                    collector):
        """Rank 0 of a multi-process world tries to HOST the relay
        address; when a daemon on this host already owns it, the bind
        fails and the rank falls back to pushing."""
        monkeypatch.setenv("BST_TELEMETRY_RELAY",
                           f"127.0.0.1:{collector.port}")
        monkeypatch.setenv("BST_PROCESS_ID", "0")
        monkeypatch.setenv("BST_NUM_PROCESSES", "2")
        got = relay.ensure_started()
        try:
            assert isinstance(got, relay.RelayClient)
            assert relay.collector() is None   # module collector unset:
            #      the fixture's instance owns the port, not the global
            _wait_for(lambda: any(
                r["connected"] and r["process_index"] == 0
                for r in collector.cluster_status()["ranks"]),
                what="fallback client connect")
        finally:
            relay.stop()


# -- satellites ---------------------------------------------------------------


class TestMetricsHostKnob:
    def test_default_binds_loopback(self):
        exp = httpexport.start(0)
        try:
            assert exp._server.server_address[0] == "127.0.0.1"
        finally:
            httpexport.stop()

    def test_knob_widens_the_bind(self, monkeypatch):
        monkeypatch.setenv("BST_METRICS_HOST", "0.0.0.0")
        exp = httpexport.start(0)
        try:
            assert exp._server.server_address[0] == "0.0.0.0"
            # the convenience url still answers locally
            assert _get(exp.url + "/healthz")[0] == 200
        finally:
            httpexport.stop()


def _fake_manifest(directory, pi, pc, *, tool="affine-fusion", seconds,
                   span_s, read_bytes):
    os.makedirs(directory, exist_ok=True)
    doc = {
        "schema": "bst-run-manifest/1", "tool": tool, "argv": [],
        "params": {}, "world": {"process_index": pi, "process_count": pc},
        "device": {}, "started_at": "2026-08-04T00:00:00",
        "seconds": seconds, "status": "ok",
        "spans": {"fusion.kernel": {"count": 3, "total_s": span_s,
                                    "max_s": span_s, "min_s": 0.01}},
        "metrics": {"bst_io_read_bytes_total"
                    '{op="x",path="y"}': read_bytes},
        "stages": [{"stage": "fusion", "done": 8, "total": 8}],
        "events_file": None,
    }
    path = os.path.join(directory, f"manifest-{pi:05d}-of-{pc:05d}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return path


class TestPodHistory:
    def test_telemetry_merge_appends_pod_record(self, tmp_path,
                                                monkeypatch):
        """Satellite: with BST_HISTORY_DIR set, `bst telemetry-merge`
        appends the merged POD manifest to the history store, and two
        pod records diff via `bst perf-diff`."""
        hist = str(tmp_path / "hist")
        monkeypatch.setenv("BST_HISTORY_DIR", hist)
        runner = CliRunner()
        for tag, span_s, nbytes in (("a", 0.05, 10 << 20),
                                    ("b", 0.50, 80 << 20)):
            d = str(tmp_path / f"tel-{tag}")
            for pi in (0, 1):
                _fake_manifest(d, pi, 2, seconds=1.0 + span_s,
                               span_s=span_s, read_bytes=nbytes)
            out = _cli_ok(runner, ["telemetry-merge", d]).output
            assert "recorded in history as" in out
        entries = history.list_records(hist)
        assert len(entries) == 2
        assert all(e["tool"] == "affine-fusion" and e["status"] == "ok"
                   for e in entries)
        assert all(e["id"].startswith("pod-") for e in entries)
        rec = history.load_record(entries[0]["id"], hist)
        # the merged record carries the SUMMED span/metric surface
        assert rec["spans"]["fusion.kernel"]["count"] == 6
        assert rec["world"]["process_count"] == 2
        out = _cli_ok(runner, ["perf-diff", "--last", "2",
                               "--threshold", "50"]).output
        assert "REGRESSION" in out and "fusion.kernel" in out

    def test_manifestless_merge_records_unknown_not_ok(self, tmp_path):
        """A pod run that died on every rank before finalize (event
        logs only, zero manifests) must not enter the history as a
        healthy 'ok' baseline."""
        hist = str(tmp_path / "h")
        rid = history.record_merged_report(
            {"processes": [], "process_count": 2, "wall_clock_s": 0.0,
             "spans": {}, "metrics": {}, "stages": []},
            directory=hist)
        rec = history.load_record(rid, hist)
        assert rec["status"] == "unknown"

    def test_merge_without_history_dir_is_unchanged(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.delenv("BST_HISTORY_DIR", raising=False)
        d = str(tmp_path / "tel")
        _fake_manifest(d, 0, 1, seconds=1.0, span_s=0.1,
                       read_bytes=1 << 20)
        out = _cli_ok(CliRunner(), ["telemetry-merge", d]).output
        assert "recorded in history" not in out
