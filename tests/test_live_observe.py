"""The live observability plane: embedded HTTP exporter (/metrics,
/healthz, /status, /jobs), the serve daemon's stall watchdog, on-demand
flight-recorder dumps, `bst top`, and the manifest history store +
`bst perf-diff` regression diff.

Acceptance contract (ISSUE 13): with a daemon running a fusion job,
/healthz answers 200 and live /metrics shows a nonzero bst_serve_* gauge
mid-job; an artificially wedged job flips /healthz non-200 and `bst
jobs` shows `stalled` within BST_STALL_TIMEOUT_S; `bst trace-dump`
mid-job produces a Perfetto JSON the trace-report path loads; and two
recorded runs diff via `bst perf-diff` with a regression threshold
flagging an injected slowdown.

Daemons run IN-PROCESS on tmp-path sockets with OS-assigned exporter
ports (metrics_port=0), so the suite never collides on a fixed port.
"""

import json
import os
import re
import threading
import time
import urllib.error
import urllib.request

import click
import pytest
from click.testing import CliRunner

from bigstitcher_spark_tpu import observe, profiling
from bigstitcher_spark_tpu.cli.main import cli
from bigstitcher_spark_tpu.observe import events, history, httpexport, metrics
from bigstitcher_spark_tpu.serve import client
from bigstitcher_spark_tpu.serve.daemon import Daemon


def _get(url: str, timeout: float = 10.0):
    """(status_code, body) — non-200 responses return, never raise."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _cli_ok(runner, args):
    r = runner.invoke(cli, args, catch_exceptions=False)
    assert r.exit_code == 0, f"bst {' '.join(args)}\n{r.output}"
    return r


@pytest.fixture()
def daemon(tmp_path):
    """In-process daemon with an ephemeral live-exporter port."""
    d = Daemon(str(tmp_path / "bst.sock"), slots=2,
               jobs_root=str(tmp_path / "jobs"), metrics_port=0).start()
    try:
        yield d
    finally:
        if not d.wait(timeout=0):
            d.shutdown(drain=False, wait=True)


@pytest.fixture()
def wedge_tool():
    """A temporary CLI tool that runs without ever emitting progress —
    the artificial wedge the stall watchdog must flag. It polls the
    ambient cancel token, so `bst cancel` (and daemon teardown) always
    unwinds it."""
    @click.command("wedge")
    @click.option("--seconds", type=float, default=60.0)
    def wedge_cmd(seconds):
        from bigstitcher_spark_tpu.utils import cancel

        t0 = time.time()
        while time.time() - t0 < seconds:
            cancel.check()
            time.sleep(0.02)

    cli.add_command(wedge_cmd, "wedge")
    yield "wedge"
    cli.commands.pop("wedge", None)


# -- the exporter alone ------------------------------------------------------


class TestHttpExporter:
    def test_endpoints_and_process_gauges(self):
        exp = httpexport.start(0)
        try:
            base = exp.url
            code, body = _get(base + "/metrics")
            assert code == 200
            assert "bst_process_uptime_seconds" in body
            assert re.search(r"^bst_process_threads \d+$", body, re.M)
            code, body = _get(base + "/healthz")
            assert code == 200 and json.loads(body)["ok"] is True
            code, body = _get(base + "/status")
            assert code == 200
            st = json.loads(body)
            assert st["process"]["pid"] == os.getpid()
            assert st["process"]["uptime_s"] >= 0
            code, body = _get(base + "/jobs")
            assert code == 200 and json.loads(body)["jobs"] == []
            code, _ = _get(base + "/nope")
            assert code == 404
        finally:
            httpexport.stop()

    def test_knob_zero_is_off(self, monkeypatch):
        monkeypatch.setenv("BST_METRICS_PORT", "0")
        assert httpexport.ensure_started() is None
        monkeypatch.delenv("BST_METRICS_PORT")
        assert httpexport.ensure_started() is None

    def test_unhealthy_provider_flips_healthz(self):
        exp = httpexport.start(0)
        try:
            httpexport.set_providers(
                health=lambda: (False, {"ok": False, "why": "test"}))
            code, body = _get(exp.url + "/healthz")
            assert code == 503 and json.loads(body)["ok"] is False
        finally:
            httpexport.clear_providers()
            httpexport.stop()

    def test_live_scrape_races_running_jobs(self):
        """Satellite: a /metrics render racing concurrent metric updates
        (and concurrent NEW-series creation, the registry-mutation case)
        must never throw or emit a torn series."""
        reg = metrics.MetricsRegistry()
        stop = threading.Event()
        errors: list[BaseException] = []

        def writer(i):
            try:
                c = reg.counter("hammer_ops_total", job=f"j{i}")
                h = reg.histogram("hammer_wait_seconds", job=f"j{i}")
                g = reg.gauge("hammer_depth")
                n = 0
                while not stop.is_set():
                    c.inc(3)
                    h.observe(0.01 * (n % 7))
                    g.set(n % 5)
                    n += 1
                    if n % 50 == 0:   # mint fresh series mid-render
                        reg.counter("hammer_ops_total", job=f"j{i}-{n}")
            except BaseException as e:   # noqa: BLE001
                errors.append(e)

        line_re = re.compile(
            r'[a-zA-Z_:][\w:]*(\{[^}]*\})? -?[\d.e+-]+(e[+-]?\d+)?$')

        def scraper():
            try:
                for _ in range(150):
                    text = reg.render_prometheus()
                    for line in text.strip().splitlines():
                        assert line.startswith("#") or line_re.fullmatch(
                            line), f"torn line: {line!r}"
                    snap = reg.snapshot_delta(reg.snapshot())
                    for v in snap.values():
                        assert isinstance(v, (int, float, dict))
            except BaseException as e:   # noqa: BLE001
                errors.append(e)

        writers = [threading.Thread(target=writer, args=(i,))
                   for i in range(2)]
        scrapers = [threading.Thread(target=scraper) for _ in range(3)]
        for t in writers + scrapers:
            t.start()
        for t in scrapers:
            t.join()
        stop.set()
        for t in writers:
            t.join()
        assert not errors, errors
        # histograms stayed internally consistent: +Inf bucket == _count
        text = reg.render_prometheus()
        counts = dict(re.findall(
            r'hammer_wait_seconds_count\{job="(j\d+)"\} (\d+)', text))
        infs = dict(re.findall(
            r'hammer_wait_seconds_bucket\{job="(j\d+)",le="\+Inf"\} (\d+)',
            text))
        for job, c in counts.items():
            assert infs[job] == c


# -- daemon: live scrape, watchdog, trace dump, top --------------------------


def _mk_project(tmp_path, name="proj", **kw):
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    spec = dict(n_tiles=(2, 1, 1), tile_size=(64, 64, 32), overlap=16,
                jitter=1.0, n_beads_per_tile=20, seed=7)
    spec.update(kw)
    return make_synthetic_project(str(tmp_path / name), **spec).xml_path


class TestDaemonLive:
    def test_live_metrics_and_healthz_mid_fusion(self, tmp_path, daemon):
        """Acceptance: while the daemon runs a fusion job, a live
        /metrics scrape shows a nonzero bst_serve_* gauge and /healthz
        answers 200."""
        sock = daemon.socket_path
        base = f"http://127.0.0.1:{daemon.metrics_port}"
        xml = _mk_project(tmp_path)
        proj = os.path.dirname(xml)
        res = client.submit(sock, "create-fusion-container",
                            ["-x", xml, "-o", f"{proj}/fused.zarr",
                             "-s", "ZARR", "-d", "UINT16",
                             "--blockSize", "16,16,16",
                             "--minIntensity", "0",
                             "--maxIntensity", "65535"])
        assert res["exit_code"] == 0
        result = {}

        def go():
            result["r"] = client.submit(
                sock, "affine-fusion",
                ["-o", f"{proj}/fused.zarr", "--blockScale", "1,1,1"])

        th = threading.Thread(target=go)
        th.start()
        seen_active = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and th.is_alive():
            code, body = _get(base + "/metrics")
            assert code == 200
            m = re.search(r"^bst_serve_active_jobs (\d+)$", body, re.M)
            if m and int(m.group(1)) >= 1:
                seen_active = int(m.group(1))
                hcode, hbody = _get(base + "/healthz")
                assert hcode == 200, hbody
                assert json.loads(hbody)["active"] >= 1
                break
            time.sleep(0.02)
        th.join(timeout=300)
        assert result["r"]["exit_code"] == 0, result["r"]
        assert seen_active and seen_active >= 1, \
            "never scraped a live nonzero bst_serve_active_jobs"

    def test_wedged_job_stalls_healthz_and_recovers(self, tmp_path,
                                                    monkeypatch,
                                                    wedge_tool):
        """Acceptance: a job whose progress never advances flips
        /healthz non-200 and shows `stalled` in `bst jobs` within
        BST_STALL_TIMEOUT_S; trace-dump works mid-job; cancelling the
        job recovers health."""
        monkeypatch.setenv("BST_STALL_TIMEOUT_S", "1")
        d = Daemon(str(tmp_path / "bst.sock"), slots=1,
                   jobs_root=str(tmp_path / "jobs"), metrics_port=0)
        d.start()
        try:
            sock = d.socket_path
            base = f"http://127.0.0.1:{d.metrics_port}"
            jid = client.submit(sock, wedge_tool, ["--seconds", "120"],
                                follow=False)["job"]
            stalled_row = None
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                rows = [j for j in client.list_jobs(sock)["jobs"]
                        if j["id"] == jid]
                if rows and rows[0].get("stalled"):
                    stalled_row = rows[0]
                    break
                time.sleep(0.1)
            assert stalled_row, "watchdog never flagged the wedged job"
            assert stalled_row["stalled_for_s"] >= 1
            code, body = _get(base + "/healthz")
            assert code == 503
            assert jid in json.loads(body)["stalled_jobs"]
            code, body = _get(base + "/metrics")
            assert re.search(r"^bst_serve_jobs_stalled 1$", body, re.M)
            # the warn event landed on the JOB's scoped sink
            logs = [os.path.join(d.jobs_root, jid, f)
                    for f in os.listdir(os.path.join(d.jobs_root, jid))
                    if f.startswith("events-job-")]
            assert logs
            stall_events = [rec for rec in events.iter_events(logs[0])
                            if rec.get("type") == "job.stall"]
            assert stall_events and "BST_STALL_TIMEOUT_S" in \
                stall_events[0]["message"]
            # the human surfaces agree
            runner = CliRunner()
            out = _cli_ok(runner, ["jobs", "--socket", sock]).output
            assert "STALLED" in out
            out = _cli_ok(runner, ["top", "--once", "--socket",
                                   sock]).output
            assert "STALLED" in out and "stalled 1" in out

            # acceptance: on-demand flight-recorder dump MID-JOB, loadable
            # by the existing trace-report path, recorder left running
            dump_path = str(tmp_path / "live-trace.json")
            out = _cli_ok(runner, ["trace-dump", "--socket", sock,
                                   "--out", dump_path]).output
            assert dump_path in out
            from bigstitcher_spark_tpu.analysis.tracereport import (
                build_report, load_events,
            )
            evs, meta = load_events(dump_path)
            build_report(evs, meta)   # must not raise
            doc = json.load(open(dump_path))
            assert doc["bst"]["schema"] == "bst-trace/2"
            names = {e.get("name") for e in doc["traceEvents"]}
            assert "serve.submit" in names
            from bigstitcher_spark_tpu.observe import trace as _trace
            assert _trace.stats()["enabled"], \
                "trace-dump must not stop the recorder"

            # disabling the watchdog live (knob read per sweep) must
            # RELEASE the stall state, not freeze a stale 503
            monkeypatch.setenv("BST_STALL_TIMEOUT_S", "0")
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if _get(base + "/healthz")[0] == 200:
                    break
                time.sleep(0.1)
            assert _get(base + "/healthz")[0] == 200, \
                "disabled watchdog froze the stalled state"
            monkeypatch.setenv("BST_STALL_TIMEOUT_S", "1")
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if _get(base + "/healthz")[0] == 503:
                    break
                time.sleep(0.1)
            assert _get(base + "/healthz")[0] == 503

            # cancel -> progress bookkeeping clears -> health recovers
            client.cancel(sock, jid)
            deadline = time.monotonic() + 20
            recovered = False
            while time.monotonic() < deadline:
                code, _ = _get(base + "/healthz")
                if code == 200:
                    recovered = True
                    break
                time.sleep(0.1)
            assert recovered, "healthz never recovered after cancel"
            # the gauge follows on the watchdog's next sweep
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                code, body = _get(base + "/metrics")
                if re.search(r"^bst_serve_jobs_stalled 0$", body, re.M):
                    break
                time.sleep(0.1)
            assert re.search(r"^bst_serve_jobs_stalled 0$", body, re.M)
        finally:
            if not d.wait(timeout=0):
                d.shutdown(drain=False, wait=True)

    def test_status_op_ping_and_jobs_agree(self, daemon):
        """Satellite: uptime/process gauges come from ONE place —
        /status (the status op) and `bst jobs --json` report the same
        shape, and ping carries the exporter port."""
        st = client.status(daemon.socket_path)
        via_jobs = client.list_jobs(daemon.socket_path)["daemon"]
        assert set(st) == set(via_jobs)
        for d in (st, via_jobs):
            assert d["process"]["pid"] == os.getpid()
            assert d["uptime_s"] >= 0
            assert "inflight" in d and "dag" in d and "trace" in d
        pong = client.ping(daemon.socket_path)
        assert pong["metrics_port"] == daemon.metrics_port
        assert pong["uptime_s"] >= 0
        # the /status HTTP endpoint serves the same document
        code, body = _get(f"http://127.0.0.1:{daemon.metrics_port}/status")
        assert code == 200 and set(json.loads(body)) == set(st)

    def test_top_over_http_url(self, daemon):
        runner = CliRunner()
        out = _cli_ok(runner, [
            "top", "--once",
            "--url", f"http://127.0.0.1:{daemon.metrics_port}"]).output
        assert "bst serve pid" in out and "slots 2" in out

    def test_serve_surface_tools_not_submittable(self, daemon):
        for tool in ("top", "trace-dump"):
            with pytest.raises(RuntimeError, match="unservable"):
                client.submit(daemon.socket_path, tool, [])


# -- history store + perf-diff ----------------------------------------------


@pytest.fixture()
def _clean_observe():
    yield
    if observe.active():
        observe.finalize(tool="test-cleanup")
    events.close()


def _record_run(tmp_path, tag, sleep_s, extra_bytes, hist):
    """One telemetry-dir'd run with an injected span duration + byte
    traffic; records into ``hist`` via the finalize hook."""
    profiling.get().reset()
    observe.configure(str(tmp_path / f"tel-{tag}"))
    with profiling.span("fusion.kernel"):
        time.sleep(sleep_s)
    metrics.counter("bst_io_read_bytes_total", op="hist-test",
                    path="synthetic").inc(extra_bytes)
    return observe.finalize(tool="demo")


class TestHistoryPerfDiff:
    def test_finalize_records_and_diff_flags_slowdown(self, tmp_path,
                                                      monkeypatch,
                                                      _clean_observe):
        """Acceptance: two recorded runs diff cleanly; the injected
        slowdown (6x span time, 6x bytes) is flagged at a 50%%
        threshold, and the reverse direction is clean."""
        hist = str(tmp_path / "hist")
        monkeypatch.setenv("BST_HISTORY_DIR", hist)
        _record_run(tmp_path, "a", 0.05, 10 << 20, hist)
        _record_run(tmp_path, "b", 0.30, 60 << 20, hist)
        entries = history.list_records(hist)
        assert len(entries) == 2
        assert all(e["tool"] == "demo" and e["status"] == "ok"
                   for e in entries)

        runner = CliRunner()
        out = _cli_ok(runner, ["history", "list"]).output
        assert entries[0]["id"] in out and entries[1]["id"] in out

        rec = json.loads(_cli_ok(
            runner, ["history", "show", entries[0]["id"]]).output)
        assert rec["tool"] == "demo" and "spans" in rec and "metrics" in rec

        out = _cli_ok(runner, ["perf-diff", "--last", "2",
                               "--threshold", "50"]).output
        assert "REGRESSION" in out and "fusion.kernel" in out
        rep = json.loads(_cli_ok(
            runner, ["perf-diff", "--last", "2", "--threshold", "50",
                     "--json"]).output)
        kinds = {r["kind"] for r in rep["regressions"]}
        assert "span" in kinds and "bytes" in kinds
        # explicit ids work too, and the reverse diff is regression-free
        rep2 = json.loads(_cli_ok(
            runner, ["perf-diff", entries[1]["id"], entries[0]["id"],
                     "--threshold", "50", "--json"]).output)
        assert rep2["regressions"] == []
        # CI-gate exit code
        r = runner.invoke(cli, ["perf-diff", "--last", "2",
                                "--threshold", "50",
                                "--fail-on-regression"])
        assert r.exit_code == 2

    def test_history_add_imports_manifests(self, tmp_path, _clean_observe):
        # a run recorded WITHOUT the knob set...
        observe.configure(str(tmp_path / "tel"))
        observe.finalize(tool="demo")
        hist = str(tmp_path / "hist2")
        assert not os.path.exists(os.path.join(hist, "index.jsonl"))
        runner = CliRunner()
        # ...imports later, by telemetry dir
        out = _cli_ok(runner, ["history", "add", str(tmp_path / "tel"),
                               "--history-dir", hist]).output
        rid = out.strip()
        assert rid
        entries = history.list_records(hist)
        assert [e["id"] for e in entries] == [rid]
        rec = history.load_record(rid, hist)
        assert rec["tool"] == "demo"

    def test_jobrun_manifests_record_with_job_label(self, tmp_path,
                                                    monkeypatch):
        hist = str(tmp_path / "hist3")
        monkeypatch.setenv("BST_HISTORY_DIR", hist)
        jr = observe.JobRun("jtest", str(tmp_path / "job"), tool="config")
        with jr:
            pass
        jr.finalize(status="ok")
        entries = history.list_records(hist)
        assert len(entries) == 1 and entries[0]["job"] == "jtest"

    def test_cache_ratio_regression(self):
        a = {"id": "a", "seconds": 1.0, "spans": {}, "metrics": {
            "bst_chunk_cache_hits_total": 90,
            "bst_chunk_cache_misses_total": 10}}
        b = {"id": "b", "seconds": 1.0, "spans": {}, "metrics": {
            "bst_chunk_cache_hits_total": 10,
            "bst_chunk_cache_misses_total": 90}}
        rep = history.diff(a, b, threshold_pct=20.0)
        assert any(r["kind"] == "cache" for r in rep["regressions"])
        assert history.diff(b, a, threshold_pct=20.0)["regressions"] == []

    def test_histogram_metrics_flatten_into_diff(self):
        a = {"id": "a", "seconds": 1.0, "spans": {},
             "metrics": {"bst_serve_wait_seconds":
                         {"count": 2, "sum": 0.5}}}
        rep = history.diff(a, a)
        assert rep["regressions"] == []

    def test_missing_history_dir_is_a_clean_error(self, monkeypatch):
        monkeypatch.delenv("BST_HISTORY_DIR", raising=False)
        runner = CliRunner()
        r = runner.invoke(cli, ["perf-diff", "x", "y"])
        assert r.exit_code != 0 and "history dir" in r.output
        r = runner.invoke(cli, ["history", "list"])
        assert r.exit_code != 0 and "history dir" in r.output
