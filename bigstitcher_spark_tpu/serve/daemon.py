"""The resident ``bst serve`` daemon.

One process owns jax, the device mesh, and every process-wide cache
(decoded-chunk LRU, HBM tile cache, compiled-fn bucket tables); submitted
jobs execute the SAME click commands the one-shot CLI runs, in-process on
executor-slot threads, so a warm second job skips jax init, compile and
cache fill entirely. Isolation is scoping:

- **config** — each job runs under :func:`config.overrides` with its own
  knob dict; unless the job sets them itself, the daemon splits the
  derived in-flight byte budgets (``BST_INFLIGHT_BYTES``,
  ``BST_PAIR_INFLIGHT_BYTES``) across the executor slots so concurrent
  jobs SHARE the per-device windows instead of each claiming all of HBM;
- **telemetry** — each job gets an :class:`observe.JobRun` (its own
  ``events-job-*.jsonl`` + manifest + metric deltas in its own
  directory) and its stdout routed to its own ``output.log``;
- **cancellation** — each job carries a :class:`utils.cancel.CancelToken`
  that the shared work loops poll at their safe points;
- **crash isolation** — a job is one big try/except on its slot thread:
  a failing job records FAILED and the mesh, caches and every other job
  keep running.

Lifecycle: SIGTERM/SIGINT (or the ``shutdown`` op) drains — the queue
closes (queued jobs cancel), running jobs finish (or are cancelled when
``drain=false``), then the accept loop exits and the socket unlinks.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import queue as _queuemod
import signal
import socket
import sys
import threading
import time

from .. import config, observe, profiling
from ..observe.relay import _shutdown_close
from ..observe import events, httpexport, metrics as _metrics, \
    trace as _trace
from ..utils import cancel as _cancel
from ..utils.threads import ctx_thread
from . import protocol
from .jobs import CANCELLED, DONE, FAILED, QUEUED, RUNNING, Job, JobQueue

# tools a job may NOT be: the serve surface itself (a job submitting jobs
# recurses; `top` would follow its own daemon forever), plus flags that
# would re-enter the process-global telemetry lifecycle under the
# daemon's feet
_BLOCKED_TOOLS = {"serve", "submit", "jobs", "cancel", "top", "trace-dump"}
_BLOCKED_FLAGS = {"--telemetry-dir", "--profile", "--trace"}

_WARM_HITS = _metrics.counter("bst_serve_compile_warm_hits_total")
_PROFILES_APPLIED = _metrics.counter("bst_tune_profiles_applied_total")

# events forwarded to following submit clients (everything else stays in
# the job's JSONL only — a chatty fusion log must not flood the socket)
_STREAMED_EVENTS = {"job.start", "job.end", "stage.start", "stage.progress",
                    "stage.end", "log", "retry.round", "pair.redispatch",
                    "job.stall", "job.resume"}

_STALLED = _metrics.gauge("bst_serve_jobs_stalled")

# a slot loop that is IDLE (no job) must touch its heartbeat at least
# every take() timeout; past this age the loop thread is presumed dead
_SLOT_DEAD_AFTER_S = 15.0


class _StdoutRouter(io.TextIOBase):
    """Routes ``sys.stdout`` writes to the emitting context's job log.

    click.echo and the drivers' progress prints all write to the process
    stdout; in a multi-job daemon that interleaves jobs. The router keys
    on the ambient event scope (the same contextvar the event log routes
    by, carried into worker threads by utils.threads) and appends to the
    job's ``output.log``, falling back to the real stdout outside any job
    scope."""

    def __init__(self):
        self._real = sys.__stdout__
        self._lock = threading.Lock()
        self._files: dict[str, object] = {}

    def register(self, label: str, path: str) -> None:
        """Open the job's log and make sure the router IS sys.stdout.

        Installation happens here, per job, not at daemon start: anything
        else that swaps sys.stdout while the daemon idles (pytest's
        capture does, between test phases) would silently orphan an
        install-once router. Re-checking at every job start self-heals —
        whatever stream is current becomes the fallthrough target."""
        with self._lock:
            self._files[label] = open(path, "a", encoding="utf-8",
                                      buffering=1)
            if sys.stdout is not self:
                self._real = sys.stdout
                sys.stdout = self

    def unregister(self, label: str) -> None:
        with self._lock:
            f = self._files.pop(label, None)
            if not self._files and sys.stdout is self:
                sys.stdout = self._real
        if f is not None:
            f.close()

    def _target(self):
        label = events.current_job()
        if label is not None:
            with self._lock:
                f = self._files.get(label)
            if f is not None:
                return f
        return self._real

    def write(self, s) -> int:
        return self._target().write(s)

    def flush(self) -> None:
        try:
            self._target().flush()
        except ValueError:
            pass

    @property
    def encoding(self):
        return getattr(self._real, "encoding", "utf-8")

    def isatty(self):
        return False


class Daemon:
    """The resident server. ``start()`` binds and spawns the accept loop
    and executor slots; ``wait()`` blocks until shutdown completes (the
    foreground ``bst serve`` mode); tests drive it in-process."""

    def __init__(self, socket_path: str | None = None,
                 slots: int | None = None,
                 jobs_root: str | None = None,
                 idle_timeout: float | None = None,
                 metrics_port: int | None = None,
                 relay: str | None = None):
        self.socket_path = socket_path or protocol.default_socket_path()
        self.slots = slots if slots is not None else \
            max(1, config.get_int("BST_SERVE_SLOTS") or 1)
        self.jobs_root = os.path.abspath(
            jobs_root or (self.socket_path + "-jobs"))
        self.idle_timeout = (idle_timeout if idle_timeout is not None
                             else config.get_int("BST_SERVE_IDLE_TIMEOUT")
                             or 0)
        # live HTTP exporter: None reads BST_METRICS_PORT (whose 0 means
        # OFF); an EXPLICIT 0 (CLI --metrics-port 0, tests) asks the OS
        # for a free ephemeral port instead — the resolved port lands in
        # self.metrics_port / the ping response
        self._metrics_port_arg = metrics_port
        self.metrics_port = 0
        # telemetry relay collector: an explicit --relay host:port beats
        # the BST_TELEMETRY_RELAY knob; a daemon always HOSTS (it is the
        # pod's natural fan-in point — multi-host daemons inherit the
        # aggregated live plane for free)
        self._relay_arg = relay
        self._own_relay = False
        self._own_exchange = False
        self.queue = JobQueue(self.slots)
        self.started_at = time.time()
        self._sock: socket.socket | None = None
        self._threads: list[threading.Thread] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._drained = threading.Event()
        self._job_seq = 0
        self._dump_seq = 0
        self._last_activity = time.monotonic()
        self._router: _StdoutRouter | None = None
        self._inflight_base: int | None = None
        self._pair_base: int | None = None
        self.device_info: dict = {}
        self._slot_seen = [time.monotonic()] * self.slots
        self._slot_busy = [False] * self.slots
        self._own_exporter = False
        self._own_trace = False

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "Daemon":
        os.makedirs(self.jobs_root, exist_ok=True)
        self._warm_mesh()
        # a resident process records its flight recorder ALWAYS (bounded
        # ring, newest-wins): `bst trace-dump` can then snapshot the last
        # BST_TRACE_BUFFER_BYTES of timeline at any point without anyone
        # having thought to pass --trace before the incident
        if not _trace.enabled():
            _trace.configure()
            self._own_trace = True
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        with contextlib.suppress(OSError):
            os.unlink(self.socket_path)
        s.bind(self.socket_path)
        s.listen(16)
        s.settimeout(1.0)
        self._sock = s
        with self._lock:
            self._router = _StdoutRouter()   # installs itself per job
        self._start_exporter()
        self._start_relay()
        self._start_exchange()
        for slot in range(self.slots):
            th = ctx_thread(self._slot_loop, (slot,),
                            name=f"bst-serve-slot-{slot}")
            th.start()
            self._threads.append(th)
        th = ctx_thread(self._accept_loop, (), name="bst-serve-accept")
        th.start()
        self._threads.append(th)
        th = ctx_thread(self._watchdog_loop, (), name="bst-serve-watchdog")
        th.start()
        self._threads.append(th)
        observe.log(f"bst serve: listening on {self.socket_path} "
                    f"({self.slots} slot(s), "
                    f"{self.device_info.get('local_device_count', '?')} "
                    f"device(s))", stage="serve")
        if self.metrics_port:
            exp = httpexport.active()
            url = exp.url if exp is not None \
                else f"http://127.0.0.1:{self.metrics_port}"
            observe.log(f"bst serve: live exporter on {url} "
                        f"(/metrics /healthz /status /jobs /cluster)",
                        stage="serve")
        return self

    def _start_exporter(self) -> None:
        """Bring the live HTTP exporter up (explicit port arg beats the
        BST_METRICS_PORT knob) and point its providers at this daemon;
        bind failure downgrades to socket-only serving, never a crash."""
        exp = httpexport.active()
        if exp is None:
            try:
                if self._metrics_port_arg is not None:
                    exp = httpexport.start(self._metrics_port_arg)
                else:
                    exp = httpexport.ensure_started()
                self._own_exporter = exp is not None
            except OSError as e:
                observe.log(f"bst serve: live exporter disabled "
                            f"({e})", stage="serve")
                return
        if exp is not None:
            httpexport.set_providers(status=self._status,
                                     health=self._health,
                                     jobs=self._jobs_payload)
            self.metrics_port = exp.port

    def _start_relay(self) -> None:
        """Host the pod telemetry collector (--relay / the knob) so the
        daemon's /metrics, /healthz and /cluster aggregate every relayed
        rank; bind failure downgrades, never a crash."""
        from ..observe import relay as _relay

        addr = (self._relay_arg if self._relay_arg is not None
                else config.get_str("BST_TELEMETRY_RELAY"))
        if not addr or _relay.collector() is not None:
            return
        try:
            col = _relay.serve(addr)
        except (OSError, ValueError) as e:
            observe.log(f"bst serve: relay collector disabled ({e})",
                        stage="serve")
            return
        self._own_relay = True
        observe.log(f"bst serve: telemetry relay collecting on "
                    f"{col.host}:{col.port}", stage="serve")

    def _start_exchange(self) -> None:
        """Host this rank's cross-host block-exchange endpoint
        (BST_DAG_EXCHANGE_ADDR) so multi-process pipeline jobs submitted
        to the daemon stream blocks between ranks; inert without the
        knob or in a single-process world, and a bind failure downgrades
        (the pipeline job will then reject multi-process specs loudly)."""
        from ..dag import exchange as _exchange

        try:
            x = _exchange.ensure_started()
        except Exception as e:   # noqa: BLE001 — never block the daemon
            observe.log(f"bst serve: block exchange disabled ({e})",
                        stage="serve")
            return
        if x is not None:
            self._own_exchange = True
            host, port = x.addresses[x.rank]
            observe.log(f"bst serve: block exchange rank {x.rank}/"
                        f"{x.world} serving on {host}:{port}",
                        stage="serve")

    def _warm_mesh(self) -> None:
        """Pay jax init + device placement ONCE, before accepting work;
        derive the budget bases concurrent jobs split."""
        from ..utils.devicemem import dispatch_budget_bytes, pair_budget

        import jax

        devs = jax.local_devices()
        self.device_info = {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "local_device_count": len(devs),
        }
        self._inflight_base = dispatch_budget_bytes(devs[0])
        self._pair_base = pair_budget(devs[0], 1)[0]

    def _on_signal(self, signum, frame) -> None:
        self.shutdown(drain=True, wait=False)

    def shutdown(self, drain: bool = True, wait: bool = True) -> None:
        """Close the queue (queued jobs cancel); ``drain`` lets running
        jobs finish, otherwise their tokens are set too. Idempotent."""
        _trace.instant("serve.shutdown")
        doomed = self.queue.close()
        for job in doomed:
            self._notify(job, {"event": "done", "job": job.id,
                               "state": job.state, "exit_code": None})
            job.waiters.clear()
        if not drain:
            for job in self.queue.jobs():
                if job.state == RUNNING:
                    job.token.cancel()
        self._stop.set()
        if wait:
            self.wait()

    def wait(self, timeout: float | None = None) -> bool:
        """Block until the daemon fully stopped (socket closed, slots
        joined)."""
        return self._drained.wait(timeout)

    def _finish_stop(self) -> None:
        if self._sock is not None:
            with contextlib.suppress(OSError):
                self._sock.close()
        with contextlib.suppress(OSError):
            os.unlink(self.socket_path)
        for th in self._threads:
            if th is not threading.current_thread():
                # no timeout: drain means running jobs FINISH (a cancel
                # already poisons them when drain=False)
                th.join()
        with self._lock:
            router = self._router
            self._router = None
        if router is not None and sys.stdout is router:
            sys.stdout = router._real   # no job left it installed
        httpexport.clear_providers()
        if self._own_relay:
            from ..observe import relay as _relay

            _relay.stop_collector()   # frees the address, clears the
            #                           cluster providers it attached
        if self._own_exchange:
            from ..dag import exchange as _exchange

            _exchange.shutdown()   # frees the rank's exchange port
        if self._own_exporter:
            httpexport.stop()   # frees the port for the next daemon
        if self._own_trace and _trace.enabled():
            _trace.reset()      # leave the recorder as we found it
        self._drained.set()

    # -- accept / connection handling ----------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                if (self.idle_timeout and self.queue.idle()
                        and time.monotonic() - self._last_activity
                        > self.idle_timeout):
                    observe.log("bst serve: idle timeout, exiting",
                                stage="serve")
                    self.shutdown(drain=True, wait=False)
                    break
                continue
            except OSError:
                break
            self._last_activity = time.monotonic()
            th = ctx_thread(self._handle_conn, (conn,),
                            name="bst-serve-conn")
            th.start()
        # the accept thread owns teardown so shutdown(wait=False) callers
        # (signal handlers) never block inside the handler
        self._finish_stop()

    def _handle_conn(self, conn: socket.socket) -> None:
        f = conn.makefile("rwb")
        try:
            try:
                req = protocol.read_line(f)
            except (ValueError, OSError) as e:
                protocol.send_line(f, {"event": "error",
                                       "error": f"bad request: {e!r}"})
                return
            if not req:
                return
            op = req.get("op")
            if op == "ping":
                rly = self._relay_summary()
                protocol.send_line(f, {
                    "event": "pong", "pid": os.getpid(),
                    "uptime_s": self.uptime_s(),
                    "metrics_port": self.metrics_port,
                    "relay": rly["address"] if rly else None,
                    "device": self.device_info})
            elif op == "jobs":
                protocol.send_line(f, {"event": "jobs",
                                       "daemon": self._status(),
                                       "jobs": self._jobs_payload()})
            elif op == "cancel":
                self._op_cancel(f, req)
            elif op == "shutdown":
                protocol.send_line(f, {"event": "shutdown",
                                       "drain": bool(req.get("drain",
                                                             True))})
                self.shutdown(drain=bool(req.get("drain", True)),
                              wait=False)
            elif op == "submit":
                self._op_submit(f, req)
            elif op == "status":
                protocol.send_line(f, {"event": "status",
                                       "status": self._status()})
            elif op == "trace-dump":
                self._op_trace_dump(f, req)
            elif op == "cluster":
                self._op_cluster(f)
            else:
                protocol.send_line(f, {"event": "error",
                                       "error": f"unknown op {op!r}"})
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass   # client went away; jobs keep running
        finally:
            with contextlib.suppress(OSError):
                f.close()
            # shutdown before close: f is an io-ref on the same fd, so a
            # bare close() would leave the connection half-open and the
            # client hanging on a reply that cannot come
            _shutdown_close(conn)

    def uptime_s(self) -> float:
        """Daemon uptime — the ONE place it is computed (ping, /status
        and `bst jobs --json` must all agree)."""
        return round(time.time() - self.started_at, 1)

    def _stalled_jobs(self) -> list[str]:
        return [j.id for j in self.queue.jobs()
                if j.stalled and j.state == RUNNING]

    def _relay_summary(self) -> dict | None:
        from ..observe import relay as _relay

        col = _relay.collector()
        if col is None:
            return None
        doc = col.cluster_status()["collector"]
        return {"address": doc["address"], "ranks": doc["ranks"],
                "connected": doc["connected"]}

    def _status(self) -> dict:
        from ..io.chunkcache import get_cache

        return {
            "pid": os.getpid(),
            "socket": self.socket_path,
            "slots": self.slots,
            "uptime_s": self.uptime_s(),
            "metrics_port": self.metrics_port,
            "queue_depth": self.queue.depth(),
            "active": self.queue.active(),
            "stalled": self._stalled_jobs(),
            "device": self.device_info,
            # the same process self-view the /metrics scrape refreshes,
            # so `bst jobs --json` and /status literally agree
            "process": httpexport.process_stats(),
            "share_runtime_s": {k: round(v, 3) for k, v in
                                self.queue.share_runtime().items()},
            # warm-cache state: why the second submit is cheaper
            "chunk_cache": get_cache().stats(),
            "compiled_fn": {
                "warm_hits": _metrics.counter(
                    "bst_compiled_fn_warm_hits_total").value,
                "cold_builds": _metrics.counter(
                    "bst_compiled_fn_cold_builds_total").value,
            },
            # live frontier gauges: the in-flight HBM high-water and the
            # streamed-pipeline exchange/stall state (a starved dag
            # consumer shows up here while it is starving, not post-run)
            "inflight": {
                "bytes": _metrics.gauge("bst_inflight_bytes").value,
                "highwater_bytes": _metrics.gauge(
                    "bst_inflight_bytes_highwater").value,
            },
            "dag": {
                "exchange_bytes": _metrics.gauge(
                    "bst_dag_exchange_bytes").value,
                "exchange_blocks": _metrics.gauge(
                    "bst_dag_exchange_blocks").value,
                "producer_stall_s": _metrics.counter(
                    "bst_dag_producer_stall_seconds_total").value,
                "consumer_wait_s": _metrics.counter(
                    "bst_dag_consumer_wait_seconds_total").value,
            },
            "trace": _trace.stats(),
            # the relay collector's pod summary (None when not hosting)
            "relay": self._relay_summary(),
        }

    def _health(self) -> tuple[bool, dict]:
        """The /healthz verdict: 200 only while every slot loop's
        heartbeat is fresh (idle slots tick each take() timeout; a busy
        slot is alive by definition), no running job is stalled, and the
        daemon is not draining."""
        now = time.monotonic()
        stalled = self._stalled_jobs()
        ages = [round(now - seen, 1) for seen in self._slot_seen]
        dead_slots = [i for i in range(self.slots)
                      if not self._slot_busy[i]
                      and ages[i] > _SLOT_DEAD_AFTER_S]
        draining = self._stop.is_set()
        ok = not stalled and not dead_slots and not draining
        return ok, {
            "ok": ok,
            "uptime_s": self.uptime_s(),
            "device": self.device_info,
            "slot_heartbeat_age_s": ages,
            "dead_slots": dead_slots,
            "stalled_jobs": stalled,
            "active": self.queue.active(),
            "queue_depth": self.queue.depth(),
            "draining": draining,
        }

    def _jobs_payload(self) -> list[dict]:
        rows = []
        for j in self.queue.jobs():
            d = j.describe()
            open_ids = self.queue.waiting_on(j.id)
            if open_ids:
                d["waiting_on"] = sorted(open_ids)
            rows.append(d)
        return rows

    def _op_cancel(self, f, req: dict) -> None:
        job = self.queue.get(str(req.get("job", "")))
        if job is None:
            protocol.send_line(f, {"event": "error",
                                   "error": f"no such job "
                                            f"{req.get('job')!r}"})
            return
        self.queue.cancel(job.id)
        _trace.instant("serve.cancel", item=job.id)
        # cancelled straight off the queue (the job itself, and any
        # dependents its cancellation cascaded into): no slot will ever
        # notify their followers, so close those streams here
        for j in self.queue.jobs():
            if j.state == CANCELLED and j.started_at is None and j.waiters:
                self._notify(j, {"event": "done", "job": j.id,
                                 "state": j.state, "exit_code": None,
                                 "error": j.error})
                j.waiters.clear()
        protocol.send_line(f, {"event": "cancelled", "job": job.id,
                               "state": job.state})

    def _op_trace_dump(self, f, req: dict) -> None:
        """Snapshot the live flight-recorder ring to Perfetto JSON
        without pausing jobs (the ring copy happens under the trace
        lock; the recorder keeps recording). With ``cluster`` set, the
        relay collector additionally pulls every connected rank's live
        ring and folds them — barrier-aligned — into the one file."""
        out = req.get("out")
        if not out:
            with self._lock:
                self._dump_seq += 1
                n = self._dump_seq
            out = os.path.join(self.jobs_root, f"trace-dump-{n:04d}.json")
        if req.get("cluster"):
            from ..observe import relay as _relay

            col = _relay.collector()
            if col is None:
                protocol.send_line(f, {
                    "event": "error",
                    "error": "no relay collector in this daemon — start "
                             "it with --relay HOST:PORT (or "
                             "BST_TELEMETRY_RELAY)"})
                return
            try:
                res = col.cluster_trace_dump(os.path.abspath(str(out)))
            except (RuntimeError, OSError) as e:
                protocol.send_line(f, {"event": "error", "error": str(e)})
                return
            _trace.instant("serve.trace_dump",
                           item=os.path.basename(res["path"]))
            protocol.send_line(f, {"event": "trace-dump", **res})
            return
        try:
            path = _trace.dump_live(os.path.abspath(str(out)))
        except (RuntimeError, OSError) as e:
            protocol.send_line(f, {"event": "error", "error": str(e)})
            return
        _trace.instant("serve.trace_dump", item=os.path.basename(path))
        protocol.send_line(f, {"event": "trace-dump", "path": path,
                               **_trace.stats()})

    def _op_cluster(self, f) -> None:
        """The /cluster JSON over the daemon socket (`bst top --cluster`
        without an HTTP exporter)."""
        from ..observe import relay as _relay

        col = _relay.collector()
        if col is None:
            protocol.send_line(f, {
                "event": "error",
                "error": "no relay collector in this daemon — start it "
                         "with --relay HOST:PORT (or "
                         "BST_TELEMETRY_RELAY)"})
            return
        protocol.send_line(f, {"event": "cluster", **col.cluster_status()})

    # -- stall watchdog ------------------------------------------------------

    def _watchdog_loop(self) -> None:
        """Flags RUNNING jobs whose stage.progress stopped advancing for
        BST_STALL_TIMEOUT_S: raises the bst_serve_jobs_stalled gauge,
        warns on the job's scoped event sink (and the follower stream),
        and clears the flag the moment progress resumes. The knob is read
        per sweep, so a long-lived daemon can be retuned live."""
        while not self._stop.is_set():
            timeout_s = config.get_int("BST_STALL_TIMEOUT_S") or 0
            now = time.time()
            stalled_n = 0
            for job in self.queue.jobs():
                # clearing runs even with the watchdog disabled: setting
                # the knob to 0 must RELEASE stale stall state (flags,
                # gauge, /healthz), not freeze it
                if job.state != RUNNING or timeout_s <= 0:
                    job.stalled = False
                    continue
                last = job.last_progress or job.started_at or now
                is_stalled = now - last > timeout_s
                if is_stalled:
                    stalled_n += 1
                if is_stalled and not job.stalled:
                    job.stalled = True
                    _trace.instant("serve.stall", item=job.id)
                    self._emit_job_event(
                        job, "job.stall",
                        message=f"no stage.progress for "
                                f"{round(now - last, 1)}s "
                                f"(BST_STALL_TIMEOUT_S={timeout_s})",
                        stalled_for_s=round(now - last, 1),
                        # the streamed-exchange state diagnoses a
                        # starved dag consumer live
                        dag_exchange_bytes=_metrics.gauge(
                            "bst_dag_exchange_bytes").value,
                        dag_producer_stall_s=_metrics.counter(
                            "bst_dag_producer_stall_seconds_total"
                        ).value,
                        dag_consumer_wait_s=_metrics.counter(
                            "bst_dag_consumer_wait_seconds_total"
                        ).value)
                elif not is_stalled and job.stalled:
                    job.stalled = False
                    self._emit_job_event(job, "job.resume",
                                         message="progress resumed")
            _STALLED.set(stalled_n)
            self._stop.wait(max(0.2, min(timeout_s / 4, 5.0))
                            if timeout_s > 0 else 1.0)
        _STALLED.set(0)

    def _emit_job_event(self, job: Job, etype: str, **fields) -> None:
        """Emit a daemon-side event INTO the job's scoped sink (and so
        its follower stream) from the watchdog thread."""
        token = events.activate_job(job.id)
        try:
            events.emit(etype, job=job.id, **fields)
        finally:
            events.deactivate_job(token)

    def _op_submit(self, f, req: dict) -> None:
        from ..cli.main import cli as _cli

        tool = str(req.get("tool", ""))
        args = [str(a) for a in (req.get("args") or [])]
        if tool not in _cli.commands or tool in _BLOCKED_TOOLS:
            protocol.send_line(f, {"event": "error",
                                   "error": f"unknown or unservable tool "
                                            f"{tool!r}"})
            return
        # match both the split ("--flag", "v") and the fused ("--flag=v")
        # spellings click accepts
        bad = sorted({a for a in args
                      if a.split("=", 1)[0] in _BLOCKED_FLAGS})
        if bad:
            protocol.send_line(f, {
                "event": "error",
                "error": f"{bad} are daemon-owned: per-job telemetry is "
                         f"automatic (see the job directory)"})
            return
        try:
            ov = config.validate_overrides(req.get("overrides") or {})
        except KeyError as e:
            protocol.send_line(f, {"event": "error", "error": str(e)})
            return
        # tuned-profile application: an explicit `submit --profile` ref,
        # or BST_PROFILE_AUTO resolving every job against the store. The
        # profile's knobs merge UNDER the job's own --set overrides (the
        # operator's explicit word always wins) and the applied key rides
        # in the job description + manifest params for auditability.
        prof = None
        prof_ref = req.get("profile")
        if prof_ref or config.get_bool("BST_PROFILE_AUTO"):
            try:
                prof = self._resolve_profile(str(prof_ref or "auto"))
            except (KeyError, FileNotFoundError, ValueError) as e:
                if prof_ref and prof_ref != "auto":
                    # the client named a specific profile: failing to
                    # resolve it must not silently run untuned
                    protocol.send_line(f, {"event": "error",
                                           "error": str(e)})
                    return
                prof = None   # auto is best-effort by design
        if prof is not None:
            try:
                prof_ov = config.validate_overrides(
                    prof.get("overrides") or {})
            except KeyError as e:   # store written by a newer/older build
                protocol.send_line(f, {"event": "error", "error": str(e)})
                return
            ov = {**prof_ov, **ov}
            _PROFILES_APPLIED.inc()
        with self._lock:
            self._job_seq += 1
            jid = f"j{self._job_seq:04d}"
        job = Job(
            id=jid, tool=tool, args=args,
            priority=int(req.get("priority") or 0),
            share=str(req.get("share") or "default"),
            overrides=ov,
            cost=float(req.get("cost") or 1.0),
            after=[str(a) for a in (req.get("after") or [])],
        )
        if prof is not None:
            job.profile = prof.get("key")
        job.telemetry_dir = os.path.join(self.jobs_root, jid)
        follow = bool(req.get("follow", True))
        waiter = None
        if follow:
            waiter = _queuemod.Queue()
            job.waiters.append(waiter)
        try:
            self.queue.submit(job)
        except RuntimeError as e:   # draining
            protocol.send_line(f, {"event": "error", "error": str(e)})
            return
        except KeyError as e:       # unknown --after parent
            protocol.send_line(f, {"event": "error", "error": str(e)})
            return
        _trace.instant("serve.submit", item=jid)
        events.emit("serve.submit", job=jid, tool=tool, share=job.share,
                    priority=job.priority, after=job.after)
        accepted = {"event": "accepted", "job": jid,
                    "telemetry_dir": job.telemetry_dir}
        if job.profile:
            accepted["profile"] = job.profile
        protocol.send_line(f, accepted)
        if job.state == CANCELLED:
            # a parent had already failed/cancelled: terminal on arrival
            self._notify(job, {"event": "done", "job": jid,
                               "state": job.state, "exit_code": None,
                               "error": job.error})
            job.waiters.clear()
        if not follow:
            return
        while True:
            msg = waiter.get()
            protocol.send_line(f, msg)
            if msg.get("event") == "done":
                return

    def _resolve_profile(self, ref: str) -> dict | None:
        """Resolve a submit-time profile reference against the tuned-
        profile store (BST_HISTORY_DIR/profiles.json) along THIS
        daemon's backend axes. ``auto`` returns None when nothing
        matches; an explicit ref raises KeyError (handled by the
        caller into a submit error)."""
        from ..tune import profiles as _profiles

        store = _profiles.load_store(None)
        backend = self.device_info.get("platform") or "cpu"
        ndev = int(self.device_info.get("local_device_count") or 1)
        return _profiles.match_profile(store, backend=backend,
                                       device_count=ndev, ref=ref)

    # -- job execution -------------------------------------------------------

    def _notify(self, job: Job, msg: dict) -> None:
        for w in list(job.waiters):
            w.put(msg)

    def _job_budget_overrides(self, job: Job) -> dict[str, str]:
        """The job's effective override layer: its own knobs win; below
        them, the derived per-device byte windows split across the
        executor slots so concurrent jobs share HBM instead of each
        claiming the full budget (the window ledger's high-water gauge
        stays <= the single-job budget)."""
        ov = dict(job.overrides)
        if self.slots > 1:
            if self._inflight_base and "BST_INFLIGHT_BYTES" not in ov:
                ov["BST_INFLIGHT_BYTES"] = str(
                    max(1, self._inflight_base // self.slots))
            if self._pair_base and "BST_PAIR_INFLIGHT_BYTES" not in ov:
                ov["BST_PAIR_INFLIGHT_BYTES"] = str(
                    max(1, self._pair_base // self.slots))
        return ov

    def _slot_loop(self, slot: int) -> None:
        while True:
            self._slot_seen[slot] = time.monotonic()
            job = self.queue.take(slot, timeout=0.5)
            if job is None:
                if self._stop.is_set():
                    return
                continue
            self._last_activity = time.monotonic()
            self._slot_busy[slot] = True
            try:
                self._run_job(slot, job)
            finally:
                self._slot_busy[slot] = False
                self._slot_seen[slot] = time.monotonic()
            self._last_activity = time.monotonic()

    def _run_job(self, slot: int, job: Job) -> None:
        """The crash-isolated job wrapper: whatever this raises is THIS
        job's failure — the slot, the mesh and the caches live on. The
        per-job SETUP (job dir, telemetry sink, output router) sits
        inside the isolation too: a full disk must fail the job, not
        kill the slot thread and wedge the queue."""
        import click

        from ..cli.main import cli as _cli

        jobrun = None
        router = None
        warm0 = _metrics.counter("bst_compiled_fn_warm_hits_total").value
        state, rc, error = DONE, 0, None
        try:
            os.makedirs(job.telemetry_dir, exist_ok=True)
            jobrun = observe.JobRun(job.id, job.telemetry_dir,
                                    tool=job.tool)
            # live heartbeats: the job's event sink exists now, bridge its
            # progress subset to every following client (the sink — and
            # with it this subscription — is dropped by jobrun.finalize)
            events.subscribe(job.id, _streaming_forwarder(job))
            with self._lock:
                router = self._router
            if router is not None:
                router.register(job.id, os.path.join(job.telemetry_dir,
                                                     "output.log"))
            # new remote-cache coherence window: chunks cached from remote
            # object stores (BST_REMOTE_CACHE=run) are pinned to one run —
            # another writer may have touched the bucket between jobs, so
            # each job re-validates via fresh metadata signatures. Local
            # stores keep their mtime-keyed warmth across jobs.
            from ..io.chunkstore import bump_remote_pin

            bump_remote_pin()
            with config.overrides(self._job_budget_overrides(job)), \
                    _cancel.scope(job.token), jobrun:
                # the stall clock starts NOW: a job that never emits a
                # heartbeat stalls timeout_s after start, not after epoch
                job.last_progress = time.time()
                self._notify(job, {"event": "start", "job": job.id,
                                   "slot": slot})
                with profiling.span("serve.job", stage=job.tool,
                                    item=job.id):
                    _cli(args=[job.tool, *job.args], prog_name="bst",
                         standalone_mode=False)
        except _cancel.Cancelled:
            state, rc, error = CANCELLED, 130, "cancelled"
        except click.exceptions.Exit as e:
            rc = int(e.exit_code or 0)
            state = DONE if rc == 0 else FAILED
        except SystemExit as e:   # a tool calling sys.exit stays one job
            rc = int(e.code) if isinstance(e.code, int) else 1
            state = DONE if rc == 0 else FAILED
        except click.ClickException as e:
            state, rc, error = FAILED, e.exit_code or 1, e.format_message()
        except BaseException as e:  # noqa: BLE001 — crash isolation
            state, rc, error = FAILED, 1, repr(e)[:500]
        if job.token.cancelled and state != CANCELLED:
            # token set but the job finished first: report what happened
            state = state if state == DONE else CANCELLED
        job.warm_compile_hits = int(
            _metrics.counter("bst_compiled_fn_warm_hits_total").value
            - warm0)
        _WARM_HITS.inc(job.warm_compile_hits)
        try:
            if jobrun is None:
                raise RuntimeError("job setup failed before telemetry")
            jobrun.finalize(
                status={DONE: "ok", CANCELLED: "cancelled"}.get(state,
                                                                "error"),
                error=error,
                params={"tool": job.tool, "args": job.args,
                        "overrides": job.overrides,
                        "profile": job.profile,
                        "priority": job.priority, "share": job.share,
                        "slot": slot,
                        "warm_compile_hits": job.warm_compile_hits})
        except Exception:   # manifest IO must not flip the job's outcome
            pass
        if router is not None:
            router.unregister(job.id)
        cascaded = self.queue.finish(job, state, exit_code=rc, error=error)
        self._notify(job, {"event": "done", "job": job.id, "state": state,
                           "exit_code": rc, "error": error,
                           "seconds": job.describe().get("seconds"),
                           "warm_compile_hits": job.warm_compile_hits,
                           "telemetry_dir": job.telemetry_dir})
        job.waiters.clear()   # done delivered; drop follower queues
        for child in cascaded:
            # dependents cancelled because THIS job failed: their
            # followers' streams close here — no slot will ever run them
            self._notify(child, {"event": "done", "job": child.id,
                                 "state": child.state, "exit_code": None,
                                 "error": child.error})
            child.waiters.clear()


def _streaming_forwarder(job: Job):
    """events->waiters bridge: forwards the heartbeat subset of a job's
    event stream to every following client, and feeds the stall
    watchdog's progress clock + `bst top`'s live progress row."""
    def cb(rec: dict) -> None:
        t = rec.get("type")
        if t in ("stage.start", "stage.progress", "stage.end"):
            job.last_progress = time.time()
            if t == "stage.progress":
                job.progress = {k: rec[k] for k in
                                ("stage", "done", "total", "rate_per_s",
                                 "eta_s") if k in rec}
            elif t == "stage.end":
                job.progress = None   # stage finished; row is stale
        if t in _STREAMED_EVENTS:
            for w in list(job.waiters):
                w.put({"event": "job-event", "job": job.id, **rec})

    return cb


def run_foreground(socket_path: str | None = None, slots: int | None = None,
                   jobs_root: str | None = None,
                   idle_timeout: float | None = None,
                   metrics_port: int | None = None,
                   relay: str | None = None) -> int:
    """``bst serve`` without --detach: start, block until shutdown.

    Signal handling lives HERE, not in Daemon.start(): only the
    foreground CLI owns the process (and the main thread signal.signal
    requires) — an in-process daemon (tests, bench) must never hijack
    its host's SIGINT/SIGTERM. Previous handlers are restored on exit."""
    d = Daemon(socket_path, slots=slots, jobs_root=jobs_root,
               idle_timeout=idle_timeout, metrics_port=metrics_port,
               relay=relay)
    d.start()
    prev = {}
    if threading.current_thread() is threading.main_thread():
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev[sig] = signal.signal(sig, d._on_signal)
    try:
        while not d.wait(timeout=0.5):
            pass
    except KeyboardInterrupt:
        d.shutdown(drain=True, wait=True)
    finally:
        for sig, h in prev.items():
            signal.signal(sig, h)
    return 0


def spawn_detached(socket_path: str | None = None, slots: int | None = None,
                   jobs_root: str | None = None,
                   idle_timeout: float | None = None,
                   metrics_port: int | None = None,
                   relay: str | None = None,
                   ready_timeout: float = 180.0) -> int:
    """``bst serve --detach``: fork a daemon subprocess, wait until its
    socket answers ping, return its pid."""
    import subprocess

    from . import client

    path = socket_path or protocol.default_socket_path()
    # the daemon inherits the caller's cwd (so the job's relative paths
    # resolve the same way), which need not be the package checkout —
    # put wherever THIS package imports from on the child's path
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = (pkg_root + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else pkg_root)
    args = [sys.executable, "-m", "bigstitcher_spark_tpu.cli.main",
            "serve", "--socket", path]
    if slots is not None:
        args += ["--slots", str(slots)]
    if jobs_root is not None:
        args += ["--jobs-root", jobs_root]
    if idle_timeout is not None:
        args += ["--idle-timeout", str(int(idle_timeout))]
    if metrics_port is not None:
        args += ["--metrics-port", str(int(metrics_port))]
    if relay is not None:
        args += ["--relay", relay]
    log_path = path + ".log"
    with open(log_path, "ab") as logf:
        proc = subprocess.Popen(args, stdout=logf, stderr=logf, env=env,
                                start_new_session=True)
    deadline = time.monotonic() + ready_timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"serve daemon exited rc={proc.returncode} before ready "
                f"(log: {log_path})")
        try:
            client.ping(path, timeout=2.0)
            return proc.pid
        except (OSError, ValueError):
            time.sleep(0.2)
    raise TimeoutError(f"serve daemon not ready after {ready_timeout}s "
                       f"(log: {log_path})")
