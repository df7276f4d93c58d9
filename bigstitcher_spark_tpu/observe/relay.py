"""Cross-host telemetry relay: rank-N push clients, a rank-0 collector.

Every live-observability surface so far — ``/metrics``, ``/healthz``,
``bst top``, ``bst trace-dump`` — is strictly host-local: the exporter
binds one host, the event/trace files are per-process and only fold
post-hoc through ``bst telemetry-merge``. A pod run (or a future
multi-host daemon) is therefore blind *while it runs*: no live view of a
remote rank, no pod health verdict, no way to tell which host stalls.
The Spark reference leans on the driver UI for exactly this cluster-wide
live view; in a driverless SPMD world this module builds the fan-in:

- **push client** (:class:`RelayClient`): every non-collector process
  with ``BST_TELEMETRY_RELAY`` set ships periodic metric-registry
  snapshots (the rendered Prometheus text), health heartbeats (process
  stats, stage progress, cache/in-flight gauges, trace state) and a
  warn/error event subset to the collector over one TCP connection. All
  traffic flows through a BOUNDED queue drained by a dedicated relay
  thread: a slow or absent collector fills the queue and further
  messages drop (counted in ``bst_relay_dropped_total``) — the
  producing rank's hot path never blocks on telemetry. The client
  reconnects with backoff after a collector restart.
- **collector** (:class:`RelayCollector`): rank 0 (or any ``bst serve``
  daemon) binds the ``BST_TELEMETRY_RELAY`` address and merges the
  per-rank state into the existing live plane via
  :mod:`observe.httpexport`'s cluster providers: ``/metrics`` gains a
  ``host``/``process_index``-labeled copy of every rank's series (its
  own included), ``/healthz`` becomes a pod verdict (a rank whose
  heartbeat goes silent past ``BST_STALL_TIMEOUT_S`` → 503 naming the
  host, recovering when heartbeats resume), and ``/cluster`` serves the
  per-rank JSON rows behind ``bst top --cluster``. The collector can
  also pull a live flight-recorder snapshot from every connected rank
  (:meth:`RelayCollector.cluster_trace_dump`) and fold them — plus its
  own ring — through the barrier-anchored ``merge_traces`` into ONE
  Perfetto file mid-run (``bst trace-dump --cluster``).

Role resolution (:func:`ensure_started`): with the knob unset the relay
is fully off — zero overhead, byte-identical telemetry. With it set,
process 0 of a multi-process world tries to HOST the address and falls
back to pushing when the bind fails (someone on this host — typically a
``bst serve`` daemon, which always hosts — already owns it); every
other process pushes. The wire is line-delimited JSON over a plain TCP
socket with NO auth — same trust assumption as ``BST_METRICS_HOST``:
pod-internal networks only (README "Live monitoring").
"""

from __future__ import annotations

import contextlib
import json
import os
import queue as _queuemod
import shutil
import socket
import tempfile
import threading
import time

from . import metrics as _metrics
from . import trace as _trace
from .. import config, profiling

SCHEMA = "bst-relay/1"

# event types a push client forwards to the collector (the warn/error
# surface an operator watches a pod for; stage.progress deliberately
# rides the periodic snapshot instead — per-block spam would drown the
# bounded queue)
FORWARDED_EVENTS = frozenset({
    "block.fail", "retry.round", "job.stall", "job.resume",
    "run.start", "run.end", "stage.end", "barrier",
})

# events kept per rank on the collector for /cluster display
_RANK_EVENT_KEEP = 25

_SENT = _metrics.counter("bst_relay_sent_total")
_SENT_BYTES = _metrics.counter("bst_relay_send_bytes_total")
_DROP_QUEUE = _metrics.counter("bst_relay_dropped_total", reason="queue")
_DROP_CONN = _metrics.counter("bst_relay_dropped_total", reason="conn")
_RECONNECTS = _metrics.counter("bst_relay_reconnects_total")
_RANKS_CONNECTED = _metrics.gauge("bst_relay_ranks_connected")


def parse_address(addr: str) -> tuple[str, int]:
    """``host:port`` -> (host, port); the host part may be empty
    (collector: bind all interfaces)."""
    host, sep, port = addr.rpartition(":")
    if not sep:
        raise ValueError(f"BST_TELEMETRY_RELAY wants host:port, got "
                         f"{addr!r}")
    return host, int(port)


def _set_keepalive(sock: socket.socket) -> None:
    """Both relay roles hold long-lived mostly-idle connections whose
    readers treat silence as normal, so a HALF-OPEN peer (host
    power-cut, no FIN/RST) would otherwise look alive indefinitely —
    the client until its send buffer fills, the collector until TCP
    retransmission gives up (~15 min), leaving a phantom connected rank
    that stalls every cluster dump for its full timeout. Keepalive
    probes surface dead peers to the blocked recv in ~25s. Each option
    is guarded on its own — TCP_KEEPALIVE is the Darwin spelling of the
    idle time, and a sandbox denying one setsockopt must neither kill
    the relay thread nor abandon the remaining tuning."""
    with contextlib.suppress(OSError):
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_KEEPALIVE, 1)
    for opt, val in (("TCP_KEEPIDLE", 10), ("TCP_KEEPALIVE", 10),
                     ("TCP_KEEPINTVL", 5), ("TCP_KEEPCNT", 3)):
        o = getattr(socket, opt, None)
        if o is not None:
            with contextlib.suppress(OSError):
                sock.setsockopt(socket.IPPROTO_TCP, o, val)


def _shutdown_close(sock: socket.socket) -> None:
    """shutdown(SHUT_RDWR) then close: a handler thread's makefile
    holds an io-ref, so close() alone defers the real close and leaves
    the connection (and the remote client) fully alive."""
    with contextlib.suppress(OSError):
        sock.shutdown(socket.SHUT_RDWR)
    with contextlib.suppress(OSError):
        sock.close()


def _identity() -> tuple[str, int, int]:
    """(host, process_index, process_count) of THIS process. The
    explicit BST_PROCESS_ID / BST_NUM_PROCESSES launch env wins over the
    live jax world: two independently-launched local workers (no shared
    jax.distributed runtime) would otherwise both claim rank (0, 1) and
    collapse into one collector row."""
    pi = config.get_int("BST_PROCESS_ID")
    pc = config.get_int("BST_NUM_PROCESSES")
    if pi is None or pc is None:
        from . import events as _events

        jpi, jpc = _events.world()
        pi = jpi if pi is None else pi
        pc = jpc if pc is None else pc
    return socket.gethostname(), int(pi), int(pc)


# -- push client -------------------------------------------------------------


class RelayClient:
    """One process's push side: a bounded queue drained by a relay
    thread that owns the TCP connection. ``offer`` (and the event tap
    feeding it) never block — backpressure drops and counts."""

    def __init__(self, address: str, *, host: str | None = None,
                 process_index: int | None = None,
                 process_count: int | None = None,
                 interval_s: float | None = None,
                 queue_max: int | None = None):
        self.address = parse_address(address)
        h, pi, pc = _identity()
        self.host = host if host is not None else h
        self.process_index = (process_index if process_index is not None
                              else pi)
        self.process_count = (process_count if process_count is not None
                              else pc)
        self._interval_arg = interval_s
        self._q: _queuemod.Queue = _queuemod.Queue(
            maxsize=max(8, queue_max
                        if queue_max is not None
                        else config.get_int("BST_RELAY_QUEUE") or 256))
        self._stop = threading.Event()
        self._sock: socket.socket | None = None
        self._sock_lock = threading.Lock()
        self._next_connect = 0.0
        self._backoff = 1.0
        self._connects = 0
        self._thread: threading.Thread | None = None
        self._own_trace = False
        self.connected = threading.Event()   # test/diagnostic surface

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "RelayClient":
        from . import events as _events

        # a relayed rank records its flight recorder always (bounded
        # ring, newest wins) so a cluster trace-dump has something to
        # pull without anyone having passed --trace before the incident
        if not _trace.enabled():
            _trace.configure()
            self._own_trace = True
        from . import progress as _progress

        _progress.set_live_tracking(True)
        _events.add_tap(self._tap)
        # raw daemon thread on purpose: the relay sender is process-lived
        # telemetry infrastructure serving every job — it must not pin
        # the starting job's cancel scope or config overrides
        self._thread = threading.Thread(target=self._run,  # bst-lint: off=thread-spawn
                                        name="bst-relay-client",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        if self._stop.is_set():
            return   # idempotent (atexit + explicit stop)
        from . import events as _events
        from . import progress as _progress

        _events.remove_tap(self._tap)
        _progress.set_live_tracking(False)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
        self._close_sock()
        if self._own_trace and _trace.enabled():
            _trace.reset()

    def _interval(self) -> float:
        if self._interval_arg is not None:
            return float(self._interval_arg)
        return float(config.get_float("BST_RELAY_INTERVAL_S") or 2.0)

    # -- producer side (never blocks) ---------------------------------------

    def offer(self, msg: dict) -> bool:
        """Enqueue one message for the relay thread; full queue drops
        and counts instead of blocking the caller."""
        try:
            self._q.put_nowait(msg)
            return True
        except _queuemod.Full:
            _DROP_QUEUE.inc()
            return False

    def _tap(self, rec: dict) -> None:
        if rec.get("type") in FORWARDED_EVENTS:
            self.offer({"t": "event", "rec": rec})

    # -- relay thread --------------------------------------------------------

    def _run(self) -> None:
        next_snap = 0.0
        while not self._stop.is_set():
            now = time.monotonic()
            # clamp so lowering BST_RELAY_INTERVAL_S live takes effect
            # immediately instead of after one old-length sleep
            next_snap = min(next_snap, now + max(0.2, self._interval()))
            if now >= next_snap:
                self.offer({"t": "snap", "payload": self._snapshot()})
                next_snap = now + max(0.2, self._interval())
            try:
                msg = self._q.get(timeout=max(
                    0.05, min(0.5, next_snap - time.monotonic())))
            except _queuemod.Empty:
                continue
            self._deliver(msg)
        # drain what is already queued, then say goodbye so the
        # collector can tell a finished rank from a dead one
        while True:
            try:
                self._deliver(self._q.get_nowait())
            except _queuemod.Empty:
                break
        self._deliver({"t": "bye"})

    def _snapshot(self) -> dict:
        from . import httpexport as _httpexport
        from . import progress as _progress

        payload: dict = {
            "ts": round(time.time(), 3),
            "process": _httpexport.process_stats(),
            "progress": _progress.latest(),
            "trace": _trace.stats(),
            "dropped": {"queue": int(_DROP_QUEUE.value),
                        "conn": int(_DROP_CONN.value)},
            "inflight": {
                "bytes": _metrics.gauge("bst_inflight_bytes").value,
                "highwater_bytes": _metrics.gauge(
                    "bst_inflight_bytes_highwater").value,
            },
            "prom": _metrics.get_registry().render_prometheus(),
        }
        try:
            from ..io.chunkcache import get_cache

            payload["chunk_cache"] = get_cache().stats()
        except Exception:   # cache layer optional for bare clients
            pass
        try:
            from ..parallel.pairsched import process_util_snapshot

            util = process_util_snapshot()
            if util:
                payload["pair_util"] = util
        except Exception:   # scheduler layer optional for bare clients
            pass
        return payload

    def _deliver(self, msg: dict) -> None:
        if not self._ensure_conn():
            _DROP_CONN.inc()
            return
        data = (json.dumps(msg, default=str) + "\n").encode()
        with profiling.span("relay.send", nbytes=len(data)):
            # read the ref under the lock, send OUTSIDE it: a send that
            # rides its 10s timeout must not stall _close_sock and the
            # reconnect path behind it. A connection swapped mid-send
            # errors out and _close_sock(expected) ignores the stale ref.
            with self._sock_lock:
                sock = self._sock
            if sock is None:
                _DROP_CONN.inc()
                return
            try:
                sock.sendall(data)
            except OSError:
                self._close_sock(sock)
                _DROP_CONN.inc()
                return
        _SENT.inc()
        _SENT_BYTES.inc(len(data))

    def _ensure_conn(self) -> bool:
        if self._sock is not None:
            return True
        now = time.monotonic()
        if now < self._next_connect:
            return False
        try:
            sock = socket.create_connection(self.address, timeout=5.0)
        except OSError:
            self._next_connect = now + self._backoff
            self._backoff = min(self._backoff * 2, 5.0)
            return False
        # sends must eventually error on a dead-but-open collector so
        # the client falls back to dropping instead of wedging forever
        sock.settimeout(10.0)
        _set_keepalive(sock)
        hello = (json.dumps({
            "t": "hello", "schema": SCHEMA, "host": self.host,
            "process_index": self.process_index,
            "process_count": self.process_count, "pid": os.getpid(),
        }) + "\n").encode()
        try:
            sock.sendall(hello)
        except OSError:
            _shutdown_close(sock)
            self._next_connect = now + self._backoff
            return False
        with self._sock_lock:
            self._sock = sock
        self._backoff = 1.0
        self._connects += 1
        if self._connects > 1:
            _RECONNECTS.inc()
        _trace.instant("relay.connect", item=f"{self.address[0]}:"
                                             f"{self.address[1]}")
        self.connected.set()
        # raw daemon thread on purpose: connection-lived reader, same
        # no-job-context rationale as the sender thread
        threading.Thread(target=self._reader, args=(sock,),  # bst-lint: off=thread-spawn
                         name="bst-relay-reader", daemon=True).start()
        return True

    def _close_sock(self, expected: socket.socket | None = None) -> None:
        """Drop the current connection; with ``expected`` given, only if
        it is still the current one — a check-then-close outside the
        lock could otherwise tear down a connection a concurrent
        reconnect just established (one spurious reconnect cycle: the
        very flap the idle-tolerant reader exists to prevent)."""
        with self._sock_lock:
            sock = self._sock
            if expected is not None and sock is not expected:
                return
            self._sock = None
        self.connected.clear()
        if sock is not None:
            _shutdown_close(sock)   # also wakes a reader blocked in recv

    def _reader(self, sock: socket.socket) -> None:
        """Collector->client requests (cluster trace pulls) arrive on
        the same connection; responses go back through the bounded
        queue so the relay thread stays the only socket writer. The
        socket timeout exists for the WRITER (a wedged sendall must
        eventually error) — the collector is silent except for trace
        pulls, so a read timing out just means idle: keep listening.
        Only EOF or a real socket error tears the connection down."""
        buf = b""
        try:
            # deliberately NOT gated on _stop: stop() drains the queue
            # and sends the goodbye AFTER setting it — a reader that
            # exited on the flag mid-drain would close the socket under
            # that final sendall. stop()'s own _close_sock (after the
            # relay thread joins) wakes the blocked recv to exit.
            while sock is self._sock:
                try:
                    chunk = sock.recv(65536)
                except TimeoutError:
                    continue   # idle connection — normal, not a failure
                if not chunk:
                    break   # EOF: the collector closed on us
                buf += chunk
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    try:
                        msg = json.loads(line)
                    except ValueError:
                        continue
                    if not isinstance(msg, dict):
                        continue
                    if msg.get("t") == "trace-dump":
                        self.offer({"t": "trace", "req": msg.get("req"),
                                    "doc": self._trace_doc()})
        except OSError:
            pass
        finally:
            self._close_sock(sock)

    def _trace_doc(self) -> dict | None:
        if not _trace.enabled():
            return None
        return _trace.export(self.process_index, self.process_count)


# -- collector ---------------------------------------------------------------


def _merge_expositions(texts: list) -> str:
    """Merge ``(host, process_index, prometheus_text)`` expositions into
    ONE valid exposition: every metric family appears exactly once, as a
    contiguous group under a single ``# TYPE`` comment holding the
    series of every source — duplicate or split families are invalid
    per the Prometheus text-format spec (promtool/OpenMetrics reject
    them even though the scraper tolerates them). ``host=None`` marks
    the local render (series pass through unlabeled); every other
    source gets ``host``/``process_index`` injected into each series."""
    fams: dict[str, dict] = {}   # insertion-ordered: first sight wins

    def fam(name: str) -> dict:
        f = fams.get(name)
        if f is None:
            f = fams[name] = {"type": None, "lines": []}
        return f

    for host, pi, text in texts:
        inject = (None if host is None
                  else f'host="{host}",process_index="{pi}"')
        for line in text.splitlines():
            if not line:
                continue
            if line.startswith("# TYPE "):
                parts = line.split()
                if len(parts) >= 4 and fam(parts[2])["type"] is None:
                    fams[parts[2]]["type"] = parts[3]
                continue
            if line.startswith("#"):
                continue
            name_part, _, value = line.rpartition(" ")
            if not name_part:
                continue
            if "{" in name_part:
                name, rest = name_part.split("{", 1)
                series = (line if inject is None
                          else f"{name}{{{inject},{rest} {value}")
            else:
                name = name_part
                series = (line if inject is None
                          else f"{name}{{{inject}}} {value}")
            # histogram sample suffixes group under the parent family
            # (whose TYPE line precedes its series in every render)
            base = name
            for suf in ("_bucket", "_sum", "_count"):
                if name.endswith(suf):
                    parent = fams.get(name[:-len(suf)])
                    if parent is not None and parent["type"] in (
                            "histogram", "summary"):
                        base = name[:-len(suf)]
                    break
            fam(base)["lines"].append(series)
    out: list[str] = []
    for name, f in fams.items():
        if not f["lines"]:
            continue
        if f["type"] is not None:
            out.append(f"# TYPE {name} {f['type']}")
        out.extend(f["lines"])
    return "\n".join(out) + "\n"


class RelayCollector:
    """The fan-in side: accepts push clients, keeps per-rank state, and
    plugs the aggregate into the live HTTP plane (cluster providers)."""

    def __init__(self, host: str, port: int):
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host, int(port)))
        srv.listen(32)
        srv.settimeout(1.0)
        self._srv = srv
        self.host = host or "0.0.0.0"
        self.port = srv.getsockname()[1]
        self.started_at = time.time()
        self._lock = threading.Lock()
        self._ranks: dict[tuple, dict] = {}
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._recv = {t: _metrics.counter("bst_relay_recv_total", type=t)
                      for t in ("hello", "snap", "event", "trace", "bye")}
        self._dump_lock = threading.Lock()
        self._dump_seq = 0
        self._dumps: dict[int, dict] = {}

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "RelayCollector":
        from . import httpexport as _httpexport

        # raw daemon thread on purpose: the collector is a standalone
        # process-lived service, no job context exists to carry
        th = threading.Thread(target=self._accept_loop,  # bst-lint: off=thread-spawn
                              name="bst-relay-accept", daemon=True)
        th.start()
        self._threads.append(th)
        _httpexport.set_cluster_providers(health=self.pod_health,
                                          cluster=self.cluster_status,
                                          metrics_render=self.metrics_render)
        return self

    def stop(self) -> None:
        from . import httpexport as _httpexport

        _httpexport.clear_cluster_providers()
        self._stop.set()
        with contextlib.suppress(OSError):
            self._srv.close()
        with self._lock:
            conns = [r.get("conn") for r in self._ranks.values()]
        for c in conns:
            if c is not None:
                _shutdown_close(c)
        for th in self._threads:
            if th is not threading.current_thread():
                th.join(timeout=5)
        _RANKS_CONNECTED.set(0)

    # -- accept / per-connection readers ------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            # accepted sockets don't inherit the listener's options and
            # the handler blocks in a plain read — without keepalive a
            # no-FIN dead worker stays a phantom connected rank
            _set_keepalive(conn)
            # raw daemon thread on purpose: per-rank collector handler,
            # no job context exists in the collector process
            th = threading.Thread(target=self._handle, args=(conn,),  # bst-lint: off=thread-spawn
                                  name="bst-relay-conn", daemon=True)
            th.start()
            # prune finished handlers so a long-lived daemon with flaky
            # reconnecting clients never accumulates dead Thread objects
            self._threads = [t for t in self._threads
                             if t.is_alive()] + [th]

    def _update_connected_gauge(self) -> None:
        with self._lock:
            n = sum(1 for r in self._ranks.values() if r["connected"])
        _RANKS_CONNECTED.set(n)

    def _handle(self, conn: socket.socket) -> None:
        rank: dict | None = None
        wlock = threading.Lock()
        try:
            f = conn.makefile("rb")
            for line in f:
                try:
                    msg = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(msg, dict):
                    continue   # valid JSON, wrong shape: a stray peer
                t = msg.get("t")
                c = self._recv.get(t)
                if c is not None:
                    c.inc()
                if t == "hello":
                    rank = self._register(msg, conn, wlock)
                elif rank is None:
                    continue
                elif t == "snap":
                    with self._lock:
                        rank["last_seen"] = time.time()
                        rank["snap_at"] = rank["last_seen"]
                        rank["snap"] = msg.get("payload") or {}
                        rank["done"] = False
                elif t == "event":
                    with self._lock:
                        rank["last_seen"] = time.time()
                        rank["events"].append(msg.get("rec") or {})
                        del rank["events"][:-_RANK_EVENT_KEEP]
                elif t == "trace":
                    self._dump_response(msg)
                elif t == "bye":
                    with self._lock:
                        rank["done"] = True
                    break
        except OSError:
            pass
        finally:
            if rank is not None:
                with self._lock:
                    if rank.get("conn") is conn:
                        rank["connected"] = False
                        rank["conn"] = None
                self._update_connected_gauge()
            _shutdown_close(conn)

    def _register(self, msg: dict, conn, wlock) -> dict:
        key = (str(msg.get("host")), int(msg.get("process_index") or 0),
               int(msg.get("process_count") or 1))
        with self._lock:
            rank = self._ranks.get(key)
            if rank is None:
                rank = {"host": key[0], "process_index": key[1],
                        "process_count": key[2], "events": []}
                self._ranks[key] = rank
            old = rank.get("conn")
            rank.update(conn=conn, wlock=wlock, pid=msg.get("pid"),
                        connected=True, done=False,
                        last_seen=time.time())
        if old is not None and old is not conn:
            _shutdown_close(old)   # wake its handler too
        self._update_connected_gauge()
        return rank

    # -- aggregate views ------------------------------------------------------

    def _rows(self) -> list[dict]:
        now = time.time()
        timeout_s = config.get_int("BST_STALL_TIMEOUT_S") or 0
        with self._lock:
            ranks = [dict(r) for r in self._ranks.values()]
        rows = []
        for r in sorted(ranks, key=lambda r: (r["host"],
                                              r["process_index"])):
            age = round(now - r["last_seen"], 1)
            snap = r.get("snap") or {}
            rows.append({
                "host": r["host"],
                "process_index": r["process_index"],
                "process_count": r["process_count"],
                "pid": r.get("pid"),
                "connected": r["connected"],
                "done": r.get("done", False),
                "age_s": age,
                # the pod verdict: silent past the stall timeout, and
                # neither finished nor merely between reconnects with a
                # fresh heartbeat
                "stalled": (timeout_s > 0 and not r.get("done")
                            and age > timeout_s),
                "progress": snap.get("progress"),
                "process": snap.get("process"),
                "chunk_cache": snap.get("chunk_cache"),
                "pair_util": snap.get("pair_util"),
                "inflight": snap.get("inflight"),
                "trace": snap.get("trace"),
                "dropped": snap.get("dropped"),
                "events": [e.get("type") for e in r.get("events", [])][-5:],
            })
        return rows

    def cluster_status(self) -> dict:
        rows = self._rows()
        return {
            "collector": {
                "address": f"{self.host}:{self.port}",
                "uptime_s": round(time.time() - self.started_at, 1),
                "stall_timeout_s": config.get_int("BST_STALL_TIMEOUT_S")
                or 0,
                "ranks": len(rows),
                "connected": sum(1 for r in rows if r["connected"]),
            },
            "ranks": rows,
        }

    def pod_health(self, ok: bool, payload: dict) -> tuple[bool, dict]:
        """Merge the pod verdict into a local /healthz result: any rank
        silent past BST_STALL_TIMEOUT_S makes the pod unhealthy, naming
        the host; a finished (bye) rank never does."""
        rows = self._rows()
        silent = [{"host": r["host"],
                   "process_index": r["process_index"],
                   "age_s": r["age_s"]}
                  for r in rows if r["stalled"]]
        payload = dict(payload)
        payload["cluster"] = {
            "ranks": len(rows),
            "connected": sum(1 for r in rows if r["connected"]),
            "silent_ranks": silent,
        }
        if silent:
            ok = False
        payload["ok"] = ok
        return ok, payload

    def metrics_render(self, local_text: str) -> str:
        """The collector's /metrics body: the local registry render
        merged with a host/process_index-labeled copy of every rank's
        series — the collector's own included (unless a connected rank
        already claims its identity). Families merge contiguously under
        one TYPE comment each, keeping the exposition valid (see
        :func:`_merge_expositions`); ranks colliding on (host,
        process_index) — independently-launched workers with mismatched
        process_count claims occupy distinct _ranks keys — dedupe to
        the freshest SNAPSHOT (snap_at, not last_seen: heartbeats and
        events also touch last_seen and must not let a stale snapshot
        win), since duplicate identical-label samples are as invalid as
        split families."""
        with self._lock:
            newest: dict = {}
            for r in self._ranks.values():
                prom = (r.get("snap") or {}).get("prom")
                if not prom:
                    continue
                k = (r["host"], r["process_index"])
                snap_at = r.get("snap_at", 0)
                if k not in newest or snap_at > newest[k][0]:
                    newest[k] = (snap_at, prom)
        host, pi, _pc = _identity()
        texts: list = [(None, 0, local_text)]
        if (host, pi) not in newest:
            texts.append((host, pi, local_text))
        texts += [(h, p, prom)
                  for (h, p), (_seen, prom) in sorted(newest.items())]
        return ("# relay-aggregated cluster render (one labeled copy "
                "per rank, families merged)\n"
                + _merge_expositions(texts))

    # -- cluster flight-recorder pull ----------------------------------------

    def _dump_response(self, msg: dict) -> None:
        req = msg.get("req")
        with self._dump_lock:
            pend = self._dumps.get(req)
            if pend is None:
                return
            pend["results"].append(msg.get("doc"))
            if len(pend["results"]) >= pend["want"]:
                pend["event"].set()

    def cluster_trace_dump(self, out: str,
                           timeout_s: float = 15.0) -> dict:
        """Pull the live flight-recorder ring of every connected rank,
        fold them (plus the local ring) through the barrier-anchored
        ``merge_traces`` into ONE Perfetto file at ``out`` — mid-run,
        nothing pauses. Ranks that fail to answer within ``timeout_s``
        are reported missing, never fatal."""
        with profiling.span("relay.dump"):
            have_local = _trace.enabled()
            lhost, lpi, lpc = _identity()
            with self._dump_lock:
                self._dump_seq += 1
                req = self._dump_seq
            with self._lock:
                # the hosting rank's self-client would hand back the
                # very ring the local export below already contributes —
                # pulling both would duplicate every local event in the
                # merged file. Identify the self-CONNECTION by pid (an
                # unrelated same-host worker may legitimately claim the
                # same process_index — see _identity's collision note)
                targets = [(k, r["conn"], r["wlock"])
                           for k, r in self._ranks.items()
                           if r["connected"] and r.get("conn") is not None
                           and not (have_local and k[0] == lhost
                                    and r.get("pid") == os.getpid())]
            asked = []
            line = (json.dumps({"t": "trace-dump", "req": req})
                    + "\n").encode()
            # want starts unreachable so a fast rank answering before
            # every request went out cannot complete the wait early
            pend = {"results": [], "want": float("inf"),
                    "event": threading.Event()}
            with self._dump_lock:
                self._dumps[req] = pend
            for key, conn, wlock in targets:
                try:
                    # per-connection writer lock held across the send on
                    # purpose: it serializes dump requests with the
                    # handler's replies on the SAME socket, nothing else
                    # contends for it, and the socket's own timeout
                    # bounds the stall
                    with wlock:
                        conn.sendall(line)  # bst-lint: off=blocking-under-lock — single-writer serialization, see above
                    asked.append(key)
                except OSError:
                    continue
            with self._dump_lock:
                pend["want"] = len(asked)
                if len(pend["results"]) >= pend["want"]:
                    pend["event"].set()
            if asked:
                pend["event"].wait(timeout_s)
            with self._dump_lock:
                self._dumps.pop(req, None)
            docs = [d for d in pend["results"] if d]
            tmpdir = tempfile.mkdtemp(prefix="bst-relay-dump-")
            try:
                if have_local:
                    docs = [_trace.export(lpi, lpc), *docs]
                written = 0
                for doc in docs:
                    meta = doc.get("bst") or {}
                    pi = int(meta.get("process_index") or 0)
                    pc = int(meta.get("process_count") or 1)
                    path = os.path.join(tmpdir, _trace.trace_name(pi, pc))
                    n = 0
                    while os.path.exists(path):   # identity collisions
                        n += 1
                        path = os.path.join(
                            tmpdir, f"trace-{pi:05d}-of-{pc:05d}-{n}.json")
                    with open(path, "w", encoding="utf-8") as f:
                        json.dump(doc, f, default=str)
                    written += 1
                merged = _trace.merge_traces(tmpdir,
                                             output=os.path.abspath(out))
            finally:
                shutil.rmtree(tmpdir, ignore_errors=True)
            if merged is None:
                raise RuntimeError(
                    "no flight-recorder rings to dump: neither this "
                    "process nor any connected rank is recording")
            return {"path": str(merged), "ranks": len(pend["results"]),
                    "asked": len(asked),
                    "missing": max(0, len(asked)
                                   - len(pend["results"])),
                    "local_ring": have_local,
                    "traces": written, **merged.bst}


# -- module singletons / role resolution -------------------------------------

_rlock = threading.Lock()
_CLIENT: RelayClient | None = None
_COLLECTOR: RelayCollector | None = None


def client() -> RelayClient | None:
    return _CLIENT


def collector() -> RelayCollector | None:
    return _COLLECTOR


def serve(address: str) -> RelayCollector:
    """Host the collector at ``address`` (singleton; raises OSError when
    the bind fails — callers fall back to pushing or log and continue)."""
    global _COLLECTOR
    host, port = parse_address(address)
    with _rlock:
        if _COLLECTOR is not None:
            return _COLLECTOR
        _COLLECTOR = RelayCollector(host, port).start()
        return _COLLECTOR


def connect(address: str) -> RelayClient:
    """Start the push client toward ``address`` (singleton). Returns
    immediately; the relay thread connects (and reconnects) on its own.
    A process-exit hook sends the ``bye`` goodbye so a finished rank
    never reads as a silent (stalled) one on the collector."""
    global _CLIENT
    import atexit

    with _rlock:
        if _CLIENT is not None:
            return _CLIENT
        _CLIENT = RelayClient(address).start()
        atexit.register(stop)
        return _CLIENT


def ensure_started():
    """Knob-driven idempotent bring-up (called beside the multi-host
    ``initialize`` and by workload tools): no-op unless
    ``BST_TELEMETRY_RELAY`` is set. Process 0 of a multi-process world
    hosts, falling back to pushing when the address is already owned
    (a daemon on this host); everyone else pushes."""
    addr = config.get_str("BST_TELEMETRY_RELAY")
    if not addr:
        return None
    if _COLLECTOR is not None:
        return _COLLECTOR
    if _CLIENT is not None:
        return _CLIENT
    _h, pi, pc = _identity()
    if pi == 0 and pc > 1:
        try:
            col = serve(addr)
        except OSError:
            pass   # someone on this host already collects — push instead
        else:
            # the hosting rank is a pod member too: push into our own
            # collector so /cluster and the pod health verdict cover
            # rank 0, not only ranks 1..N-1 — via the BOUND interface
            # (a collector on a routable address has nothing listening
            # on loopback; wildcard binds map back to 127.0.0.1)
            from . import httpexport as _httpexport

            connect(f"{_httpexport.display_host(col.host)}:{col.port}")
            return col
    return connect(addr)


def stop() -> None:
    """Stop whichever role this process runs and drop the singletons."""
    global _CLIENT, _COLLECTOR
    with _rlock:
        cl, _CLIENT = _CLIENT, None
        co, _COLLECTOR = _COLLECTOR, None
    if cl is not None:
        cl.stop()
    if co is not None:
        co.stop()


def stop_collector() -> None:
    """Stop only the collector (the serve daemon's drain path — a push
    client owned by the surrounding process lives on)."""
    global _COLLECTOR
    with _rlock:
        co, _COLLECTOR = _COLLECTOR, None
    if co is not None:
        co.stop()
