"""Multi-host execution world (ISSUE 18): global solve mesh, default-on
multihost pair split, and cross-host block streaming.

Acceptance contract:

- the psum-sharded relax under a global links axis is BITWISE equal to
  the local/single-device solve (any world shape); the intensity CG is
  bitwise equal across the ranks of one world and tolerance-equal
  (1e-6) across world shapes — the gloo cross-process allreduce orders
  its reduction differently from XLA's local all-reduce;
- the cost-weighted process partition covers every item exactly once,
  LPT-balances heavy tails, and degenerates cleanly (tail smaller than
  the world, world size 1);
- the rank-addressed block exchange fetches a remote-owned chunk ONCE
  over TCP into the decoded-chunk LRU (zero container re-reads), the
  chunk gate releases on remote producers-done, and a dead peer fails
  exactly the waiting read with ``ExchangeError``;
- :class:`TestMultiprocessWorld` runs all three tentpole pieces through
  a REAL 2-process jax.distributed CPU world (subprocess workers, gloo
  collectives, TCP exchange) and checks bitwise fusion parity against a
  single-process run of the same streamed pipeline.
"""

import hashlib
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

from bigstitcher_spark_tpu import config
from bigstitcher_spark_tpu.dag import PipelineSpec, SpecError, example_spec
from bigstitcher_spark_tpu.dag import exchange, stream
from bigstitcher_spark_tpu.dag.executor import _Executor, run_pipeline
from bigstitcher_spark_tpu.io import chunkcache
from bigstitcher_spark_tpu.io.chunkstore import ChunkStore, StorageFormat
from bigstitcher_spark_tpu.io.spimdata import ViewId
from bigstitcher_spark_tpu.models import solver as S
from bigstitcher_spark_tpu.observe import metrics
from bigstitcher_spark_tpu.ops import models as M
from bigstitcher_spark_tpu.ops import solve as OS
from bigstitcher_spark_tpu.ops.intensity import (
    match_stats,
    solve_intensity_coefficients,
)
from bigstitcher_spark_tpu.parallel.distributed import (
    partition_indices_weighted,
    partition_items_weighted,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))


# -- shared problem builders (imported by the subprocess workers too) ---------


def _mh_graph(n=(4, 3), jitter=3.0, seed=0, tile=(100, 100, 50), step=80.0):
    """Synthetic tile-grid link graph (the test_solve_device shape):
    truth-consistent 8-corner links with jittered nominal positions."""
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


def _mh_cg_system(n_coeffs=48, n_matches=150, seed=1):
    """Synthetic intensity match system for the coefficient CG."""
    rng = np.random.default_rng(seed)
    matches = []
    for _ in range(n_matches):
        ca, cb = rng.integers(0, n_coeffs, 2)
        if ca == cb:
            continue
        x = rng.uniform(100, 1000, 50)
        a, b = rng.uniform(0.8, 1.2), rng.uniform(-20, 20)
        y = a * x + b + rng.normal(0, 5, 50)
        matches.append((int(ca), int(cb), *match_stats(x / 500, y / 500)))
    return n_coeffs, matches


def _solve_sig(res) -> str:
    """Bitwise signature of a SolveResult: error history + corrections in
    a deterministic key order."""
    h = hashlib.sha256()
    h.update(np.asarray(res.history).tobytes())
    for k in sorted(res.corrections, key=repr):
        h.update(np.asarray(res.corrections[k]).tobytes())
    return h.hexdigest()


def _mh_pipeline_spec(proj: str) -> dict:
    """The streamed resave -> create -> fuse spec the multihost world
    runs SPMD: single-level resave (a pyramid would read peer-written s0
    chunks through the un-gated producer path), create pinned to rank 0
    (metadata-only; racing it corrupts the fusion container)."""
    xml = os.path.join(proj, "dataset.xml")
    rexml = os.path.join(proj, "re.xml")
    return {
        "name": "mh-pipe",
        "datasets": {
            "resaved": {"path": os.path.join(proj, "resaved.n5"),
                        "ephemeral": True},
            "fused": {"path": os.path.join(proj, "fused.n5")},
        },
        "stages": [
            {"id": "resave", "tool": "resave",
             "args": ["-x", xml, "-xo", rexml, "-o", "@resaved", "--N5",
                      "--blockSize", "32,32,16", "-ds", "1,1,1"],
             "writes": ["resaved"]},
            {"id": "create", "tool": "create-fusion-container",
             "args": ["-x", rexml, "-o", "@fused", "-s", "N5",
                      "-d", "UINT16", "--minIntensity", "0",
                      "--maxIntensity", "65535",
                      "--blockSize", "32,32,16"],
             "after": ["resave"], "ranks": [0]},
            {"id": "fuse", "tool": "affine-fusion", "args": ["-o", "@fused"],
             "after": ["create"], "reads": ["resaved"],
             "writes": ["fused"]},
        ],
    }


def _mk_project(root: str) -> str:
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    return make_synthetic_project(root, n_tiles=(2, 1, 1),
                                  tile_size=(64, 64, 32), overlap=16,
                                  jitter=1.0, n_beads_per_tile=20,
                                  seed=7).xml_path


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _fused_sha(proj: str) -> str:
    ds = ChunkStore.open(os.path.join(proj, "fused.n5")) \
        .open_dataset("ch0tp0/s0")
    arr = ds.read((0, 0, 0), ds.shape)
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()


# -- cost-weighted process partition ------------------------------------------


class TestWeightedPartition:
    def test_covers_every_item_exactly_once(self):
        costs = [((i * 13) % 7) + 0.5 for i in range(23)]
        world = 3
        seen = []
        for pi in range(world):
            seen += partition_indices_weighted(costs, pi, world)
        assert sorted(seen) == list(range(len(costs)))

    def test_lpt_balances_heavy_tail(self):
        # one huge item + many small ones: round-robin would pair the
        # huge item with half the small ones on one rank; LPT gives the
        # huge item its own bin
        costs = [100.0] + [1.0] * 10
        a = partition_indices_weighted(costs, 0, 2)
        b = partition_indices_weighted(costs, 1, 2)
        loads = {0: sum(costs[i] for i in a), 1: sum(costs[i] for i in b)}
        heavy = 0 if 0 in a else 1
        assert loads[1 - heavy] == 10.0       # all small items together
        assert [i for i in (a if heavy == 0 else b)] == [0]

    def test_items_variant_preserves_order_and_alignment(self):
        items = [f"it{i}" for i in range(9)]
        costs = [float((i * 5) % 4 + 1) for i in range(9)]
        got = partition_items_weighted(items, costs, 1, 2)
        idx = partition_indices_weighted(costs, 1, 2)
        assert got == [items[i] for i in idx]
        assert idx == sorted(idx)

    def test_tail_smaller_than_world(self):
        # 2 items across a 4-process world: two ranks get one item each,
        # the others get an empty (not erroring) slice
        costs = [3.0, 1.0]
        slices = [partition_indices_weighted(costs, pi, 4)
                  for pi in range(4)]
        assert sorted(i for s in slices for i in s) == [0, 1]
        assert sum(1 for s in slices if not s) == 2

    def test_world_one_is_identity(self):
        assert partition_indices_weighted([5.0, 1.0], 0, 1) == [0, 1]

    def test_length_mismatch_raises(self):
        with pytest.raises(ValueError, match="length mismatch"):
            partition_items_weighted([1, 2, 3], [1.0], 0, 2)

    def test_out_of_range_rank_raises(self):
        with pytest.raises(ValueError, match="outside world"):
            partition_indices_weighted([1.0], 5, 2)


# -- global solve mesh layout -------------------------------------------------


class TestSolveLayout:
    def test_knob_forces_global_layout(self):
        with config.overrides({"BST_SOLVE_GLOBAL": "1",
                               "BST_SOLVE_SHARD": 1}):
            assert OS.global_enabled()
            n, g = OS.solve_layout(64)
            assert (n, g) == (8, True)
            ndev, nproc = OS.global_axis_span(n, g)
            assert ndev == 8 and nproc == 1   # single-process pytest world
        with config.overrides({"BST_SOLVE_GLOBAL": "0",
                               "BST_SOLVE_SHARD": 1}):
            assert not OS.global_enabled()
            n, g = OS.solve_layout(64)
            assert (n, g) == (8, False)

    def test_auto_follows_world(self):
        # pytest runs a 1-process world: auto must pin to local devices
        with config.overrides({"BST_SOLVE_GLOBAL": "auto"}):
            assert not OS.global_enabled()

    def test_global_relax_bitwise_equals_local(self):
        tiles, links = _mh_graph()
        fixed = {tiles[0]}
        params = S.SolverParams(model=M.TRANSLATION, backend="device")
        with config.overrides({"BST_SOLVE_SHARD": 1,
                               "BST_SOLVE_GLOBAL": "0"}):
            local = S.relax(links, tiles, fixed, params)
        with config.overrides({"BST_SOLVE_SHARD": 1,
                               "BST_SOLVE_GLOBAL": "1"}):
            glob = S.relax(links, tiles, fixed, params)
        assert local.iterations == glob.iterations
        assert _solve_sig(local) == _solve_sig(glob)

    def test_global_cg_matches_local_to_tolerance(self):
        C, matches = _mh_cg_system()
        with config.overrides({"BST_SOLVE_SHARD": 1,
                               "BST_SOLVE_GLOBAL": "0"}):
            local = solve_intensity_coefficients(C, matches, 0.1,
                                                 backend="device")
        with config.overrides({"BST_SOLVE_SHARD": 1,
                               "BST_SOLVE_GLOBAL": "1"}):
            glob = solve_intensity_coefficients(C, matches, 0.1,
                                                backend="device")
        np.testing.assert_allclose(np.asarray(glob), np.asarray(local),
                                   rtol=0, atol=1e-6)


# -- rank pinning -------------------------------------------------------------


class TestRankPinning:
    def _spec(self, ranks):
        d = _mh_pipeline_spec("/tmp/x")
        d["stages"][1]["ranks"] = ranks
        return d

    def test_spec_parses_and_validates_ranks(self):
        spec = PipelineSpec.from_dict(self._spec([0, 1]))
        assert {s.id: s.ranks for s in spec.stages}["create"] == [0, 1]
        with pytest.raises(SpecError, match="non-negative"):
            PipelineSpec.from_dict(self._spec([-1]))

    def test_example_spec_pins_create_to_rank_zero(self):
        d = example_spec("/tmp/does-not-matter.xml")
        create = {s["id"]: s for s in d["stages"]}["create"]
        assert create["ranks"] == [0]
        PipelineSpec.from_dict(d)   # still validates

    def test_owner_resolution(self):
        spec = PipelineSpec.from_dict(self._spec([0]))
        run = lambda ex: ex.runs["create"]  # noqa: E731
        # single-process worlds ignore pinning entirely
        ex1 = _Executor(spec, "r", rank=0, world=1)
        assert ex1._owners(run(ex1)) is None
        # the owner rank runs the tool itself
        ex0 = _Executor(spec, "r", rank=0, world=2)
        assert ex0._owners(run(ex0)) is None
        # a non-owner adopts the owners' outcome
        exn = _Executor(spec, "r", rank=1, world=2)
        assert exn._owners(run(exn)) == {0}
        # ranks entirely outside the world: every rank runs it
        spec2 = PipelineSpec.from_dict(self._spec([7]))
        exo = _Executor(spec2, "r", rank=1, world=2)
        assert exo._owners(exo.runs["create"]) is None

    def test_wait_remote_done_outcomes(self):
        reg = stream.StreamRegistry()
        reg.remote_done("st", 0, ok=True)
        assert reg.wait_remote_done("st", {0}) is True
        reg.remote_done("bad", 0, ok=False)
        assert reg.wait_remote_done("bad", {0}) is False
        reg.remote_rank_dead(2)
        assert reg.wait_remote_done("never", {2}) is False

    def test_wait_remote_done_blocks_until_broadcast(self):
        reg = stream.StreamRegistry()
        got = {}

        def waiter():
            got["ok"] = reg.wait_remote_done("late", {0, 1})

        th = threading.Thread(target=waiter)
        th.start()
        time.sleep(0.3)
        assert th.is_alive()
        reg.remote_done("late", 0)
        time.sleep(0.3)
        assert th.is_alive()          # still one owner outstanding
        reg.remote_done("late", 1)
        th.join(10)
        assert not th.is_alive() and got["ok"] is True


# -- exchange protocol (in-process two-rank world) ----------------------------


class TestExchangeProtocol:
    def test_parse_addresses(self):
        assert exchange.parse_addresses("a:1, b:2 ,127.0.0.1:3") == \
            [("a", 1), ("b", 2), ("127.0.0.1", 3)]
        assert exchange.parse_addresses(":4") == [("127.0.0.1", 4)]
        with pytest.raises(ValueError, match="host:port"):
            exchange.parse_addresses("nope")

    def test_ensure_started_none_when_unconfigured(self, monkeypatch):
        monkeypatch.delenv("BST_DAG_EXCHANGE_ADDR", raising=False)
        assert exchange.ensure_started() is None
        # configured but single-process world: still nothing to exchange
        monkeypatch.setenv("BST_DAG_EXCHANGE_ADDR", "127.0.0.1:1,127.0.0.1:2")
        assert exchange.ensure_started() is None

    def test_rank_outside_address_list_raises(self):
        with pytest.raises(ValueError, match="outside"):
            exchange.Exchange(3, [("127.0.0.1", _free_port())],
                              registry=stream.StreamRegistry())

    def test_stop_interrupts_inflight_fetch(self):
        """Teardown regression (found by `bst lint` blocking-under-lock):
        _close_fetch used to take _fetch_lock, which an in-flight fetch
        holds for up to the 30s round-trip timeout — a peer dying
        mid-fetch wedged stop() for the full timeout. Teardown now shuts
        the socket down under the separate ref lock, so the blocked
        reader unblocks with EOF and stop() returns promptly."""
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", 0))
        srv.listen(4)
        conns = []

        def silent_server():
            # accept the fetch connection, then never reply: the fetch
            # round trip stays blocked in readline until interrupted
            while True:
                try:
                    c, _ = srv.accept()
                except OSError:
                    return
                conns.append(c)

        threading.Thread(target=silent_server, daemon=True).start()
        peer = exchange._Peer(1, srv.getsockname(), 0, queue_max=8)
        errs = []
        fetch_done = threading.Event()

        def do_fetch():
            try:
                peer.fetch("root", "s0", (0, 0, 0))
            except exchange.ExchangeError as e:
                errs.append(e)
            fetch_done.set()

        threading.Thread(target=do_fetch, daemon=True).start()
        deadline = time.monotonic() + 10
        while not conns:
            assert time.monotonic() < deadline, "fetch never connected"
            time.sleep(0.02)
        time.sleep(0.2)    # let the fetch enter its blocked readline
        t0 = time.monotonic()
        peer.stop()
        stop_s = time.monotonic() - t0
        # well under the 30s fetch timeout the old teardown waited out
        assert stop_s < 10.0, f"stop() wedged for {stop_s:.1f}s"
        assert fetch_done.wait(10.0), "interrupted fetch never returned"
        assert errs, "fetch must raise ExchangeError after teardown"
        srv.close()
        for c in conns:
            c.close()

    def test_two_rank_streaming_world(self, tmp_path):
        """The full exchange contract in one simulated two-rank world
        (two private registries + two TCP endpoints in one process):
        cover broadcast, fetch-once into the chunk LRU with zero
        container re-reads, producers-done release, dead-peer failure."""
        addrs = [("127.0.0.1", _free_port()), ("127.0.0.1", _free_port())]
        regA, regB = stream.StreamRegistry(), stream.StreamRegistry()
        xa = exchange.Exchange(0, addrs, regA)
        xb = exchange.Exchange(1, addrs, regB)
        regA.set_exchange(xa)
        regB.set_exchange(xb)
        edgeA = None
        try:
            store = ChunkStore.create(str(tmp_path / "edge.n5"),
                                      StorageFormat.N5)
            dsB = store.create_dataset("s0", (64, 32, 16), (16, 16, 16),
                                       "uint16")
            prodB = stream.StageToken("prod", "r")
            consB = stream.StageToken("cons", "r")
            edgeB = stream.EdgeState("e", store.root, {prodB}, {consB})
            regB.register([edgeB])
            data = np.arange(64 * 32 * 16,
                             dtype=np.uint16).reshape(64, 32, 16)
            # rank 1 produces only the first two x-chunk rows: positions
            # (3, y, z) stay uncovered so the gate phases below have
            # something to wait on
            with stream.stage_scope(prodB):
                dsB.write(data[:32], (0, 0, 0))
            # simulate process isolation: "rank 0" never decoded these
            chunkcache.get_cache().clear()

            prodA = stream.StageToken("prod", "r")
            consA = stream.StageToken("cons", "r")
            dsA = ChunkStore.open(store.root).open_dataset("s0")
            edgeA = stream.EdgeState("e", store.root, {prodA}, {consA})
            regA.register([edgeA])

            def covers():
                with regA._lock:
                    return sum(len(v)
                               for v in regA._remote_cov.values()) >= 4
            deadline = time.monotonic() + 20
            while not covers():
                assert time.monotonic() < deadline, "covers never arrived"
                time.sleep(0.05)

            fetched0 = metrics.counter("bst_dag_xhost_bytes_total").value
            with stream.stage_scope(consA):
                out = dsA.read((0, 0, 0), (32, 32, 16))
            np.testing.assert_array_equal(out, data[:32])
            db = metrics.counter("bst_dag_xhost_bytes_total").value - fetched0
            assert db > 0 and edgeA.bytes_xhost > 0
            assert edgeA.bytes_reread == 0

            # fetch-once: the same box again moves zero new xhost bytes
            before = metrics.counter("bst_dag_xhost_bytes_total").value
            with stream.stage_scope(consA):
                dsA.read((0, 0, 0), (32, 32, 16))
            assert metrics.counter("bst_dag_xhost_bytes_total").value \
                == before
            assert edgeA.bytes_reread == 0

            # producers-done release: a read of an unwritten box blocks
            # until EVERY rank's producer instance is terminal
            done = threading.Event()

            def late_read():
                with stream.stage_scope(consA):
                    dsA.read((48, 0, 0), (16, 16, 16))
                done.set()

            th = threading.Thread(target=late_read)
            th.start()
            time.sleep(0.4)
            assert not done.is_set()
            regA.stage_finished(prodA)
            time.sleep(0.4)
            assert not done.is_set()      # the remote producer still runs
            regB.stage_finished(prodB)
            th.join(15)
            assert done.is_set()

            # dead peer: drop rank 1's connections without a bye; a gate
            # waiting on its blocks raises instead of hanging
            err = {}

            def doomed_read():
                try:
                    with regA._lock:
                        regA._coverage.clear()
                        regA._remote_cov.clear()
                        regA._finished.clear()
                        regA._remote_done.clear()
                    with stream.stage_scope(consA):
                        dsA.read((48, 16, 0), (16, 16, 16))
                except Exception as e:  # noqa: BLE001 - asserted below
                    err["e"] = e

            xb._stop.set()
            for p in xb._peers.values():
                p._close()
                p._close_fetch()
            deadline = time.monotonic() + 15
            while 1 not in regA._dead_ranks:
                assert time.monotonic() < deadline, "peer death unnoticed"
                time.sleep(0.05)
            th2 = threading.Thread(target=doomed_read)
            th2.start()
            th2.join(20)
            assert isinstance(err.get("e"), exchange.ExchangeError)
        finally:
            if edgeA is not None:
                regA.unregister([edgeA])
            xa.stop()
            xb.stop()


# -- the real thing: a 2-process jax.distributed world ------------------------


_WORKER = """
import hashlib, json, os, sys
import numpy as np
sys.path.insert(0, os.environ["MH_TESTDIR"])
from bigstitcher_spark_tpu.parallel.distributed import init_distributed, world
assert init_distributed(), "worker failed to join the jax world"
import jax
from bigstitcher_spark_tpu import config
from bigstitcher_spark_tpu.dag.executor import run_pipeline
from bigstitcher_spark_tpu.models import solver as S
from bigstitcher_spark_tpu.ops import models as M
from bigstitcher_spark_tpu.ops import solve as OS
from bigstitcher_spark_tpu.ops.intensity import solve_intensity_coefficients
from bigstitcher_spark_tpu.parallel import pairsched
from test_multihost import (
    _fused_sha, _mh_cg_system, _mh_graph, _mh_pipeline_spec, _solve_sig,
)

rank, pc = world()
out = {"rank": rank, "world": pc,
       "local_devices": jax.local_device_count(),
       "global_devices": jax.device_count()}

# tentpole 1: the global solve mesh is on by default at world > 1 and
# its links axis really spans both processes
assert OS.global_enabled(), "global solve must be auto-on at world 2"
with config.overrides({"BST_SOLVE_SHARD": 1}):
    n, g = OS.solve_layout(64)
    out["layout"] = [int(n), bool(g)]
    out["span"] = list(OS.global_axis_span(n, g))
    tiles, links = _mh_graph()
    res = S.relax(links, tiles, {tiles[0]},
                  S.SolverParams(model=M.TRANSLATION, backend="device"))
    out["relax_iters"] = int(res.iterations)
    out["relax_sig"] = _solve_sig(res)
    C, matches = _mh_cg_system()
    co = solve_intensity_coefficients(C, matches, 0.1, backend="device")
    out["cg"] = np.asarray(co).ravel().tolist()

# tentpole 2: pair split is default-on; every rank returns the full
# result list while computing only its LPT slice
assert pairsched.multihost_active(), "pair split must be auto-on"
tasks = [pairsched.PairTask(index=i, cost=float(1 + (i * 7) % 5))
         for i in range(13)]
ran = []
def dispatch(t):
    ran.append(t.index)
    return t.index * t.index
vals = pairsched.run_pair_tasks(tasks, dispatch, stage="mh-e2e")
out["pair_results"] = [int(v) for v in vals]
out["pair_local"] = sorted(int(i) for i in ran)
util = pairsched.process_util_snapshot()
out["pair_util_recorded"] = "mh-e2e" in util
out["pair_util"] = util.get("mh-e2e")

# tentpole 3: the streamed pipeline SPMD across both ranks, remote
# chunks arriving over the exchange
proj = os.environ["MH_PROJECT"]
res = run_pipeline(_mh_pipeline_spec(proj), workdir=proj)
d = res.to_dict()
assert res.ok, d
edges = {e["edge"]: e for e in d["edges"]}
out["xhost_bytes"] = int(edges["resaved"]["bytes_xhost"])
out["reread"] = int(edges["resaved"]["bytes_reread"])
out["elided"] = bool(edges["resaved"]["elided"])
out["s0_sha"] = _fused_sha(proj)
print("RESULT " + json.dumps(out), flush=True)
"""


class TestMultiprocessWorld:
    def _spawn(self, tmp_path, rank, coord, xaddrs, proj):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            "BST_COORDINATOR": coord,
            "BST_NUM_PROCESSES": "2",
            "BST_PROCESS_ID": str(rank),
            "BST_DAG_EXCHANGE_ADDR": xaddrs,
            "MH_TESTDIR": TESTS,
            "MH_PROJECT": proj,
        })
        script = tmp_path / "worker.py"
        script.write_text(_WORKER)
        return subprocess.Popen([sys.executable, str(script)], env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT)

    def test_two_process_world_end_to_end(self, tmp_path):
        """Acceptance: REAL 2-process CPU world (gloo collectives + TCP
        exchange). Global relax bitwise vs the single-process solve, CG
        identical across ranks and 1e-6 vs single-process, pair split
        exact-parity with per-process utilization recorded, and the
        streamed pipeline bitwise-equal to a 1-process run with xhost
        bytes > 0 and zero container re-reads."""
        proj = str(tmp_path / "world")
        _mk_project(proj)
        coord = f"127.0.0.1:{_free_port()}"
        xaddrs = f"127.0.0.1:{_free_port()},127.0.0.1:{_free_port()}"
        procs = {r: self._spawn(tmp_path, r, coord, xaddrs, proj)
                 for r in (0, 1)}
        outs = {}
        try:
            for r, p in procs.items():
                raw, _ = p.communicate(timeout=420)
                outs[r] = raw.decode()
                assert p.returncode == 0, f"rank {r}:\n{outs[r]}"
        finally:
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
        reports = {}
        for r, txt in outs.items():
            lines = [ln for ln in txt.splitlines()
                     if ln.startswith("RESULT ")]
            assert lines, f"rank {r} produced no RESULT:\n{txt}"
            reports[r] = json.loads(lines[-1][len("RESULT "):])

        r0, r1 = reports[0], reports[1]
        assert (r0["world"], r1["world"]) == (2, 2)
        # the global links axis spans both processes' devices
        for r in (r0, r1):
            assert r["layout"] == [8, True]
            assert r["span"] == [8, 2]

        # relax: bitwise identical across ranks AND across world shapes
        tiles, links = _mh_graph()
        with config.overrides({"BST_SOLVE_SHARD": 1}):
            golden = S.relax(links, tiles, {tiles[0]},
                             S.SolverParams(model=M.TRANSLATION,
                                            backend="device"))
        assert r0["relax_sig"] == r1["relax_sig"] == _solve_sig(golden)
        assert r0["relax_iters"] == golden.iterations

        # CG: bitwise across the ranks of one world; tolerance-level vs
        # the single-process solve (gloo reduction order differs from
        # XLA's local all-reduce)
        assert r0["cg"] == r1["cg"]
        C, matches = _mh_cg_system()
        with config.overrides({"BST_SOLVE_SHARD": 1}):
            cg_golden = solve_intensity_coefficients(C, matches, 0.1,
                                                     backend="device")
        np.testing.assert_allclose(np.asarray(r0["cg"], dtype=np.float64),
                                   np.asarray(cg_golden).ravel(),
                                   rtol=0, atol=1e-6)

        # pair split: full results on every rank, disjoint+complete local
        # slices, per-process utilization recorded for the relay plane
        expect = [i * i for i in range(13)]
        assert r0["pair_results"] == expect
        assert r1["pair_results"] == expect
        assert set(r0["pair_local"]).isdisjoint(r1["pair_local"])
        assert sorted(r0["pair_local"] + r1["pair_local"]) == list(range(13))
        assert 0 < len(r0["pair_local"]) < 13   # both ranks really worked
        assert r0["pair_util_recorded"] and r1["pair_util_recorded"]

        # pipeline: remote chunks crossed the wire exactly once on each
        # rank, never re-read from the (elided) container
        for r in (r0, r1):
            assert r["elided"] is True
            assert r["xhost_bytes"] > 0
            assert r["reread"] == 0
        assert r0["s0_sha"] == r1["s0_sha"]

        # bitwise parity with a single-process run of the same spec
        gproj = str(tmp_path / "golden")
        _mk_project(gproj)
        gres = run_pipeline(_mh_pipeline_spec(gproj), workdir=gproj)
        assert gres.ok, gres.to_dict()
        assert _fused_sha(gproj) == r0["s0_sha"]
