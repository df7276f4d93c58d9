#!/usr/bin/env python3
"""Two questions only the chip can answer, asked in one process:

1. Is ``block_until_ready`` a completion barrier on this backend?
   (``profiling.device_sync`` exists because an earlier remote client
   acknowledged the enqueue.) A program whose execution is bounded below
   by HBM bandwidth is dispatched; if ``block_until_ready`` returns in far
   less than that bound it is an enqueue-ack, otherwise a barrier.
2. Does the float64 global solve (``ops/solve.py``: ``lax.while_loop`` with
   ``jnp.linalg.solve/svd/det``) compile and run on this backend when the
   device is asked for explicitly, per model; how long do compile and a
   warm solve take; how far is the result from the numpy reference; and
   where does ``ops.solve.resolve_backend`` place it when nobody asks?

Prints one JSON object. Run on the chip:
``chiprun -- python scripts/chip_probe.py``.
"""

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from test_solve_device import _graph  # noqa: E402  (the suite's link graph)

from bigstitcher_spark_tpu.models import solver as S  # noqa: E402
from bigstitcher_spark_tpu.ops import models as M  # noqa: E402

# v5e HBM bandwidth as the datasheet gives it — not measured here. It only
# has to separate "returned in microseconds" from "ran the program"
DATASHEET_HBM_BYTES_PER_S = 819e9


def sync_probe() -> dict:
    n = int(os.environ.get("PROBE_N", 1 << 28))  # 1 GiB of f32
    reps = 40

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(0, reps, lambda i, v: v * 1.0001 + 1.0, x)

    x = jnp.ones((n,), jnp.float32)
    np.asarray(chain(x)[0])           # compile + settle
    # each sweep reads and writes 1 GiB: >= 2 GiB / 819 GB/s = 2.6 ms
    bound_ms = reps * 2 * n * 4 / DATASHEET_HBM_BYTES_PER_S * 1e3
    out = {"program_lower_bound_ms": round(bound_ms, 1),
           "lower_bound_from": "datasheet HBM bandwidth "
                               f"{DATASHEET_HBM_BYTES_PER_S / 1e9:.0f} GB/s "
                               "(v5e), not a measured figure",
           "runs": []}
    for _ in range(3):
        t0 = time.perf_counter()
        y = chain(x)
        t1 = time.perf_counter()
        y.block_until_ready()
        t2 = time.perf_counter()
        np.asarray(y[0])
        t3 = time.perf_counter()
        out["runs"].append({"dispatch_ms": round((t1 - t0) * 1e3, 3),
                            "block_until_ready_ms": round((t2 - t0) * 1e3, 3),
                            "then_fetch_ms": round((t3 - t2) * 1e3, 3)})
    bur = min(r["block_until_ready_ms"] for r in out["runs"])
    out["block_until_ready_is_completion_barrier"] = bool(bur > 0.5 * bound_ms)
    return out


def solve_probe() -> list[dict]:
    tiles, links = _graph(n=(8, 8))
    out = []
    for model, reg in ((M.TRANSLATION, M.NONE), (M.RIGID, M.NONE),
                       (M.AFFINE, M.NONE), (M.AFFINE, M.RIGID)):
        rec = {"model": model, "regularization": reg,
               "tiles": len(tiles), "links": len(links),
               # what `bst solver` picks when no backend is named
               "auto_placement": S._resolve_backend(S.SolverParams(
                   model=model, regularization=reg))}
        ref = S.relax(links, tiles, {tiles[0]}, S.SolverParams(
            model=model, regularization=reg, backend="numpy"))
        try:
            pd = S.SolverParams(model=model, regularization=reg,
                                backend="device")
            t0 = time.perf_counter()
            S.relax(links, tiles, {tiles[0]}, pd)
            t1 = time.perf_counter()
            dev = S.relax(links, tiles, {tiles[0]}, pd)
            t2 = time.perf_counter()
            rec.update(
                first_call_s=round(t1 - t0, 2), warm_call_s=round(t2 - t1, 3),
                iterations=[int(ref.iterations), int(dev.iterations)],
                max_abs_diff_vs_numpy=float(max(
                    np.abs(ref.corrections[k] - dev.corrections[k]).max()
                    for k in ref.corrections)))
        except Exception as e:  # a probe: the error text is the finding
            rec["error"] = f"{type(e).__name__}: {str(e)[:600]}"
        out.append(rec)
    return out


if __name__ == "__main__":
    d = jax.devices()
    print(json.dumps({
        "device": {"platform": d[0].platform, "kind": d[0].device_kind,
                   "count": len(d)},
        "jax": jax.__version__,
        "sync": sync_probe(),
        "solve_f64": solve_probe(),
    }, indent=1))
