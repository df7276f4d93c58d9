#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the staged pipeline still starts,
and answers right, on the chip.

Drives the main path once, the way a user runs the reference's per-stage
tools: a 2x2 grid of 1024x1024x256 uint16 tiles (2.1 GB in, ~1 Gvox fused)
goes through ``resave -> stitching -> solver -> detect-interestpoints ->
match-interestpoints -> solver -> create-fusion-container ->
affine-fusion`` with the reference's default parameters, every stage its own
``python -m bigstitcher_spark_tpu.cli.main <tool>`` process with the
environment inherited. Then it checks the answers (solved offsets of both
registration routes against the generator's ground truth; fused and pyramid
blocks against the independent numpy fusion) and the proof of the device
(every stage's run manifest must say ``platform == "tpu"``).

One process per chip: this parent imports neither jax nor the package.
Only the fixture and the numpy-reference children force ``JAX_PLATFORMS=cpu``;
no stage child sets it. Any stage's non-zero exit, any failed check or any
non-TPU platform exits non-zero and prints no result line. The last line of
a passing run is ``{"ok": true, "device": {...}}``.

``--rehearsal`` runs the same stages and checks at toy size on whatever
platform jax finds (the CPU, in the sandbox) — a test of this script, not of
the chip, and labelled so in its output.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
BST = [sys.executable, "-m", "bigstitcher_spark_tpu.cli.main"]
DEADLINE_S = 1150          # the contract allows 1200 s, compile included
OFFSET_TOL_PX = 0.5        # relative solved offsets vs ground truth
# stages that bound their dispatched-but-undrained bytes with a window
WINDOWED = ("resave", "stitching", "detect-interestpoints",
            "match-interestpoints")

# the acquisition a user would call real, and the toy the rehearsal runs.
# FULL is the only chip size: if the time limit ever forces a cut, cut
# ``planes`` here (never the XY shape or the defaults) and write it down
FULL = dict(tile_xy=1024, planes=256, overlap=96, beads_per_tile=1000,
            min_overlap_beads=50)
TOY = dict(tile_xy=160, planes=48, overlap=48, beads_per_tile=150,
           min_overlap_beads=10)


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# children that force the CPU: fixture builder and numpy reference
# ---------------------------------------------------------------------------


def child_fixture(work: str, seed: int, size: dict) -> None:
    """Build the synthetic acquisition (numpy only, from ``seed``) and write
    the generator's ground truth next to it."""
    import numpy as np

    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    tile = (size["tile_xy"], size["tile_xy"], size["planes"])
    proj = make_synthetic_project(
        os.path.join(work, "proj"), n_tiles=(2, 2, 1), tile_size=tile,
        overlap=size["overlap"], jitter=3.0, dtype="uint16", seed=seed,
        block_size=(128, 128, 64), n_beads_per_tile=size["beads_per_tile"])
    ids = sorted(proj.true_offsets)
    fewest = None
    for i, a in enumerate(ids):
        for b in ids[i + 1:]:
            lo = np.maximum(proj.true_offsets[a], proj.true_offsets[b])
            hi = np.minimum(proj.true_offsets[a], proj.true_offsets[b]) \
                + np.array(tile)
            n = int(np.all((proj.bead_positions >= lo)
                           & (proj.bead_positions < hi), axis=1).sum())
            # diagonal neighbours share only a corner column: the bead
            # floor is for the pairs that share a face
            if np.prod(hi - lo) >= size["overlap"] * tile[1] * tile[2] // 2:
                fewest = n if fewest is None else min(fewest, n)
    if fewest < size["min_overlap_beads"]:
        raise SystemExit(f"fixture: an overlap holds only {fewest} beads "
                         f"(< {size['min_overlap_beads']})")
    with open(os.path.join(work, "truth.json"), "w") as f:
        json.dump({
            "tile_size": tile,
            "true_offsets": {str(k): v.tolist()
                             for k, v in proj.true_offsets.items()},
            "nominal_offsets": {str(k): v.tolist()
                                for k, v in proj.nominal_offsets.items()},
            "beads": int(len(proj.bead_positions)),
            "fewest_beads_in_a_face_overlap": fewest,
        }, f)


def reference_fuse_block(sd, loader, views, block_global, blend_range=40.0):
    """The smoke's own reference: one output block fused the way the
    reference's BlkAffineFusion does it, in plain host code, separate from
    every kernel of the package. Per view: inverse-affine coordinates,
    trilinear sample (scipy.ndimage.map_coordinates order=1), cosine-edge
    blend weight; then the weighted average (AVG_BLEND), rounded and
    clipped to uint16. Runs in the CPU verify child only."""
    import numpy as np
    from scipy.ndimage import map_coordinates

    from bigstitcher_spark_tpu.utils.geometry import (
        Interval, invert_affine, transformed_interval,
    )

    shape = block_global.shape
    acc = np.zeros(shape, np.float32)
    wsum = np.zeros(shape, np.float32)
    axes = [
        (np.arange(shape[d], dtype=np.float32) + block_global.min[d]).reshape(
            [-1 if i == d else 1 for i in range(3)])
        for d in range(3)
    ]
    for v in views:
        inv = invert_affine(sd.model(v)).astype(np.float32)
        img_dim = np.asarray(sd.view_size(v), np.float32)
        src = transformed_interval(inv, block_global).expand(1)
        img_iv = Interval.from_shape(sd.view_size(v))
        if not src.overlaps(img_iv):
            continue
        clipped = src.intersect(img_iv)
        if clipped.is_empty():
            continue
        patch = loader.read_block(v, 0, tuple(clipped.min), clipped.shape
                                  ).astype(np.float32)
        w = None
        coords = []
        for i in range(3):
            li = (inv[i, 0] * axes[0] + inv[i, 1] * axes[1]
                  + inv[i, 2] * axes[2] + inv[i, 3])  # (X,Y,Z) level coords
            coords.append(li - np.float32(clipped.min[i]))
            d = np.minimum(li, (img_dim[i] - 1.0) - li)
            ramp = 0.5 * (np.cos((1.0 - d / np.float32(blend_range)) * np.pi)
                          + 1.0)
            wi = np.where(d < 0, np.float32(0),
                          np.where(d < blend_range, ramp, np.float32(1)))
            w = wi if w is None else w * wi
        val = map_coordinates(patch, coords, order=1, mode="constant",
                              cval=0.0, output=np.float32)
        acc += val * w
        wsum += w
    fused = np.where(wsum > 0, acc / np.maximum(wsum, np.float32(1e-20)), 0.0)
    return np.clip(np.round(fused), 0, 65535).astype("uint16")


def child_verify(work: str) -> None:
    """The answers, by the repo's own references, on the CPU: solved
    offsets of both routes vs ground truth; fused s0 blocks (corner, centre,
    far edge) vs the numpy fusion ``reference_fuse_block`` above, held to a
    mean |diff| under one grey level; one s1 block vs the mean of its s0
    parents; the rerun container vs the first one."""
    import numpy as np

    from bigstitcher_spark_tpu.io.container import (
        open_container, read_container_meta,
    )
    from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
    from bigstitcher_spark_tpu.io.spimdata import SpimData
    from bigstitcher_spark_tpu.utils.geometry import Interval

    with open(os.path.join(work, "truth.json")) as f:
        truth = {int(k): np.array(v)
                 for k, v in json.load(f)["true_offsets"].items()}
    out: dict = {"offsets": {}, "fused": [], "failures": []}

    for route in ("stitching", "ip"):
        sd = SpimData.load(os.path.join(work, "proj", f"{route}.xml"))
        solved = {v.setup: np.asarray(sd.model(v))[:, 3]
                  for v in sd.view_ids()}
        worst = max(float(np.abs((solved[s] - solved[0])
                                 - (truth[s] - truth[0])).max())
                    for s in solved)
        out["offsets"][route] = {"worst_relative_error_px": round(worst, 4)}
        if not worst <= OFFSET_TOL_PX:
            out["failures"].append(
                f"{route} route: solved offsets off by {worst:.3f} px "
                f"(> {OFFSET_TOL_PX})")

    store = open_container(os.path.join(work, "fused.ome.zarr"))
    meta = read_container_meta(store)
    sd = SpimData.load(meta.input_xml)
    loader = ViewLoader(sd)
    mr = meta.mr_infos[0]
    s0 = store.open_dataset(mr[0].dataset.strip("/"))
    dims = meta.bbox.shape
    blk = tuple(meta.block_size)

    def read(ds, off, shape):
        return np.asarray(ds.read((*off, 0, 0), (*shape, 1, 1)))[..., 0, 0]

    corners = {
        "corner": (0, 0, 0),
        "centre": tuple((d // 2) // b * b for d, b in zip(dims, blk)),
        "far-edge": tuple((d - 1) // b * b for d, b in zip(dims, blk)),
    }
    for name, off in corners.items():
        shape = tuple(min(b, d - o) for b, d, o in zip(blk, dims, off))
        ref = reference_fuse_block(
            sd, loader, sd.view_ids(),
            Interval.from_shape(shape, off).translate(meta.bbox.min))
        got = read(s0, off, shape)
        diff = np.abs(got.astype(np.float64) - ref.astype(np.float64))
        rec = {"block": name, "offset": off, "shape": shape,
               "mean_abs_diff": round(float(diff.mean()), 4),
               "max_abs_diff": float(diff.max()),
               "std": round(float(got.std()), 2)}
        out["fused"].append(rec)
        if not (diff.mean() < 1.0 and got.std() > 0.0):
            out["failures"].append(f"fused {name} block disagrees with the "
                                   f"numpy fusion: {rec}")

    # one s1 block against the mean of its s0 parents
    s1 = store.open_dataset(mr[1].dataset.strip("/"))
    rel = [int(v) for v in mr[1].relativeDownsampling]
    d1 = [int(v) for v in mr[1].dimensions]
    off1 = tuple((d // 2) // b * b for d, b in zip(d1, blk))
    shape1 = tuple(min(b, d - o) for b, d, o in zip(blk, d1, off1))
    parents = read(s0, tuple(o * r for o, r in zip(off1, rel)),
                   tuple(s * r for s, r in zip(shape1, rel)))
    mean = parents.astype(np.float64).reshape(
        shape1[0], rel[0], shape1[1], rel[1], shape1[2], rel[2]
    ).mean(axis=(1, 3, 5))
    d = np.abs(read(s1, off1, shape1).astype(np.float64) - mean)
    out["pyramid"] = {"level": mr[1].dataset, "relative": rel,
                      "levels": len(mr), "offset": off1, "shape": shape1,
                      "max_abs_diff": round(float(d.max()), 3)}
    if not d.max() <= 1.0:
        out["failures"].append(f"s1 block is not the mean of its s0 "
                               f"parents: {out['pyramid']}")

    # the rerun (warm compile cache) must reproduce the first container
    s0b = open_container(os.path.join(work, "fused-rerun.ome.zarr")
                         ).open_dataset(mr[0].dataset.strip("/"))
    off = corners["centre"]
    shape = tuple(min(b, d - o) for b, d, o in zip(blk, dims, off))
    same = bool(np.array_equal(read(s0, off, shape), read(s0b, off, shape)))
    out["rerun_identical"] = same
    if not same:
        out["failures"].append("rerun container differs from the first")

    with open(os.path.join(work, "verify.json"), "w") as f:
        json.dump(out, f)


# ---------------------------------------------------------------------------
# the parent: stdlib only
# ---------------------------------------------------------------------------


class Runner:
    def __init__(self, work: str, t0: float):
        self.work = work
        self.t0 = t0
        self.stages: list[dict] = []

    def _run(self, name: str, cmd: list[str], env: dict | None) -> float:
        left = DEADLINE_S - (time.time() - self.t0)
        if left <= 0:
            raise SmokeFailure(f"{name}: no time left inside {DEADLINE_S} s")
        log = os.path.join(self.work, "logs", f"{name}.log")
        t = time.time()
        with open(log, "w") as lf:
            # own session: a timeout (or our own death) takes the whole
            # group down, so nothing this script started outlives it
            proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=lf,
                                    stderr=subprocess.STDOUT,
                                    start_new_session=True)
            try:
                rc = proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                rc = None
            finally:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        dt = time.time() - t
        if rc != 0:
            with open(log) as lf:
                tail = "".join(lf.readlines()[-25:])
            why = (f"timed out after {dt:.0f} s" if rc is None
                   else f"exit code {rc}")
            raise SmokeFailure(f"{name}: {why}\n{tail}")
        return dt

    def cpu_child(self, name: str, *args: str) -> float:
        """A numpy-only helper of this script, held to the CPU so it never
        touches the chip."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        dt = self._run(name, [sys.executable, os.path.abspath(__file__),
                              "--child", name, "--workdir", self.work,
                              *args], env)
        say(f"{name}: {dt:.1f} s (cpu child)")
        return dt

    def stage(self, name: str, tool: str, *args: str) -> dict:
        """One pipeline stage exactly as a user runs it: its own process,
        the environment inherited (no JAX_PLATFORMS here)."""
        tel = os.path.join(self.work, "telemetry", name)
        dt = self._run(name, [*BST, tool, *args, "--telemetry-dir", tel],
                       None)
        paths = glob.glob(os.path.join(tel, "manifest-*.json"))
        if len(paths) != 1:
            raise SmokeFailure(f"{name}: expected one run manifest in {tel},"
                               f" found {len(paths)}")
        with open(paths[0]) as f:
            man = json.load(f)
        rec = summarize(name, dt, man)
        self.stages.append(rec)
        say(f"{name}: {dt:.1f} s wall on {rec['device'].get('platform')}  "
            + json.dumps({k: v for k, v in rec.items() if k not in (
                "stage", "wall_s", "device", "cache_dir")}))
        return rec


def _metric_sum(metrics: dict, name: str) -> float:
    return sum(v for k, v in metrics.items()
               if k.split("{", 1)[0] == name and isinstance(v, (int, float)))


def _by_label(metrics: dict, name: str, label: str) -> dict:
    """{label value: series value} of a labelled counter (a stage's
    manifest holds only that stage's series)."""
    return {k.split(f'{label}="', 1)[1].split('"', 1)[0]: v
            for k, v in metrics.items()
            if k.split("{", 1)[0] == name and f'{label}="' in k}


def summarize(name: str, wall_s: float, man: dict) -> dict:
    dev, met = man.get("device") or {}, man.get("metrics") or {}
    rec = {
        "stage": name, "wall_s": round(wall_s, 1),
        "device": {k: dev.get(k) for k in ("platform", "device_kind",
                                           "device_count", "error")
                   if dev.get(k) is not None},
        "cold_builds": _metric_sum(met, "bst_compiled_fn_cold_builds_total"),
        "cache_entries": (dev.get("compile_cache") or {}).get("entries"),
        "cache_dir": (dev.get("compile_cache") or {}).get("dir"),
        # dispatch windows the stage opened, by where their byte budget
        # came from (recorded by the window itself, not re-derived)
        "budget_windows": {k: v for k, v in _by_label(
            met, "bst_inflight_windows_total", "source").items() if v},
        "native_codec": dev.get("native_codec_loaded"),
        "retry_rounds": _metric_sum(met, "bst_retry_rounds_total"),
        "blocks_failed": _metric_sum(met, "bst_blocks_failed_total"),
        "redispatch": _metric_sum(met, "bst_pair_redispatch_total"),
        "peak_hbm_bytes": dev.get("peak_bytes_in_use"),
    }
    for key, field in (("fusion_driver", "path"),
                       ("solver_backend", "backend")):
        found = [s[field] for s in man.get("stages", []) if s.get(field)]
        if found:
            rec[key] = found[0]
    for key, counter in (("dispatch_per_device", "bst_pair_dispatch_total"),
                         ("drain_rows_per_device",
                          "bst_mesh_drain_rows_total")):
        per = _by_label(met, counter, "device")
        if per:
            rec[key] = {int(d): v for d, v in per.items()}
    return rec


def check_device_proof(stages: list[dict], rehearsal: bool) -> list[str]:
    """What every manifest must show for the run to count as a chip run."""
    bad = []
    for r in stages:
        n, dev = r["stage"], r["device"]
        if not dev.get("platform"):
            bad.append(f"{n}: manifest has no device inventory ({dev})")
        elif dev["platform"] != "tpu" and not rehearsal:
            bad.append(f"{n}: ran on platform {dev['platform']!r}, not tpu")
        if r["retry_rounds"] or r["blocks_failed"] or r["redispatch"]:
            bad.append(f"{n}: retries happened (rounds {r['retry_rounds']}, "
                       f"failed blocks {r['blocks_failed']}, pair "
                       f"redispatches {r['redispatch']})")
        windows = r["budget_windows"]
        if n in WINDOWED and not windows:
            bad.append(f"{n}: no dispatch window recorded its budget")
        if set(windows) - {"stats"} and not rehearsal:
            bad.append(f"{n}: in-flight budgets came from {windows}, not "
                       "only the device's memory_stats")
        if n.startswith("solver") and r.get("solver_backend") != "device":
            bad.append(f"{n}: the relaxation ran on "
                       f"{r.get('solver_backend')!r}, not the device")
        if n in ("resave", "affine-fusion") and not r["native_codec"]:
            bad.append(f"{n}: chunk IO did not go through the native codec")
        n_dev = dev.get("device_count") or 1
        if n_dev > 1 and not rehearsal:
            # one process drives every chip: each must have done work (a
            # toy grid has fewer blocks per kernel bucket than devices)
            fusion = n.startswith("affine-fusion")
            key = ("drain_rows_per_device" if fusion
                   else "dispatch_per_device"
                   if n in ("stitching", "match-interestpoints") else None)
            if key:
                per = r.get(key) or {}
                idle = [d for d in range(n_dev) if not per.get(d)]
                # a pair stage has only as many tasks to place as it has
                # pairs or PCM shape buckets (a 2x2 grid: three buckets)
                spare = 0 if fusion else max(0, n_dev - sum(per.values()))
                if len(idle) > spare:
                    bad.append(f"{n}: devices {idle} did no work "
                               f"({key} = {per})")
            if fusion and r.get("fusion_driver") != "sharded":
                bad.append(f"{n}: {n_dev} devices but the "
                           f"{r.get('fusion_driver')!r} driver ran")
    return bad


def preflight(rehearsal: bool) -> None:
    """Fail before the 2 GB fixture when there is no chip to test: the
    first stage a user would run, ``bst env``, says what jax found."""
    proc = subprocess.run([*BST, "env"], cwd=REPO, capture_output=True,
                          text=True, timeout=300)
    line = next((ln for ln in proc.stdout.splitlines()
                 if ln.startswith("backend:")), None)
    if proc.returncode != 0 or line is None:
        raise SmokeFailure(f"`bst env` failed (exit {proc.returncode}):\n"
                           f"{proc.stdout[-2000:]}{proc.stderr[-2000:]}")
    say(f"bst env -> {line}")
    backend = line.split(":", 1)[1].split(";", 1)[0].strip()
    if backend != "tpu" and not rehearsal:
        raise SmokeFailure(
            f"no TPU: jax found backend {backend!r} ({line}). chip_smoke.py "
            "proves the pipeline on the chip and does not fall back; "
            "--rehearsal runs a toy-size CPU rehearsal of this script")


def run(args) -> dict:
    t0 = time.time()
    size = TOY if args.rehearsal else FULL
    work = os.path.abspath(args.workdir)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.join(work, "logs"))
    r = Runner(work, t0)
    preflight(args.rehearsal)
    say(f"fixture: 2x2x1 tiles of {size['tile_xy']}x{size['tile_xy']}x"
        f"{size['planes']} uint16, overlap {size['overlap']} px, jitter 3 px,"
        f" seed {args.seed}")
    r.cpu_child("fixture", "--seed", str(args.seed),
                "--size", json.dumps(size))

    def x(name: str) -> str:
        return os.path.join(work, "proj", name)

    # reference defaults throughout (BASELINE.md): resave blocks 128,128,64
    # with a 2,2,1 level; stitching ds 2,2,1 / 5 peaks / minR 0.3; detection
    # blocks 512,512,128 at dsxy 2; fusion 128^3 blocks x blockScale 2,2,1
    r.stage("resave", "resave", "-x", x("dataset.xml"), "-xo",
            x("resaved.xml"), "-o", x("resaved.n5"), "--N5",
            "--blockSize", "128,128,64", "-ds", "1,1,1;2,2,1")
    # each registration route starts from the same unregistered project
    for route in ("stitching", "ip"):
        shutil.copy(x("resaved.xml"), x(f"{route}.xml"))
    r.stage("stitching", "stitching", "-x", x("stitching.xml"))
    r.stage("solver-stitching", "solver", "-x", x("stitching.xml"),
            "-s", "STITCHING")
    r.stage("detect-interestpoints", "detect-interestpoints", "-x",
            x("ip.xml"), "-l", "beads")
    r.stage("match-interestpoints", "match-interestpoints", "-x",
            x("ip.xml"), "-l", "beads")
    r.stage("solver-ip", "solver", "-x", x("ip.xml"), "-s", "IP",
            "-l", "beads")
    fused = os.path.join(work, "fused.ome.zarr")
    container = ["-x", x("ip.xml"), "-s", "ZARR", "-d", "UINT16",
                 "--blockSize", "128,128,128", "--minIntensity", "0",
                 "--maxIntensity", "65535", "--multiRes"]
    r.stage("create-fusion-container", "create-fusion-container",
            "-o", fused, *container)
    r.stage("affine-fusion", "affine-fusion", "-o", fused, "--pyramid")
    # the compile cache: a rerun into a fresh container must add nothing
    rerun = os.path.join(work, "fused-rerun.ome.zarr")
    r.stage("create-fusion-container-rerun", "create-fusion-container",
            "-o", rerun, *container)
    r.stage("affine-fusion-rerun", "affine-fusion", "-o", rerun, "--pyramid")

    r.cpu_child("verify")
    with open(os.path.join(work, "verify.json")) as f:
        verify = json.load(f)
    with open(os.path.join(work, "truth.json")) as f:
        truth = json.load(f)

    failures = list(verify["failures"])
    failures += check_device_proof(r.stages, args.rehearsal)
    by = {s["stage"]: s for s in r.stages}
    first, again = by["affine-fusion"], by["affine-fusion-rerun"]
    added = again["cache_entries"] - by["create-fusion-container-rerun"][
        "cache_entries"]
    if added:
        failures.append(f"affine-fusion rerun added {added} compile-cache "
                        f"entries in {again['cache_dir']} (expected 0)")

    report = {
        "rehearsal": args.rehearsal,
        "fixture": {**size, "seed": args.seed, "beads": truth["beads"],
                    "fewest_beads_in_a_face_overlap":
                        truth["fewest_beads_in_a_face_overlap"]},
        "stages": r.stages,
        "verify": verify,
        "compile_cache": {
            "dir": again["cache_dir"],
            "placed_by": ("JAX_COMPILATION_CACHE_DIR"
                          if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          else "checkout default"),
            "entries_after_first_fusion": first["cache_entries"],
            "entries_added_by_rerun": added,
            "fusion_wall_s_cold": first["wall_s"],
            "fusion_wall_s_cached": again["wall_s"]},
        "total_wall_s": round(time.time() - t0, 1),
        "failures": failures,
    }
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke_report.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    say("offsets: " + json.dumps(verify["offsets"]))
    say("fused:   " + json.dumps(verify["fused"]))
    say("pyramid: " + json.dumps(verify["pyramid"]))
    say("cache:   " + json.dumps(report["compile_cache"]))
    say(f"fusion driver: {first.get('fusion_driver')}; peak HBM "
        f"{first['peak_hbm_bytes']}; total {report['total_wall_s']} s")
    if failures:
        raise SmokeFailure("checks failed:\n  " + "\n  ".join(failures))
    return {"ok": True,
            **({"rehearsal": True} if args.rehearsal else {}),
            "device": {"platform": first["device"]["platform"],
                       "kind": first["device"]["device_kind"],
                       "count": first["device"]["device_count"]}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy-size run on whatever platform jax finds — a "
                         "test of this script, not of the chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir",
                    default=os.path.join(REPO, "chip_smoke_work"))
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory")
    ap.add_argument("--child", choices=["fixture", "verify"],
                    help=argparse.SUPPRESS)
    ap.add_argument("--size", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == "fixture":
        child_fixture(args.workdir, args.seed, json.loads(args.size))
        return 0
    if args.child == "verify":
        child_verify(args.workdir)
        return 0
    if not os.path.isdir(os.path.join(REPO, "bigstitcher_spark_tpu")):
        print(f"chip_smoke: no bigstitcher_spark_tpu package next to "
              f"{__file__} — run it from a checkout of the repo",
              file=sys.stderr)
        return 2
    if args.rehearsal:
        say("REHEARSAL: toy size, any platform — proves nothing about the "
            "chip")
    try:
        result = run(args)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        if not args.keep:
            shutil.rmtree(os.path.abspath(args.workdir), ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
