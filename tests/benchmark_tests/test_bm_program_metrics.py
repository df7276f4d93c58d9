"""The metric definitions ISSUE 25 leaves for the next ``benchmark`` issue
read the program's new spans and counters through the kinds of reader that
exist, plus one new kind: shown on a temporary copy of the benchmark with
the metric files written in and both cells' lists extended, each cell
rehearsed with ``--trace 1``. No file of the benchmark itself is touched:
an accepted cell takes a metric only through its cell file, which a PR of
that kind may not edit (PERF.md section 7)."""

import json
import os
import shutil

import pytest

from bm_helpers import ROOT, run_cell

READER = '''
"""The sum of a counter of seconds over the window: as a share of the
window in percent where ``rest`` is "window", else in seconds."""

from . import series


def read(ctx, p):
    vals = series(ctx["counters"], p["part"])
    if not vals:
        return None
    total = float(sum(vals.values()))
    return 100.0 * total / ctx["window_s"] if p.get("rest") == "window" \\
        else total
'''


def span_share(layer, moves, *spans):
    return {"layer": layer, "unit": "%", "better": "lower",
            "source": "program_span", "moves": moves,
            "reader": {"kind": "span_share", "spans": list(spans)}}


def seconds(layer, moves, counter, share):
    return {"layer": layer, "unit": "%" if share else "s",
            "better": "lower", "source": "program_counter", "moves": moves,
            "reader": {"kind": "counter_seconds", "part": counter,
                       **({"rest": "window"} if share else {})}}


DRIVERS, L1, L4, L5 = ("drivers models", "L1 io", "L4 io spimdata",
                       "L5 cli process")
METRICS = {
    "grid1k.stitch": {
        "pair_dispatch_pct": span_share(DRIVERS, "pair_rate",
                                        "stitching.kernel"),
        "pair_pack_pct": span_share(DRIVERS, "pair_rate", "stitching.pack"),
        "pair_sync_pct": span_share(DRIVERS, "pair_rate",
                                    "stitching.kernel_sync"),
        "pair_xml_pct": span_share(L4, "pair_rate", "spimdata.load",
                                   "spimdata.save", "stitching.store"),
        "pair_decode_pct": seconds(L1, "pair_rate",
                                   "bst_io_read_seconds_total", True),
        "pair_compile_s": seconds(L5, "pair_rate",
                                  "bst_jax_compile_seconds_total", False),
    },
    "multiview.fuse": {
        "fuse_plan_pct": span_share(DRIVERS, "voxel_rate", "fusion.plan"),
        "fuse_h2d_pct": span_share(DRIVERS, "voxel_rate", "fusion.h2d"),
        "fuse_kernel_wait_pct": span_share(DRIVERS, "voxel_rate",
                                           "fusion.kernel"),
        "fuse_d2h_pct": span_share(DRIVERS, "voxel_rate", "fusion.d2h"),
        "fuse_decode_pct": seconds(L1, "voxel_rate",
                                   "bst_io_read_seconds_total", True),
        "fuse_compile_s": seconds(L5, "voxel_rate",
                                  "bst_jax_compile_seconds_total", False),
    },
}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    b = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), b,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(b, "readers", "counter_seconds.py"), "w") as f:
        f.write(READER)
    for cell, metrics in METRICS.items():
        for name, spec in metrics.items():
            with open(os.path.join(b, "metrics", name + ".json"), "w") as f:
                json.dump(spec, f)
        path = os.path.join(b, "cells", cell + ".json")
        with open(path) as f:
            doc = json.load(f)
        doc["per_layer"] += list(metrics)
        with open(path, "w") as f:
            json.dump(doc, f)
    return root


def test_the_new_kind_reads_nothing_from_nothing(checkout):
    """What ``test_every_kind_of_reader_is_a_file`` asks of a kind, with
    the parameter names it passes."""
    scope: dict = {}
    exec(READER.replace("from . import series",
                        "from benchmark.readers import series"), scope)
    empty = {"values": {}, "window_s": 1.0, "spans": {}, "counters": {}}
    params = {"key": "none", "spans": ["none"], "part": "a", "rest": "b",
              "modules": ["none"], "kernel": "pcm"}
    assert scope["read"](empty, params) is None
    ctx = {**empty, "window_s": 4.0,
           "counters": {'a{path="x"}': 0.5, 'a{path="y"}': 1.5, "ab": 9.0}}
    assert scope["read"](ctx, {"part": "a"}) == 2.0
    assert scope["read"](ctx, {"part": "a", "rest": "window"}) == 50.0


@pytest.mark.parametrize("cell", sorted(METRICS))
def test_every_new_metric_reads_the_program(checkout, cell):
    rc, line, err = run_cell(cell, trace=1, cwd=checkout, seed=2147483659)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, line["compared"]
    got = line["metrics"]
    for name, spec in METRICS[cell].items():
        assert name in got, f"{name} is missing from the line: {sorted(got)}"
        value = got[name]["value"]
        assert got[name]["unit"] == spec["unit"]
        if spec["unit"] == "%":
            # a share of the window; spans sum over threads, but none of
            # these runs on more than one
            assert 0.0 <= value <= 100.0, (name, value)
        else:
            assert 0.0 <= value < 600.0, (name, value)
    # the spans the accepted metrics read are still there, beside the new
    accepted = {"grid1k.stitch": ("pair_refine_pct", "pair_extract_pct"),
                "multiview.fuse": ("fuse_prefetch_pct", "fuse_write_pct")}
    for name in accepted[cell]:
        assert got[name]["value"] > 0
    if cell == "grid1k.stitch":
        # packing is inside the dispatch span
        assert got["pair_pack_pct"]["value"] <= \
            got["pair_dispatch_pct"]["value"]
        assert got["pair_xml_pct"]["value"] > 0
        assert got["pair_decode_pct"]["value"] > 0
    else:
        assert got["fuse_kernel_wait_pct"]["value"] > 0
        assert got["fuse_h2d_pct"]["value"] > 0
        assert got["fuse_d2h_pct"]["value"] > 0
        assert got["fuse_decode_pct"]["value"] > 0
