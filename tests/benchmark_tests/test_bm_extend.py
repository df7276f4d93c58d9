"""A later PR adds a configuration, a traffic mix, a cell, a stage adapter
and a per-layer metric as files of its own, and edits none that is there:
shown by adding one of each to a copy of the benchmark and rehearsing the
new cell there."""

import json
import os
import shutil

from bm_helpers import ROOT, run_cell

STAGE = '''
"""A stage adapter of its own: fusion, counting each pass's blocks."""
from .fuse import Stage as Fuse


class Stage(Fuse):
    def run_pass(self, index):
        out = super().run_pass(index)
        out["blocks"] = len(list(self._grid(self.block)))
        return out
'''

READER = '''
"""Mvox a pass: a kind of reader of its own, where none that is there
fits."""


def read(ctx, params):
    return ctx["values"]["traced_rate"] * ctx["window_s"] / params["passes"]
'''


def write(path, doc):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(doc if isinstance(doc, str) else json.dumps(doc))


def test_everything_later_is_data(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _d, fs in os.walk(root) for p in fs}
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", "grid1k.json")) as f:
        config = json.load(f)
    config["name"] = "grid-3x1"
    config["rehearsal_fixture"]["tiles"] = [3, 1, 1]
    write(os.path.join(b, "configs", "grid-3x1.json"), config)
    with open(os.path.join(b, "traffic", "fuse-affine.json")) as f:
        traffic = json.load(f)
    traffic["stage"] = "fuse_counted"
    traffic["options"]["blending_range"] = [20, 20, 20]
    write(os.path.join(b, "traffic", "fuse-narrow-blend.json"), traffic)
    write(os.path.join(b, "stages", "fuse_counted.py"), STAGE)
    write(os.path.join(b, "metrics", "fuse_pass_mvox.json"), {
        "layer": "drivers models", "unit": "Mvox", "better": "higher",
        "source": "host_clock", "moves": "voxel_rate",
        "reader": {"kind": "mvox_a_pass", "passes": 1}})
    write(os.path.join(b, "readers", "mvox_a_pass.py"), READER)
    write(os.path.join(b, "metrics", "fuse_d2h_pct.json"), {
        "layer": "drivers models", "unit": "%", "better": "lower",
        "source": "program_span", "moves": "voxel_rate",
        "reader": {"kind": "span_share", "spans": ["fusion.d2h"]}})
    part = {"offset_blocks": [0, 0, 0], "size_blocks": [3, 1, 1]}
    write(os.path.join(b, "cells", "grid-3x1.fuse.json"), {
        "config": "grid-3x1", "traffic": "fuse-narrow-blend", "chips": 1,
        "part": part, "expect_path": "per-block",
        "per_layer": ["setup_warm_s", "fuse_pass_mvox", "fuse_d2h_pct"],
        "limits": {"fuse_mean_abs_diff": 0.001, "fuse_max_abs_diff": 2.0,
                   "fuse_missing_chunks": 0.0, "path_mismatch": 0.0},
        # a toy volume would fit the composite driver: hold it to this one
        "rehearsal": {"part": part, "env": {"BST_DEVICE_TILE_BUDGET": "1"},
                      "options_override": {"block_size": [32, 32, 16]}},
        "why": "three tiles in a row, a narrower blend"})

    rc, line, err = run_cell("grid-3x1.fuse", trace=1, cwd=root,
                             seconds=0.0)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] == 1
    assert set(line["metrics"]) == {"setup_warm_s", "fuse_pass_mvox",
                                    "fuse_d2h_pct"}
    assert line["metrics"]["fuse_pass_mvox"]["value"] > 0
    assert line["metrics"]["fuse_d2h_pct"]["value"] > 0
    # and no file that was there changed
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _d, fs in os.walk(root) for p in fs
             if ".cache" not in dp and "__pycache__" not in dp}
    assert {p: t for p, t in after.items() if p in before} == \
        {p: t for p, t in before.items() if p in after}
