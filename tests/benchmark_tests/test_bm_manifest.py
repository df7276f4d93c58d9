"""BENCHMARK.json and the files it names stay in step."""

import json
import os
import re

import pytest

from bm_helpers import ROOT

B = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = load(ROOT, "BENCHMARK.json")
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}


def test_names_units_and_sizes():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in MANIFEST["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in MANIFEST["configs"]:
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
    assert E2E["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in E2E.values())


def test_a_full_check_fits_the_chip_time_allowed():
    s = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 2)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_has_every_file_it_names(name):
    cell = load(B, "cells", name + ".json")
    entry = CELLS[name]
    assert {k: cell[k] for k in ("config", "traffic", "chips", "why")} == \
        {k: entry[k] for k in ("config", "traffic", "chips", "why")}
    config = load(B, "configs", cell["config"] + ".json")
    listed = next(c for c in MANIFEST["configs"]
                  if c["name"] == cell["config"])
    assert listed["file"] == f"benchmark/configs/{cell['config']}.json"
    assert listed["source"] == config["source"]
    assert listed["reduced"] == config["reduced"]
    assert not any(re.search(r"(_dim|_rank|size|width)$", k)
                   for k in config["reduced"])
    traffic = load(B, "traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(B, "stages",
                                       traffic["stage"] + ".py"))
    assert traffic["end_to_end"] in E2E
    assert name in E2E[traffic["end_to_end"]].get("workloads", [name])
    assert cell["per_layer"], "a cell reports at least one per-layer metric"
    for metric in cell["per_layer"]:
        spec = load(B, "metrics", metric + ".json")
        if spec["reader"]["kind"] == "roofline":
            assert os.path.exists(os.path.join(
                B, "kernels", spec["reader"]["kernel"] + ".py"))
            assert metric.endswith("_roofline") and spec["unit"] == "%"
    assert set(cell["limits"]) - {"path_mismatch"} or cell["limits"]


@pytest.mark.parametrize("name", sorted(LAYER))
def test_metric_file_and_manifest_entry_agree(name):
    """The metric's file says what it is; which cells report it is said
    once, by the cells' own ``per_layer`` lists, and the entry's
    ``workloads`` in BENCHMARK.json (the one list a PR that adds a cell
    extends) has to say the same."""
    spec = load(B, "metrics", name + ".json")
    entry = LAYER[name]
    assert {k: v for k, v in spec.items() if k != "reader"} == \
        {k: v for k, v in entry.items() if k not in ("name", "workloads")}
    assert spec["source"] in ("device_trace", "program_span",
                              "program_counter", "host_clock")
    assert spec["moves"] in E2E
    reporting = [c for c in CELLS
                 if name in load(B, "cells", c + ".json")["per_layer"]]
    assert sorted(entry["workloads"]) == sorted(reporting)
    # and every cell that reports it reports the metric it moves
    moved = E2E[spec["moves"]]
    assert all(c in moved.get("workloads", list(CELLS)) for c in reporting)


@pytest.mark.parametrize("name", sorted(
    {load(B, "metrics", m + ".json")["reader"]["kind"] for m in LAYER}))
def test_every_kind_of_reader_is_a_file(name):
    from benchmark import readers

    assert os.path.exists(os.path.join(B, "readers", name + ".py"))
    # a reader with nothing to read returns nothing, never 0
    empty = {"values": {}, "window_s": 1.0, "spans": {}, "counters": {}}
    params = {"key": "none", "spans": ["none"], "part": "a", "rest": "b",
              "modules": ["none"], "kernel": "pcm"}
    kind = __import__(f"benchmark.readers.{name}", fromlist=["read"])
    assert kind.read(empty, params) is None
    assert readers.series({"a{x=\"1\"}": 2, "ab": 3}, "a") == \
        {"a{x=\"1\"}": 2}


def test_layers_are_named_alike():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert "\n" not in layer and len(layer) <= 200
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_an_unknown_device_is_an_error():
    from benchmark import files

    assert files.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        files.peaks("TPU v9 imaginary")
