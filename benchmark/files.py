"""Everything that belongs to one cell, configuration, traffic mix, stage
or per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it. A later PR adds files and entries; it edits
none of these."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> dict:
    return _load("cells", name + ".json")


def config(name: str) -> dict:
    return _load("configs", name + ".json")


def traffic(name: str) -> dict:
    return _load("traffic", name + ".json")


def metric(name: str) -> dict:
    return _load("metrics", name + ".json")


def peaks(device_kind: str) -> dict:
    table = _load("peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({sorted(table)}): add its "
                       "published peaks with their source")
    return table[device_kind]


def stage(name: str):
    """The adapter module of a stage: ``benchmark/stages/<name>.py``."""
    return importlib.import_module(f"benchmark.stages.{name}")


def kernel(name: str):
    """The ops-and-bytes module of a kernel: ``benchmark/kernels/<name>.py``."""
    return importlib.import_module(f"benchmark.kernels.{name}")
