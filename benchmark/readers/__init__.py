"""Per-layer metrics, read by kind. A metric is one JSON file under
``benchmark/metrics/`` naming a kind and its parameters; a kind is one
module here, ``benchmark/readers/<kind>.py`` with ``read(ctx, params)``,
found by that name. A metric no kind fits brings a kind of its own: one
more file. A reader that finds nothing to read returns None and the metric
is left out of the line; no reader returns 0 for a share of a roofline.

``ctx`` is what one run gathered:
  values    the harness's own numbers (clocks, rates, idle share, peak HBM)
  window_s  length of the window
  spans     {span name: seconds} inside the window (``profiling`` table)
  counters  {series key: delta} of the ``observe`` registry over the window
  modules   {device module name: [calls, seconds]} from the device trace
  calls     the stage adapter's kernel calls for the traced passes
  peaks     the ``peaks.json`` entry of this device kind
"""

from __future__ import annotations

import importlib
import re

from .. import files


def series(counters: dict, name: str) -> dict:
    """The counter's series, whatever their labels."""
    return {k: v for k, v in counters.items()
            if k.split("{", 1)[0] == name and isinstance(v, (int, float))}


def module_time(ctx: dict, patterns) -> tuple[int, float]:
    """(calls, seconds) of the device modules whose names match."""
    n, s = 0, 0.0
    for name, (calls, seconds) in (ctx.get("modules") or {}).items():
        if any(re.fullmatch(p, name) for p in patterns):
            n, s = n + calls, s + seconds
    return n, s


def read(name: str, ctx: dict):
    reader = files.metric(name)["reader"]
    kind = importlib.import_module(f"benchmark.readers.{reader['kind']}")
    return kind.read(ctx, reader)
