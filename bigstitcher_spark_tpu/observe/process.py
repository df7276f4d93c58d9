"""The process start, seen from inside a ``bst`` command.

Every stage is a fresh process, and what it pays before its first block
(interpreter and imports, the backend's start, compiles or cache loads)
is set-up no span sees. ``cli/main.py`` stamps two instants against the
kernel's own record of when the process began; the run manifest carries
them with JAX's compile seconds by phase (:mod:`.compiles`). Gauges only:
nothing here is on a hot path.
"""

from __future__ import annotations

import os
import time

from . import metrics as _metrics

_IMPORTED_AT = time.time()
_STAMPS: dict[str, float] = {}


def started_at() -> float:
    """Unix time at which this process started: the kernel's start time
    (``/proc/self/stat``, in clock ticks since boot) where it can be
    read, else the time this module was imported."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            # the command name (field 2) may hold spaces: split after it
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        since_boot = ticks / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - since_boot
        return time.time() - age if age >= 0 else _IMPORTED_AT
    except (OSError, ValueError, IndexError, AttributeError):
        return _IMPORTED_AT


def imports_done() -> None:
    """The ``bst`` package and its tools are imported."""
    if "imports_s" not in _STAMPS:
        _STAMPS["imports_s"] = time.time() - started_at()
        _metrics.gauge("bst_process_start_imports_seconds").set(
            _STAMPS["imports_s"])


def backend_ready() -> None:
    """Bring the JAX backend up (the first ``jax.devices()``) and stamp
    when it answered."""
    import jax

    jax.devices()
    if "backend_s" not in _STAMPS:
        _STAMPS["backend_s"] = time.time() - started_at()
        _metrics.gauge("bst_process_start_backend_seconds").set(
            _STAMPS["backend_s"])


def summary() -> dict:
    """What the manifest records: the start, the stamps taken so far
    (seconds after it) and the compile seconds by phase."""
    from . import compiles

    return {"started_at": round(started_at(), 3),
            **{k: round(v, 3) for k, v in _STAMPS.items()},
            "compile_s": {ph: round(s, 3) for ph, s in
                          compiles.seconds_by_phase().items()}}
