"""The seeded fixture's cache: one directory per (parameters, seed) under
``benchmark/.cache/fixtures``, made by a child process that imports neither
JAX nor the program, so the parent can bring the backend up meanwhile."""

from __future__ import annotations

import glob
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

from . import files

CACHE = os.path.join(files.HERE, ".cache")
KEPT = 2
# glibc hands every large numpy temporary of the generator's threads back to
# the kernel and faults it in again; kept on the heap the same work takes a
# fifth of the time (27 s -> 5 s for grid1k on 8 cores)
_MALLOC = {"MALLOC_MMAP_THRESHOLD_": "33554432",
           "MALLOC_TRIM_THRESHOLD_": "2147483648",
           "MALLOC_TOP_PAD_": "268435456"}


def _generator() -> str:
    """A digest of the generator's source: a fixture made by another
    version of it is not this one's."""
    h = hashlib.sha256()
    for name in ("fixture.py", "blockio.py"):
        with open(os.path.join(files.HERE, "reference", name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


class SeededFixture:
    """``path`` holds the whole fixture once ``wait()`` has returned."""

    def __init__(self, params: dict, seed: int):
        key = hashlib.sha256(json.dumps([params, seed, _generator()],
                                        sort_keys=True).encode()
                             ).hexdigest()[:16]
        self.path = os.path.join(CACHE, "fixtures", key)
        self._child = None
        if os.path.exists(os.path.join(self.path, "truth.json")):
            os.utime(self.path)
            return
        # made beside the cache and moved in when whole, so that runs side
        # by side never see half of one
        self._tmp = f"{self.path}.{os.getpid()}.tmp"
        shutil.rmtree(self._tmp, ignore_errors=True)
        os.makedirs(self._tmp)
        with open(os.path.join(self._tmp, "params.json"), "w") as f:
            json.dump(params, f)
        self._child = subprocess.Popen(
            [sys.executable, "-m", "benchmark.reference.fixture",
             os.path.join(self._tmp, "params.json"), str(seed), self._tmp],
            cwd=files.ROOT, env={**os.environ, **_MALLOC})

    def wait(self) -> None:
        if self._child is None:
            return
        rc = self._child.wait()
        self._child = None
        if rc != 0:
            shutil.rmtree(self._tmp, ignore_errors=True)
            raise RuntimeError(f"the fixture child exited with {rc}")
        try:
            os.rename(self._tmp, self.path)
        except OSError:         # another run brought the same one first
            shutil.rmtree(self._tmp, ignore_errors=True)
        # drop what has gone unused longest, never one touched within the
        # last quarter of an hour: another run may be reading it
        others = sorted((p for p in glob.glob(os.path.join(
            os.path.dirname(self.path), "*"))
            if p != self.path and not p.endswith(".tmp")),
            key=os.path.getmtime)
        for p in others[:max(0, len(others) - (KEPT - 1))]:
            if time.time() - os.path.getmtime(p) > 900:
                shutil.rmtree(p, ignore_errors=True)

    def abandon(self) -> None:
        """Leaving early: no orphan, no half fixture."""
        if self._child is not None:
            self._child.kill()
            self._child.wait()
            self._child = None
            shutil.rmtree(self._tmp, ignore_errors=True)
