"""Device-memory budget for in-flight dispatch windows.

Every multi-dispatch driver (parallel/mesh.run_sharded_batches, the tiled
descriptor matcher, the segmented stitching drain) bounds how many programs
it keeps in flight by BYTES — inputs + outputs + a workspace multiplier —
instead of a fixed batch count: a fixed window sized for one block shape
either under-fills small problems or OOMs big ones. The budget derives
from the backend's real memory stats when the runtime exposes them
(TPU/GPU PJRT ``memory_stats``), with ``BST_INFLIGHT_BYTES`` as the
explicit override and a conservative constant for backends (XLA:CPU) that
report nothing.
"""

from __future__ import annotations

import threading

from .. import config
from ..observe import metrics as _metrics

# fallback when the backend reports no memory stats: two batches at the
# historical 1e9 per-device staging budget (the pre-window heuristic kept
# at most two batches resident — see BST_PER_DEV_BUDGET in the fusion
# driver), so CPU behavior matches the old fixed double-buffering
DEFAULT_BUDGET = int(2e9)

# of the device memory the runtime says is free, keep this fraction for
# in-flight dispatch work; the rest covers compiled-program workspace the
# estimate cannot see
_FREE_FRACTION = 0.6

_INFLIGHT = _metrics.gauge("bst_inflight_bytes")
_HIGHWATER = _metrics.gauge("bst_inflight_bytes_highwater")
_LOCK = threading.Lock()


def derived_budget(device=None) -> tuple[int, str]:
    """(budget bytes, source) with source ``"env"`` (the process-wide
    ``BST_INFLIGHT_BYTES``), ``"stats"`` (the device's own
    ``memory_stats``, genuinely per device) or ``"fallback"`` (the
    backend reports no memory stats — XLA:CPU). ``InflightWindow``
    records the source of every window it opens, so a TPU run that sized
    its windows from the CPU constant is visible in its manifest."""
    env = config.get_bytes("BST_INFLIGHT_BYTES")
    if env is not None:
        return env, "env"
    import jax

    if device is None:
        device = jax.local_devices()[0]
    stats = device.memory_stats() or {}
    limit = int(stats.get("bytes_limit", 0))
    if limit > 0:
        free = limit - int(stats.get("bytes_in_use", 0))
        return max(256 << 20, int(_FREE_FRACTION * free)), "stats"
    return DEFAULT_BUDGET, "fallback"


def dispatch_budget_bytes(device=None) -> int:
    """Byte budget for dispatched-but-not-drained device work.

    ``BST_INFLIGHT_BYTES`` wins when set; otherwise ``device``'s (default:
    the first local device's) ``memory_stats`` (free = limit - in_use)
    scaled by a safety fraction; otherwise ``DEFAULT_BUDGET``. Per-device
    callers (the pair scheduler's one-window-per-device workers) pass
    their own device so each window sizes to its own HBM."""
    return derived_budget(device)[0]


def pair_budget(device=None, n_local: int = 1) -> tuple[int, str]:
    """(budget bytes, source) of the per-device in-flight budget for one
    of ``n_local`` concurrent pair scheduler workers:
    ``BST_PAIR_INFLIGHT_BYTES`` wins verbatim (it is defined per device,
    source ``"pair_env"``); a ``memory_stats``-derived budget is genuinely
    per device and used as is; the process-wide knobs (the
    ``BST_INFLIGHT_BYTES`` env, the no-stats fallback) are SPLIT across
    the workers — N workers must not each claim the whole process
    budget."""
    env = config.get_bytes("BST_PAIR_INFLIGHT_BYTES")
    if env is not None:
        return env, "pair_env"
    budget, source = derived_budget(device)
    if source != "stats":
        budget = max(1, budget // max(n_local, 1))
    return budget, source


class InflightWindow:
    """Byte ledger for one driver's in-flight dispatches.

    ``charge``/``release`` keep a per-window total and feed the
    process-wide current/high-water gauges, so artifacts record how close
    the window ran to its budget. Opening a window records the budget it
    was sized with and where that came from (``derived_budget``'s sources,
    ``"pair_env"``, or ``"caller"`` for a budget handed in) — the run
    manifest's ``metrics`` carry these series, so what a run's windows
    were really given is read there, not re-derived afterwards."""

    def __init__(self, budget: int | None = None, source: str = "caller"):
        if budget is None:
            budget, source = derived_budget()
        self.budget = budget
        self.inflight = 0
        _metrics.counter("bst_inflight_windows_total", source=source).inc()
        _metrics.gauge("bst_inflight_budget_bytes", source=source).set(budget)

    def fits(self, nbytes: int) -> bool:
        """Whether one more dispatch of ``nbytes`` stays inside the budget.
        An empty window always fits (forward progress must never block)."""
        return self.inflight == 0 or self.inflight + nbytes <= self.budget

    def charge(self, nbytes: int) -> None:
        self.inflight += nbytes
        with _LOCK:
            _INFLIGHT.inc(nbytes)
            cur = _INFLIGHT.value
            if cur > _HIGHWATER.value:
                _HIGHWATER.set(cur)

    def release(self, nbytes: int) -> None:
        self.inflight = max(0, self.inflight - nbytes)
        # under _LOCK like charge(): a bare dec racing a charge's
        # read-modify-write of the high-water pair could under-record it
        with _LOCK:
            _INFLIGHT.inc(-nbytes)
