"""JAX's compile pipeline, as the program's own counters.

Which step compiled, and what a stage's first call cost, used to be
visible only to a harness that registered its own ``jax.monitoring``
listener. One listener here turns JAX's duration events into
``bst_jax_compile_events_total{phase}`` /
``bst_jax_compile_seconds_total{phase}`` and, while the flight recorder
runs, a ``jax.compile`` instant for each lowering, build or cache load,
whose ``stage`` is the span open where it happened and whose ``item``
names the phase and the function.

Phases: ``trace`` (jaxpr tracing), ``lower`` (jaxpr to MLIR),
``backend_compile`` (the XLA build, or the persistent cache's load in its
place: JAX times both under this event) and ``cache_load`` (the load's own
part of that). A repeat call of a compiled shape emits nothing.
"""

from __future__ import annotations

import threading

from . import metrics as _metrics
from . import trace as _trace

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_EVENTS = {ph: _metrics.counter("bst_jax_compile_events_total", phase=ph)
           for ph in _PHASES.values()}
_SECONDS = {ph: _metrics.counter("bst_jax_compile_seconds_total", phase=ph)
            for ph in _PHASES.values()}

_lock = threading.Lock()
_listening = False


def _on_duration(event: str, seconds: float, **kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    _EVENTS[phase].inc()
    _SECONDS[phase].inc(float(seconds))
    # tracing fires once a traced sub-function, hundreds of times a
    # program: counted above, but only the steps that build or load a
    # program mark the timeline
    if phase != "trace" and _trace.enabled():
        cur = _trace.CURRENT.get()
        _trace.instant("jax.compile",
                       stage=cur.name if cur is not None else None,
                       item=[phase, str(kw.get("fun_name", ""))])


def listen() -> None:
    """Register the listener, once a process (JAX keeps listeners for the
    process's life; the counters are cheap and compiles are rare)."""
    global _listening
    with _lock:
        if _listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def seconds_by_phase() -> dict[str, float]:
    """Seconds inside each phase so far in this process."""
    return {ph: float(c.value) for ph, c in _SECONDS.items()}
