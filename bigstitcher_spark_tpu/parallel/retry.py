"""App-level block retry (RetryTrackerSpark equivalent).

The reference resubmits failed grid blocks ≤5 times with a 2 s delay, then
gives up hard (RetryTrackerSpark.java:28-61; loops at
SparkAffineFusion.java:467-479,682-696). Block writes are idempotent, so
resubmission is always safe.

Every run feeds the observability layer: a per-stage progress heartbeat
(done/total, rate, ETA), ``block.fail`` / ``retry.round`` events carrying
the exception class, and retry/failure counters — the Spark retry
accounting this port previously only ``print``ed.
"""

from __future__ import annotations

import time
import traceback
from typing import Callable, Sequence, TypeVar

from .. import profiling
from ..observe import events, metrics, progress, trace
from ..utils.cancel import Cancelled
from ..utils.threads import CtxThreadPool

T = TypeVar("T")


def _item_key(it):
    """JSON-safe work-item identity for trace attribution: grid blocks
    carry their offset (matching the fusion spans' item key), scalars pass
    through, anything else stays anonymous."""
    off = getattr(it, "offset", None)
    if off is not None:
        try:
            return tuple(int(v) for v in off)
        except (TypeError, ValueError):
            return None
    return it if isinstance(it, (int, str)) else None


class RetryError(RuntimeError):
    pass


def run_with_retry(
    items: Sequence[T],
    process: Callable[[T], None],
    max_retries: int = 5,
    delay_s: float = 2.0,
    label: str = "block",
    verbose: bool = True,
    threads: int = 1,
) -> int:
    """Process all items; collect failures and resubmit only those.

    ``threads > 1`` runs items on a host thread pool — safe for IO-bound
    chunk copy work (tensorstore releases the GIL; writers own disjoint
    chunks by construction). Returns the number of retry rounds used. Raises
    RetryError when items still fail after ``max_retries`` rounds (reference
    exits the JVM); its message includes the per-exception-class failure
    breakdown accumulated across ALL rounds, not just the first traceback."""
    pending: list[T] = list(items)
    rounds = 0
    err_counts: dict[str, int] = {}
    hb = progress.Heartbeat(label, len(pending))
    while pending:
        failed: list[tuple[T, Exception]] = []

        def attempt(it: T):
            try:
                t0 = time.perf_counter()
                with profiling.span("retry.attempt", stage=label,
                                    item=_item_key(it)):
                    process(it)
                hb.tick(seconds=time.perf_counter() - t0)
                return None
            except Cancelled:
                # cancellation is not a block failure: resubmitting a
                # cancelled item would defeat the cancel — unwind now
                raise
            except Exception as e:  # noqa: BLE001 - any task failure is retryable
                trace.instant("block.fail", stage=label, item=_item_key(it))
                return (it, e)

        if threads > 1:
            # context-propagating pool: items processed on workers keep the
            # caller's job scope (config overrides, event sink, cancel token)
            with CtxThreadPool(max_workers=threads) as pool:
                failed = [r for r in pool.map(attempt, pending) if r is not None]
        else:
            failed = [r for r in map(attempt, pending) if r is not None]
        for _, e in failed:
            exc = type(e).__name__
            err_counts[exc] = err_counts.get(exc, 0) + 1
            metrics.counter("bst_blocks_failed_total", stage=label,
                            exception=exc).inc()
        if events.enabled():
            for it, e in failed:
                events.emit("block.fail", stage=label,
                            exception=type(e).__name__,
                            error=repr(e)[:300], round=rounds)
        if not failed:
            break
        rounds += 1
        hb.retry_round()
        metrics.counter("bst_retry_rounds_total", stage=label).inc()
        if rounds > max_retries:
            hb.finish(failed=len(failed))
            events.emit("retry.exhausted", stage=label,
                        failures=len(failed), rounds=rounds - 1,
                        by_exception=err_counts)
            tb = "".join(traceback.format_exception(failed[0][1]))
            breakdown = ", ".join(
                f"{k} x{v}" for k, v in sorted(err_counts.items(),
                                               key=lambda kv: -kv[1]))
            raise RetryError(
                f"{len(failed)} {label}(s) still failing after "
                f"{max_retries} retries; failure breakdown across rounds: "
                f"{breakdown}; first error:\n{tb}"
            )
        events.emit("retry.round", stage=label, round=rounds,
                    max_retries=max_retries, failures=len(failed),
                    by_exception=err_counts, delay_s=delay_s)
        if verbose:
            print(
                f"[retry] {len(failed)} {label}(s) failed "
                f"(round {rounds}/{max_retries}), resubmitting in {delay_s}s: "
                f"{failed[0][1]!r}"
            )
        time.sleep(delay_s)
        pending = [it for it, _ in failed]
    hb.finish()
    return rounds
