"""The measured window: whole passes, one after another, each started as a
stage process starts."""

from __future__ import annotations

import time


def run_window(run_pass, seconds: float, clock=time.perf_counter) -> dict:
    """Call ``run_pass(index)`` until ``seconds`` have gone by. No pass
    starts after that; the one in progress finishes and counts. The rate's
    time runs from the window's start to the end of its last pass, so a
    stalled pass lowers the rate and nothing is dropped."""
    passes = []
    t0 = clock()
    while True:
        started = clock()
        if passes and started - t0 >= seconds:
            break
        result = run_pass(len(passes))
        ended = clock()
        passes.append({**result, "start_s": started - t0,
                       "seconds": ended - started})
    return {"passes": passes, "window_s": passes[-1]["start_s"]
            + passes[-1]["seconds"]}
