"""Shared by the benchmark's tests: run a cell as the driver does, at toy
size, in a process of its own (a run switches the program's telemetry on
and must not leak that into the worker's other tests)."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def child_env(devices: int = 1) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=ROOT)
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return env


def run_cell(workload: str, *, seed: int = 5, trace: int = 0,
             devices: int = 1, cwd: str = ROOT, rehearse: bool = True,
             prelude: str = "", seconds: float = 1.0):
    """Returns (exit code, last stdout line parsed or None, stderr).
    ``prelude`` is python run in the child before the harness starts: the
    place to break the timed path underneath it."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)] \
        + (["--rehearse"] if rehearse else [])
    code = (prelude + "\nimport sys\nfrom benchmark import run\n"
            f"sys.exit(run.main({argv!r}))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=cwd,
                          env=child_env(devices), capture_output=True,
                          text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    line = None
    if proc.returncode == 0 and lines:
        line = json.loads(lines[-1])
    return proc.returncode, line, proc.stderr
