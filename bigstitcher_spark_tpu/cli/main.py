"""CLI entry point: one subcommand per pipeline stage, names matching the
reference's installed shell wrappers (install:122-139) so users of
BigStitcher-Spark can switch 1:1.

Run: ``python -m bigstitcher_spark_tpu.cli.main <tool> [options]``
"""

from __future__ import annotations

import click

from . import (
    analysis_tools,
    detection_tools,
    fusion_tools,
    intensity_tools,
    observe_tools,
    pipeline_tools,
    resave_tools,
    serve_tools,
    solver_tools,
    stitching_tools,
    telemetry_tools,
    tune_tools,
    utility_tools,
)


# tools whose first act is device work: for these the backend's start is
# stamped (observe/process.py) where it would happen anyway. Host-only
# tools are left alone: bringing a TPU up costs them ten seconds
_DEVICE_TOOLS = {"affine-fusion", "nonrigid-fusion", "stitching",
                 "detect-interestpoints", "match-interestpoints"}

# tools that must NOT auto-bind the BST_METRICS_PORT exporter: daemon
# management and thin clients run on the same host as the daemon that
# owns the port (the `bst serve --detach` parent or a `bst submit` would
# steal it for milliseconds and break the resident daemon's bind), and
# the short diagnostic tools have nothing live to export. The daemon
# itself starts its exporter inside Daemon.start().
_NO_LIVE_EXPORTER = {"serve", "submit", "jobs", "cancel", "top",
                     "trace-dump", "history", "perf-diff", "config",
                     "env", "lint", "telemetry-merge", "trace-report",
                     "tune"}


@click.group()
@click.pass_context
def cli(ctx):
    """TPU-native BigStitcher: distributed stitching & fusion tools."""
    # multi-host bootstrap: no-op unless BST_COORDINATOR/BST_NUM_PROCESSES/
    # BST_PROCESS_ID (or BST_DISTRIBUTED=1 on an autodetecting pod) are
    # set. The telemetry relay (BST_TELEMETRY_RELAY) rides along for
    # workload tools only — a short `bst submit`/`bst jobs` has nothing
    # live to push, and a `bst serve` daemon hosts the collector itself
    # inside Daemon.start()
    from ..observe import process
    from ..parallel.distributed import init_distributed

    process.imports_done()
    init_distributed(
        start_relay=ctx.invoked_subcommand not in _NO_LIVE_EXPORTER)
    if ctx.invoked_subcommand in _DEVICE_TOOLS:
        process.backend_ready()
    # live HTTP exporter for long one-shot runs: no-op unless
    # BST_METRICS_PORT is set (the serve daemon wires richer providers in)
    if ctx.invoked_subcommand not in _NO_LIVE_EXPORTER:
        from ..observe import httpexport

        httpexport.ensure_started()


cli.add_command(fusion_tools.create_fusion_container_cmd, "create-fusion-container")
cli.add_command(fusion_tools.affine_fusion_cmd, "affine-fusion")
cli.add_command(resave_tools.resave_cmd, "resave")
cli.add_command(resave_tools.downsample_cmd, "downsample")
cli.add_command(stitching_tools.stitching_cmd, "stitching")
cli.add_command(solver_tools.solver_cmd, "solver")
cli.add_command(detection_tools.detect_interestpoints_cmd, "detect-interestpoints")
cli.add_command(detection_tools.match_interestpoints_cmd, "match-interestpoints")
cli.add_command(fusion_tools.nonrigid_fusion_cmd, "nonrigid-fusion")
cli.add_command(utility_tools.clear_interestpoints_cmd, "clear-interestpoints")
cli.add_command(utility_tools.clear_registrations_cmd, "clear-registrations")
cli.add_command(utility_tools.transform_points_cmd, "transform-points")
cli.add_command(utility_tools.split_images_cmd, "split-images")
cli.add_command(intensity_tools.match_intensities_cmd, "match-intensities")
cli.add_command(intensity_tools.solve_intensities_cmd, "solve-intensities")
cli.add_command(utility_tools.inspect_interestpoints_cmd, "inspect-interestpoints")
cli.add_command(utility_tools.map_setup_ids_cmd, "map-setup-ids")
cli.add_command(utility_tools.env_cmd, "env")
cli.add_command(utility_tools.serve_container_cmd, "serve-container")
cli.add_command(telemetry_tools.telemetry_merge_cmd, "telemetry-merge")
cli.add_command(telemetry_tools.trace_report_cmd, "trace-report")
cli.add_command(analysis_tools.lint_cmd, "lint")
cli.add_command(analysis_tools.config_cmd, "config")
cli.add_command(serve_tools.serve_cmd, "serve")
cli.add_command(serve_tools.submit_cmd, "submit")
cli.add_command(serve_tools.jobs_cmd, "jobs")
cli.add_command(serve_tools.cancel_cmd, "cancel")
cli.add_command(pipeline_tools.pipeline_cmd, "pipeline")
cli.add_command(observe_tools.top_cmd, "top")
cli.add_command(observe_tools.trace_dump_cmd, "trace-dump")
cli.add_command(observe_tools.history_cmd, "history")
cli.add_command(observe_tools.perf_diff_cmd, "perf-diff")
cli.add_command(tune_tools.tune_cmd, "tune")


def main():
    cli(prog_name="bst")


if __name__ == "__main__":
    main()
