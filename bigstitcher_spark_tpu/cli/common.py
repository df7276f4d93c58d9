"""Shared CLI options (the L4 base-class hierarchy of the reference,
abstractcmdline/*.java, re-expressed as click decorator stacks)."""

from __future__ import annotations


import click

from ..io.spimdata import SpimData


def _set_s3_region(ctx, param, value):
    if value:
        from ..io.uris import set_s3_region

        set_s3_region(value)
    return value


def _register_telemetry_close(ctx):
    """Finalize telemetry exactly once when the command's context closes
    (ctx.params is fully resolved by then, so the manifest records the
    command's actual configuration)."""
    if ctx.meta.get("bst.telemetry.registered"):
        return
    ctx.meta["bst.telemetry.registered"] = True

    def _close():
        import sys

        from .. import observe, profiling
        from ..observe import trace

        # during unwinding from a command error, the in-flight exception is
        # the active one — best-effort status for the manifest
        err = sys.exc_info()[1]
        report = (profiling.get().report()
                  if ctx.meta.get("bst.telemetry.profile") else None)
        traced = trace.enabled()
        if observe.active():
            # finalize archives the trace next to the manifest when on
            observe.finalize(
                tool=ctx.info_name, params=ctx.params,
                status="error" if err is not None else "ok",
                error=repr(err) if err is not None else None)
        if trace.enabled():   # --trace without --telemetry-dir
            trace.finalize()
        if traced and trace.last_path():
            click.echo(f"[trace] {trace.last_path()} "
                       f"(load in ui.perfetto.dev or run "
                       f"'bst trace-report')", err=True)
        if report is not None:
            click.echo(f"[profile]\n{report}", err=True)
            profiling.enable(False)

    ctx.call_on_close(_close)


def _set_telemetry_dir(ctx, param, value):
    if value:
        from .. import observe

        observe.configure(value)
        _register_telemetry_close(ctx)
    return value


def _set_profile(ctx, param, value):
    if value:
        from .. import profiling

        profiling.enable(True)
        ctx.meta["bst.telemetry.profile"] = True
        _register_telemetry_close(ctx)
    return value


def _set_trace(ctx, param, value):
    # --trace-device implies --trace; whichever click resolves first
    # configures the recorder, and the device session is started at most
    # once
    if value:
        from ..observe import trace

        device = param.name == "trace_device"
        if not trace.enabled() or (device and not trace.device_session()):
            trace.configure(device=device)
        _register_telemetry_close(ctx)
    return value


def infrastructure_options(f):
    """--dryRun / --s3Region (AbstractInfrastructure.java:14-27) plus the
    shared observability switches every tool inherits: --telemetry-dir
    activates the event log / metrics textfile / run manifest
    (observe package), --profile prints the span-stat table at exit."""
    f = click.option("--dryRun", "dry_run", is_flag=True, default=False,
                     help="compute but do not persist results")(f)
    f = click.option("--s3Region", "s3_region", default=None,
                     expose_value=False, callback=_set_s3_region,
                     help="AWS region for s3:// storage roots")(f)
    f = click.option("--telemetry-dir", "telemetry_dir", default=None,
                     expose_value=False, callback=_set_telemetry_dir,
                     help="write a JSONL event log, Prometheus metrics "
                          "textfile and run manifest into this directory "
                          "(one file set per process; merge pod runs with "
                          "'bst telemetry-merge')")(f)
    f = click.option("--profile", is_flag=True, default=False,
                     expose_value=False, callback=_set_profile,
                     help="record per-span wall-clock aggregates and print "
                          "the span table on exit")(f)
    f = click.option("--trace", is_flag=True, default=False,
                     expose_value=False, callback=_set_trace,
                     help="record a begin/end timeline of every span "
                          "(flight recorder, BST_TRACE_BUFFER_BYTES ring) "
                          "and write a Perfetto-loadable trace JSON on "
                          "exit (next to --telemetry-dir files when set, "
                          "else BST_TRACE_PATH / ./bst-trace.json); "
                          "analyze with 'bst trace-report'")(f)
    f = click.option("--trace-device", "trace_device", is_flag=True,
                     default=False, expose_value=False,
                     callback=_set_trace,
                     help="--trace, plus a JAX profiler session (python "
                          "tracer off) for the command's length: the "
                          "trace file gains 'device N (XLA)' tracks (XLA "
                          "module events, the union of XLA ops) on the "
                          "host spans' clock, and 'bst trace-report' then "
                          "reads device busy and idle from the device "
                          "itself")(f)
    return f


def _xml_path_ok(ctx, param, value):
    from ..io.uris import has_scheme, strip_file_scheme

    if value is not None and not has_scheme(value):
        import os

        value = strip_file_scheme(value)
        if not os.path.exists(value):
            raise click.BadParameter(f"XML not found: {value}")
    return value


def xml_option(f):
    """-x/--xml; accepts local paths and s3://, gs://, memory:// URIs
    (AbstractBasic.java:43-70 + URITools)."""
    return click.option("-x", "--xml", "xml", required=True,
                        callback=_xml_path_ok,
                        help="path or URI of the SpimData XML project")(f)


def view_selection_options(f):
    """view subset flags (AbstractSelectableViews.java:38-112)."""
    for opt in (
        click.option("--angleId", "angle_ids", default=None,
                     help="comma-separated angle ids to process"),
        click.option("--channelId", "channel_ids", default=None,
                     help="comma-separated channel ids to process"),
        click.option("--illuminationId", "illumination_ids", default=None,
                     help="comma-separated illumination ids to process"),
        click.option("--tileId", "tile_ids", default=None,
                     help="comma-separated tile ids to process"),
        click.option("--timepointId", "timepoint_ids", default=None,
                     help="comma-separated timepoint ids to process"),
        click.option("-vi", "vi", multiple=True,
                     help="explicit view ids 'timepoint,setup' (repeatable)"),
    ):
        f = opt(f)
    return f


def load_project(xml: str) -> SpimData:
    return SpimData.load(xml)


def parse_csv_ints(s: str | None, n: int | None = None) -> list[int] | None:
    if s is None:
        return None
    vals = [int(v) for v in s.split(",")]
    if n is not None and len(vals) != n:
        raise click.BadParameter(f"expected {n} comma-separated ints: {s!r}")
    return vals


def select_views_from_kwargs(sd, kwargs):
    from ..utils.viewselect import select_views

    return select_views(
        sd,
        angle_ids=kwargs.get("angle_ids"),
        channel_ids=kwargs.get("channel_ids"),
        illumination_ids=kwargs.get("illumination_ids"),
        tile_ids=kwargs.get("tile_ids"),
        timepoint_ids=kwargs.get("timepoint_ids"),
        vi=kwargs.get("vi"),
    )
