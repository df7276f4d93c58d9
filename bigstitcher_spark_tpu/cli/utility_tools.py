"""CLI: clear-interestpoints, clear-registrations, transform-points,
split-images (reference tools ClearInterestPoints.java, ClearRegistrations.java,
TransformPoints.java, SplitDatasets.java)."""

from __future__ import annotations

import click
import numpy as np

from .common import (
    infrastructure_options,
    load_project,
    parse_csv_ints,
    select_views_from_kwargs,
    view_selection_options,
    xml_option,
)


@click.command()
@xml_option
@view_selection_options
@infrastructure_options
@click.option("-l", "--label", default=None,
              help="only this interest point label (default: all labels)")
@click.option("--correspondencesOnly", "--onlyCorrespondences", "only_corrs",
              is_flag=True,
              help="delete only correspondences, keep the points")
def clear_interestpoints_cmd(xml, dry_run, label, only_corrs, **kw):
    """Delete interest points (or only correspondences) from XML + store
    (ClearInterestPoints.java:92-117)."""
    from ..io.interestpoints import InterestPointStore

    sd = load_project(xml)
    views = select_views_from_kwargs(sd, kw)
    store = InterestPointStore.for_project(sd)
    n = 0
    for v in views:
        labels = ([label] if label else list(sd.interest_points.get(v, {})))
        for lab in labels:
            if lab not in sd.interest_points.get(v, {}):
                continue
            if dry_run:
                click.echo(f"would clear {v} label {lab!r}")
                continue
            if only_corrs:
                store.clear_correspondences(v, lab)
            else:
                store.remove_view(v, lab)
                del sd.interest_points[v][lab]
                if not sd.interest_points[v]:
                    del sd.interest_points[v]
            n += 1
    what = "correspondences" if only_corrs else "interest points"
    click.echo(f"cleared {what} of {n} (view, label) entries")
    if not dry_run:
        sd.save(xml)


@click.command()
@xml_option
@view_selection_options
@infrastructure_options
@click.option("--keep", type=int, default=None,
              help="keep only the first N transformations "
                   "(in order of application: calibration first)")
@click.option("--remove", type=int, default=None,
              help="remove the last N transformations (the most recent)")
def clear_registrations_cmd(xml, dry_run, keep, remove, **kw):
    """Remove view transforms from the XML (ClearRegistrations.java:74-101).

    The chain is stored outermost-first: list index 0 is the LAST-applied
    transform, so --remove pops from the front and --keep pops the front
    until N remain."""
    if (keep is None) == (remove is None) or (keep or 0) < 0 or (remove or 0) < 0:
        raise click.ClickException("specify exactly one of --keep / --remove, >= 0")
    sd = load_project(xml)
    views = select_views_from_kwargs(sd, kw)
    for v in views:
        chain = sd.registrations.get(v)
        if not chain:
            continue
        if remove is not None:
            drop = chain[: min(remove, len(chain))]
        else:
            drop = chain[: max(len(chain) - keep, 0)]
        for t in drop:
            click.echo(f"{v}: removing {t.name!r}")
        sd.registrations[v] = chain[len(drop):]
    if not dry_run:
        sd.save(xml)
        click.echo("saved XML")


@click.command()
@xml_option
@infrastructure_options
@click.option("-vi", "vi", required=True,
              help="view 'timepoint,setup' whose transform chain to apply")
@click.option("-p", "--point", "points", multiple=True,
              help="input point 'x,y,z' (repeatable)")
@click.option("--csvIn", "csv_in", default=None, type=click.Path(exists=True),
              help="CSV file with x,y,z rows")
@click.option("--csvOut", "csv_out", default=None,
              help="write transformed points to this CSV instead of stdout")
def transform_points_cmd(xml, dry_run, vi, points, csv_in, csv_out):
    """Apply a view's full pixel->world affine chain to 3-D points
    (TransformPoints.java:71-134)."""
    from ..io.spimdata import ViewId
    from ..utils.geometry import apply_affine

    sd = load_project(xml)
    tp, setup = (int(v) for v in vi.split(","))
    view = ViewId(tp, setup)
    if view not in sd.registrations:
        raise click.ClickException(f"view {view} has no registration")
    pts = []
    for p in points:
        pts.append([float(v) for v in p.split(",")])
    if csv_in:
        with open(csv_in) as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                pts.append([float(v) for v in line.replace(";", ",").split(",")[:3]])
    if not pts:
        raise click.ClickException("no points given (-p or --csvIn)")
    out = apply_affine(sd.model(view), np.asarray(pts, np.float64))
    lines = [",".join(repr(float(v)) for v in row) for row in out]
    if csv_out and not dry_run:
        with open(csv_out, "w") as f:
            f.write("\n".join(lines) + "\n")
        click.echo(f"wrote {len(lines)} transformed points to {csv_out}")
    else:
        for src, dst in zip(pts, lines):
            click.echo(f"{tuple(src)} -> {dst}")


@click.command()
@xml_option
@infrastructure_options
@click.option("-xo", "--xmlout", "xml_out", default=None,
              help="output XML (default: overwrite input)")
@click.option("-tis", "--targetImageSize", "-s", "--targetSize",
              "target_size", default="4000,4000,2000",
              help="target sub-image size x,y,z (SplitDatasets defaults)")
@click.option("-to", "-o", "--targetOverlap", "target_overlap",
              default="200,200,100",
              help="target sub-image overlap x,y,z")
@click.option("--disableOptimization", "disable_optimization", is_flag=True,
              help="use the target size/overlap exactly instead of the "
                   "closest larger divisible-by-downsampling sizes")
@click.option("--assignIlluminations", "assign_illums", is_flag=True,
              help="store old tile ids as illumination ids")
@click.option("-fip", "--fakeInterestPoints", "fake_ips", is_flag=True,
              help="plant corresponding fake points in split overlaps")
@click.option("--fipDensity", "fip_density", type=float, default=100.0)
@click.option("--fipMinNumPoints", "fip_min", type=int, default=20)
@click.option("--fipMaxNumPoints", "fip_max", type=int, default=500)
@click.option("--fipError", "fip_error", type=float, default=0.5)
@click.option("--fipExclusionRadius", "fip_exclusion_radius", type=float,
              default=20.0,
              help="minimum distance between planted fake points")
@click.option("--displayResult", "display_result", is_flag=True,
              help="GUI preview is unavailable headless: prints the split "
                   "layout instead")
def split_images_cmd(xml, dry_run, xml_out, target_size, target_overlap,
                     disable_optimization, assign_illums, fake_ips,
                     fip_density, fip_min, fip_max, fip_error,
                     fip_exclusion_radius, display_result):
    """Virtually split large tiles into overlapping sub-tiles
    (SplitDatasets / SplittingTools.splitImages)."""
    from ..io.dataset_io import ViewLoader
    from ..io.interestpoints import InterestPointStore
    from ..models.splitting import split_images

    sd = load_project(xml)
    loader = ViewLoader(sd)
    store = InterestPointStore.for_project(sd) if fake_ips else None
    new_sd = split_images(
        sd, loader,
        tuple(parse_csv_ints(target_size, 3)),
        tuple(parse_csv_ints(target_overlap, 3)),
        assign_illuminations=assign_illums,
        fake_interest_points=fake_ips,
        fip_density=fip_density, fip_min=fip_min, fip_max=fip_max,
        fip_error=fip_error, fip_store=store,
        fip_exclusion_radius=fip_exclusion_radius,
        optimize=not disable_optimization,
    )
    if display_result:
        for sid in sorted(new_sd.setups):
            su = new_sd.setups[sid]
            src = new_sd.split_info.get(sid)
            click.echo(f"  setup {sid}: size {su.size}"
                  + (f" <- source setup {src[0]} @ offset {tuple(src[1])}"
                     if src is not None else ""))
    click.echo(f"split {len(sd.setups)} setups into {len(new_sd.setups)} sub-views")
    if dry_run:
        click.echo("dryRun: not saving")
        return
    out = xml_out or xml
    new_sd.save(out)
    click.echo(f"saved {out}")


@click.command()
@xml_option
@click.option("-vi", "vi", multiple=True,
              help="restrict to view ids 'timepoint,setup' (repeatable)")
@click.option("-l", "--label", "labels", multiple=True,
              help="restrict to these labels")
def inspect_interestpoints_cmd(xml, vi, labels):
    """Print the interestpoints.n5 layout: per (view, label) the point/
    correspondence datasets, counts, and parameters (debug printer role of
    SpimData2Util.java:49-162)."""
    import numpy as np

    from ..io.interestpoints import InterestPointStore, view_group
    from ..io.spimdata import SpimData, ViewId

    import os

    sd = SpimData.load(xml)
    root = os.path.join(os.path.dirname(sd.xml_path or "."),
                        "interestpoints.n5")
    if not os.path.isdir(root):
        click.echo(f"no interestpoints store at {root}")
        return
    store = InterestPointStore(root)
    click.echo(f"interestpoints store: {store.root}")
    views = sorted(sd.interest_points)
    if vi:
        want = {ViewId(*(int(x) for x in v.split(","))) for v in vi}
        views = [v for v in views if v in want]
    total_p = total_c = 0
    for v in views:
        for label, lk in sorted(sd.interest_points.get(v, {}).items()):
            if labels and label not in labels:
                continue
            grp = view_group(v, label)
            ids, locs = store.load_points(v, label)
            corrs = store.load_correspondences(v, label)
            total_p += len(ids)
            total_c += len(corrs)
            click.echo(f"{v} label '{label}' ({grp}):")
            click.echo(f"  interestpoints: {len(ids)} points"
                       + (f", loc dims {locs.shape[1]}" if len(ids) else ""))
            if len(ids):
                mn = np.min(locs, axis=0)
                mx = np.max(locs, axis=0)
                click.echo(f"  bounds: {mn.round(1).tolist()} -> "
                           f"{mx.round(1).tolist()}")
            if lk.params:
                click.echo(f"  parameters: {lk.params}")
            by_other = {}
            for c in corrs:
                key = (c.other_view, c.other_label)
                by_other[key] = by_other.get(key, 0) + 1
            click.echo(f"  correspondences: {len(corrs)} total")
            for (ov, ol), n in sorted(by_other.items(),
                                      key=lambda kv: str(kv[0])):
                click.echo(f"    -> {ov} '{ol}': {n}")
    click.echo(f"TOTAL: {total_p} points, {total_c} correspondences "
               f"in {len(views)} views")


@click.command()
@xml_option
@infrastructure_options
@click.option("-xo", "--xmlout", "xml_out", default=None,
              help="output XML (default: overwrite input)")
@click.option("--rows", type=int, required=True,
              help="tile grid row count")
@click.option("--columns", type=int, required=True,
              help="tile grid column count")
@click.option("--parallelRows", "parallel_rows", type=int, default=4,
              help="rows acquired in parallel (mirror scope sets)")
def map_setup_ids_cmd(xml, dry_run, xml_out, rows, columns, parallel_rows):
    """Remap ViewSetup ids to acquisition order for parallel-row mirror
    scopes (SetupIDMapper.java:36-107: grid ids run bottom-right row-first;
    acquisition completes every parallelRows-th row right-to-left first)."""
    from ..io.spimdata import SpimData
    from ..utils.viewselect import keller_mirror_scope_map

    sd = SpimData.load(xml)
    mapping = keller_mirror_scope_map(rows, columns, parallel_rows)
    if set(mapping) != set(sd.setups):
        raise click.ClickException(
            f"grid {rows}x{columns} needs setups {min(mapping)}..{max(mapping)}; "
            f"XML has {sorted(sd.setups)[:3]}..{sorted(sd.setups)[-3:]}")
    for old in sorted(mapping):
        click.echo(f"  setup {old} -> {mapping[old]}")
    if dry_run:
        return
    try:
        sd.remap_setup_ids(mapping)
    except ValueError as e:
        raise click.ClickException(str(e)) from e
    sd.save(xml_out or xml)
    click.echo(f"remapped {len(mapping)} setups -> {xml_out or xml}")


@click.command()
def env_cmd():
    """Print runtime diagnostics: devices, native codec, storage config
    (the role of the reference's Spark/executor-identity printouts,
    util/Spark.java:235-238 / cloud/TestCloudFunctions.java)."""
    import jax

    import bigstitcher_spark_tpu
    from ..io import native_blockio, uris
    from ..parallel.distributed import world

    click.echo(f"bigstitcher_spark_tpu {getattr(bigstitcher_spark_tpu, '__version__', 'dev')}")
    click.echo(f"jax {jax.__version__}")
    try:
        devs = jax.local_devices()
        pi, pc = world()
        click.echo(f"backend: {jax.default_backend()}; "
                   f"{len(devs)} local device(s): "
                   f"{', '.join(str(d) for d in devs)}")
        click.echo(f"process {pi} of {pc}"
                   + (" (multi-host runtime active)" if pc > 1 else ""))
    except RuntimeError as e:  # diagnostics: report it and show the rest
        click.echo(f"backend: UNAVAILABLE ({e})")
    import tensorstore as ts

    ts_ver = getattr(ts, "__version__", None)
    click.echo(f"tensorstore {ts_ver or '(version attribute unavailable)'}")
    try:
        click.echo("native codec: built"
                   + (", lz4" if native_blockio.has_lz4() else ", no-lz4"))
    except (RuntimeError, OSError) as e:  # diagnostics, as above
        click.echo(f"native codec: UNAVAILABLE ({e})")
    # the full resolved knob surface (defaults vs env overrides) instead
    # of the single raw BST_NATIVE_IO echo this used to print — `bst
    # config -v` adds per-knob docs
    from .. import config

    click.echo("runtime config (bst config -v for docs; (env) = overridden):")
    for line in config.describe().splitlines():
        click.echo(f"  {line}")
    if uris.get_s3_region():
        click.echo(f"s3 region: {uris.get_s3_region()}")
    if uris.get_s3_endpoint():
        click.echo(f"s3 endpoint: {uris.get_s3_endpoint()}")


def make_container_server(root: str, port: int = 0):
    """HTTP server over a local container directory with CORS headers
    (browser viewers — neuroglancer in particular — refuse cross-origin
    chunk fetches without Access-Control-Allow-Origin). port=0 binds an
    ephemeral port; the caller reads ``server_address``."""
    import functools
    import http.server

    class Handler(http.server.SimpleHTTPRequestHandler):
        def end_headers(self):
            self.send_header("Access-Control-Allow-Origin", "*")
            super().end_headers()

        def log_message(self, *args):  # keep the CLI output readable
            pass

    return http.server.ThreadingHTTPServer(
        ("127.0.0.1", port), functools.partial(Handler, directory=root))


@click.command()
@click.argument("container", type=click.Path(exists=True, file_okay=False))
@click.option("--port", type=int, default=8399, show_default=True,
              help="listen port (0 picks a free one)")
def serve_container_cmd(container, port):
    """Serve a local fusion container over HTTP for interactive preview —
    the headless counterpart of the reference's --displayResult BDV window
    (SplitDatasets.java:131) and GUI loading probe
    (cloud/TestN5Loading.java:115-143). Open the printed source in
    neuroglancer, or point BigDataViewer/Fiji (Open N5/OME-ZARR via URL)
    at the served address."""
    import os

    srv = make_container_server(container, port)
    host, p = srv.server_address
    fmt = ("n5" if os.path.exists(os.path.join(container, "attributes.json"))
           else "zarr")
    click.echo(f"serving {container} at http://{host}:{p}/ (CORS enabled)")
    click.echo(f"neuroglancer source: {fmt}://http://{host}:{p}/<dataset>")
    click.echo("BigDataViewer/Fiji: Plugins > BigDataViewer > "
               f"Open N5/OME-ZARR -> http://{host}:{p}/")
    click.echo("Ctrl-C to stop")
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        srv.server_close()
