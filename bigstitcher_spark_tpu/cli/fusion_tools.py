"""``create-fusion-container`` and ``affine-fusion`` commands.

Reference tools: CreateFusionContainer.java (driver-only container setup) and
SparkAffineFusion.java (the distributed fusion workload). Flag names follow
the reference CLI surface.
"""

from __future__ import annotations

import os
import time

import click
import numpy as np

from ..io.chunkstore import StorageFormat
from ..io.container import (
    open_container,
    create_fusion_container,
    estimate_multires_pyramid,
    read_container_meta,
)
from ..io.dataset_io import ViewLoader
from ..io.spimdata import SpimData, ViewId
from ..models.affine_fusion import BlendParams, fuse_volume
from ..models.downsample_driver import write_pyramid
from ..ops.fusion import FUSION_TYPES
from ..io.uris import has_scheme
from ..utils.geometry import Interval
from ..utils.viewselect import (
    anisotropy_factor_from_voxel_sizes,
    maximal_bounding_box,
)
from .common import (
    infrastructure_options,
    parse_csv_ints,
    select_views_from_kwargs,
    view_selection_options,
    xml_option,
)


def _abs_if_local(path: str) -> str:
    """abspath local paths; cloud URIs pass through untouched."""
    return path if has_scheme(path) else os.path.abspath(path)


_DTYPES = ("UINT8", "UINT16", "FLOAT32")


@click.command()
@xml_option
@view_selection_options
@infrastructure_options
@click.option("-o", "--outputPath", "--output", "output", required=True,
              help="output container path (.n5 / .zarr)")
@click.option("-s", "--storage", type=click.Choice(["N5", "ZARR", "HDF5"]),
              default="ZARR", help="storage format")
@click.option("-d", "--dataType", "data_type",
              type=click.Choice(_DTYPES), default="FLOAT32")
@click.option("--blockSize", "block_size", default="128,128,128",
              help="block size, e.g. 128,128,64")
@click.option("-ch", "--numChannels", "num_channels_opt", type=int,
              default=None,
              help="number of container channels (default: from the XML "
                   "view selection)")
@click.option("-tp", "--numTimepoints", "num_timepoints_opt", type=int,
              default=None,
              help="number of container timepoints (default: from the XML "
                   "view selection)")
@click.option("--bdv", is_flag=True, default=False,
              help="write a BDV-project layout (+XML) instead of a plain container")
@click.option("-xo", "--xmlout", "xml_out", default=None,
              help="output XML path for --bdv")
@click.option("--multiRes", "multi_res", is_flag=True, default=False,
              help="automatically create a multiresolution pyramid")
@click.option("-ds", "--downsampling", "downsampling", multiple=True,
              help="manual pyramid steps, e.g. -ds 1,1,1 -ds 2,2,1 -ds 4,4,2")
@click.option("--preserveAnisotropy", "preserve_anisotropy", is_flag=True,
              default=False)
@click.option("--anisotropyFactor", "anisotropy_factor", type=float,
              default=float("nan"))
@click.option("--minIntensity", "min_intensity", type=float, default=None)
@click.option("--maxIntensity", "max_intensity", type=float, default=None)
@click.option("-b", "--boundingBox", "bounding_box", default=None,
              help="use a named bounding box from the XML instead of the maximal one")
@click.option("-c", "--compression", default="zstd",
              type=click.Choice(["zstd", "gzip", "raw", "blosc", "bzip2", "xz",
                                 "lz4"]))
@click.option("-cl", "--compressionLevel", "compression_level", type=int,
              default=None,
              help="codec-specific compression level (CreateFusionContainer "
                   "-cl)")
def create_fusion_container_cmd(xml, output, storage, data_type, block_size,
                                num_channels_opt, num_timepoints_opt,
                                bdv, xml_out, multi_res, downsampling,
                                preserve_anisotropy, anisotropy_factor,
                                min_intensity, max_intensity, bounding_box,
                                compression, compression_level, dry_run,
                                **kwargs):
    """Create an empty fusion output container + metadata (driver-only)."""
    sd = SpimData.load(xml)
    views = select_views_from_kwargs(sd, kwargs)
    storage_format = StorageFormat(storage)
    if compression in ("xz", "lz4") and storage_format != StorageFormat.N5:
        raise click.ClickException(
            f"{compression} compression is only available for N5 containers")
    if compression_level is not None:
        compression = f"{compression}:{compression_level}"

    channels = sorted({sd.setups[v.setup].attributes.get("channel", 0) for v in views})
    tps = sorted({v.timepoint for v in views})
    num_channels = (num_channels_opt if num_channels_opt is not None
                    else len(channels))
    num_timepoints = (num_timepoints_opt if num_timepoints_opt is not None
                      else len(tps))

    if preserve_anisotropy and not np.isfinite(anisotropy_factor):
        anisotropy_factor = anisotropy_factor_from_voxel_sizes(sd, views)

    from ..models.affine_fusion import anisotropy_transform

    aniso = anisotropy_transform(anisotropy_factor) if preserve_anisotropy else None
    if bounding_box is not None:
        if bounding_box not in sd.bounding_boxes:
            raise click.ClickException(
                f"bounding box {bounding_box!r} not in XML; "
                f"have {sorted(sd.bounding_boxes)}"
            )
        bbox = sd.bounding_boxes[bounding_box]
        if aniso is not None:
            mn = list(bbox.min); mx = list(bbox.max)
            mn[2] = int(np.round(mn[2] / anisotropy_factor))
            mx[2] = int(np.round(mx[2] / anisotropy_factor))
            bbox = Interval(mn, mx)
    else:
        bbox = maximal_bounding_box(sd, views, aniso)

    bs = parse_csv_ints(block_size, 3)
    if downsampling:
        ds = [parse_csv_ints(d, 3) for d in downsampling]
    elif multi_res:
        ds = estimate_multires_pyramid(bbox.shape, anisotropy_factor
                                       if preserve_anisotropy else float("nan"))
    else:
        ds = [[1, 1, 1]]

    click.echo(f"BoundingBox: {bbox.min} -> {bbox.max} dims={bbox.shape}")
    click.echo(f"numChannels={num_channels} numTimepoints={num_timepoints}")
    click.echo(f"pyramid: {ds}")
    if dry_run:
        click.echo("(dry run, not writing)")
        return

    bdv_xml = xml_out or output + ".xml"
    setup_offset = 0
    append_sd = None
    if bdv and os.path.exists(bdv_xml):
        # fuse into the EXISTING BDV project: new ViewSetups get the next
        # setup/channel ids (BDVSparkInstantiateViewSetup.java:57-112)
        if storage_format != StorageFormat.N5:
            raise click.ClickException(
                "appending to an existing BDV project XML is supported for "
                "N5 containers (delete the XML for a fresh project)")
        append_sd = SpimData.load(bdv_xml)
        existing_root = append_sd.resolve_loader_path()

        def canon(p):
            from ..io import uris

            return (uris.normpath(p) if has_scheme(p)
                    else os.path.realpath(p))

        if canon(existing_root) != canon(output):
            raise click.ClickException(
                f"existing BDV project {bdv_xml} points at container "
                f"{existing_root!r}, not the requested output {output!r} — "
                "refusing to append (pick the project's own container, or a "
                "fresh --xmlout)")
        setup_offset = max(append_sd.setups) + 1 if append_sd.setups else 0
        click.echo(f"appending to existing BDV project {bdv_xml}: "
                   f"new setups start at {setup_offset}")

    meta = create_fusion_container(
        output, storage_format, _abs_if_local(xml),
        num_timepoints, num_channels, bbox,
        data_type=data_type.lower(), block_size=bs, downsamplings=ds,
        compression=compression, bdv=bdv,
        preserve_anisotropy=preserve_anisotropy,
        anisotropy_factor=anisotropy_factor,
        min_intensity=min_intensity, max_intensity=max_intensity,
        setup_id_offset=setup_offset,
    )
    if bdv and append_sd is not None:
        _append_bdv_output_xml(append_sd, bdv_xml, meta, setup_offset)
    elif bdv:
        _write_bdv_output_xml(bdv_xml, output, meta, storage_format)
    click.echo(f"created {meta.fusion_format} container at {output}")


def _append_bdv_output_xml(sd, xml_out: str, meta, setup_offset: int) -> None:
    """Append this fusion's ViewSetups to an existing BDV project: next
    channel ids, identity registrations, shared container
    (BDVSparkInstantiateViewSetup.java:57-112 — the default rule increments
    the channel when nothing else distinguishes the new setups)."""
    from ..io.spimdata import AttributeEntity, ViewSetup, ViewTransform
    from ..utils.geometry import identity_affine

    next_channel = max(sd.attributes["channel"], default=-1) + 1
    dims = meta.bbox.shape
    for c in range(meta.num_channels):
        ch = next_channel + c
        sid = setup_offset + c
        sd.attributes["channel"][ch] = AttributeEntity(ch, f"Channel {ch}")
        sd.setups[sid] = ViewSetup(
            id=sid, name=f"setup {sid}", size=tuple(dims),
            attributes={"illumination": 0, "channel": ch, "tile": 0,
                        "angle": 0},
        )
        for t in range(meta.num_timepoints):
            if t not in sd.timepoints:
                sd.timepoints.append(t)
            sd.registrations[ViewId(t, sid)] = [
                ViewTransform("fused", identity_affine())
            ]
    sd.timepoints.sort()
    sd.save(xml_out)


def _write_bdv_output_xml(xml_out: str, container: str, meta, storage_format) -> None:
    """Minimal BDV project XML for the fused dataset
    (SpimData2Tools.createNewSpimDataForFusion role)."""
    from ..io.spimdata import (
        AttributeEntity, ImageLoader, SpimData, ViewSetup, ViewTransform,
    )
    from ..utils.geometry import identity_affine

    out = SpimData()
    fmt = {StorageFormat.N5: "bdv.n5", StorageFormat.ZARR: "bdv.zarr",
           StorageFormat.HDF5: "bdv.hdf5"}[storage_format]
    out.image_loader = ImageLoader(format=fmt, path=_abs_if_local(container),
                                  path_type="absolute")
    out.timepoints = list(range(meta.num_timepoints))
    dims = meta.bbox.shape
    out.attributes["illumination"][0] = AttributeEntity(0, "0")
    out.attributes["angle"][0] = AttributeEntity(0, "0")
    out.attributes["tile"][0] = AttributeEntity(0, "0")
    for c in range(meta.num_channels):
        out.attributes["channel"][c] = AttributeEntity(c, f"Channel {c}")
        out.setups[c] = ViewSetup(
            id=c, name=f"setup {c}", size=tuple(dims),
            attributes={"illumination": 0, "channel": c, "tile": 0, "angle": 0},
        )
        for t in range(meta.num_timepoints):
            out.registrations[ViewId(t, c)] = [
                ViewTransform("fused", identity_affine())
            ]
    out.save(xml_out)


@click.command()
@infrastructure_options
@click.option("-o", "--n5Path", "--output", "output", required=True,
              help="fusion container created by create-fusion-container")
@click.option("-s", "--storage", "storage_opt", default=None,
              type=click.Choice(["N5", "ZARR", "HDF5"]),
              help="container storage format (validated against the "
                   "container's own metadata)")
@view_selection_options
@click.option("-f", "--fusion", "--fusionType", "fusion_type",
              type=click.Choice(FUSION_TYPES, case_sensitive=False),
              default="AVG_BLEND")
@click.option("--blockScale", "block_scale", default="2,2,1",
              help="how many container blocks per compute block")
@click.option("--masks", is_flag=True, default=False,
              help="write coverage masks instead of fused data")
@click.option("--maskOffset", "mask_offset", default="0.0,0.0,0.0")
@click.option("--blendingRange", "blending_range", default="40,40,40")
@click.option("--blendingBorder", "blending_border", default="0,0,0")
@click.option("-c", "--channelIndex", "channel_index", type=int, default=None,
              help="process only this channel index of the container")
@click.option("-t", "--timepointIndex", "timepoint_index", type=int,
              default=None,
              help="process only this timepoint index of the container")
@click.option("--prefetch/--no-prefetch", "prefetch", default=True,
              help="prefetch source chunks ahead of the kernel (always on in "
                   "this implementation's host IO pipeline; --no-prefetch "
                   "serializes IO for debugging)")
@click.option("--intensityN5", "intensity_n5", default=None, is_flag=False,
              flag_value="",
              help="apply solved intensity coefficients (optionally give the "
                   "N5 path; default: intensity.n5 next to the input XML)")
@click.option("--devices", "devices", type=int, default=None,
              help="local devices to shard the block grid over (default: "
                   "all; 1 selects the single-device composite/per-block "
                   "paths — the control runs --trace attribution compares "
                   "against)")
@click.option("--pyramid/--no-pyramid", "pyramid_epilogue", default=False,
              help="materialize the container's downsample pyramid as a "
                   "fused kernel epilogue while the data is device-"
                   "resident, shipped in the same drain (bit-identical to "
                   "the downsample stage, which then skips those levels "
                   "instead of re-reading the full-res container)")
def affine_fusion_cmd(output, storage_opt, fusion_type, block_scale, masks,
                      mask_offset, blending_range, blending_border,
                      channel_index, timepoint_index, prefetch, intensity_n5,
                      devices, pyramid_epilogue, dry_run, **kwargs):
    """Fuse all views into the prepared container (THE workload)."""
    t_start = time.time()
    store = open_container(output)
    if storage_opt is not None and store.format != StorageFormat(storage_opt):
        raise click.ClickException(
            f"--storage {storage_opt} does not match the container at "
            f"{output} ({store.format.name})")
    try:
        meta = read_container_meta(store)
    except ValueError as e:
        raise click.ClickException(str(e)) from e
    click.echo(f"FusionFormat: {meta.fusion_format}; bbox {meta.bbox.min}->"
               f"{meta.bbox.max}; dataType {meta.data_type}")
    sd = SpimData.load(meta.input_xml)
    loader = ViewLoader(sd)
    all_views = select_views_from_kwargs(sd, kwargs)

    coefficients = None
    if intensity_n5 is not None:
        from ..models.intensity import IntensityStore

        istore = (IntensityStore(intensity_n5) if intensity_n5
                  else IntensityStore.for_project(sd))
        coefficients = {}
        for v in all_views:
            c = istore.load_coefficients(v)
            if c is not None:
                coefficients[v] = c.astype(np.float32)
        click.echo(f"intensity correction: coefficients for "
                   f"{len(coefficients)}/{len(all_views)} views from {istore.root}")

    blend = BlendParams(
        border=tuple(float(v) for v in blending_border.split(",")),
        range=tuple(float(v) for v in blending_range.split(",")),
    )
    bscale = parse_csv_ints(block_scale, 3)
    is_zarr5d = meta.fusion_format in ("OME-ZARR", "BDV/OME-ZARR")

    # container channel/timepoint indices are positions in the FULL sorted
    # lists — keep them stable under --channelIndex/--timepointIndex filtering
    # so data lands in the matching mr_infos dataset / zarr slot
    channels = sorted({sd.setups[v.setup].attributes.get("channel", 0)
                       for v in all_views})
    tps = sorted({v.timepoint for v in all_views})
    c_indices = ([channel_index] if channel_index is not None
                 else list(range(len(channels))))
    t_indices = ([timepoint_index] if timepoint_index is not None
                 else list(range(len(tps))))
    moff = tuple(float(v) for v in mask_offset.split(","))

    total_vox = 0
    for ti in t_indices:
        t = tps[ti]
        for ci in c_indices:
            c = channels[ci]
            views = [
                v for v in all_views
                if v.timepoint == t
                and sd.setups[v.setup].attributes.get("channel", 0) == c
            ]
            if not views:
                continue
            mr = meta.mr_infos[ci + ti * meta.num_channels]
            ds = store.open_dataset(mr[0].dataset.strip("/"))
            click.echo(f"fusing channel {c} timepoint {t}: {len(views)} views "
                       f"-> {mr[0].dataset}")
            if dry_run:
                continue
            pyr = None
            if pyramid_epilogue and len(mr) > 1:
                from ..models.affine_fusion import pyramid_from_mr

                pyr = pyramid_from_mr(store, mr)
            stats = fuse_volume(
                sd, loader, views, ds, meta.bbox,
                block_size=tuple(meta.block_size), block_scale=tuple(bscale),
                fusion_type=fusion_type.upper(), blend=blend,
                anisotropy_factor=(meta.anisotropy_factor
                                   if meta.preserve_anisotropy else float("nan")),
                out_dtype=meta.data_type,
                min_intensity=meta.min_intensity,
                max_intensity=meta.max_intensity,
                masks=masks,
                mask_offset=moff,
                zarr_ct=(ci, ti) if is_zarr5d else None,
                coefficients=coefficients,
                devices=devices,
                io_threads=4 if prefetch else 1,
                pyramid=pyr,
            )
            total_vox += stats.voxels
            click.echo(f"  {stats.voxels} voxels in {stats.seconds:.2f}s "
                       f"({stats.voxels / max(stats.seconds, 1e-9):,.0f} vox/s; "
                       f"{stats.skipped_empty} empty blocks skipped)")
            if stats.pyramid_levels:
                click.echo(
                    f"  epilogue: {stats.pyramid_levels} pyramid level(s), "
                    f"{stats.pyramid_voxels} voxels shipped in the fusion "
                    "drain ("
                    f"{(stats.voxels + stats.pyramid_voxels) / max(stats.seconds, 1e-9):,.0f}"
                    " vox/s incl. pyramid)")
            if len(mr) > 1 and not dry_run:
                write_pyramid(store, mr, is_zarr5d, (ci, ti),
                              epilogue_levels=stats.pyramid_levels)
    click.echo(f"done, {total_vox} voxels, took {time.time() - t_start:.1f}s")


@click.command()
@infrastructure_options
@click.option("-o", "--n5Path", "--output", "output", required=True,
              help="fusion container created by create-fusion-container, or "
                   "a fresh path with -x/--dataType (direct-output mode)")
@click.option("-x", "--xml", "xml", default=None,
              help="dataset XML (direct-output mode only; containers carry "
                   "their InputXML)")
@view_selection_options
@click.option("-ip", "--interestPoints", "-l", "--label", "labels",
              multiple=True, default=("beads",),
              help="interest point label(s) defining the deformation")
@click.option("-cpd", "--controlPointDistance", "cpd", type=float, default=10.0,
              help="control point grid spacing in px")
@click.option("--alpha", type=float, default=1.0,
              help="inverse-distance weight exponent")
@click.option("--fusionType", "fusion_type",
              type=click.Choice(FUSION_TYPES, case_sensitive=False),
              default="AVG_BLEND")
@click.option("--blockScale", "block_scale", default="2,2,1")
@click.option("--blendingRange", "blending_range", default="40,40,40")
@click.option("--blendingBorder", "blending_border", default="0,0,0")
@click.option("--channelIndex", "channel_index", type=int, default=None)
@click.option("--timepointIndex", "timepoint_index", type=int, default=None)
@click.option("-s", "--storage", "storage_opt", default=None,
              type=click.Choice(["N5", "ZARR", "HDF5"]),
              help="storage format for direct-output mode (default ZARR)")
@click.option("-d", "--n5Dataset", "n5_dataset", default=None,
              help="accepted for compatibility; the container layout fixes "
                   "the dataset names")
@click.option("-p", "--dataType", "data_type", default=None,
              type=click.Choice(_DTYPES),
              help="output data type (direct-output mode)")
@click.option("--minIntensity", "min_intensity", type=float, default=None)
@click.option("--maxIntensity", "max_intensity", type=float, default=None)
@click.option("-b", "--boundingBox", "bounding_box", default=None,
              help="named bounding box (direct-output mode)")
@click.option("--bdv", is_flag=True, default=False,
              help="also write a BDV project XML (direct-output mode)")
@click.option("-xo", "--xmlout", "xml_out", default=None,
              help="output XML path for --bdv (direct-output mode)")
def nonrigid_fusion_cmd(output, xml, labels, cpd, alpha, fusion_type,
                        block_scale, blending_range, blending_border,
                        channel_index, timepoint_index, storage_opt,
                        n5_dataset, data_type, min_intensity, max_intensity,
                        bounding_box, bdv, xml_out, dry_run, **kwargs):
    """Distributed non-rigid fusion driven by corresponding interest points
    (SparkNonRigidFusion)."""
    from ..models.nonrigid_fusion import fuse_nonrigid_project

    t_start = time.time()
    try:
        store = open_container(output)
        meta = read_container_meta(store)
    except (ValueError, FileNotFoundError) as e:
        # direct-output mode (the reference's SparkNonRigidFusion writes
        # straight to an N5/ZARR, no create-fusion-container step): create
        # the container here from -x/--dataType/--boundingBox
        if xml is None or data_type is None:
            raise click.ClickException(
                f"{output} is not a fusion container ({e}); for direct "
                "output pass -x <dataset.xml> and -p/--dataType "
                "(plus optionally -s, -b, --minIntensity/--maxIntensity, "
                "--bdv/-xo)") from e
        # call the container-creation logic as a plain function (the
        # undecorated click callback) so stdout streams normally and the
        # view-selection/infrastructure flags given to nonrigid-fusion
        # carry through to the container bounding box (ADVICE r4)
        create_fusion_container_cmd.callback(
            xml=xml, output=output, storage=storage_opt or "ZARR",
            data_type=data_type, block_size="128,128,128",
            num_channels_opt=None, num_timepoints_opt=None,
            bdv=bdv, xml_out=xml_out, multi_res=False, downsampling=(),
            preserve_anisotropy=False, anisotropy_factor=float("nan"),
            min_intensity=min_intensity, max_intensity=max_intensity,
            bounding_box=bounding_box, compression="zstd",
            compression_level=None, dry_run=False, **kwargs,
        )
        click.echo(f"direct output: created container at {output}")
        store = open_container(output)
        meta = read_container_meta(store)
    sd = SpimData.load(meta.input_xml)
    total_vox = fuse_nonrigid_project(
        store, meta, sd, select_views_from_kwargs(sd, kwargs), list(labels),
        cpd, alpha, fusion_type.upper(),
        BlendParams(
            border=tuple(float(v) for v in blending_border.split(",")),
            range=tuple(float(v) for v in blending_range.split(",")),
        ),
        parse_csv_ints(block_scale, 3), channel_index=channel_index,
        timepoint_index=timepoint_index, dry_run=dry_run, log=click.echo)
    click.echo(f"done, {total_vox} voxels, took {time.time() - t_start:.1f}s")
