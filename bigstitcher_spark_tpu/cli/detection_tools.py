"""CLI: detect-interestpoints, match-interestpoints, clear-interestpoints
(reference tools SparkInterestPointDetection / SparkGeometricDescriptorMatching
/ ClearInterestPoints)."""

from __future__ import annotations

import click

from .common import (
    infrastructure_options,
    load_project,
    select_views_from_kwargs,
    view_selection_options,
    xml_option,
)


@click.command()
@xml_option
@view_selection_options
@infrastructure_options
@click.option("-l", "--label", default="beads", help="interest point label")
@click.option("-s", "--sigma", default=1.8, type=float,
              help="DoG sigma (at detection resolution)")
@click.option("-t", "--threshold", default=0.008, type=float,
              help="DoG response threshold")
@click.option("-dsxy", "--downsampleXY", "downsample_xy", default=2, type=int)
@click.option("-dsz", "--downsampleZ", "downsample_z", default=1, type=int)
@click.option("-i0", "--minIntensity", "min_intensity", default=None,
              type=float)
@click.option("-i1", "--maxIntensity", "max_intensity", default=None,
              type=float)
@click.option("--localization", default="QUADRATIC",
              type=click.Choice(["NONE", "QUADRATIC"]),
              help="subpixel localization method")
@click.option("--onlyCompareOverlapTiles", "only_tiles", is_flag=True,
              default=False,
              help="with --overlappingOnly, test overlap only against views "
                   "of the same timepoint+channel (i.e. across tiles)")
@click.option("--prefetch", is_flag=True, default=False,
              help="accepted for reference compatibility; chunk prefetch is "
                   "always on (double-buffered host IO)")
@click.option("--type", "extrema", default="MAX",
              type=click.Choice(["MAX", "MIN", "BOTH"]),
              help="detect maxima, minima or both")
@click.option("--overlappingOnly", "overlapping_only", is_flag=True,
              help="only detect in regions overlapping other selected views")
@click.option("--maxSpots", "max_spots", default=0, type=int,
              help="keep only the brightest N spots per view (0 = all)")
@click.option("--maxSpotsPerOverlap", "max_spots_per_overlap", is_flag=True,
              help="distribute --maxSpots over overlap regions by volume")
@click.option("--keepTemporaryN5", "keep_temporary_n5", is_flag=True,
              default=False, expose_value=False,
              help="accepted for compatibility: this implementation compacts detections on device and never stages a temporary N5")
@click.option("--storeIntensities", "store_intensities", is_flag=True,
              help="sample + store per-point image intensities")
@click.option("--medianFilter", "median_radius", default=0, type=int,
              help="background-divide by per-slice median of this radius (0=off)")
@click.option("--blockSize", "block_size", default="512,512,128",
              help="detection block size at detection resolution")
def detect_interestpoints_cmd(xml, dry_run, **kw):
    """Distributed DoG interest-point detection (SparkInterestPointDetection)."""
    from ..models.detection import DetectionParams, detect_project
    from .common import parse_csv_ints

    params = DetectionParams(
        label=kw["label"], sigma=kw["sigma"], threshold=kw["threshold"],
        downsample_xy=kw["downsample_xy"], downsample_z=kw["downsample_z"],
        min_intensity=kw["min_intensity"], max_intensity=kw["max_intensity"],
        find_max=kw["extrema"] in ("MAX", "BOTH"),
        find_min=kw["extrema"] in ("MIN", "BOTH"),
        overlapping_only=kw["overlapping_only"],
        localization=kw["localization"],
        only_compare_overlap_tiles=kw["only_tiles"],
        max_spots=kw["max_spots"],
        max_spots_per_overlap=kw["max_spots_per_overlap"],
        store_intensities=kw["store_intensities"],
        median_radius=kw["median_radius"],
        block_size=tuple(parse_csv_ints(kw["block_size"], 3)),
    )
    detect_project(xml, params,
                   select=lambda sd: select_views_from_kwargs(sd, kw),
                   dry_run=dry_run, log=click.echo)


@click.command()
@xml_option
@view_selection_options
@infrastructure_options
@click.option("-l", "--label", "labels", multiple=True, default=("beads",),
              help="interest point label(s); repeat for multiple")
@click.option("--matchAcrossLabels", "match_across", is_flag=True,
              default=False,
              help="with multiple -l labels, also match between label classes")
@click.option("-m", "--method", default="FAST_ROTATION",
              type=click.Choice(["FAST_ROTATION", "FAST_TRANSLATION",
                                 "PRECISE_TRANSLATION", "ICP"]),
              help="matching method (SparkGeometricDescriptorMatching enum)")
@click.option("--transformationModel", "model", default="AFFINE",
              type=click.Choice(["TRANSLATION", "RIGID", "AFFINE"]))
@click.option("--regularizationModel", "reg", default="RIGID",
              type=click.Choice(["NONE", "IDENTITY", "TRANSLATION",
                                 "RIGID", "AFFINE"]))
@click.option("--lambda", "lam", default=0.1, type=float,
              help="regularization weight")
@click.option("-rtp", "--registrationTP", "registration_tp",
              default="TIMEPOINTS_INDIVIDUALLY",
              type=click.Choice(["TIMEPOINTS_INDIVIDUALLY", "ALL_TO_ALL",
                                 "ALL_TO_ALL_WITH_RANGE", "REFERENCE_TIMEPOINT"]))
@click.option("--referenceTP", "reference_tp", default=0, type=int)
@click.option("--rangeTP", "range_tp", default=5, type=int)
@click.option("-s", "--significance", "ratio_of_distance", default=3.0, type=float,
              help="descriptor ratio-of-distance threshold")
@click.option("-n", "--numNeighbors", "n_neighbors", default=3, type=int)
@click.option("-r", "--redundancy", "redundancy", default=1, type=int)
@click.option("-rit", "--ransacIterations", "ransaciterations", default=10000, type=int)
@click.option("-rme", "--ransacMaxError", "--ransacMaxEpsilon",
              "ransacmaxepsilon", default=5.0, type=float)
@click.option("-rmir", "--ransacMinInlierRatio", "ransacmininlierratio", default=0.1, type=float)
@click.option("-rmni", "--ransacMinNumInliers", "ransacminnuminliers", default=12, type=int)
@click.option("-rmc", "--ransacMultiConsensus", "ransac_multi", is_flag=True,
              default=False,
              help="ransac performs multiconsensus matching")
@click.option("-ime", "--icpMaxError", "--icpMaxDistance", "icpmaxdistance",
              default=2.5, type=float)
@click.option("-iit", "--icpIterations", "--icpMaxIterations",
              "icpmaxiterations", default=200, type=int)
@click.option("--icpUseRANSAC", "icp_use_ransac", is_flag=True, default=False,
              help="ICP filters correspondences with RANSAC every iteration")
@click.option("-sr", "--searchRadius", "search_radius", type=float,
              default=None,
              help="only for PRECISE_TRANSLATION: limit corresponding points "
                   "to this distance in global coordinates")
@click.option("-vr", "--viewReg", "view_reg", default="OVERLAPPING_ONLY",
              type=click.Choice(["OVERLAPPING_ONLY", "ALL_AGAINST_ALL"]),
              help="which view pairs to match")
@click.option("--interestPointsForOverlapOnly", "overlap_only_points",
              is_flag=True, help="match only points inside the pair overlap")
@click.option("-ipfr", "--interestpointsForReg", "ipfr", default=None,
              type=click.Choice(["ALL", "OVERLAPPING_ONLY"]),
              help="which interest points to use for pairwise registrations "
                   "(reference -ipfr; OVERLAPPING_ONLY is equivalent to "
                   "--interestPointsForOverlapOnly)")
@click.option("--clearCorrespondences", "clear_corrs", is_flag=True,
              help="drop existing correspondences instead of merging")
@click.option("--groupTiles", "group_tiles", is_flag=True,
              help="merge all tiles of one angle/channel/illum/timepoint")
@click.option("--groupChannels", "group_channels", is_flag=True,
              help="merge all channels of one angle/illum/tile/timepoint")
@click.option("--groupIllums", "group_illums", is_flag=True,
              help="merge all illuminations of one angle/channel/tile/timepoint")
@click.option("--splitTimepoints", "split_timepoints", is_flag=True,
              help="treat each timepoint as one grouped view")
@click.option("--interestPointMergeDistance", "merge_distance", default=5.0,
              type=float, help="merge radius (px) for grouped interest points")
def match_interestpoints_cmd(xml, dry_run, **kw):
    """Distributed pairwise interest-point matching
    (SparkGeometricDescriptorMatching)."""
    from ..io.interestpoints import InterestPointStore
    from ..models.matching import (
        MatchingParams,
        match_interest_points,
        save_matches,
    )

    sd = load_project(xml)
    views = select_views_from_kwargs(sd, kw)
    labels = list(kw["labels"]) or ["beads"]
    params = MatchingParams(
        label=labels[0], labels=tuple(labels[1:]),
        match_across_labels=kw["match_across"],
        method=kw["method"], model=kw["model"],
        regularization=kw["reg"], lam=kw["lam"],
        n_neighbors=kw["n_neighbors"], redundancy=kw["redundancy"],
        ratio_of_distance=kw["ratio_of_distance"],
        ransac_iterations=kw["ransaciterations"],
        ransac_max_epsilon=kw["ransacmaxepsilon"],
        ransac_min_inlier_ratio=kw["ransacmininlierratio"],
        ransac_min_inliers=kw["ransacminnuminliers"],
        ransac_multi_consensus=kw["ransac_multi"],
        search_radius=kw["search_radius"],
        icp_max_distance=kw["icpmaxdistance"],
        icp_max_iterations=kw["icpmaxiterations"],
        icp_use_ransac=kw["icp_use_ransac"],
        overlap_filter=kw["view_reg"] == "OVERLAPPING_ONLY",
        registration_tp=kw["registration_tp"],
        reference_tp=kw["reference_tp"], range_tp=kw["range_tp"],
        interest_points_for_overlap_only=(kw["overlap_only_points"]
            or kw.get("ipfr") == "OVERLAPPING_ONLY"),
        clear_correspondences=kw["clear_corrs"],
        group_tiles=kw["group_tiles"], group_channels=kw["group_channels"],
        group_illums=kw["group_illums"],
        split_timepoints=kw["split_timepoints"],
        merge_distance=kw["merge_distance"],
    )
    store = InterestPointStore.for_project(sd)
    results = match_interest_points(sd, views, params, store)
    total = sum(len(r.ids_a) for r in results)
    click.echo(f"matched {total} correspondences over {len(results)} pairs")
    if dry_run:
        click.echo("dryRun: not saving")
        return
    save_matches(sd, store, results, params, views)
    click.echo("saved correspondences")
