"""The image of a group of views in plain numpy: what ``stitching``
correlates when it compares tiles and groups their channels and
illuminations (BigStitcher's ``GroupedViewAggregator``, as
``SparkPairwiseStitching.java:204-208`` sets it up: channels combined by
AVERAGE, illuminations by PICK_BRIGHTEST unless the user says otherwise).

First the channels of each illumination are combined, then the
illuminations' results. AVERAGE is the arithmetic mean, voxel by voxel;
PICK_BRIGHTEST hands on the one image whose intensities sum highest (the
first of equals). Everything in float64: the mean of uint16 values is
exact there (and, for two of them, in the float32 the program computes it
in: a half at most).

Departures from upstream, whose class is BigStitcher core's and not in
this repository (SURVEY section 2.1 names it and its two modes): upstream
averages in the image's real type and ranks illuminations by mean
intensity; over crops of one shape the means order as the sums do. No
network here: what upstream does beyond that (a downsampled copy for the
ranking, as remembered) could not be read again and is not imitated.
"""

from __future__ import annotations

import numpy as np


def combine(images: list[np.ndarray], how: str) -> np.ndarray:
    """One image of several of the same shape, float64."""
    images = [np.asarray(im, np.float64) for im in images]
    if len(images) == 1:
        return images[0]
    if how == "AVERAGE":
        return np.sum(images, axis=0) / float(len(images))
    if how == "PICK_BRIGHTEST":
        return images[int(np.argmax([im.sum() for im in images]))]
    raise ValueError(f"unknown way to combine a group's images: {how!r}")


def group_image(views: list[tuple[int, int, np.ndarray]],
                channel_combine: str = "AVERAGE",
                illum_combine: str = "PICK_BRIGHTEST") -> np.ndarray:
    """The group's image from its views' crops, each given as
    (illumination, channel, crop): channels in the order of their ids
    within an illumination, illuminations in the order of theirs."""
    by_illum: dict[int, list[tuple[int, np.ndarray]]] = {}
    for illum, channel, crop in views:
        by_illum.setdefault(int(illum), []).append((int(channel), crop))
    per_illum = [combine([c for _ch, c in sorted(members,
                                                 key=lambda m: m[0])],
                         channel_combine)
                 for _illum, members in sorted(by_illum.items())]
    return combine(per_illum, illum_combine)
