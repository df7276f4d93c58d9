"""Stage adapter: pairwise stitching of a two-channel tile grid through the
tool's default grouping, project XML in to project XML out.

A pass is ``stages/stitch.py``'s, letter for letter, on the eight-setup
project: load it, ``stitch_all_pairs`` over all views (the tool groups a
tile's views over channel and illumination, combines the channels by
AVERAGE and the illuminations by PICK_BRIGHTEST, its defaults and the
traffic's options, and correlates the groups: 4 groups of 2 views, the 6
pairs of groups that overlap) -> ``filter_results`` -> ``store_results``,
save the XML. The project is what the configuration adds to ``grid1k``'s
acquisition: the second channel (``reference/channels.py`` says which) is
made in set-up (the first pass, index -1, which the harness counts as
set-up) in the run's work directory, beside the project XML and a
container of links to it and to the cached one-channel fixture, which is
read and never written. It is made anew every run and goes with the work
directory: kept in the cache, a seed run twice would start 10 s sooner (a
third of ``setup_s``, whose bound is a quarter) and the cache would hold
4.2 GB a seed for a quarter of an hour.

The comparison is ``stages/stitch.py``'s with two differences. A side's
reference crop is ``reference/aggregate.py`` over its tile's two channels'
stored-level crops, both made again from the seed, fed to
``reference/pcm.py`` unchanged. And a stored result counts only where each
of its sides names both views of one tile: a result for a group of one
view is a pair missing. The control is the parent class's: the same
reference scored in float32.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import numpy as np

from .. import files, fixtures
from ..reference import aggregate, channels
from . import stitch


class Stage(stitch.Stage):

    def __init__(self, job: dict):
        super().__init__(job)
        # the pass is stages/stitch.py's, which runs the tool's defaults
        grouping = (self.opt["channel_combine"], self.opt["illum_combine"])
        if grouping != ("AVERAGE", "PICK_BRIGHTEST"):
            raise ValueError(f"the pass runs bst stitching's default "
                             f"grouping, the traffic asks for {grouping}")
        self.ch1 = channels.second_channel(
            self.acq.p, job["config"]["second_channel"], self.acq.seed)

    # ------------------------------------------------------ the timed path

    def xml_text(self) -> str:
        """The unregistered two-channel project; the first call, from the
        pass the harness counts as set-up, makes the second channel in the
        run's work directory (the generator in a child of its own, as
        ``fixtures.py`` runs it for the first: neither JAX nor the program,
        and the allocator settings that make it five times faster) and
        writes the project over both."""
        if self._xml_text is None:
            work = self.job["work_dir"]
            made = os.path.join(work, "channel1")
            os.makedirs(made)
            params = os.path.join(made, "params.json")
            with open(params, "w") as f:
                json.dump(self.ch1.p, f)
            subprocess.run(
                [sys.executable, "-m", "benchmark.reference.fixture",
                 params, str(self.ch1.seed), made],
                cwd=files.ROOT, env={**os.environ, **fixtures._MALLOC},
                check=True)
            with open(channels.link_project(
                    self.job["fixture_dir"], made, self.acq.n_views,
                    work)) as f:
                self._xml_text = f.read()
        return self._xml_text

    # ------------------------------------------------------ the comparison

    def _crop(self, tile: int, lo, hi):
        """The overlap as the stage combines it: the group's image of the
        tile's two channels at the stored level (one illumination)."""
        c0, p0 = super()._crop(tile, lo, hi)
        level = self.acq.levels.index(self.ds)
        c1 = self.ch1.region(tile, level, p0, p0 + np.array(c0.shape))
        return aggregate.group_image(
            [(0, 0, c0), (0, 1, c1)], self.opt["channel_combine"],
            self.opt["illum_combine"]), p0

    def stored(self, xml: str) -> dict:
        """``stages/stitch.py``'s {(tile a, tile b): (shift xyz, r)}, a
        group's first view standing for its tile, of the results whose two
        sides each name both channels of one tile; parsed here and not by
        the program."""
        n = self.acq.n_views
        whole = set()
        for el in ET.parse(xml).getroot().find("StitchingResults"):
            sides = [sorted(int(v.split(",")[1])
                            for v in el.get(side).split(";") if v)
                     for side in ("views_a", "views_b")]
            if all(len(g) == 2 and g[0] < n and g[1] == g[0] + n
                   for g in sides):
                whole.add((sides[0][0], sides[1][0]))
        return {k: v for k, v in stitch.Stage.stored(xml).items()
                if k in whole}
