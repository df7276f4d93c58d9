"""The second channel of a tiled acquisition, made from the configuration
and ``--seed`` without the program, and the two-channel project a run
reads: channel 0 where the cached fixture keeps it, channel 1 beside it in
the run's own directory.

What the second fluorophore shows (the configuration's ``second_channel``):
the same specimen. The beads fluoresce in both channels, so they lie where
the first channel's lie and are seen through the same stage error
(``geometry_seed``), under the channel's own ``bead_amplitude`` and
``background``. Its noise is its own: ``fixture.Acquisition`` draws every
voxel's 5-bit noise from a Philox stream keyed by (seed, view, block), and
the second channel is that generator under the seed ``channel_seed`` makes
of ``--seed``, a key no first-channel block of any admissible ``--seed``
has. A block stays a pure function of (seed, view, block index), so the
comparison makes again the voxels it needs (``second_channel(...).region``)
instead of reading them back through the code under test.

On disk, as a two-fluorophore acquisition lies after ``bst resave``: eight
view setups in one ``dataset.n5``, the channel-0 setups numbered as the
tiles are, the channel-1 setups after them (setup ``n + v`` is tile v's
channel 1), both channels of a tile under the same registration. The second
channel is a fixture like the first, ``fixture.py``'s own under
``second_params`` and ``channel_seed``, written into the run's directory.
``link_project`` builds the container a run reads out of links to both
(read through, never written to: the cached fixture's directory is shared
by every run with the same parameters and seed) and writes the project XML
beside it.
"""

from __future__ import annotations

import os
import xml.etree.ElementTree as ET

from . import blockio
from .fixture import Acquisition

PROJECT = "unregistered-2ch.xml"


def channel_seed(seed: int) -> int:
    """The second channel's noise seed: ``--seed`` is a whole number up to
    a little over 2**31, so no run's first channel is keyed by this."""
    return (1 << 40) + int(seed)


def second_params(params: dict, spec: dict) -> dict:
    """The generator's parameters for channel 1 of the grid ``params``
    describe: the first channel's geometry, beads and levels under
    ``spec``'s amplitude and background."""
    return {**params, "bead_amplitude": spec["bead_amplitude"],
            "background": spec["background"]}


def second_channel(params: dict, spec: dict, seed: int) -> Acquisition:
    """Channel 1 with a noise stream of its own. ``view`` counts tiles, as
    the first channel's does; on disk the view is setup ``n_views +
    view``."""
    return Acquisition(second_params(params, spec), channel_seed(seed))


def two_channel_xml(one_channel: str, n_views: int) -> str:
    """The project of ``one_channel`` (a grid's XML as ``fixture.py``
    writes it) with a second channel: setup ``n_views + v`` is tile v's
    channel 1, under tile v's registration."""
    root = ET.fromstring(one_channel)
    setups = root.find("SequenceDescription/ViewSetups")
    first = setups.findall("ViewSetup")
    if len(first) != n_views:
        raise ValueError(f"{len(first)} view setups, {n_views} tiles")
    at = list(setups).index(first[-1]) + 1
    for v, el in enumerate(first):
        twin = ET.fromstring(ET.tostring(el))
        twin.find("id").text = str(n_views + v)
        twin.find("name").text = f"view{v}-channel1"
        twin.find("attributes/channel").text = "1"
        setups.insert(at + v, twin)
    channels = next(a for a in setups.findall("Attributes")
                    if a.get("name") == "channel")
    channels.append(ET.fromstring(
        "<Channel><id>1</id><name>1</name></Channel>"))
    regs = root.find("ViewRegistrations")
    for v, el in enumerate(regs.findall("ViewRegistration")):
        twin = ET.fromstring(ET.tostring(el))
        twin.set("setup", str(n_views + v))
        regs.append(twin)
    return ("<?xml version='1.0' encoding='utf-8'?>\n"
            + ET.tostring(root, encoding="unicode") + "\n")


def link_project(first_dir: str, second_dir: str, n_views: int,
                 out_dir: str) -> str:
    """``out_dir/dataset.n5`` with all ``2 * n_views`` setups, each a link
    into the fixture that holds it (``first_dir``: channel 0, ``second_dir``:
    channel 1, its setups numbered from 0 there), and the project XML that
    names the container by absolute path; returns the XML's path. Nothing
    is written under either fixture."""
    n5 = os.path.join(out_dir, "dataset.n5")
    blockio.write_json(os.path.join(n5, "attributes.json"), {"n5": "2.5.1"})
    for at, fixture_dir in ((0, first_dir), (n_views, second_dir)):
        source = os.path.join(os.path.abspath(fixture_dir), "dataset.n5")
        for v in range(n_views):
            os.symlink(os.path.join(source, f"setup{v}"),
                       os.path.join(n5, f"setup{at + v}"),
                       target_is_directory=True)
    with open(os.path.join(first_dir, "unregistered.xml")) as f:
        doc = two_channel_xml(f.read(), n_views).replace(
            '<n5 type="relative">dataset.n5</n5>',
            f'<n5 type="absolute">{n5}</n5>')
    xml = os.path.join(out_dir, PROJECT)
    with open(xml, "w") as f:
        f.write(doc)
    return xml
