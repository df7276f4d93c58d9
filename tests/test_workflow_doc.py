"""WORKFLOW.md must stay runnable: extract its ``bst ...`` commands and run
them in order against the generated example project. Any drift between the
documented pipeline and the CLI breaks this test. The scripts under
``scripts/`` are held to the CLI by name: every ``bst <tool>`` one calls is
a command (and, of a group, a subcommand) that exists."""

import os
import re
import shlex

import click
import numpy as np
import pytest
from click.testing import CliRunner

from bigstitcher_spark_tpu.cli.main import cli

DOC = os.path.join(os.path.dirname(__file__), "..", "WORKFLOW.md")
SCRIPTS = os.path.join(os.path.dirname(__file__), "..", "scripts")


def doc_commands():
    """All ``bst ...`` commands from WORKFLOW.md's code fences, in order."""
    text = open(DOC).read()
    cmds = []
    for block in re.findall(r"```bash\n(.*?)```", text, re.S):
        joined = block.replace("\\\n", " ")
        for line in joined.splitlines():
            line = line.split("#")[0].strip()
            if line.startswith("bst "):
                cmds.append(shlex.split(line)[1:])
    return cmds


def test_workflow_runs(tmp_path, monkeypatch):
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    monkeypatch.chdir(tmp_path)
    make_synthetic_project("example", n_tiles=(2, 2, 1),
                           tile_size=(96, 96, 32), overlap=24,
                           jitter=2.0, n_beads_per_tile=40)
    cmds = doc_commands()
    assert len(cmds) >= 14, f"expected the full pipeline, got {len(cmds)}"
    runner = CliRunner()
    for args in cmds:
        r = runner.invoke(cli, args, catch_exceptions=False)
        assert r.exit_code == 0, f"bst {' '.join(args)}\n{r.output}"

    # the pipeline must actually have registered + fused the tiles
    from bigstitcher_spark_tpu.io.chunkstore import ChunkStore
    from bigstitcher_spark_tpu.io.spimdata import SpimData

    ds = ChunkStore.open("example/fused.ome.zarr").open_dataset("0")
    vol = np.asarray(ds.read((0, 0, 0, 0, 0), (*ds.shape[:3], 1, 1)))
    assert vol.std() > 0
    nr = ChunkStore.open("example/nonrigid.ome.zarr").open_dataset("0")
    nvol = np.asarray(nr.read((0, 0, 0, 0, 0), (*nr.shape[:3], 1, 1)))
    assert nvol.std() > 0
    sd = SpimData.load("example/resaved.xml")
    # clear-registrations --keep 1 ran last: back to one transform per view
    assert all(len(ch) == 1 for ch in sd.registrations.values())


def script_calls(name):
    """(tool, next word) of every ``bst <tool> ...`` or ``python -m
    bigstitcher_spark_tpu.cli.main <tool> ...`` command line of a script;
    comments and quoted prose do not count."""
    with open(os.path.join(SCRIPTS, name)) as f:
        text = f.read().replace("\\\n", " ")
    calls = []
    for line in text.splitlines():
        line = re.sub(r"'[^']*'|\"[^\"]*\"", "''", line.split("#")[0])
        calls += re.findall(
            r"(?:(?<![\w.$/<-])bst|-m\s+bigstitcher_spark_tpu\.cli\.main)"
            r"\s+([a-z][\w-]*)(?:\s+([\w-]+))?", line)
    return calls


@pytest.mark.parametrize("script", sorted(
    n for n in os.listdir(SCRIPTS) if n.endswith(".sh") and script_calls(n)))
def test_every_tool_a_script_calls_is_a_command(script):
    for tool, sub in script_calls(script):
        cmd = cli.commands.get(tool)
        assert cmd is not None, f"{script}: `bst {tool}` is no command"
        if isinstance(cmd, click.Group) and sub and not sub.startswith("-"):
            assert sub in cmd.commands, \
                f"{script}: `bst {tool} {sub}` is no command"
