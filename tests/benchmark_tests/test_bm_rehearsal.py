"""Each cell rehearsed at toy size on XLA:CPU ends in a line with the
contract's keys; without --rehearse and without a TPU there is no line."""

import pytest

from bm_helpers import run_cell

CELLS = [("grid1k.stitch", 0, 1), ("grid1k.stitch", 1, 1),
         ("multiview.fuse", 0, 1), ("multiview.fuse", 1, 1)]


@pytest.mark.parametrize("workload,trace,devices", CELLS)
def test_rehearsal_ends_in_a_result_line(workload, trace, devices):
    rc, line, err = run_cell(workload, trace=trace, devices=devices,
                             seed=2147483659)
    assert rc == 0, err[-3000:]
    assert line["rehearsal"] is True
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    names = set(line["metrics"])
    if trace:
        assert "setup_s" not in names and "setup_warm_s" in names
        assert not any(n.endswith("_roofline") for n in names), \
            "a CPU run reports no share of a chip's roofline"
    else:
        assert "setup_s" in names and len(names) == 2
    assert all(isinstance(m["value"], float) and m["unit"]
               for m in line["metrics"].values())
    # each number compared stands beside its limit, last in the line and
    # last on standard error
    assert list(line)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert err.strip().splitlines()[-1].startswith("compared ")


def test_without_a_tpu_there_is_no_result():
    rc, line, err = run_cell("grid1k.stitch", rehearse=False)
    assert rc != 0 and line is None
    assert "needs 1 TPU chip" in err
