"""The reduction from a profiler trace to busy time, module time and
attributed idle gaps: on a hand-made trace whose answers are known, and on
a small trace recorded on a TPU v5e."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
S = 1e9


def planes(offset=0.0):
    """Two devices. Device 0 runs ops over [1,2] and [1.5,3] (union 2 s)
    and [6,7]; device 1 one op over [2,4]. Window [0,10]."""
    def ev(name, a, b):
        return (name, a * S - offset, (b - a) * S)
    return [
        ("/host:CPU", [("python", [ev(tr.ANCHOR, 0.0, 0.001)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [ev("jit_pcm_peaks(123)", 1, 3),
                             ev("jit_pcm_peaks(123)", 6, 7)]),
            ("XLA Ops", [ev("fft.1", 1, 2), ev("fusion.2", 1.5, 3),
                         ev("fft.1", 6, 7)]),
        ]),
        ("/device:TPU:1", [
            ("XLA Modules", [ev("jit_other(9)", 2, 4)]),
            ("XLA Ops", [ev("copy.3", 2, 4)]),
        ]),
    ]


RING = [
    {"ts": 0.5, "ph": "B", "name": "pair.drain", "tid": 1},
    {"ts": 9.5, "ph": "E", "name": "pair.drain", "tid": 1},
    {"ts": 3.0, "ph": "B", "name": "stitching.refine", "tid": 1},
    {"ts": 5.9, "ph": "E", "name": "stitching.refine", "tid": 1},
    {"ts": 7.0, "ph": "B", "name": "stitching.extract", "tid": 1},
    {"ts": 8.0, "ph": "E", "name": "stitching.extract", "tid": 1},
    {"ts": 9.0, "ph": "B", "name": "never.closed", "tid": 2},
]


@pytest.mark.parametrize("offset", [0.0, 1.7e18])
def test_hand_made_trace(offset):
    """Whatever the profiler's zero, the anchor ties it to the host."""
    out = tr.reduce_planes(planes(offset * 1.0), 0.0 if offset else None,
                           0.0, 10.0, RING)
    assert out["busy_s"] == pytest.approx({"0": 3.0, "1": 2.0})
    assert out["busy_s_max"] == pytest.approx(3.0)
    assert out["busy_s_mean"] == pytest.approx(2.5)
    assert out["modules"]["jit_pcm_peaks"] == [2, pytest.approx(3.0)]
    assert out["modules"]["jit_other"] == [1, pytest.approx(2.0)]
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["module:jit_pcm_peaks"] == pytest.approx(3.0)
    assert ops["fft.1"] == pytest.approx(2.0)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["device1:stitching.extract", pytest.approx(6.0)]
    assert ["device0:stitching.refine", pytest.approx(3.0)] in gaps
    assert ["device0:pair.drain", pytest.approx(3.0)] in gaps
    # a gap goes to the innermost span open at its middle, not to the
    # long one around it; where none is open it says so
    assert ["device0:pair.drain", pytest.approx(1.0)] in gaps
    assert tr._span_over(tr.open_spans(RING), 9.6, 9.8) == "(no span)"
    assert len(gaps) <= 10


def test_the_window_clips():
    out = tr.reduce_planes(planes(), None, 1.75, 6.5, RING)
    assert out["busy_s"] == pytest.approx({"0": 1.25 + 0.5, "1": 2.0})
    assert out["modules"]["jit_pcm_peaks"] == [2, pytest.approx(1.75)]


def test_no_device_plane_gives_nothing():
    host_only = [p for p in planes() if p[0].startswith("/host")]
    assert tr.reduce_planes(host_only, None, 0.0, 10.0, RING) is None


def test_recorded_v5e_trace():
    """data/v5e_small.xplane.pb: three calls of jit_pcm_peaks and three of
    jit_fuse_block_shift_impl on one TPU v5 lite chip with sleeps between
    (benchmark/README.md says how it was recorded)."""
    path = os.path.join(HERE, "data", "v5e_small.xplane.pb")
    loaded = tr.load_planes(path)
    starts = [s for _p, lines in loaded for _l, evs in lines
              for _n, s, _d in evs]
    t0, t1 = min(starts) / S, max(starts) / S + 1.0
    out = tr.reduce_planes(loaded, None, t0, t1, [])
    assert list(out["busy_s"]) == ["0"]
    assert out["modules"]["jit_pcm_peaks"][0] == 3
    assert out["modules"]["jit_fuse_block_shift_impl"][0] == 3
    module_s = sum(v[1] for v in out["modules"].values())
    assert 0 < out["busy_s"]["0"] <= module_s * 1.001
    # the sleeps between the calls are the longest gaps
    assert out["breakdown"]["idle_gaps"][0][1] > 0.015
    assert out["busy_s"]["0"] < 0.5 * (t1 - t0)
