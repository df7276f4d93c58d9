"""The window rule: whole passes, none started after --seconds."""

import pytest

from benchmark import window


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("seconds,pass_s,expected", [
    (10.0, 4.0, 3),     # starts at 0, 4, 8; 12 >= 10 ends it
    (10.0, 5.0, 2),     # the pass that would start at 10 does not
    (10.0, 25.0, 1),    # one pass always runs, and finishes
    (0.0, 1.0, 1),
])
def test_no_pass_starts_after_the_seconds(seconds, pass_s, expected):
    clock = Clock()
    starts = []

    def one(index):
        starts.append(clock.t - 100.0)
        clock.t += pass_s
        return {"work": 6.0}

    win = window.run_window(one, seconds, clock=clock)
    assert len(win["passes"]) == expected
    assert all(s < seconds or i == 0 for i, s in enumerate(starts))
    # the pass in progress finished and counts, and so does its time
    assert win["window_s"] == pytest.approx(expected * pass_s)
    assert [p["start_s"] for p in win["passes"]] == pytest.approx(starts)


def test_a_stalled_pass_lowers_the_rate():
    clock = Clock()
    lengths = iter([1.0, 7.0, 1.0])

    def one(index):
        clock.t += next(lengths)
        return {"work": 6.0}

    win = window.run_window(one, 8.5, clock=clock)
    work = sum(p["work"] for p in win["passes"])
    assert work / win["window_s"] == pytest.approx(18 / 9.0)
