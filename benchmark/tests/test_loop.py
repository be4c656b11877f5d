"""The measured loop on a fake step: stamps, counts and the window's end."""

import itertools
import time

import pytest

from benchmark.lib import loop


class _Loss:
    def block_until_ready(self):
        return self


def _step(seconds):
    def step(state, batch):
        time.sleep(seconds)
        return state + 1, _Loss()
    return step


def test_every_dispatch_gets_a_stamp_one_step_late():
    state, rec = loop.run_window(
        _step(0.0), 0, itertools.count(), iterations=7
    )
    assert state == rec["dispatched"] == 7
    assert len(rec["stamps"]) == 7 and len(rec["sync_s"]) == 6
    assert len(rec["dispatch_s"]) == len(rec["input_wait_s"]) == 7
    assert rec["stamps"] == sorted(rec["stamps"])
    with pytest.raises(ValueError):
        loop.run_window(_step(0.0), 0, itertools.count())


def test_a_short_window_runs_on_until_it_has_its_steps():
    _, rec = loop.run_window(
        _step(0.002), 0, itertools.count(), seconds=0.05, min_steps=40
    )
    assert len(rec["stamps"]) - 1 >= 40
    assert rec["stamps"][-1] - rec["t_begin"] >= 0.05


def test_but_never_beyond_three_times_its_seconds():
    _, rec = loop.run_window(
        _step(0.005), 0, itertools.count(), seconds=0.05, min_steps=10**6
    )
    assert 0.15 <= rec["stamps"][-1] - rec["t_begin"] < 0.3
