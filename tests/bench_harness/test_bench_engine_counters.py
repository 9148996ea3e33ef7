"""The two readers of the engine's cumulative counters
(`LLMEngine.stats()`), on hand-made polls."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.readers import stats_first, stats_ratio  # noqa: E402


def poll(steps, secs, admit, sync, warm=2.5):
    return {"decode_steps": steps, "step_secs": secs,
            "phase_secs": {"admit": admit, "decode_sync": sync},
            "startup_secs": {"warm": warm}}


def test_stats_ratio_is_the_windows_change_over_all_replicas():
    polls = [[poll(10, 1.0, 0.1, 0.5), poll(99, 9.0, 0.9, 4.0),
              poll(30, 3.0, 0.4, 1.5)],
             [poll(0, 0.0, 0.0, 0.0), poll(20, 1.0, 0.1, 0.5)]]
    obs = {"polls": polls}
    # replicas summed: (0.3 + 0.1) / (2.0 + 1.0); the middle poll is not read
    assert stats_ratio.read(obs, {"num": ["phase_secs.admit"],
                                  "den": ["step_secs"], "scale": 100.0}
                            ) == pytest.approx(100.0 * 0.4 / 3.0)
    # keys summed: (0.4 + 1.5) / 3.0
    assert stats_ratio.read(obs, {"num": ["phase_secs.admit",
                                          "phase_secs.decode_sync"],
                                  "den": ["step_secs"]}
                            ) == pytest.approx(1.9 / 3.0)
    # no denominator: the plain change, and a change of nothing is 0
    assert stats_ratio.read(obs, {"num": ["decode_steps"]}) == 40
    still = {"polls": [[poll(5, 1.0, 0.1, 0.5), poll(5, 2.0, 0.2, 0.6)]]}
    assert stats_ratio.read(still, {"num": ["decode_steps"]}) == 0
    # a denominator that did not move, one poll, no polls, and a program
    # without the counter: nothing to read, and no error
    assert stats_ratio.read(still, {"num": ["step_secs"],
                                    "den": ["decode_steps"]}) is None
    for empty in ({}, {"polls": []}, {"polls": [[poll(1, 1, 1, 1)]]}):
        assert stats_ratio.read(empty, {"num": ["decode_steps"]}) is None
    for missing in ("prefill_secs", "phase_secs.prefill_sync",
                    "decode_steps.deeper"):
        assert stats_ratio.read(obs, {"num": [missing],
                                      "den": ["step_secs"]}) is None
        assert stats_ratio.read(obs, {"num": ["step_secs"],
                                      "den": [missing]}) is None


def test_stats_first_is_the_first_polls_and_the_slowest_replicas():
    obs = {"polls": [[poll(1, 1, 1, 1, warm=2.5), poll(2, 2, 2, 2, warm=9)],
                     [poll(1, 1, 1, 1, warm=4.0)], []]}
    assert stats_first.read(obs, {"key": "startup_secs.warm"}) == 4.0
    assert stats_first.read(obs, {"key": "startup_secs.params"}) is None
    assert stats_first.read({"polls": []}, {"key": "startup_secs.warm"}) \
        is None
    assert stats_first.read({}, {"key": "startup_secs.warm"}) is None
