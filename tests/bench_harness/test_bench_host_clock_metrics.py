"""The six per-layer metrics that read the engine's clock beyond the
step (PR 36: `device_starved_pct.*`, `host_turnaround_ms.*`,
`host_off_cpu_pct.*`): data files for the reader that was there
(`stats_ratio`), found through BENCHMARK.json as every metric is; and
what `trace_reduce` makes of a gap that straddles two `llm.step` spans,
with and without the span that runs across them."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import trace_reduce  # noqa: E402
from benchmarks.spec import Spec  # noqa: E402

TAIL = ["serve-chat-steady", "serve-laguna-mixed-steady",
        "serve-pangu-longprompt-steady"]
LOAD = ["serve-chat-overload"]
PARTS = ("build", "dispatch", "sync", "emit")


def _stats(loop, starved, turn_secs, turns, wall, off_cpu):
    """What a poll holds of `LLMEngine.stats()` for these metrics, and
    beside it what the program had before (a parent's poll has no more)."""
    phases = dict.fromkeys(
        ["prefill_" + part for part in PARTS]
        + ["decode_" + part for part in PARTS], 0.0)
    phases.update(admit=0.1 * loop, decode_sync=0.8 * loop)
    return {"decode_steps": int(100 * loop), "step_secs": 0.9 * loop,
            "phase_secs": phases,
            "loop_secs": loop, "starved_secs_total": starved,
            "starved_secs": {"between": starved},
            "turnaround_secs": turn_secs, "turnarounds_total": turns,
            "host_wall_secs": wall, "host_off_cpu_secs": off_cpu}


# one replica's first and last poll inside the window, and a second
# replica's (the reader sums the changes over replicas)
POLLS = [[_stats(10.0, 0.5, 2.0, 500, 4.0, 0.25),
          _stats(99.0, 9.9, 9.9, 999, 9.9, 9.9),       # not read
          _stats(60.0, 5.5, 12.0, 3000, 24.0, 1.25)],
         [_stats(0.0, 0.0, 0.0, 0, 0.0, 0.0),
          _stats(50.0, 5.0, 10.0, 2500, 20.0, 3.0)]]
WANT = {"device_starved_pct": 100.0 * (5.0 + 5.0) / (50.0 + 50.0),
        "host_turnaround_ms": 1000.0 * (10.0 + 10.0) / (2500 + 2500),
        "host_off_cpu_pct": 100.0 * (1.0 + 3.0) / (20.0 + 20.0)}
METRICS = [(name + suffix, cells, moves, want)
           for name, want in WANT.items()
           for suffix, cells, moves in ((".tail", TAIL, "tpot_p95_ms"),
                                        (".load", LOAD,
                                         "serve_tokens_per_s"))]


@pytest.fixture(scope="module")
def spec():
    return Spec()


def _read(spec, name, cell, obs):
    """`Spec.read_layer_metrics` of the one metric `name`: its entry in
    BENCHMARK.json, its file, its reader (the other metrics' readers
    want a whole run's observations)."""
    one = Spec()
    one.benchmark = dict(spec.benchmark, per_layer=[
        m for m in spec.benchmark["per_layer"] if m["name"] == name])
    assert len(one.benchmark["per_layer"]) == 1, name
    return one.read_layer_metrics(cell, obs)


@pytest.mark.parametrize("name,cells,moves,want", METRICS,
                         ids=[m[0] for m in METRICS])
def test_a_host_clock_metric_reads_the_windows_change(spec, name, cells,
                                                      moves, want):
    entry = [m for m in spec.benchmark["per_layer"] if m["name"] == name]
    assert len(entry) == 1, name
    entry = entry[0]
    # the entry as the issue gives it; its cells exist and report the
    # end-to-end metric it moves; its file exists and agrees with it
    assert entry["workloads"] == cells and entry["moves"] == moves
    assert (entry["source"], entry["layer"], entry["better"]) \
        == ("program_counter", "engine", "lower")
    known = {w["name"] for w in spec.benchmark["workloads"]}
    assert set(cells) <= known
    moved = [m for m in spec.benchmark["end_to_end"] if m["name"] == moves]
    assert moved and set(cells) <= set(moved[0].get("workloads", known))
    path = os.path.join(REPO, "benchmarks", "layer_metrics", name + ".json")
    with open(path) as f:
        meta = json.load(f)
    assert meta["reader"] == "stats_ratio" and meta["unit"] == entry["unit"]
    assert os.path.isfile(os.path.join(REPO, "benchmarks", "readers",
                                       meta["reader"] + ".py"))
    for cell in cells:
        assert _read(spec, name, cell, {"polls": POLLS}) == {
            name: {"value": pytest.approx(want), "unit": entry["unit"]}}
    # a cell that is not the metric's does not report it
    for cell in (set(TAIL) | set(LOAD)) - set(cells):
        assert _read(spec, name, cell, {"polls": POLLS}) == {}


@pytest.mark.parametrize("name,cells,moves,want", METRICS,
                         ids=[m[0] for m in METRICS])
def test_a_program_without_the_counter_reports_nothing(spec, name, cells,
                                                       moves, want):
    """The parent's polls (no clock beyond the step) and a window in
    which the denominator stood still: the metric is left out, nothing
    raises, and the metrics the parent has are read as before."""
    new = {"loop_secs", "starved_secs_total", "starved_secs",
           "turnaround_secs", "turnarounds_total", "host_wall_secs",
           "host_off_cpu_secs"}
    parents = [[{k: v for k, v in row.items() if k not in new}
                for row in rows] for rows in POLLS]
    still = [[POLLS[0][0], dict(POLLS[0][0], decode_steps=2000)]]
    had = "step_host_share_pct." + name.rsplit(".", 1)[1]
    for cell in cells:
        for obs in ({"polls": parents}, {"polls": still}, {}):
            assert _read(spec, name, cell, obs) == {}
        assert _read(spec, had, cell, {"polls": parents})[had]["value"] \
            == pytest.approx(100.0 * 0.1 / 0.9)


def _reduced(host, gap=(0.010, 0.014)):
    """One device whose operations leave ONE gap, [10, 14) ms without
    another, under `host`'s spans (seconds)."""
    devices = {"/device:TPU:0": [(gap[0] - 0.010, gap[0], "pass n+1"),
                                 (gap[1], gap[1] + 0.010, "pass n+2")]}
    out = trace_reduce.reduce_events(
        devices, host, unattributed="engine host, unattributed")
    (label, secs), = out["idle_gaps"]
    assert secs == pytest.approx(gap[1] - gap[0])
    return label


# the gap straddles two steps: it begins in step n+1's emit and ends in
# step n+2's first dispatch, and no piece of it, nor either step, is
# half of it
STRADDLE = [(0.0040, 0.0119, "llm.step"), (0.0095, 0.0118, "llm.decode.emit"),
            (0.0119, 0.0121, "llm.between"),
            (0.0121, 0.0200, "llm.step"), (0.0121, 0.0126, "llm.admit"),
            (0.0126, 0.0134, "llm.decode.build"),
            (0.0134, 0.0145, "llm.decode.dispatch")]
TURNAROUND = (0.0096, 0.0141, "llm.turnaround")
# the same gap under a build that takes most of it
LONG_BUILD = [(0.0040, 0.0109, "llm.step"), (0.0095, 0.0108, "llm.decode.emit"),
              (0.0109, 0.0110, "llm.between"),
              (0.0110, 0.0200, "llm.step"), (0.0110, 0.0114, "llm.admit"),
              (0.0114, 0.0136, "llm.decode.build"),
              (0.0136, 0.0145, "llm.decode.dispatch")]


@pytest.mark.parametrize("host,label", [
    (STRADDLE, "engine host, unattributed"),
    (STRADDLE + [TURNAROUND], "llm.turnaround"),
    # a phase that covers half of the gap still wins: it is the shorter
    (LONG_BUILD + [TURNAROUND], "llm.decode.build"),
    # and a step that covers the gap whole loses to the turnaround in it
    ([(0.0040, 0.0200, "llm.step"), TURNAROUND], "llm.turnaround"),
], ids=["no-turnaround-span", "turnaround-span", "a-phase-covers-half",
        "inside-one-step"])
def test_a_gap_across_two_steps_goes_to_the_span_across_them(host, label):
    assert _reduced(host) == label


# a lull in the arrivals: 290 ms with nothing to do, cut into parks of
# 50 ms with a step that found nothing between them
LULL = (0.100, 0.390)
PARKS = [(0.101 + 0.0502 * i, 0.151 + 0.0502 * i, "llm.park")
         for i in range(6)]
IDLE_STEPS = [(e, e + 0.0002, "llm.step") for _s, e, _n in PARKS]


@pytest.mark.parametrize("host,label", [
    (PARKS + IDLE_STEPS, "engine host, unattributed"),
    (PARKS + IDLE_STEPS + [(0.101, 0.4025, "llm.idle")], "llm.idle"),
], ids=["parks-alone", "idle-span"])
def test_a_lull_longer_than_two_parks_goes_to_the_span_over_it(host, label):
    assert _reduced(host, LULL) == label
