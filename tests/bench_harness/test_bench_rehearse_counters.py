"""The serving cells' rehearsals, traced: every per-layer metric that
reads `LLMEngine.stats()`' phase, request, compile and start-up counters
is in the line of its cell, and the engine's spans name the gaps."""

import os
import sys

import pytest

from bench_rehearsal_helper import rehearse

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.spec import Spec  # noqa: E402

SPEC = Spec(REPO)


def counter_metrics(cell):
    return [m["name"] for m in SPEC.metrics_of("per_layer", cell)
            if m["source"] == "program_counter"]


@pytest.mark.parametrize("cell", ["serve-chat-steady",
                                  "serve-chat-overload"])
def test_a_traced_rehearsal_prints_every_counter_metric(cell):
    _said, would = rehearse(cell, trace=1, seconds="8")
    m = would["metrics"]
    assert set(counter_metrics(cell)) <= set(m), sorted(m)
    suffix = ".tail" if cell == "serve-chat-steady" else ".load"
    for name in ("step_prefill_share_pct", "step_host_share_pct",
                 "prefill_fill_pct"):
        assert 0 < m[name + suffix]["value"] < 100, name
    assert m["compiles_in_window" + suffix]["value"] == 0
    assert m["replica_warm_s"]["value"] > 0
    assert m["replica_weights_s"]["value"] > 0
    if cell == "serve-chat-steady":
        assert m["queue_wait_mean_ms"]["value"] >= 0
        assert m["prefill_wait_mean_ms"]["value"] > 0
        # nothing on the CPU is a tpu_custom_call: the kernel's share has
        # nothing to read and is left out
        assert "paged_decode_kernel_busy_pct" not in m
    else:
        assert 1 <= m["decode_lanes_mean.load"]["value"] <= 16
    # the engine's spans are on the profiler's clock: the gaps the
    # harness could not name before carry the engine's own names now
    gaps = dict(would["breakdown"]["idle_gaps"])
    assert any(label.startswith("llm.") for label in gaps), gaps
