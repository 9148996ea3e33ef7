"""The whole command at toy size on the CPU, serving cell below the
knee, traced: every path walked under the harness's rehearsal flag, no
result printed."""

from bench_rehearsal_helper import rehearse


def test_steady_cell_walks_every_path_traced():
    # 8 s: the profiler's first start can take seconds on a busy box, and
    # the traced span has to catch some of the window's requests
    said, would = rehearse("serve-chat-steady", trace=1, seconds="8")
    assert would["attempted"] > 0 and would["failed"] == 0
    m = would["metrics"]
    for name in ("gen_late_p95_ms", "ready_s", "ttft_p50_ms", "ttft_p95_ms",
                 "tpot_p50_ms",
                 "decode_step_ms.tail", "decode_hbm_bound_pct"):
        assert m[name]["value"] > 0, name
    # a toy request is over in milliseconds: a poll may find no lane held
    assert 0 <= m["lane_occupancy_pct.tail"]["value"] <= 100
    assert "ttft_p75_ms" not in m          # end-to-end: the untraced run's
    assert "decode_step_ms.load" not in m  # the overloaded cell's
    dev = would["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert would["breakdown"]["device_ops"]
    assert said["client"]["finished"] == would["attempted"]
    assert said["replicas"]["compiled_steps"] == [3]
    # all 16 tokens of all four canaries were held to the reference
    ref = said["reference"]
    assert ref["positions"] == 64
    assert ref["worst_ulps"] <= ref["tolerance_ulps"]
    assert "harness" in said["stalls"]
    assert len(said["replica_stalls"]["since_warm_up"]) == 1
