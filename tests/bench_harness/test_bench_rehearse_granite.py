"""The whole command at toy size on the CPU for the hybrid state-space
cell, traced: every path walked under the harness's rehearsal flag as
the driver would run it, no result printed."""

from bench_rehearsal_helper import rehearse


def test_granite_cell_walks_every_path_traced():
    said, would = rehearse("serve-granite-longanswer-steady", trace=1,
                           seconds="8")
    assert would["attempted"] > 0 and would["failed"] == 0
    m = would["metrics"]
    for name in ("gen_late_p95_ms", "ready_s", "ttft_p50_ms", "tpot_p50_ms",
                 "decode_step_ms.tail", "prefill_pass_ms.tail",
                 "paged_grid_live_pct.tail", "decode_hbm_bound_pct.granite",
                 "state_lanes_per_decode_step.tail",
                 "state_pool_fill_pct.tail"):
        assert m[name]["value"] > 0, name
    assert m["compiles_in_window.tail"]["value"] == 0
    # four lanes, four state slots
    assert m["state_lanes_per_decode_step.tail"]["value"] <= 4
    assert m["state_pool_fill_pct.tail"]["value"] <= 100
    # other families' arithmetic and kernels are not read here, and the
    # interpreter's trace names no kernel
    for name in ("decode_hbm_bound_pct", "decode_hbm_bound_pct.laguna",
                 "decode_hbm_bound_pct.pangu", "latent_decode_roofline_pct",
                 "moe_busy_pct", "kv_window_pages_saved_pct",
                 "ssm_decode_roofline_pct", "ttft_p75_ms",
                 "host_turnaround_ms.tail", "device_starved_pct.tail"):
        assert name not in m, name
    dev = would["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert said["client"]["finished"] == would["attempted"]
    rep = said["replicas"]
    assert rep["cache_kinds"] == ["state", "state", "full", "state"]
    # three state layers, five slots of (3 x (512 + 32) conv inputs and
    # 16 x 32 x 16 state numbers), float32
    assert rep["state_pool_bytes"] == 3 * 5 * (3 * 544 + 8192) * 4
    ref = said["reference"]
    assert ref["positions"] == ref["judged"] == sum(
        min(m, 64) for _n, m in (
            (24, 16), (64, 16), (150, 32), (330, 16), (700, 64), (1100, 16),
            (1500, 256), (2048, 1024)))
    assert ref["near_tie_share"] == 0.0 and ref["moved_asked_alone"] == 0
    assert ref["off_share"] <= ref["max_off_share"]
