"""The whole command at toy size on the CPU for the hybrid
linear-attention cell, traced: every path walked under the harness's
rehearsal flag as the driver would run it, no result printed."""

from bench_rehearsal_helper import rehearse

CELL = "serve-olmo-hybrid-longdoc-steady"


def test_olmo_cell_walks_every_path_traced():
    said, would = rehearse(CELL, trace=1, seconds="8")
    assert would["attempted"] > 0 and would["failed"] == 0
    m = would["metrics"]
    for name in ("gen_late_p95_ms", "ready_s", "ttft_p50_ms", "tpot_p50_ms",
                 "decode_step_ms.tail", "prefill_pass_ms.tail",
                 "paged_grid_live_pct.tail", "decode_hbm_bound_pct.olmo",
                 "kv_pages_fill_pct.tail"):
        assert m[name]["value"] > 0, name
    assert m["compiles_in_window.tail"]["value"] == 0
    assert m["kv_pages_fill_pct.tail"]["value"] <= 100
    # other families' arithmetic and kernels are not read here, and the
    # interpreter's trace names no kernel
    for name in ("decode_hbm_bound_pct", "decode_hbm_bound_pct.granite",
                 "decode_hbm_bound_pct.laguna", "ssm_decode_roofline_pct",
                 "state_pool_fill_pct.tail",
                 "state_lanes_per_decode_step.tail", "moe_busy_pct",
                 "gdn_chunk_roofline_pct", "gdn_decode_roofline_pct",
                 "ttft_p75_ms", "host_turnaround_ms.tail",
                 "device_starved_pct.tail"):
        assert name not in m, name
    dev = would["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert said["client"]["finished"] == would["attempted"]
    rep = said["replicas"]
    assert rep["cache_kinds"] == ["state", "state", "state", "full"]
    # three linear layers, five slots of (3 x 384 conv inputs and
    # 2 pairs x 24 x 96 state numbers), float32
    assert rep["state_pool_bytes"] == 3 * 5 * (3 * 384 + 4608) * 4
    # 97 pages of 16 rows of 8 heads x 32, keys and values, one full layer
    assert rep["kv_pool_bytes"] - rep["state_pool_bytes"] \
        == 97 * 16 * 2 * 8 * 32 * 4
    ref = said["reference"]
    assert ref["positions"] == ref["judged"] == sum(
        min(m, 64) for _n, m in (
            (24, 16), (64, 16), (150, 32), (330, 16), (900, 64), (1100, 32),
            (3000, 256), (5000, 128), (15872, 512)))
    assert ref["near_tie_share"] == 0.0 and ref["moved_asked_alone"] == 0
    assert ref["off_share"] <= ref["max_off_share"]
    # the longest canary's states, read from its slot in the pool behind
    # its last token: the float32 toy engine's are the reference's
    carry = said["carry"]
    assert len(carry["carry_layers"]) == 3
    assert carry["carry_off"] <= carry["carry_head_off"] < 1e-4
    assert carry["carry_layer_off"] < 1e-4 < carry["max_carry_off"]
    assert rep["engine_peak_bytes"] == [0]     # the CPU reads no memory
