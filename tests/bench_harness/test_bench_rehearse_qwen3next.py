"""The whole command at toy size on the CPU for the hybrid expert cell,
traced: every path walked under the harness's rehearsal flag as the
driver would run it, no result printed."""

from bench_rehearsal_helper import rehearse

CELL = "serve-qwen3next-manystreams-steady"


def test_qwen3next_cell_walks_every_path_traced():
    said, would = rehearse(CELL, trace=1, seconds="6")
    assert would["attempted"] > 0 and would["failed"] == 0
    m = would["metrics"]
    for name in ("gen_late_p95_ms", "ready_s", "ttft_p50_ms", "tpot_p50_ms",
                 "decode_step_ms.tail", "prefill_pass_ms.tail",
                 "paged_grid_live_pct.tail", "kv_pages_fill_pct.qwen3next",
                 "state_pool_fill_pct.tail", "moe_experts_touched_pct.tail",
                 "moe_load_max_over_mean.tail",
                 "moe_row_tiles_per_expert.tail",
                 "decode_hbm_bound_pct.qwen3next",
                 "moe_rows_per_expert_call.tail"):
        assert m[name]["value"] > 0, name
    assert m["compiles_in_window.tail"]["value"] == 0
    assert m["kv_pages_fill_pct.qwen3next"]["value"] <= 100
    assert m["moe_rows_per_expert_call.tail"]["value"] >= 1
    # other families' arithmetic is not read here, and the interpreter's
    # trace names no kernel
    for name in ("decode_hbm_bound_pct", "decode_hbm_bound_pct.olmo",
                 "decode_hbm_bound_pct.laguna", "gdn_chunk_roofline_pct",
                 "gdn_decode_roofline_pct",
                 "gdn_chunk_roofline_pct.qwen3next",
                 "gdn_chunk_kernel_busy_pct", "kv_pages_fill_pct.tail",
                 "gdn_decode_roofline_pct.qwen3next",
                 "moe_experts_roofline_pct", "ttft_p75_ms",
                 "host_turnaround_ms.tail", "device_starved_pct.tail"):
        assert name not in m, name
    dev = would["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert said["client"]["finished"] == would["attempted"]
    rep = said["replicas"]
    assert rep["cache_kinds"] == ["state", "state", "state", "full"]
    assert rep["model"] == {"experts_held": [0, 4], "num_experts": 8,
                            "vocab_rows": 512}
    # three linear layers, five slots of (3 x 128 conv inputs and 2 pairs
    # x 16 x 32 state numbers), float32
    assert rep["state_pool_bytes"] == 3 * 5 * (3 * 128 + 1024) * 4
    # 97 pages of 16 FLAT rows of 2 x 32 numbers, keys and values, one
    # full layer: what the shape says is what the memory holds
    assert rep["kv_pool_bytes"] - rep["state_pool_bytes"] \
        == 97 * 16 * 2 * 64 * 4
    assert rep["cache_spec"][3] == ["full", 0, 2, 32]
    ref = said["reference"]
    assert ref["positions"] == sum(
        min(m, 64) for _n, m in (
            (24, 16), (64, 16), (150, 32), (330, 16), (900, 64), (1100, 32),
            (3000, 128), (8192, 1536)))
    assert ref["judged"] >= ref["positions"] // 2
    assert ref["moved_asked_alone"] == 0
    assert ref["off_share"] <= ref["max_off_share"]
    assert ref["far_share"] <= ref["max_far_share"]
    # the longest canary's states, read from its slot in the pool behind
    # its last token: the float32 toy engine's are the reference's
    carry = said["carry"]
    assert len(carry["carry_layers"]) == 3
    assert carry["carry_off"] <= carry["carry_head_off"] < 1e-4
    assert carry["carry_layer_off"] < 1e-4 < carry["max_carry_off"]
