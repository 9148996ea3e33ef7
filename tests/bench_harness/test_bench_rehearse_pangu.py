"""The whole command at toy size on the CPU for the latent-attention
cell, traced: every path walked under the harness's rehearsal flag as
the driver would run it, no result printed."""

from bench_rehearsal_helper import rehearse


def test_pangu_cell_walks_every_path_traced():
    said, would = rehearse("serve-pangu-longprompt-steady", trace=1,
                           seconds="8")
    assert would["attempted"] > 0 and would["failed"] == 0
    m = would["metrics"]
    for name in ("gen_late_p95_ms", "ready_s", "ttft_p50_ms", "tpot_p50_ms",
                 "decode_step_ms.tail", "prefill_pass_ms.tail",
                 "moe_experts_touched_pct.tail",
                 "moe_load_max_over_mean.tail", "paged_grid_live_pct.tail",
                 "decode_hbm_bound_pct.pangu",
                 "latent_rows_per_decode_lane.tail"):
        assert m[name]["value"] > 0, name
    assert m["moe_experts_touched_pct.tail"]["value"] < 100
    assert m["compiles_in_window.tail"]["value"] == 0
    # a context of 8 to 216 rows a lane
    assert 8 <= m["latent_rows_per_decode_lane.tail"]["value"] <= 216
    # other families' arithmetic and kernels are not read here
    for name in ("decode_hbm_bound_pct", "decode_hbm_bound_pct.laguna",
                 "paged_decode_kernel_busy_pct", "kv_window_pages_saved_pct",
                 "ttft_p75_ms"):
        assert name not in m, name
    dev = would["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert said["client"]["finished"] == would["attempted"]
    rep = said["replicas"]
    assert rep["model"] == {"experts_held": [0, 4], "num_experts": 8,
                            "vocab_rows": 256}
    assert rep["cache_spec"] == [["full", 0, 0, 0, 40]] * 5
    # one pool a layer, 40 numbers a row stored 128 wide, float32
    assert rep["kv_pool_bytes"] == 5 * (1 + 4 * 16) * 16 * 128 * 4
    # the experts held here were placed by load: half of a sample's
    # assignments on this one of two chips, whatever the seed drew
    assert [p["layer"] for p in rep["placement"]] == [1, 2, 3, 4]
    for p in rep["placement"]:
        assert abs(p["share_placed"] - 0.5) <= 0.05, p   # 8 experts: coarse
        assert abs(p["share_placed"] - 0.5) <= abs(
            p["share_as_drawn"] - 0.5) + 1e-9, p
    # one prefill width (256 positions are 4 chunks), two decode widths
    assert rep["compiled_steps"] == [3]
    ref = said["reference"]
    assert ref["positions"] == 128 and ref["judged"] >= 32
    assert ref["near_tie_share"] <= 0.5
    assert ref["worst_ulps"] <= ref["tolerance_ulps"]
