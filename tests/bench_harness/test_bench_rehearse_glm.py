"""The whole command at toy size on the CPU for the sparse-attention
cell, traced: every path walked under the harness's rehearsal flag as
the driver would run it, no result printed."""

from bench_rehearsal_helper import rehearse


def test_glm_cell_walks_every_path_traced():
    said, would = rehearse("serve-glm5-longcontext-steady", trace=1,
                           seconds="8")
    assert would["attempted"] > 0 and would["failed"] == 0
    m = would["metrics"]
    for name in ("gen_late_p95_ms", "ready_s", "ttft_p50_ms", "tpot_p50_ms",
                 "decode_step_ms.tail", "prefill_pass_ms.tail",
                 "moe_experts_touched_pct.tail",
                 "moe_load_max_over_mean.tail",
                 "sparse_selected_share_pct.tail",
                 "decode_hbm_bound_pct.glm",
                 "sparse_rows_per_decode_lane.tail"):
        assert m[name]["value"] > 0, name
    assert m["compiles_in_window.tail"]["value"] == 0
    # index_topk 64 of toy contexts of 70 to 250 rows: a selection that
    # selects, and a decode lane gathers 64 rows a layer (the device's
    # count is read back a step behind the host's count of lane-steps,
    # so a window's two polls can cut a step's lanes apart)
    assert 10 < m["sparse_selected_share_pct.tail"]["value"] < 90
    assert abs(m["sparse_rows_per_decode_lane.tail"]["value"] - 64) <= 6
    # the dense family's arithmetic and other kernels are not read here
    for name in ("decode_hbm_bound_pct.pangu", "latent_decode_roofline_pct",
                 "latent_rows_per_decode_lane.tail",
                 "paged_grid_live_pct.tail", "decode_hbm_bound_pct",
                 "paged_decode_kernel_busy_pct", "ttft_p75_ms"):
        assert name not in m, name
    dev = would["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert said["client"]["finished"] == would["attempted"]
    rep = said["replicas"]
    assert rep["model"] == {"experts_held": [0, 4], "num_experts": 8,
                            "vocab_rows": 256}
    assert rep["cache_spec"] == [["full", 0, 40, 16]] * 5
    # two pools a layer: 40 numbers a row stored 128 wide, and the index
    # key of 16, float32
    slots = (1 + 4 * 16) * 16
    assert rep["latent_pool_bytes"] == 5 * slots * 128 * 4
    assert rep["index_pool_bytes"] == 5 * slots * 16 * 4
    assert rep["kv_pool_bytes"] == rep["latent_pool_bytes"] \
        + rep["index_pool_bytes"]
    assert [p["layer"] for p in rep["placement"]] == [1, 2, 3, 4]
    for p in rep["placement"]:
        assert abs(p["share_placed"] - 0.5) <= 0.05, p   # 8 experts: coarse
    # one prefill width (256 positions are 4 chunks), decode tables of 4
    # pages (the dense path) and 16 (top-k, gather)
    assert rep["compiled_steps"] == [3]
    ref = said["reference"]
    assert ref["positions"] == 128 and ref["judged"] >= 32
    assert ref["near_tie_share"] <= 0.5
    assert ref["worst_ulps"] <= ref["tolerance_ulps"]
