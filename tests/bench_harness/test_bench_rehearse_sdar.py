"""The whole command at toy size on the CPU for the SDAR cell, traced:
every path walked under the harness's rehearsal flag as the driver would
run it, no result printed."""

from bench_rehearsal_helper import rehearse


def test_sdar_cell_walks_every_path_traced():
    said, would = rehearse("serve-sdar-blockgen-steady", trace=1,
                           seconds="8")
    assert would["attempted"] > 0 and would["failed"] == 0
    m = would["metrics"]
    for name in ("gen_late_p95_ms", "ready_s", "ttft_p50_ms", "tpot_p50_ms",
                 "decode_step_ms.tail", "prefill_pass_ms.tail",
                 "moe_experts_touched_pct.tail",
                 "moe_load_max_over_mean.tail", "paged_grid_live_pct.tail",
                 "decode_runahead_pct.tail", "block_passes_per_block.tail",
                 "block_tokens_per_lane_pass.tail",
                 "decode_hbm_bound_pct.sdar"):
        assert m[name]["value"] > 0, name
    # no confidence of random weights clears 0.9: four denoising passes
    # and a commit a block but the last, whose lane-pass in flight counts
    assert 4.0 < m["block_passes_per_block.tail"]["value"] <= 5.0
    assert 0.6 < m["block_tokens_per_lane_pass.tail"]["value"] <= 1.0
    assert m["compiles_in_window.tail"]["value"] == 0
    for other in ("decode_hbm_bound_pct", "decode_hbm_bound_pct.laguna",
                  "paged_decode_kernel_busy_pct", "ttft_p75_ms"):
        assert other not in m
    dev = would["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert said["client"]["finished"] == would["attempted"]
    rep = said["replicas"]
    assert rep["model"] == {"experts_held": [0, 8], "num_experts": 8,
                            "vocab_rows": 256}
    assert [layer[0] for layer in rep["cache_spec"]] == ["full"] * 3
    # one prefill width (256 positions are 4 chunks), two block widths
    assert rep["compiled_steps"] == [3]
    # the routers balanced before warm-up (replica_sdar.py), layer by layer
    assert [b["layer"] for b in rep["routers_balanced"]] == [0, 1, 2]
    assert all(0 < b["touched_balanced"] <= 1
               for b in rep["routers_balanced"])
    ref = said["reference"]
    assert ref["positions"] >= 16 * 17 and ref["judged"] >= 24
    assert ref["off_share"] <= ref["max_off_share"]
    assert ref["moved_asked_alone"] == 0
