"""The whole command at toy size on the CPU for the Laguna cell, traced:
every path walked under the harness's rehearsal flag as the driver would
run it, no result printed."""

from bench_rehearsal_helper import rehearse


def test_laguna_cell_walks_every_path_traced():
    said, would = rehearse("serve-laguna-mixed-steady", trace=1, seconds="8")
    assert would["attempted"] > 0 and would["failed"] == 0
    m = would["metrics"]
    for name in ("gen_late_p95_ms", "ready_s", "ttft_p50_ms", "tpot_p50_ms",
                 "decode_step_ms.tail", "prefill_pass_ms.tail",
                 "moe_experts_touched_pct.tail",
                 "moe_load_max_over_mean.tail",
                 "decode_hbm_bound_pct.laguna",
                 "kv_window_pages_saved_pct"):
        assert m[name]["value"] > 0, name
    assert m["moe_experts_touched_pct.tail"]["value"] < 100
    assert m["moe_load_max_over_mean.tail"]["value"] >= 1.0
    assert m["compiles_in_window.tail"]["value"] == 0
    assert "decode_hbm_bound_pct" not in m       # Llama's arithmetic
    assert "ttft_p75_ms" not in m                # the untraced run's
    dev = would["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert said["client"]["finished"] == would["attempted"]
    rep = said["replicas"]
    assert rep["model"] == {"experts_held": [0, 4], "num_experts": 8,
                            "vocab_rows": 256}
    assert [layer[0] for layer in rep["cache_spec"]] == [
        "full", "window", "window", "window", "full"]
    # one prefill width (256 positions are 4 chunks), two decode widths
    assert rep["compiled_steps"] == [3]
    ref = said["reference"]
    assert ref["positions"] == 128 and ref["judged"] >= 32
    assert ref["near_tie_share"] <= 0.5
    assert ref["worst_ulps"] <= ref["tolerance_ulps"]
