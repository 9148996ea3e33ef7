"""The whole command at toy size on the CPU for the Mellum training
cell, traced: every path walked under the harness's rehearsal flag as
the driver would run it, no result printed."""

from bench_rehearsal_helper import rehearse


def test_mellum_train_cell_walks_every_path_traced():
    said, would = rehearse("train-mellum2-4l-8k", trace=1, seconds="3")
    assert would["attempted"] > 0 and would["failed"] == 0
    m = would["metrics"]
    for name in ("ready_s", "train_step_p50_ms", "train_mfu_pct.mellum",
                 "moe_load_max_over_mean.train",
                 "moe_row_tiles_active_pct.train"):
        assert m[name]["value"] > 0, name
    assert m["moe_load_max_over_mean.train"]["value"] >= 1.0
    assert m["moe_row_tiles_active_pct.train"]["value"] <= 100
    assert "train_mfu_pct" not in m          # Llama's arithmetic
    assert "attn_kernel_busy_pct.train" not in m
    assert "train_tokens_per_s" not in m     # the untraced run's
    dev = would["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    gaps = dict(would["breakdown"]["idle_gaps"])
    assert all(k.startswith(("train.step.", "bench:", "train worker"))
               for k in gaps)
    t = said["train"]
    assert t["n_params"] == 207424 and t["steps"] == would["attempted"]
    c = t["counters"]
    assert c["train_moe_layer_passes_total"] == 4 * t["steps"]
    assert (0 < c["train_moe_row_tiles_active_total"]
            < c["train_moe_row_tiles_total"])
    r = said["reference"]
    assert r["loss_off_by"] <= r["loss_tol"]
    assert set(r["grad_error_worst"]) == set(r["grad_tol"]) == {
        "embed", "head", "attention", "router", "w1", "w3", "w2"}
    for group, err in r["grad_error_worst"].items():
        limit = r["grad_tol"][group]
        assert (limit is None and group == "head") or err <= limit, group
    assert len(r["routing_differs"]) == 4
    assert max(r["routing_differs"]) <= r["max_routing_differs"]
    # the timed step itself: its own first loss, and the state it left
    assert r["step_loss"] == t["warm_losses"][0]
    assert r["step_loss_off_by"] <= r["loss_tol"]
    assert len(r["update_errors"]) == 2 + 1 + 4 * 10     # every leaf
    assert 0 <= max(r["update_error_worst"].values()) <= r["update_tol"]
    assert said["also"]["train_tokens_per_s"] > 0
    assert "reference_lower_precision" not in said   # a sweep's alone
