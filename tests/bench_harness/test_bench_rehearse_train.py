"""The whole command at toy size on the CPU, training cell; and the
command's refusal to print a result where there is no chip."""

from bench_rehearsal_helper import rehearse, run_cell


def test_no_chip_no_result():
    proc, said, results = run_cell(
        "--workload", "train-2l-8k", "--seed", "1", "--seconds", "1",
        "--trace", "0")
    assert proc.returncode == 1
    assert results == [] and "rehearsal" not in said
    assert "harness" in said["stalls"]      # a failed run says so too
    assert "TPU chip" in proc.stderr


def test_train_cell_walks_every_path_traced():
    said, would = rehearse("train-2l-8k", trace=1, seconds="2")
    m = would["metrics"]
    assert set(m) == {"ready_s", "train_step_p50_ms", "train_mfu_pct"}
    assert m["train_step_p50_ms"]["value"] > 0
    assert would["attempted"] > 0
    gaps = dict(would["breakdown"]["idle_gaps"])
    assert any(k.startswith("bench:") for k in gaps) or not gaps
    t = said["train"]
    assert t["loss_off_by"] <= t["loss_tol"] and t["compiles"] > 0
    assert "harness" in said["stalls"]
    assert "since_its_first_line" in said["worker_stalls"]
    assert said["also"]["train_tokens_per_s"] > 0
