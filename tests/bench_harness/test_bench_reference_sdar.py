"""The SDAR configuration, its arithmetic, and the comparison that
decides `correct` in its cell — at a small size on the CPU."""

import copy
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import model_math_sdar as mm  # noqa: E402
from benchmarks.kinds import serve_sdar  # noqa: E402
from benchmarks.spec import Spec  # noqa: E402

SPEC = Spec(REPO)
CFG = SPEC.config("sdar-30b-a3b-chat-serve")
CELL = "serve-sdar-blockgen-steady"
GEN = CFG["generation"]

# https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json,
# every key of the catalog row's `config`
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
NEW_METRICS = {"block_passes_per_block.tail": "model step, serve",
               "block_tokens_per_lane_pass.tail": "model step, serve",
               "decode_hbm_bound_pct.sdar": "model step, serve",
               "block_decode_kernel_busy_pct": "kernels",
               "block_decode_roofline_pct": "kernels"}


def test_the_configuration_is_the_published_one_but_depth_and_positions():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == {"num_hidden_layers",
                                              "max_position_embeddings"}
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    assert (CFG["num_hidden_layers"], CFG["max_position_embeddings"]) == (
        6, 4096)
    assert set(CFG["why_reduced"]) == set(CFG["reduced"])
    assert set(CFG["assumed"]) >= {
        "block_length", "denoising_steps", "remasking_strategy",
        "mask_token_id", "no_shift", "qk_norm", "router_order",
        "torch_dtype"}
    assert GEN == {"block_length": 4, "denoising_steps": 4,
                   "remasking_strategy": "low_confidence_dynamic",
                   "confidence_threshold": 0.9, "mask_token_id": 151669}
    dep = CFG["deployment"]
    assert dep["kind"] == "serve_sdar" and "8-stage" in dep["stands_for"]
    assert dep["engine"] == {"max_batch": 48, "page_size": 16,
                             "prefill_chunk": 256}
    assert CFG["rehearsal"]["generation"]["block_length"] == 4
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == CFG["name"]][0]
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CFG["name"], "blockgen-steady", 1)
    for group in ("configs", "workloads"):
        for e in bench[group]:
            assert len(e["why"]) <= 200, e["name"]
    # the new metrics, found by NAME (the next PR's go behind them)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"] in NEW_METRICS}
    assert set(mine) == set(NEW_METRICS)
    for name, m in mine.items():
        assert m["workloads"] == [CELL] and m["layer"] == NEW_METRICS[name]
        assert m["moves"] == "tpot_p95_ms"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    reported = {m["name"] for m in SPEC.metrics_of("per_layer", CELL)}
    assert reported >= set(NEW_METRICS) | {
        "moe_busy_pct", "moe_experts_roofline_pct",
        "moe_experts_touched_pct.tail", "moe_load_max_over_mean.tail",
        "paged_grid_live_pct.tail", "decode_step_ms.tail",
        "decode_runahead_pct.tail", "ready_s"}
    # another model's arithmetic, another kernel's name, the lists
    # `test_bench_host_clock_metrics.py` pins
    assert not reported & {
        "decode_hbm_bound_pct", "decode_hbm_bound_pct.laguna",
        "paged_decode_kernel_busy_pct", "attn_kernel_busy_pct.serve",
        "device_starved_pct.tail", "host_turnaround_ms.tail",
        "host_off_cpu_pct.tail"}
    e2e = {m["name"] for m in SPEC.metrics_of("end_to_end", CELL)}
    assert e2e == {"ttft_p75_ms", "tpot_p95_ms", "setup_s"}
    cells = len(bench["workloads"])
    assert (2 + 14 * cells) * (bench["run_seconds"] + 60) \
        + 2 * 90 * cells + 1200 <= 43200


def test_the_traffic_is_the_issues():
    t = SPEC.traffic("blockgen-steady")
    assert t["generator"] == "open_loop" and t["end"] == "drain"
    assert t["prompt_len"] == {"median": 384, "sigma": 0.8, "min": 32,
                               "max": 3072}
    assert t["output_len"] == {"median": 256, "sigma": 0.5, "min": 64,
                               "max": 768}
    assert (t["lead_in_s"], t["drain_s"], t["trace_s"]) == (15.0, 120.0, 4.0)
    assert "start_at" in t and t["rate_rps"] > 0
    # no id of a plan is the mask's, and first tokens stay distinct
    plan = SPEC.generator("open_loop")(t, 7, 50.0, 5000)
    plan["requests"][0]["tokens"][0] = 4242
    for r in plan["requests"][:5]:
        r["tokens"][-1] = 4242
    serve_sdar.without_mask(plan, 4242, 5000)
    assert not any(4242 in r["tokens"] for r in plan["requests"])
    firsts = [r["tokens"][0] for r in plan["requests"]]
    assert len(set(firsts)) == len(firsts)


def test_the_engines_model_is_made_of_the_files_keys():
    from ray_tpu.models import resolve

    family, cfg = resolve(serve_sdar.model_kwargs(CFG))
    assert family.__name__ == "ray_tpu.models.laguna"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size,
            cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.head_dim,
            cfg.num_key_value_heads, cfg.max_seq_len) == (
        2048, 6, 151936, 128, (0, 128), 8, 768, 128, 4, 4096)
    assert cfg.qk_norm and cfg.block_length == 4 and not cfg.gated
    for bad in ({"mlp_only_layers": [0]}, {"sliding_window": 512},
                {"experts_held": [0, 64]}):
        with pytest.raises(ValueError):
            serve_sdar.model_kwargs({**CFG, **bad})
    toy = {**CFG, **{k: v for k, v in CFG["rehearsal"].items()
                     if k != "deployment"}}
    _f, small = resolve(serve_sdar.model_kwargs(toy))
    assert (small.hidden_size, small.mask_token_id, small.block_length) == (
        64, 255, 4)


def test_parameters_and_bytes_against_the_issues_arithmetic():
    assert mm.attention_params(CFG) == 18874368 + 256
    assert mm.expert_params(CFG) == 4718592
    layer = mm.layer_params_outside_experts(CFG) + 128 * mm.expert_params(CFG)
    assert layer == 623120640
    assert mm.total_params(CFG) == 4361055744
    assert mm.kv_bytes_per_row(CFG, 2) == 2048
    cost = mm.block_kernel_cost(CFG, 1000, 2)
    assert cost == {"bytes": 2048000, "flops": 65536000.0}
    assert cost["flops"] / cost["bytes"] == 32           # bound by bytes
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12
    n = mm.block_pass_bytes(CFG, 2, 2, rows_read=1000, experts_touched=768)
    assert n == 2 * (mm.params_outside_experts(CFG) + 768 * 4718592) \
        + 2048000


# ------------------------------------------------------- the loop's shape

M = GEN["mask_token_id"]
REQUEST = {"tokens": list(range(1, 10)), "max_new_tokens": 6}   # tail 1
GOOD = {"tokens": [11, 12, 13, 21, 22, 23], "passes": [
    [8, [9, M, M, M], [9, M, 12, M]],
    [8, [9, M, 12, M], [9, 11, 12, M]],
    [8, [9, 11, 12, M], [9, 11, 12, 13]],
    [8, [9, 11, 12, 13], [9, 11, 12, 13]],          # the commit
    [12, [M, M, M, M], [21, M, M, M]],
    [12, [21, M, M, M], [21, M, M, 24]],
    [12, [21, M, M, 24], [21, 22, M, 24]],
    [12, [21, 22, M, 24], [21, 22, 23, 24]]]}       # the last: no commit


def _broken(edit):
    rec = copy.deepcopy(GOOD)
    edit(rec)
    return serve_sdar.structure_faults(REQUEST, rec, GEN)


def test_a_record_of_the_loop_has_no_fault():
    assert serve_sdar.structure_faults(REQUEST, GOOD, GEN) == []


@pytest.mark.parametrize("what,edit", [
    ("the prompt's tail generated and not given",
     lambda r: r["passes"][0].__setitem__(1, [M, M, M, M])),
    ("the commit left out", lambda r: r["passes"].pop(3)),
    ("a commit that changes the block",
     lambda r: r["passes"][3].__setitem__(2, [9, 11, 12, 14])),
    ("a given token rewritten",
     lambda r: r["passes"][0].__setitem__(2, [7, M, 12, M])),
    ("a pass that unmasks nothing",
     lambda r: r["passes"][1].__setitem__(2, [9, M, 12, M])),
    ("a block opened elsewhere",
     lambda r: r["passes"][4].__setitem__(0, 16)),
    ("an answer that is not the blocks'",
     lambda r: r["tokens"].__setitem__(2, 99)),
    ("a mask in the answer", lambda r: r["tokens"].__setitem__(0, M)),
], ids=lambda x: x if isinstance(x, str) else "")
def test_a_record_that_breaks_the_loops_shape_is_refused(what, edit):
    assert _broken(edit), what


def _ref_rows(record, over=()):
    """A reference that agrees with the record on everything."""
    rows = []
    for p0, before, after in record["passes"]:
        if M not in before:
            continue
        moved = [t for t in range(4) if before[t] == M and after[t] != M]
        row = {"p0": p0, "moved": moved, "top": [5.0] * len(moved),
               "top_id": [after[t] for t in moved],
               "picked": [5.0] * len(moved), "margin": [0.5] * len(moved),
               "conf_margin": [0.2] * len(moved), "over": []}
        rows.append(row)
    for (i, key), value in dict(over).items():
        rows[i][key] = value
    return rows


def _held(over=()):
    # one position of the record's seven is a share of 0.14: the limit
    # here lies between one and two of them
    return serve_sdar.check_canaries(
        [REQUEST], [GOOD], [_ref_rows(GOOD, over)], GEN, min_judged=4,
        max_off_share=0.2)


def test_what_the_comparison_holds_a_position_to():
    fine = _held()
    assert fine["off"] == [] and fine["judged"] == fine["positions"] == 7
    # another token within the tolerance; then beyond it (a spacing at 5
    # is 2 ** -5): one judged position of seven off is under the share
    near = _held({(0, "top_id"): [77], (0, "picked"): [5.0 - 3 / 32]})
    assert near["off"] == [] and near["not_argmax"] == 1
    far = _held({(0, "top_id"): [77], (0, "picked"): [5.0 - 9 / 32]})
    assert far["off_token"] == 1 and far["off"] == []
    assert far["off_share"] == pytest.approx(1 / 7)
    # a near tie of the router is set aside, whatever it holds
    tie = _held({(0, "top_id"): [77], (0, "picked"): [2.0],
                   (0, "margin"): [0.001]})
    assert tie["judged"] == 6 and tie["off_token"] == 0
    assert tie["worst_ulps_near_ties"] > 90
    # the position: a near tie of the confidences passes, a clear
    # preference of the reference for a position left masked does not
    assert _held({(1, "conf_margin"): [-0.02]})["off_position"] == 0
    assert _held({(1, "conf_margin"): [-0.5]})["off_position"] == 1
    # a position over the threshold in the reference that stayed masked
    assert _held({(3, "over"): [3]})["off_position"] == 1
    # two of seven off: over the share, and it says which
    two = _held({(1, "conf_margin"): [-0.5],
                   (0, "top_id"): [77], (0, "picked"): [2.0]})
    assert two["off"] and "2 of 7" in two["off"][0]
    few = serve_sdar.check_canaries([REQUEST], [GOOD], [_ref_rows(GOOD)],
                                    GEN)
    assert any("judged" in x for x in few["off"])


# ---------------------------------------- mutants, at toy size on the CPU


@pytest.fixture(scope="module")
def small():
    """The rehearsal's widths in float32: parameters, sizes, canaries."""
    import jax
    import numpy as np

    from ray_tpu.models import resolve

    sizes = {**CFG, **{k: v for k, v in CFG["rehearsal"].items()
                       if k != "deployment"}}
    family, cfg = resolve({**serve_sdar.model_kwargs(sizes),
                           "dtype": "float32", "param_dtype": "float32"})
    params = family.build(cfg, 16).init(
        jax.random.PRNGKey(5), np.zeros((1, 8), np.int32))["params"]
    canaries = serve_sdar.canary_requests(3, 256, 255, lengths=(9, 30, 21))
    return params, sizes, canaries


def _judge(small, **how):
    from benchmarks import reference_sdar as ref

    params, sizes, canaries = small
    prompts = [q["tokens"] for q in canaries]
    records = [ref.generate(params, p, serve_sdar.CANARY_NEW, sizes, **how)
               for p in prompts]
    refs = ref.teacher_forced(params, prompts, records, sizes)
    return serve_sdar.check_canaries(canaries, records, refs,
                                     sizes["generation"], min_judged=16)


def test_the_loop_proper_passes_exactly(small):
    said = _judge(small)
    assert said["off"] == [] and said["off_share"] == 0.0
    assert said["not_argmax"] == 0 and said["least_conf_margin"] >= 0.0


@pytest.mark.parametrize("how", [
    {"matrices": "float8_e4m3fn"}, {"mutant": "causal_in_block"},
    {"mutant": "no_qk_norm"}, {"mutant": "shift_by_one"},
    {"mutant": "no_commit"}, {"mutant": "stale_open_rows"},
    {"mutant": "tail_generated"}], ids=lambda h: next(iter(h.values())))
def test_a_lower_precision_or_a_mechanism_done_wrong_is_refused(small, how):
    said = _judge(small, **how)
    assert said["off"], said


# ---------------------------------------------------------------- readers


def _polls(steps, rows, calls):
    def at(k):
        return {"t": float(k), "decode_steps": k * steps,
                "decode_secs": k * steps * 0.012, "max_batch": 48,
                "block_rows_read_total": k * rows,
                "moe_expert_calls_total": {"decode": k * calls,
                                           "prefill": 0}}
    return [[at(0), at(1), at(2)]]


def test_the_new_readers_read_their_counters_and_nothing_without_them():
    from benchmarks.readers import block_decode_roofline as roof
    from benchmarks.readers import decode_hbm_bound_sdar as hbm

    obs = {"polls": _polls(100, 100 * 30 * 700 * 6, 100 * 768),
           "model": CFG, "device": {"kind": "TPU v5 lite"},
           "engine": {"param_bytes": 2 * mm.total_params(CFG),
                      "dtype": "bfloat16"}}
    n_bytes = mm.block_pass_bytes(CFG, 2, 2, 30 * 700 * 6, 768)
    assert hbm.read(obs, {}) == pytest.approx(
        100 * n_bytes / 819e9 / 0.012, rel=1e-3)
    bare = copy.deepcopy(obs)
    for row in bare["polls"][0]:
        del row["block_rows_read_total"]
    assert hbm.read(bare, {}) is None
    trace = {"busy_s": 4.0, "devices": 1,
             "op_seconds": {"paged_attention_block tpu_custom_call": 0.2,
                            "paged_attention_decode tpu_custom_call": 9.0},
             "span_stats": [[{"block_rows_read_total": 1000},
                             {"block_rows_read_total": 1000 + 40_000_000}]]}
    got = roof.read({**obs, "trace": trace},
                    {"pattern": "paged_attention_block"})
    assert got == pytest.approx(100 * 40e6 * 2048 / 819e9 / 0.2, rel=1e-3)
    assert 0 < got < 100
    trace["span_stats"] = [[{}, {}]]
    assert roof.read({**obs, "trace": trace},
                     {"pattern": "paged_attention_block"}) is None


def test_a_balanced_router_reads_nothing_of_the_mean_row():
    """`replica_sdar.centred`: the sample's mean row is ahead on no
    expert, and what a row holds across the mean routes as it did."""
    import numpy as np

    from benchmarks.replica_sdar import centred

    rs = np.random.RandomState(5)
    w, mean = rs.randn(64, 8).astype(np.float32), rs.randn(64) + 2.0
    got = centred(w, mean.astype(np.float32))
    assert np.abs(mean @ got).max() < 1e-4
    across = rs.randn(64)
    across -= mean * (across @ mean) / (mean @ mean)
    np.testing.assert_allclose(across @ got, across @ w, atol=1e-4)
    # a row that is the mean and a little more is routed by the little
    row = 30.0 * mean + across
    assert np.argmax(row @ got) == np.argmax(across @ w)

