"""The sparse-attention configuration, its arithmetic, its readers, and
the comparison that decides `correct` in its cell — at a small size on
the CPU."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import model_math_glm as mm  # noqa: E402
from benchmarks.kinds import serve_glm  # noqa: E402
from benchmarks.spec import Spec  # noqa: E402

SPEC = Spec(REPO)
CELL = "serve-glm5-longcontext-steady"
CFG = SPEC.config("glm-5-serve")

# https://huggingface.co/zai-org/GLM-5/blob/main/config.json, the numbers
# and switches of the catalog row
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "head_dim": 64, "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "indexer_rope_interleave": True, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 202752,
    "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880,
}
SIX = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
       "vocab_size", "max_position_embeddings", "num_nextn_predict_layers"}


def test_the_configuration_keeps_every_published_width():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == SIX
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    assert set(CFG["why_reduced"]) == set(CFG["reduced"])
    # the floors: a dense layer and four expert layers, 16 >= 8 experts,
    # an eighth of the vocabulary
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] == 4
    assert CFG["first_k_dense_replace"] == 1
    assert CFG["experts_held"] == [0, 16] and CFG["n_routed_experts"] == 16
    assert CFG["num_experts_routed_over"] == PUBLISHED["n_routed_experts"]
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CFG["num_nextn_predict_layers"] == 0
    assert set(CFG["assumed"]) >= {"indexer", "indexer_rotation",
                                   "indexer_rotary", "router", "norms",
                                   "softmax_scale"}
    assert "16-chip" in CFG["deployment"]["stands_for"]
    assert CFG["deployment"]["kind"] == "serve_glm"
    assert CFG["deployment"]["engine"] == {"max_batch": 16, "page_size": 16}
    # a rehearsal in which everything is selected walks nothing
    toy = CFG["rehearsal"]
    assert toy["index_topk"] <= toy["max_position_embeddings"] // 4
    assert toy["index_topk"] % 16 == 0
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == CFG["name"]][0]
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CFG["name"], "longcontext-steady", 1)
    for group in ("configs", "workloads"):
        for e in bench[group]:
            assert len(e["why"]) <= 200, e["name"]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10


def test_the_engines_model_is_made_of_the_files_keys():
    from ray_tpu.models import cache as kv_cache, resolve

    kw = serve_glm.model_kwargs(CFG)
    family, cfg = resolve(kw)
    assert family.__name__ == "ray_tpu.models.pangu"
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.vocab_size) == (
        256, (0, 16), 19360)
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_attention_heads, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.rope_theta) == (
        6144, 2048, 512, 192, 64, 256, 12288, 2048, 64, 8, 2.5, 1000000)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        32, 128, 2048)
    assert cfg.rope_interleave and cfg.indexer_rope_interleave
    assert cfg.topk_method == "noaux_tc" and not cfg.sandwich_norm
    assert cfg.n_shared_experts == 1 and cfg.max_seq_len == 32768
    assert cfg.cache_spec() == (
        kv_cache.IndexedLatentCache("full", 0, 576, 128),) * 5
    with pytest.raises(ValueError, match="held"):
        serve_glm.model_kwargs({**CFG, "experts_held": [0, 8]})
    with pytest.raises(ValueError, match="drafting"):
        serve_glm.model_kwargs({**CFG, "num_nextn_predict_layers": 1})
    with pytest.raises(ValueError, match="scoring_func"):
        serve_glm.model_kwargs({**CFG, "scoring_func": "softmax"})
    toy = {**CFG, **{k: v for k, v in CFG["rehearsal"].items()
                     if k != "deployment"}}
    _family, small = resolve(serve_glm.model_kwargs(toy))
    assert (small.hidden_size, small.n_routed_experts, small.experts_held,
            small.latent_width, small.index_topk) == (64, 8, (0, 4), 40, 64)
    # the parent of a run fails at the kind's check of a FILE
    assert serve_glm._MODEL.endswith("ray_tpu/ops/sparse_index.py")
    assert os.path.isfile(serve_glm._MODEL)


def test_parameters_and_bytes_against_the_issues_arithmetic():
    """ISSUE 42's arithmetic: attention 165,019,648 a layer (+ 2,560 of
    its two inner norms), indexer 9,371,648 (+ 256 of its LayerNorm), a
    dense MLP 226,492,416, an expert 37,748,736, the router 1,572,864 (+
    its 256 biases); 3.91 B held (the engine's tree: 3,909,632,768); a
    cache row 1,536 B a token a layer, 4.03 GB at 16 x 32,768 x 5."""
    assert mm.attention_params(CFG) == 165_019_648 + 2_560
    assert mm.indexer_params(CFG) == 9_371_648 + 256
    assert mm.expert_params(CFG) == 37_748_736
    assert 3 * 6144 * 12288 == 226_492_416
    assert mm.layer_params_outside_experts(CFG, 0) == \
        165_022_208 + 9_371_904 + 2 * 6144 + 226_492_416
    assert mm.layer_params_outside_experts(CFG, 1) == \
        165_022_208 + 9_371_904 + 2 * 6144 + 1_572_864 + 256 + 37_748_736
    assert mm.sparse_layers(CFG) == 4
    assert mm.total_params(CFG) == 3_909_632_768
    assert round(mm.total_params(CFG) * 2 / 1e9, 2) == 7.82
    assert mm.cache_row_bytes(CFG) == 1536
    slots = (1 + 16 * 2048) * 16
    assert round(slots * 5 * mm.cache_row_bytes(CFG) / 1e9, 2) == 4.03
    # a scored pair is 32 x 128 x 2 = 8,192 operations, a selected pair
    # 64 x (576 + 512) x 2 = 139,264; a key 256 B
    assert mm.index_score_cost(CFG, 1000, 10) == {
        "flops": 8_192_000.0, "bytes": 2_560.0}
    assert mm.selected_attention_flops(CFG, 1000) == 139_264_000.0
    outside = mm.params_outside_experts(CFG) * 2
    assert mm.decode_step_bytes(CFG, 2, 2, index_rows=5 * 16 * 8000,
                                latent_rows=5 * 16 * 2048,
                                experts_touched=20) == \
        outside + 20 * 75_497_472 + 5 * 16 * 8000 * 256 \
        + 5 * 16 * 2048 * 1152


def _obs(secs, first_more=None, **last_more):
    first = {"decode_steps": 10, "decode_lane_steps_total": 100,
             "decode_secs": 1.0,
             "moe_expert_calls_total": {"decode": 50, "prefill": 0},
             "sparse_index_pairs_total": {"decode": 0, "prefill": 0},
             "sparse_rows_selected_total": {"decode": 0, "prefill": 0},
             "sparse_rows_visible_total": {"decode": 0, "prefill": 0},
             "sparse_decode_rows_total": {"decode": 0, "prefill": 0},
             "max_batch": 16, "active": 8, "t": 0.0, **(first_more or {})}
    last = {**first, **last_more}
    return {"trace": {"busy_s": 2.0, "devices": 1, "op_seconds": dict(secs),
                      "span_stats": [[first, last]]},
            "polls": [[first, last]], "model": CFG,
            "engine": {"dtype": "bfloat16",
                       "param_bytes": 2 * mm.total_params(CFG)},
            "device": {"kind": "TPU v5 lite"}, "summary": {}}


def _meta(name):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_new_readers_on_hand_made_observations():
    from benchmarks.readers import (decode_hbm_bound_glm, sparse_roofline,
                                    sparse_rows_per_lane, stats_ratio,
                                    trace_op_share)

    secs = {"sparse_index_scores tpu_custom_call f32[8,64,8192]": 0.5,
            "latent_attention_prefill tpu_custom_call": 1.0,
            "latent_attention_decode tpu_custom_call": 0.25}
    obs = _obs(secs,
               sparse_index_pairs_total={"decode": 10 ** 9,
                                         "prefill": 10 ** 10},
               sparse_rows_selected_total={"decode": 3 * 10 ** 8,
                                           "prefill": 4 * 10 ** 8},
               sparse_rows_visible_total={"decode": 10 ** 9,
                                          "prefill": 10 ** 9},
               sparse_decode_rows_total={"decode": 5 * 900 * 2048,
                                         "prefill": 0},
               decode_steps=60, decode_lane_steps_total=1000,
               decode_secs=1.8,
               moe_expert_calls_total={"decode": 50 + 50 * 20,
                                       "prefill": 0})
    index = _meta("sparse_index_roofline_pct")["params"]
    # prefill pairs by their products; decode pairs by their keys' bytes
    # (8,192 FLOP / 197e12 = 41.6 ps against 256 B / 819e9 = 312.6 ps)
    want = 1e10 * 8192 / 197e12 + 1e9 * 256 / 819e9
    assert sparse_roofline.read(obs, index) == pytest.approx(
        100 * want / 0.5)
    chunk = _meta("sparse_prefill_roofline_pct")["params"]
    assert sparse_roofline.read(obs, chunk) == pytest.approx(
        100 * (4e8 * 139_264 / 197e12) / 1.0)
    assert trace_op_share.read(
        obs, _meta("sparse_index_busy_pct")["params"]) == pytest.approx(25.0)
    assert trace_op_share.read(
        obs, _meta("latent_decode_kernel_busy_pct")["params"]
    ) == pytest.approx(12.5)
    assert stats_ratio.read(
        obs, _meta("sparse_selected_share_pct.tail")["params"]
    ) == pytest.approx(35.0)
    assert sparse_rows_per_lane.read(obs, {}) == pytest.approx(2048.0)
    step_bytes = (mm.params_outside_experts(CFG) * 2 + 20 * 75_497_472
                  + (1e9 / 50) * 256 + (3e8 / 50) * 1152)
    assert decode_hbm_bound_glm.read(obs, {}) == pytest.approx(
        100 * (step_bytes / 819e9) / 0.016)
    # a program without the counters (the parent's), a trace without the
    # kernels: every new reader returns nothing and does not raise
    bare = _obs(secs)
    for pair in bare["trace"]["span_stats"]:
        for s in pair:
            for key in [k for k in s if k.startswith("sparse_")]:
                del s[key]
    for params in (index, chunk):
        assert sparse_roofline.read(bare, params) is None
        assert sparse_roofline.read(_obs({}), params) is None
        assert sparse_roofline.read({"trace": {}}, params) is None
    assert sparse_rows_per_lane.read(bare, {}) is None
    assert decode_hbm_bound_glm.read(bare, {}) is None
    assert sparse_rows_per_lane.read({}, {}) is None
    assert stats_ratio.read(
        bare, _meta("sparse_selected_share_pct.tail")["params"]) is None
    # nothing moved inside the span: nothing, not a division by zero
    assert sparse_roofline.read(_obs(secs), index) is None
    assert sparse_roofline.read(_obs(secs), chunk) is None


def test_the_cell_reports_only_what_a_reader_finds_on_this_family():
    names = {m["name"] for m in SPEC.metrics_of("per_layer", CELL)}
    new = {"sparse_selected_share_pct.tail", "sparse_index_busy_pct",
           "sparse_index_roofline_pct", "sparse_prefill_roofline_pct",
           "decode_hbm_bound_pct.glm", "sparse_rows_per_decode_lane.tail"}
    assert new | {"latent_decode_kernel_busy_pct", "moe_busy_pct",
                  "moe_experts_roofline_pct", "moe_experts_touched_pct.tail",
                  "moe_load_max_over_mean.tail", "decode_step_ms.tail",
                  "prefill_pass_ms.tail", "compiles_in_window.tail",
                  "ready_s"} <= names
    # the dense family's arithmetic reads every row of a context; the
    # paged grid the engine counts is not the gathered pool's
    assert not names & {"latent_decode_roofline_pct",
                        "decode_hbm_bound_pct.pangu",
                        "latent_rows_per_decode_lane.tail",
                        "paged_grid_live_pct.tail",
                        "paged_decode_kernel_busy_pct",
                        "decode_hbm_bound_pct", "attn_kernel_busy_pct.serve",
                        "device_starved_pct.tail", "host_turnaround_ms.tail",
                        "host_off_cpu_pct.tail"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # (found by name, not by their place: the next PR's entries go behind
    # them — `test_bench_reference_granite.py` pins ITS five as the
    # list's last and fails on every later addition, PERF.md section 7)
    mine = [m for m in bench["per_layer"] if m["name"] in new]
    assert len(mine) == 6
    for m in mine:
        assert m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    e2e = {m["name"] for m in SPEC.metrics_of("end_to_end", CELL)}
    assert e2e == {"ttft_p75_ms", "tpot_p95_ms", "setup_s"}
    from benchmarks import model_math_laguna

    cost = model_math_laguna.expert_matmul_cost(CFG, 100, 40)
    assert cost["flops"] == 2.0 * 100 * 37_748_736
    traffic = SPEC.traffic("longcontext-steady")
    assert traffic["generator"] == "open_loop"
    assert traffic["prompt_len"] == {"median": 6144, "sigma": 0.8,
                                     "min": 2304, "max": 30720}
    assert traffic["output_len"] == {"median": 64, "sigma": 0.6,
                                     "min": 16, "max": 192}
    # every prompt is past index_topk: every request's selection selects
    assert traffic["prompt_len"]["min"] > CFG["index_topk"]
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= CFG["max_position_embeddings"]
    assert (traffic["lead_in_s"], traffic["end"], traffic["drain_s"],
            traffic["trace_s"]) == (30.0, "drain", 120.0, 4.0)
    assert "start_at" in traffic and "knee" in traffic["rate_is"]
    assert max(serve_glm.CANARY_LENGTHS) + 16 \
        <= CFG["max_position_embeddings"]
    assert sum(n > CFG["index_topk"] for n in serve_glm.CANARY_LENGTHS) >= 5


def test_the_kind_names_every_mutant_of_the_reference():
    import subprocess

    from benchmarks import reference_glm

    assert serve_glm.MUTANTS == reference_glm.VARIANTS
    # and importing the kind brings no jax into a process (run.py's
    # parent must never hold the chip)
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmarks.kinds.serve_glm; "
            "assert 'jax' not in sys.modules" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True)


def test_either_margin_under_its_tau_sets_a_position_aside():
    refs = [{"top": [1.0] * 4, "top_id": [7] * 4, "picked": [0.5] * 4,
             "margin": [0.01, 0.0001, 0.01, 0.01],
             "select_margin": [float("inf"), 1.0, 0.0, 0.002]}]
    folded = serve_glm.fold_margins(refs, router_tau=0.001, select_tau=0.0)
    assert [m > 1.0 for m in folded[0]["margin"]] == [
        True, False, False, True]
    folded = serve_glm.fold_margins(refs, router_tau=0.001, select_tau=0.01)
    assert [m > 1.0 for m in folded[0]["margin"]] == [
        True, False, False, False]
    assert refs[0]["margin"][0] == 0.01          # not changed in place


# --------------------------------- what the comparison refuses, and passes


@pytest.fixture(scope="module")
def small():
    """The small sparse model's weights, 4 x 200 random tokens, the
    reference's logits and margins there, and `held(picks)`: the kind's
    comparison of a pick at every position past `index_topk` with what
    the reference says of it."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks import reference_glm as ref
    from ray_tpu.models.pangu import PanguConfig, build

    cfg = dataclasses.replace(PanguConfig.tiny_sparse(), index_n_heads=8)
    sizes = dict(num_hidden_layers=cfg.num_hidden_layers,
                 first_k_dense_replace=1, kv_lora_rank=cfg.kv_lora_rank,
                 qk_nope_head_dim=cfg.qk_nope_head_dim,
                 qk_rope_head_dim=cfg.qk_rope_head_dim,
                 rope_parameters={"rope_theta": cfg.rope_theta},
                 index_topk=cfg.index_topk, num_experts_per_tok=2,
                 norm_topk_prob=True, routed_scaling_factor=2.5,
                 rms_norm_eps=cfg.rms_norm_eps, experts_held=[0, 4])
    tokens = np.random.RandomState(0).randint(1, 256, (4, 200))
    params = jax.jit(build(cfg, 16).init)(
        jax.random.PRNGKey(0), jnp.asarray(tokens[:, :8]))["params"]
    past = cfg.index_topk

    def reference(**how):
        rows = [ref.logits(params, row, sizes, **how) for row in tokens]
        return tuple(np.stack([np.asarray(r[i]) for r in rows])
                     for i in range(3))

    logits, margin, picked = reference()

    def held(picks):
        picks = np.asarray(picks)
        refs = [{"top": logits[b, past:].max(-1).tolist(),
                 "top_id": logits[b, past:].argmax(-1).tolist(),
                 "picked": np.take_along_axis(
                     logits[b, past:], picks[b, past:, None],
                     -1)[:, 0].tolist(),
                 "margin": margin[b, past:].tolist(),
                 "select_margin": picked[b, past:].tolist()}
                for b in range(len(picks))]
        return serve_glm.compare(
            [{"tokens": row} for row in tokens.tolist()],
            picks[:, past:].tolist(), refs)

    return cfg, params, tokens, held, reference


def test_the_program_at_the_references_precision_passes_exactly(small):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.pangu import build

    cfg, params, tokens, held, _reference = small
    model = build(dataclasses.replace(cfg, dtype=jnp.float32), 16)
    out = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(tokens))
    got = held(np.asarray(out).argmax(-1))
    assert got["off"] == [] and got["judged"] >= 400
    assert (got["not_argmax"], got["worst_ulps"]) == (0, 0.0)


@pytest.mark.parametrize("how", [
    {"matrices": serve_glm.LOWER_PRECISION}, {"variant": "dense"},
    {"variant": "topk_half"}, {"variant": "topk_double"},
    {"variant": "no_relu"}, {"variant": "no_weights"},
    {"variant": "unrotated_keys"}, {"variant": "no_bias"}],
    ids=lambda how: next(iter(how.values())))
def test_a_reference_with_one_thing_wrong_fails(small, how):
    """The second readings: the reference in the nearest precision below
    the stated bfloat16, with the indexer left out, with a selection of
    the wrong k, without ReLU, without the head weights, with unrotated
    index keys, with a router that drops its bias — each picks tokens
    the reference proper puts beyond the tolerance at more judged
    positions than MAX_OFF_SHARE allows: not `correct`.  (The chip's
    readings are in PERF.md.)"""
    _cfg, _params, _tokens, held, reference = small
    wrong, _margin, _picked = reference(**how)
    got = held(wrong.argmax(-1))
    assert got["off_share"] > serve_glm.MAX_OFF_SHARE, got
    assert got["off"] and "judged positions" in got["off"][0]
