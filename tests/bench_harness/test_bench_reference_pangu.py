"""The latent-attention configuration, its arithmetic, its readers, and
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

from benchmarks import model_math_pangu as mm  # noqa: E402
from benchmarks.kinds import serve_pangu  # noqa: E402
from benchmarks.spec import Spec  # noqa: E402

SPEC = Spec(REPO)
CELL = "serve-pangu-longprompt-steady"
CFG = SPEC.config("openpangu-ultra-moe-718b-serve")

# https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/
# blob/main/config.json, the numbers and switches of the catalog row
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600,
}


def test_the_configuration_keeps_every_published_width():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "max_position_embeddings", "num_nextn_predict_layers"}
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
    assert set(CFG["assumed"]) >= {"router_scores", "sandwich_norm", "rotary",
                                   "softmax_scale", "shared_expert"}
    assert "16 chips" in CFG["deployment"]["stands_for"]
    assert CFG["deployment"]["kind"] == "serve_pangu"
    assert CFG["deployment"]["engine"] == {"max_batch": 32, "page_size": 16}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == CFG["name"]][0]
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CFG["name"], "longprompt-steady", 1)
    for group in ("configs", "workloads"):
        for e in bench[group]:
            assert len(e["why"]) <= 200, e["name"]


def test_the_engines_model_is_made_of_the_files_keys():
    from ray_tpu.models import resolve

    kw = serve_pangu.model_kwargs(CFG)
    family, cfg = resolve(kw)
    assert family.__name__ == "ray_tpu.models.pangu"
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.vocab_size) == (
        256, (0, 16), 19200)
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_attention_heads, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.rope_theta) == (
        7680, 1536, 512, 128, 64, 128, 18432, 2048, 128, 8, 2.5, 25600000)
    assert cfg.sandwich_norm and cfg.n_shared_experts == 1
    assert [tuple(layer) for layer in cfg.cache_spec()] == [
        ("full", 0, 0, 0, 576)] * 5
    with pytest.raises(ValueError, match="held"):
        serve_pangu.model_kwargs({**CFG, "experts_held": [0, 8]})
    with pytest.raises(ValueError, match="drafting"):
        serve_pangu.model_kwargs({**CFG, "num_nextn_predict_layers": 1})
    toy = {**CFG, **{k: v for k, v in CFG["rehearsal"].items()
                     if k != "deployment"}}
    _family, small = resolve(serve_pangu.model_kwargs(toy))
    assert (small.hidden_size, small.n_routed_experts, small.experts_held,
            small.latent_width) == (64, 8, (0, 4), 40)
    # the parent of a run fails at the kind's check of the model FILE
    assert serve_pangu._MODEL.endswith("ray_tpu/models/pangu.py")
    assert os.path.isfile(serve_pangu._MODEL)


def test_parameters_and_bytes_against_the_issues_arithmetic():
    """ISSUE 34's arithmetic, by hand there: a layer's attention
    196,575,232 + 2,048 of the two inner norms; an expert 47,185,920;
    the router 1,966,080; a dense MLP 424,673,280; 4,919 M held (the
    engine's tree: 4,919,139,840)."""
    assert mm.attention_params(CFG) == 196_575_232 + 2_048
    assert mm.expert_params(CFG) == 47_185_920
    assert mm.layer_params_outside_experts(CFG, 0) == \
        196_577_280 + 4 * 7680 + 424_673_280
    assert mm.layer_params_outside_experts(CFG, 1) == \
        196_577_280 + 4 * 7680 + 1_966_080 + 47_185_920
    assert mm.sparse_layers(CFG) == 4
    assert mm.total_params(CFG) == 4_919_139_840
    assert round(mm.total_params(CFG) * 2 / 2 ** 30, 2) == 9.16
    # a cache row is 576 numbers, 1,152 B a token a layer, and costs
    # 128 x (576 + 512) x 2 = 278,528 operations: 241.8 a byte
    assert mm.latent_row_numbers(CFG) == 576
    cost = mm.latent_attention_cost(CFG, rows=1000)
    assert cost == {"flops": 278_528_000.0, "bytes": 1_152_000.0}
    # a step reads 3.50 GB outside the experts and 94.4 MB an expert
    outside = mm.params_outside_experts(CFG) * 2
    assert outside == 3_503_569_920
    assert mm.decode_step_bytes(CFG, 2, 2, [4096] * 32, 20) == \
        outside + 20 * 94_371_840 + 5 * 32 * 4096 * 1152


def _obs(rows=0, secs=0.0, **stats):
    first = {"latent_decode_rows_total": 1000, "decode_steps": 10,
             "decode_lane_steps_total": 100, "decode_secs": 1.0,
             "moe_expert_calls_total": {"decode": 50, "prefill": 0},
             "max_batch": 32, "active": 16, "t": 0.0}
    last = {**first, "latent_decode_rows_total": 1000 + rows, **stats}
    return {"trace": {"busy_s": 2.0, "devices": 1,
                      "op_seconds": {
                          "latent_attention_decode tpu_custom_call": secs,
                          "paged_attention_decode tpu_custom_call": 9.0},
                      "span_stats": [[first, last]]},
            "polls": [[first, last]], "model": CFG,
            "engine": {"dtype": "bfloat16",
                       "param_bytes": 2 * mm.total_params(CFG)},
            "device": {"kind": "TPU v5 lite"}, "summary": {}}


def test_the_new_readers_on_hand_made_observations():
    from benchmarks.readers import (decode_hbm_bound_pangu,
                                    latent_decode_roofline,
                                    latent_rows_per_lane, trace_op_share)

    pattern = {"pattern": "latent_attention_decode"}
    # 1e9 rows: 1.152e12 B / 819e9 = 1.4066 s by memory, 2.785e14 /
    # 197e12 = 1.4138 s by compute: AT the ridge, compute a hair above
    obs = _obs(rows=10 ** 9, secs=2.0)
    assert latent_decode_roofline.read(obs, pattern) == pytest.approx(
        100 * (278_528e9 / 197e12) / 2.0)
    assert trace_op_share.read(obs, pattern) == pytest.approx(100.0)
    # the key-and-value kernels' pattern does not take this kernel in,
    # nor this one's theirs
    paged = json.load(open(os.path.join(
        REPO, "benchmarks", "layer_metrics",
        "paged_decode_kernel_busy_pct.json")))["params"]
    assert trace_op_share.read(obs, paged) == pytest.approx(450.0)
    only = _obs(rows=10, secs=1.0)
    del only["trace"]["op_seconds"][
        "paged_attention_decode tpu_custom_call"]
    assert trace_op_share.read(only, paged) is None
    # no counter (the parent's program), no kernel in the trace: nothing
    assert latent_decode_roofline.read(_obs(rows=5), pattern) is None
    bare = _obs(rows=5, secs=1.0)
    for pair in bare["trace"]["span_stats"]:
        for s in pair:
            del s["latent_decode_rows_total"]
    assert latent_decode_roofline.read(bare, pattern) is None
    assert latent_rows_per_lane.read(bare, {}) is None
    assert decode_hbm_bound_pangu.read(bare, {}) is None
    # 40 steps, 800 lane-steps, 5 layers x 800 x 3000 rows
    obs = _obs(rows=5 * 800 * 3000, decode_steps=50,
               decode_lane_steps_total=900, decode_secs=1.4,
               moe_expert_calls_total={"decode": 50 + 40 * 20,
                                       "prefill": 0})
    assert latent_rows_per_lane.read(obs, {}) == pytest.approx(3000.0)
    want = (mm.params_outside_experts(CFG) * 2 + 20 * 94_371_840
            + 5 * 20 * 3000 * 1152) / 819e9
    assert decode_hbm_bound_pangu.read(obs, {}) == pytest.approx(
        100 * want / 0.010)


def test_the_cell_reports_only_what_a_reader_finds_on_this_family():
    names = {m["name"] for m in SPEC.metrics_of("per_layer", CELL)}
    assert {"latent_decode_kernel_busy_pct", "latent_decode_roofline_pct",
            "decode_hbm_bound_pct.pangu", "latent_rows_per_decode_lane.tail",
            "moe_busy_pct", "moe_experts_roofline_pct",
            "moe_experts_touched_pct.tail", "moe_load_max_over_mean.tail",
            "decode_step_ms.tail", "prefill_pass_ms.tail",
            "ready_s"} <= names
    assert not names & {"paged_decode_kernel_busy_pct",
                        "window_decode_kernel_busy_pct",
                        "decode_hbm_bound_pct", "decode_hbm_bound_pct.laguna",
                        "kv_window_pages_saved_pct",
                        "attn_kernel_busy_pct.serve"}
    e2e = {m["name"] for m in SPEC.metrics_of("end_to_end", CELL)}
    assert e2e == {"ttft_p75_ms", "tpot_p95_ms", "setup_s"}
    # the experts' roofline reads this family's file by the same keys
    from benchmarks import model_math_laguna

    cost = model_math_laguna.expert_matmul_cost(CFG, 100, 40)
    assert cost["flops"] == 2.0 * 100 * 47_185_920
    traffic = SPEC.traffic("longprompt-steady")
    assert traffic["prompt_len"] == {"median": 2048, "sigma": 0.8,
                                     "min": 256, "max": 7936}
    assert traffic["output_len"] == {"median": 48, "sigma": 0.6,
                                     "min": 16, "max": 128}
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= CFG["max_position_embeddings"]
    assert (traffic["lead_in_s"], traffic["end"]) == (5.0, "drain")
    assert traffic["drain_s"] <= 120.0 and "start_at" in traffic


# ------------------------------------------- lower precision must not pass


@pytest.fixture(scope="module")
def small():
    """The small model's weights, 4 x 120 random tokens, the reference's
    logits and margins there, and `held(picks)`: the kind's comparison
    of a pick at every position with what the reference says of it."""
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_pangu as ref
    from ray_tpu.models.pangu import PanguConfig, build

    cfg = PanguConfig.tiny()
    sizes = dict(num_hidden_layers=cfg.num_hidden_layers,
                 first_k_dense_replace=1, sandwich_norm=True,
                 kv_lora_rank=cfg.kv_lora_rank,
                 qk_nope_head_dim=cfg.qk_nope_head_dim,
                 qk_rope_head_dim=cfg.qk_rope_head_dim,
                 rope_theta=cfg.rope_theta, num_experts_per_tok=2,
                 norm_topk_prob=True, routed_scaling_factor=2.5,
                 rms_norm_eps=cfg.rms_norm_eps, experts_held=[0, 4])
    tokens = np.random.RandomState(0).randint(1, 256, (4, 120))
    params = jax.jit(build(cfg, 16).init)(
        jax.random.PRNGKey(0), jnp.asarray(tokens[:, :8]))["params"]

    def reference(matrices=None):
        rows = [ref.logits(params, row, sizes, matrices=matrices)
                for row in tokens]
        return (np.stack([np.asarray(r[0]) for r in rows]),
                np.stack([np.asarray(r[1]) for r in rows]))

    logits, margin = reference()

    def held(picks):
        picks = np.asarray(picks)
        refs = [{"top": logits[b].max(-1).tolist(),
                 "top_id": logits[b].argmax(-1).tolist(),
                 "picked": np.take_along_axis(
                     logits[b], picks[b][:, None], -1)[:, 0].tolist(),
                 "margin": margin[b].tolist()} for b in range(len(picks))]
        return serve_pangu.check_canaries(
            [{"tokens": row} for row in tokens.tolist()], picks.tolist(),
            refs, tau=serve_pangu.ROUTER_TIE_TAU,
            max_off_share=serve_pangu.MAX_OFF_SHARE)

    return cfg, params, tokens, sizes, held, reference


def test_the_program_at_the_references_precision_passes_exactly(small):
    """First reading: float32 activations over the same stored matrices
    pick the reference's argmax at every judged position, distance 0."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.pangu import build

    cfg, params, tokens, _sizes, held, _reference = small
    model = build(dataclasses.replace(cfg, dtype=jnp.float32), 16)
    out = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(tokens))
    got = held(np.asarray(out).argmax(-1))
    assert got["off"] == [] and got["judged"] >= 400
    assert (got["not_argmax"], got["worst_ulps"]) == (0, 0.0)


def test_the_reference_in_the_nearest_lower_precision_fails(small):
    """Second reading (vi): the reference with every stored matrix
    rounded to float8_e4m3fn (the nearest precision below the stated
    bfloat16) picks tokens the reference proper puts beyond the
    tolerance at more judged positions than MAX_OFF_SHARE allows: not
    `correct`.  (The chip's readings are in PERF.md.)"""
    _cfg, _params, _tokens, _sizes, held, reference = small
    lower, _margin = reference(matrices=serve_pangu.LOWER_PRECISION)
    got = held(lower.argmax(-1))
    assert got["off_share"] > serve_pangu.MAX_OFF_SHARE
    assert got["off"] and "judged positions" in got["off"][0]


def test_a_bfloat16_router_moves_picks_and_stays_a_flip(small):
    """(vi) ISSUE 34 asks that a bfloat16 router be refused.  As for the
    softmax router of `test_bench_reference_laguna.py`, no comparison of
    outputs with a program whose activations are bfloat16 as stated can:
    a router in bfloat16 is finer than that program's own noise.  Pinned
    so that the limit is a tested fact: it DOES move picks at this size,
    by routing flips, and stays under MAX_OFF_SHARE as a correct
    bfloat16 program does; what IS refused is the test above."""
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_pangu as ref

    _cfg, _params, _tokens, _sizes, held, reference = small
    jax.clear_caches()        # `block` is jitted over the old function
    old = ref.router_scores
    ref.router_scores = lambda z: jax.nn.sigmoid(
        z.astype(jnp.bfloat16)).astype(jnp.float32)
    try:
        mutant, _margin = reference()
    finally:
        ref.router_scores = old
        jax.clear_caches()
    got = held(mutant.argmax(-1))
    assert got["off"] == [] and got["not_argmax"] > 0
