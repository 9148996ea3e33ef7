"""The hybrid state-space configuration, its arithmetic, its readers,
and the comparison that decides `correct` in its cell — at a small size
on the CPU."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import model_math_granite as mm  # noqa: E402
from benchmarks.kinds import serve_granite  # noqa: E402
from benchmarks.spec import Spec  # noqa: E402

SPEC = Spec(REPO)
CELL = "serve-granite-longanswer-steady"
CFG = SPEC.config("granite-4.0-h-micro-serve")

# https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/
# config.json, the numbers and switches of the catalog row
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}


def test_the_configuration_is_the_published_model_whole():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == {"max_position_embeddings"}
    assert CFG["published"] == {"max_position_embeddings": 131072}
    assert set(CFG["why_reduced"]) == set(CFG["reduced"])
    assert CFG["max_position_embeddings"] == 4096
    assert CFG["layer_types"].count("mamba") == 36
    assert [i for i, t in enumerate(CFG["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert set(CFG["assumed"]) >= {"state_dtype", "ssm_initialisation",
                                   "torch_dtype"}
    assert "whole" in CFG["deployment"]["stands_for"]
    assert CFG["deployment"]["kind"] == "serve_granite"
    assert CFG["deployment"]["engine"] == {"max_batch": 64, "page_size": 16}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == CFG["name"]][0]
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CFG["name"], "longanswer-steady", 1)
    for group in ("configs", "workloads"):
        for e in bench[group]:
            assert len(e["why"]) <= 200, e["name"]
    # every new metric lists this cell alone, at the end of the list
    new = bench["per_layer"][-5:]
    assert [m["name"] for m in new] == [
        "ssm_decode_kernel_busy_pct", "ssm_decode_roofline_pct",
        "decode_hbm_bound_pct.granite", "state_lanes_per_decode_step.tail",
        "state_pool_fill_pct.tail"]
    assert all(m["workloads"] == [CELL] for m in new)
    # the benchmark's time rule with one more cell
    cells = len(bench["workloads"])
    assert (2 + 14 * cells) * (bench["run_seconds"] + 60) \
        + 2 * 90 * cells + 1200 <= 43200


def test_the_engines_model_is_made_of_the_files_keys():
    from ray_tpu.models import resolve

    family, cfg = resolve(serve_granite.model_kwargs(CFG))
    assert family.__name__ == "ray_tpu.models.granite"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size,
            cfg.shared_intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.mamba_n_heads,
            cfg.mamba_d_head, cfg.mamba_d_state, cfg.d_inner, cfg.conv_dim,
            cfg.max_seq_len) == (
        2048, 40, 100352, 8192, 32, 8, 64, 64, 64, 128, 4096, 4352, 4096)
    assert (cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
        0.015625, 12, 0.22, 8)
    kinds = [layer.kind for layer in cfg.cache_spec()]
    assert kinds == ["state" if t == "mamba" else "full"
                     for t in CFG["layer_types"]]
    with pytest.raises(ValueError, match="layer_types"):
        serve_granite.model_kwargs({**CFG, "num_hidden_layers": 39})
    with pytest.raises(ValueError, match="num_local_experts"):
        resolve({**serve_granite.model_kwargs(CFG), "num_local_experts": 8})
    toy = {**CFG, **{k: v for k, v in CFG["rehearsal"].items()
                     if k != "deployment"}}
    _family, small = resolve(serve_granite.model_kwargs(toy))
    assert (small.hidden_size, small.head_dim, small.paired,
            small.d_inner) == (256, 64, True, 512)
    # the parent of a run fails at the kind's check of the model FILE
    assert serve_granite._MODEL.endswith("ray_tpu/models/granite.py")
    assert os.path.isfile(serve_granite._MODEL)


def test_parameters_and_bytes_against_the_issues_arithmetic():
    """ISSUE 40's arithmetic, by hand there: 76,182,976 parameters a
    Mamba layer, 60,821,504 an attention layer, 205,520,896 in the tied
    embedding, 3,191,396,096 in all (the engine's tree:
    tests/test_granite_model.py builds it at toy size; the rehearsal
    compile and the chip print the same count); 76,437,504 B of state a
    sequence whatever its length; 8,192 B of keys and values a token."""
    assert mm.mamba_layer_params(CFG) == 76_182_976
    assert mm.attention_layer_params(CFG) == 60_821_504
    assert mm.embedding_params(CFG) == 205_520_896
    assert mm.total_params(CFG) == 3_191_396_096 == (
        36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + 2048)
    assert mm.state_row_numbers(CFG) == 64 * 64 * 128 == 524_288
    assert mm.state_bytes_per_sequence(CFG) == 76_437_504 == 36 * (
        2_097_152 + 3 * 4352 * 2)
    assert mm.kv_bytes_per_token(CFG) == 8_192
    # the kernel: a row read and written in float32, 5 operations a
    # number: 0.6 operations a byte, bound by the bytes
    cost = mm.state_update_cost(CFG, rows=1000)
    assert cost == {"flops": 5.0 * 524_288_000, "bytes": 4_194_304_000.0}
    # a decode pass of 48 lanes at 500 tokens: the weights, 197 MB of
    # keys and values, 7.2 GB of state
    assert mm.decode_step_bytes(CFG, 2, 2, [500] * 48, 36 * 48) == (
        6_382_792_192 + 48 * 500 * 8_192 + 36 * 48 * 4_194_304)
    dep = CFG["deployment"]
    assert dep["bytes"]["weights"] == 2 * mm.total_params(CFG)
    assert 65 * mm.state_bytes_per_sequence(CFG) == 4_968_437_760


def _obs(rows=0, secs=0.0, **stats):
    first = {"state_decode_rows_total": 1000, "decode_steps": 10,
             "decode_lane_steps_total": 100, "decode_secs": 1.0,
             "state_slots_in_use": 40, "max_batch": 64, "active": 32,
             "t": 0.0}
    last = {**first, "state_decode_rows_total": 1000 + rows, **stats}
    return {"trace": {"busy_s": 2.0, "devices": 1,
                      "op_seconds": {
                          "ssm_state_update tpu_custom_call": secs,
                          "paged_attention_decode tpu_custom_call": 0.5},
                      "span_stats": [[first, last]]},
            "polls": [[first, last]], "model": CFG,
            "engine": {"dtype": "bfloat16",
                       "param_bytes": 2 * mm.total_params(CFG)},
            "device": {"kind": "TPU v5 lite"},
            "summary": {"mean_context": 400.0}}


def test_the_new_readers_on_hand_made_observations():
    from benchmarks.readers import (decode_hbm_bound_granite,
                                    ssm_decode_roofline,
                                    state_pool_fill, stats_ratio,
                                    trace_op_share)

    pattern = {"pattern": "ssm_state_update"}
    # 100,000 rows: 4.194e11 B / 819e9 = 0.5121 s over 0.8 s of kernel
    obs = _obs(rows=100_000, secs=0.8)
    assert ssm_decode_roofline.read(obs, pattern) == pytest.approx(
        100 * (100_000 * 4_194_304 / 819e9) / 0.8)
    assert trace_op_share.read(obs, pattern) == pytest.approx(40.0)
    # the paged kernel's pattern does not take this kernel in
    paged = json.load(open(os.path.join(
        REPO, "benchmarks", "layer_metrics",
        "paged_decode_kernel_busy_pct.json")))["params"]
    assert trace_op_share.read(obs, paged) == pytest.approx(25.0)
    # no kernel in the trace (the interpreter's), no counter (the
    # parent's program): nothing, and nothing raised
    assert ssm_decode_roofline.read(_obs(rows=5), pattern) is None
    assert trace_op_share.read(_obs(rows=5), pattern) is None
    bare = _obs(rows=5, secs=1.0)
    for s in bare["polls"][0]:    # the span's pair is the same two rows
        del s["state_decode_rows_total"], s["state_slots_in_use"]
    assert ssm_decode_roofline.read(bare, pattern) is None
    assert decode_hbm_bound_granite.read(bare, {}) is None
    lanes = json.load(open(os.path.join(
        REPO, "benchmarks", "layer_metrics",
        "state_lanes_per_decode_step.tail.json")))["params"]
    assert lanes["scale"] * CFG["layer_types"].count("mamba") \
        == pytest.approx(1.0)
    assert stats_ratio.read(bare, lanes) is None
    assert state_pool_fill.read(bare, {}) is None
    # 40 passes of 45 live lanes, 36 state layers
    obs = _obs(rows=36 * 40 * 45, decode_steps=50, decode_secs=1.8)
    assert stats_ratio.read(obs, lanes) == pytest.approx(45.0)
    assert state_pool_fill.read(obs, {}) == pytest.approx(62.5)
    want = (6_382_792_192 + 32 * 400 * 8_192
            + 36 * 45 * 4_194_304) / 819e9
    assert decode_hbm_bound_granite.read(obs, {}) == pytest.approx(
        100 * want / 0.020)


def test_the_cell_reports_only_what_a_reader_finds_on_this_family():
    names = {m["name"] for m in SPEC.metrics_of("per_layer", CELL)}
    assert {"ssm_decode_kernel_busy_pct", "ssm_decode_roofline_pct",
            "decode_hbm_bound_pct.granite",
            "state_lanes_per_decode_step.tail", "state_pool_fill_pct.tail",
            "paged_decode_kernel_busy_pct", "decode_step_ms.tail",
            "prefill_pass_ms.tail", "ready_s", "gen_late_p95_ms"} <= names
    # PR 36's three clocks beyond the step would read here as they do on
    # the other tail cells, but `test_bench_host_clock_metrics.py` pins
    # their lists and a file the benchmark has is not this PR's to edit
    assert not names & {"device_starved_pct.tail", "host_turnaround_ms.tail",
                        "host_off_cpu_pct.tail"}
    assert not names & {"latent_decode_kernel_busy_pct", "moe_busy_pct",
                        "window_decode_kernel_busy_pct",
                        "decode_hbm_bound_pct", "decode_hbm_bound_pct.laguna",
                        "decode_hbm_bound_pct.pangu",
                        "kv_window_pages_saved_pct",
                        "attn_kernel_busy_pct.serve"}
    e2e = {m["name"] for m in SPEC.metrics_of("end_to_end", CELL)}
    assert e2e == {"ttft_p75_ms", "tpot_p95_ms", "setup_s"}
    traffic = SPEC.traffic("longanswer-steady")
    assert traffic["generator"] == "open_loop"
    assert traffic["prompt_len"] == {"median": 192, "sigma": 0.8,
                                     "min": 16, "max": 2048}
    assert traffic["output_len"] == {"median": 256, "sigma": 0.7,
                                     "min": 32, "max": 1024}
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= CFG["max_position_embeddings"]
    assert (traffic["lead_in_s"], traffic["end"], traffic["drain_s"],
            traffic["trace_s"]) == (20.0, "drain", 120.0, 4.0)
    assert "start_at" in traffic and "0.8 x" in traffic["rate_is"]
    # the canaries reach the mix's longest request
    assert max(serve_granite.CANARIES) == (2048, 1024)
    assert sum(m for _n, m in serve_granite.CANARIES) == 1440


# ----------------------------------------------- what the comparison sees


@pytest.fixture(scope="module")
def small():
    """The small model's weights, four prompts and the bfloat16
    program's greedy answers to them, and `held(picks)`: the kind's
    comparison of those picks with what the reference says of them."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks import reference_granite as ref
    from ray_tpu.models.granite import GraniteConfig, build

    was, ref.LENGTHS = ref.LENGTHS, (256,)
    cfg = GraniteConfig.tiny()
    sizes = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
             if "dtype" not in f.name}
    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(1, 256, n)]
               for n in (40, 100, 150, 70)]
    model = build(cfg, 16)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    forward = jax.jit(lambda p, t: model.apply({"params": p}, t))

    def greedy(prompt, n=40):
        toks = list(prompt)
        for _ in range(n):
            lg = forward(params, jnp.asarray([toks + [0] * (256 - len(toks))]))
            toks.append(int(jnp.argmax(lg[0, len(toks) - 1])))
        return toks[len(prompt):]

    answers = [greedy(p) for p in prompts]

    def held(reading=None):
        picks = answers if reading is None else [
            r["top_id"] for r in ref.teacher_forced(
                params, prompts, answers, sizes, reading=reading)]
        return serve_granite.judge(
            [{"tokens": p} for p in prompts], picks,
            ref.teacher_forced(params, prompts, answers, sizes, picks=picks))

    yield held
    ref.LENGTHS = was


def _refs(n: int, dists):
    """A reference's say of one canary of `n` tokens whose picks (ids 1)
    lie `dists[j]` bfloat16 spacings under its own choice (id 2) at the
    first positions and are its choice at the rest."""
    from benchmarks.kinds.serve import bf16_ulp

    top = 8.0
    under = list(dists) + [0.0] * (n - len(dists))
    return [{"top": [top] * n,
             "top_id": [2 if d else 1 for d in under],
             "picked": [top - d * bf16_ulp(top) for d in under],
             "margin": [1.0] * n}]


@pytest.mark.parametrize("dists,refused", [
    # roundings: 4 % of the positions a few spacings off, none far
    ([6.0] * 40, None),
    # a fault everywhere: 6 % a few spacings off
    ([6.0] * 60, "more than 4.0 bfloat16 spacings"),
    # a fault in what a short sequence starts from: its first five
    # tokens tens of spacings off, 0.5 % of the positions
    ([60.0] * 5, "farther than a rounding goes"),
    # three such positions are inside what the far limit allows
    ([60.0] * 3, None),
])
def test_either_limit_refuses_alone(dists, refused):
    n = 1000
    got = serve_granite.judge([{"tokens": [3, 4]}], [[1] * n],
                              _refs(n, dists))
    assert got["judged"] == n
    assert got["off_share"] == pytest.approx(len(dists) / n)
    assert got["far_share"] == pytest.approx(
        sum(d > serve_granite.FAR_TOL_ULPS for d in dists) / n)
    if refused is None:
        assert got["off"] == []
    else:
        assert len(got["off"]) == 1 and refused in got["off"][0]
        assert "canary of 2 tokens, token 0" in got["off"][0]


def test_the_bfloat16_program_passes_and_every_position_is_judged(small):
    got = small()
    assert got["off"] == [] and got["judged"] == got["positions"] == 160
    assert got["near_tie_share"] == 0.0
    assert got["off_share"] <= serve_granite.MAX_OFF_SHARE
    assert got["far_share"] == 0.0


@pytest.mark.parametrize("reading", ["float8_e4m3fn", "scale_1_8"])
def test_a_reading_the_comparison_refuses_at_this_size_too(small, reading):
    """The float8 matrices (the nearest precision below the stated
    bfloat16) and the attention scaled by 1/8 pick tokens the reference
    proper puts beyond the tolerance at more positions than
    MAX_OFF_SHARE allows: not `correct`.  (The chip's readings of all
    five, at the published size, are in PERF.md section 6.)"""
    got = small(reading)
    assert got["off_share"] > serve_granite.MAX_OFF_SHARE
    assert got["off"] and "judged positions" in got["off"][0]


@pytest.mark.parametrize("reading", ["bfloat16_state", "decaying_pad",
                                     "stale_slot"])
def test_a_reading_too_fine_for_a_toy_model_is_pinned(small, reading):
    """At 16 state numbers a head and three state layers a carry
    rounded to bfloat16, 24 to 60 padded positions of decay, or another
    40-to-150-token sequence's state move a logit by less than the gap
    of the two largest at nearly every position: under the limit HERE.
    Pinned so that what this size can and cannot show is a tested fact;
    what the comparison says of them at the published size is the
    chip's reading (PERF.md sections 6 and 7), and the program's own
    guard against the last two is `tests/test_granite_model.py`
    (`test_a_stale_slot_would_show`) and `tests/test_ssm_ops.py` (a
    padded position leaves the state bit-identical)."""
    got = small(reading)
    assert got["off"] == []
    assert got["off_share"] <= serve_granite.MAX_OFF_SHARE
    assert got["far_share"] <= serve_granite.MAX_FAR_SHARE
