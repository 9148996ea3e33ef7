"""The hybrid expert configuration, its arithmetic, its readers, and the
comparison that decides `correct` in its cell — at a small size on the
CPU.  Every entry of BENCHMARK.json is found BY NAME: a later PR appends
its own behind them."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import model_math_qwen3next as mm  # noqa: E402
from benchmarks.kinds import serve_qwen3next as kind  # noqa: E402
from benchmarks.spec import Spec  # noqa: E402

SPEC = Spec(REPO)
CELL = "serve-qwen3next-manystreams-steady"
CFG = SPEC.config("qwen3-next-80b-a3b-serve")
NEW = ("decode_hbm_bound_pct.qwen3next", "moe_rows_per_expert_call.tail",
       "gdn_chunk_roofline_pct.qwen3next",
       "gdn_decode_roofline_pct.qwen3next",
       "gdn_chunk_kernel_busy_pct.qwen3next",
       "gdn_decode_kernel_busy_pct.qwen3next", "kv_pages_fill_pct.qwen3next")

# https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/
# config.json: the numbers and switches of the catalog row
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_configuration_is_one_share_of_the_published_model():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"}
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    assert set(CFG["why_reduced"]) == set(CFG["reduced"]) | {"the_cut"}
    # no width is cut: depth, experts HELD, vocabulary rows, positions
    assert (CFG["num_hidden_layers"], CFG["num_experts"], CFG["vocab_size"],
            CFG["max_position_embeddings"]) == (4, 256, 75968, 16384)
    assert CFG["experts_held"] == [0, 256]
    assert CFG["num_experts_routed_over"] == 512
    assert 2 * CFG["vocab_size"] == PUBLISHED["vocab_size"]
    assert CFG["num_hidden_layers"] == CFG["full_attention_interval"]
    # every assumption names its one place in the program
    assert set(CFG["assumed"]) >= {
        "norm", "gated_norm_order", "key_head_repeat", "gates", "router",
        "rotary", "gate_initialisation", "embedding_initialisation",
        "dtypes", "kv_row", "state_layout", "projection_layout"}
    for key, text in CFG["assumed"].items():
        assert key in ("dtypes", "not_served") or "models/" in text \
            or "ops/" in text, key
    dep = CFG["deployment"]
    assert "share 0 of the 2 chips" in dep["stands_for"]
    assert "Nothing stands in" in dep["stands_for"]
    assert dep["kind"] == "serve_qwen3next"
    assert dep["engine"] == {"max_batch": 128, "page_size": 16,
                             "num_pages": 32769}
    entry = _named(_bench()["configs"], "qwen3-next-80b-a3b-serve")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    cell = _named(_bench()["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b-serve", "manystreams-steady", 1)


def test_the_engines_model_is_made_of_the_files_keys():
    """`model_kwargs` hands the engine the published keys and the
    share's; the family reads them as the configuration says, and a toy
    config of the rehearsal group's keys as well."""
    from benchmarks.run import rehearsal_config
    from ray_tpu.models import resolve

    model = kind.model_kwargs(CFG)
    assert set(model) == set(kind.MODEL_KEYS) >= set(PUBLISHED)
    family, cfg = resolve(model)
    assert family.__name__.endswith("models.qwen3_next")
    assert cfg.share() == {"experts_held": [0, 256], "num_experts": 512,
                           "vocab_rows": 75968}
    assert [layer.kind for layer in cfg.cache_spec()] == [
        "state", "state", "state", "full"]
    assert cfg.cache_spec()[3].rows() == {"k": (512,), "v": (512,)}
    with pytest.raises(ValueError, match="experts_held"):
        kind.model_kwargs({**CFG, "experts_held": [0, 128]})
    _f, toy = resolve(kind.model_kwargs(rehearsal_config(CFG)))
    assert (toy.hidden_size, toy.num_experts, toy.experts_held) == (
        64, 8, (0, 4))


def test_parameters_and_bytes_against_the_issues_arithmetic():
    """ISSUE 55's arithmetic, by hand there."""
    assert mm.linear_mixer_params(CFG) == 33_718_464
    assert mm.full_mixer_params(CFG) == 27_263_488
    assert mm.expert_params(CFG) == 3_145_728
    assert mm.layer_params(CFG, mm.LINEAR) == 843_225_280
    assert mm.layer_params(CFG, mm.FULL) == 836_770_304
    assert mm.embedding_params(CFG) == 155_582_464
    assert mm.total_params(CFG) == 3_677_613_120 == (
        3 * 843_225_280 + 836_770_304 + 2 * 155_582_464 + 2_048)
    whole = {**CFG, **{k: PUBLISHED[k] for k in CFG["reduced"]}}
    assert 79.6e9 < mm.total_params(whole) < 79.7e9
    assert mm.state_row_numbers(CFG) == 32 * 128 * 128
    assert mm.state_bytes_per_sequence(CFG) == 6_438_912 == 3 * (
        2_097_152 + 49_152)
    assert mm.kv_bytes_per_token(CFG) == 2_048
    # the chunk form: K K^T and Q K^T once a KEY head, the rest a value
    # head: 5.2 MFLOP a token where 32 repeated key heads are 5.8
    cost = mm.chunk_cost(CFG, tokens=1000, lane_passes=10)
    assert cost["flops"] == 1000 * (16 * 32_768 + 32 * 147_456)
    assert cost["bytes"] == 1000 * (2 * (2048 + 2048 + 4096 + 4096) + 256) \
        + 10 * 2 * 2_097_152
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12
    assert mm.state_update_cost(CFG, rows=1000) == {
        "flops": 7.0 * 524_288_000, "bytes": 4_194_304_000.0}
    # a decode pass of 64 lanes at 1,500 tokens touching 730 (expert,
    # layer) pairs: 0.6 GB outside the experts, 4.6 GB of experts
    assert mm.params_outside_experts(CFG) == 300_805_184
    assert mm.decode_step_bytes(CFG, 2, 2, [1500] * 64, 730, 3 * 64) == (
        2 * (300_805_184 + 730 * 3_145_728) + 64 * 1500 * 2_048
        + 3 * 64 * 4_194_304)
    dep = CFG["deployment"]
    assert "3,677,613,120 parameters" in dep["bytes"]["weights"]
    assert 129 * mm.state_bytes_per_sequence(CFG) == 830_619_648
    assert "830,619,648" in dep["bytes"]["state_pool"]
    assert 32769 * 16 * 2_048 == 1_073_774_592
    assert "1,073,774,592" in dep["bytes"]["pages"]


def _obs(rows=0, tokens=0, visits=0, chunk_s=0.0, update_s=0.0, calls=0,
         picks=0, **stats):
    first = {"state_decode_rows_total": 1000, "decode_steps": 10,
             "state_prefill_rows_total": 500,
             "delta_prefill_tokens_total": {"decode": 0, "prefill": 7000},
             "moe_expert_calls_total": {"decode": 900, "prefill": 50},
             "moe_assignments_total": {"decode": 1500, "prefill": 9000},
             "decode_secs": 1.0, "used_pages": 1024, "free_pages": 3072,
             "max_batch": 128, "active": 64, "t": 0.0}
    last = {**first, "state_decode_rows_total": 1000 + rows,
            "state_prefill_rows_total": 500 + visits,
            "delta_prefill_tokens_total": {"decode": 0,
                                           "prefill": 7000 + tokens},
            "moe_expert_calls_total": {"decode": 900 + calls, "prefill": 50},
            "moe_assignments_total": {"decode": 1500 + picks,
                                      "prefill": 9000},
            **stats}
    return {"trace": {"busy_s": 2.0, "devices": 1,
                      "op_seconds": {
                          "gated_delta_chunk tpu_custom_call": chunk_s,
                          "gated_delta_update tpu_custom_call": update_s},
                      "span_stats": [[first, last]]},
            "polls": [[first, last]], "model": CFG,
            "engine": {"dtype": "bfloat16",
                       "param_bytes": 2 * mm.total_params(CFG)},
            "device": {"kind": "TPU v5 lite"},
            "summary": {"mean_context": 1500.0}}


def _meta(name):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_new_readers_on_hand_made_observations():
    from benchmarks.readers import (decode_hbm_bound_qwen3next,
                                    gdn_roofline_qwen3next, stats_ratio)

    chunk = _meta("gdn_chunk_roofline_pct.qwen3next")["params"]
    update = _meta("gdn_decode_roofline_pct.qwen3next")["params"]
    for twin in ("gdn_chunk_kernel_busy_pct", "gdn_decode_kernel_busy_pct"):
        # the twins read what the metrics they stand beside read
        ours, theirs = _meta(twin + ".qwen3next"), _meta(twin)
        assert (ours["reader"], ours["params"], ours["unit"], ours["layer"],
                ours["moves"]) == (theirs["reader"], theirs["params"],
                                   theirs["unit"], theirs["layer"],
                                   theirs["moves"])
    assert chunk["pattern"] == _meta("gdn_chunk_kernel_busy_pct.qwen3next")[
        "params"]["pattern"]
    assert update["pattern"] == _meta(
        "gdn_decode_kernel_busy_pct.qwen3next")["params"]["pattern"]
    assert _meta("kv_pages_fill_pct.qwen3next")["reader"] == _meta(
        "kv_pages_fill_pct.tail")["reader"] == "kv_pages_fill"
    obs = _obs(tokens=120_000, visits=600, chunk_s=0.4, rows=50_000,
               update_s=0.5)
    floor = (120_000 * 24_832 + 600 * 2 * 2_097_152) / 819e9
    assert floor > 120_000 * 5_242_880 / 197e12
    assert gdn_roofline_qwen3next.read(obs, chunk) == pytest.approx(
        100 * floor / 0.4)
    assert gdn_roofline_qwen3next.read(obs, update) == pytest.approx(
        100 * (50_000 * 4_194_304 / 819e9) / 0.5)
    # no kernel in the trace (the interpreter's), no counter (the
    # parent's program): nothing, and nothing raised
    assert gdn_roofline_qwen3next.read(_obs(tokens=5, visits=1), chunk) \
        is None
    assert gdn_roofline_qwen3next.read(_obs(rows=5), update) is None
    bare = _obs(tokens=5, visits=1, rows=5, chunk_s=1.0, update_s=1.0)
    for s in bare["polls"][0]:    # the span's pair is the same two rows
        del s["state_decode_rows_total"], s["delta_prefill_tokens_total"]
        del s["moe_expert_calls_total"]
    assert gdn_roofline_qwen3next.read(bare, chunk) is None
    assert gdn_roofline_qwen3next.read(bare, update) is None
    assert decode_hbm_bound_qwen3next.read(bare, {}) is None
    rows_meta = _meta("moe_rows_per_expert_call.tail")
    assert rows_meta["reader"] == "stats_ratio"
    assert stats_ratio.read(bare, rows_meta["params"]) is None
    assert gdn_roofline_qwen3next.read({"trace": {}}, chunk) is None
    # 40 passes of 64 live lanes: 3 state rows a lane, 730 experts
    # touched by 1,280 picks a pass, 12 ms a pass
    obs = _obs(rows=3 * 64 * 40, calls=730 * 40, picks=1280 * 40,
               decode_steps=50, decode_secs=1.48)
    assert stats_ratio.read(obs, rows_meta["params"]) == pytest.approx(
        1280 / 730)
    want = (2 * (300_805_184 + 730 * 3_145_728) + 64 * 1500 * 2_048
            + 3 * 64 * 4_194_304) / 819e9
    assert decode_hbm_bound_qwen3next.read(obs, {}) == pytest.approx(
        100 * want / 0.012)


def test_the_cell_reports_what_a_reader_finds_on_this_family():
    bench = _bench()
    names = {m["name"] for m in SPEC.metrics_of("per_layer", CELL)}
    assert set(NEW) | {
        "paged_decode_kernel_busy_pct", "paged_prefill_kernel_busy_pct",
        "moe_busy_pct", "moe_experts_roofline_pct",
        "moe_experts_touched_pct.tail", "moe_load_max_over_mean.tail",
        "moe_row_tiles_per_expert.tail", "state_pool_fill_pct.tail", "decode_step_ms.tail",
        "lane_occupancy_pct.tail", "ready_s", "gen_late_p95_ms"} <= names
    # the new entries are the last of `per_layer`, this cell's alone
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    assert all(m["workloads"] == [CELL] for m in tail)
    for m in tail:
        meta = _meta(m["name"])
        assert (meta["unit"], meta["layer"], meta["moves"]) == (
            m["unit"], m["layer"], m["moves"])
    # three lists are another test's and stay as they were; so do the
    # six that test_bench_reference_olmo.py pins to ITS cell (twins here)
    assert not names & {"device_starved_pct.tail", "host_turnaround_ms.tail",
                        "host_off_cpu_pct.tail", "gdn_chunk_roofline_pct",
                        "gdn_decode_roofline_pct",
                        "gdn_chunk_kernel_busy_pct",
                        "gdn_decode_kernel_busy_pct",
                        "kv_pages_fill_pct.tail",
                        "decode_hbm_bound_pct.olmo",
                        "decode_hbm_bound_pct.laguna",
                        "decode_hbm_bound_pct",
                        "state_lanes_per_decode_step.tail",
                        "attn_kernel_busy_pct.serve"}
    e2e = {m["name"] for m in SPEC.metrics_of("end_to_end", CELL)}
    assert e2e == {"ttft_p75_ms", "tpot_p95_ms", "setup_s"}
    traffic = SPEC.traffic("manystreams-steady")
    assert traffic["generator"] == "open_loop"
    assert traffic["prompt_len"] == {"median": 768, "sigma": 1.0,
                                     "min": 64, "max": 8192}
    assert traffic["output_len"] == {"median": 384, "sigma": 0.7,
                                     "min": 64, "max": 1536}
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= CFG["max_position_embeddings"]
    assert (traffic["lead_in_s"], traffic["end"], traffic["drain_s"],
            traffic["trace_s"]) == (25.0, "drain", 120.0, 4.0)
    assert "start_at" in traffic and "0.8 x" in traffic["rate_is"]
    # the canaries reach the mix's longest request
    assert max(kind.CANARIES) == (8192, 1536)
    assert sum(m for _n, m in kind.CANARIES) == 1840
    from benchmarks import reference_qwen3next as ref
    assert set(kind.READINGS) == set(ref.READINGS) and len(ref.READINGS) == 13
    assert max(ref.LENGTHS) >= 8192 + 1536 and 9728 in ref.LENGTHS
    assert CFG["vocab_size"] % ref.VOCAB_SLICE == 0


def test_the_mixs_window_holds_the_cycles_load():
    """Every seed's window holds the same requests from the same point
    of the cycle, other ids, distinct first tokens."""
    from benchmarks.generators.open_loop import generate

    traffic = SPEC.traffic("manystreams-steady")
    a = generate(traffic, 2150000011, 50.0, CFG["vocab_size"])
    b = generate(traffic, 3, 50.0, CFG["vocab_size"])
    shape = lambda plan: [(r["due_s"], len(r["tokens"]),   # noqa: E731
                           r["max_new_tokens"], r["counted"])
                          for r in plan["requests"]]
    assert shape(a) == shape(b)
    assert [r["tokens"] for r in a["requests"]] \
        != [r["tokens"] for r in b["requests"]]
    counted = [r for r in a["requests"] if r["counted"]]
    assert len(counted) == round(traffic["rate_rps"] * 50)
    firsts = [r["tokens"][0] for r in a["requests"]]
    assert len(set(firsts)) == len(firsts)
    assert max(max(r["tokens"]) for r in a["requests"]) < CFG["vocab_size"]
    lead = [r for r in a["requests"] if not r["counted"]]
    assert lead and all(r["due_s"] < 25.0 for r in lead)


# ----------------------------------------------- what the comparison sees


@pytest.fixture(scope="module")
def small():
    """The small model's weights, four prompts and the float32 program's
    greedy answers to them, and `held(reading)`: the kind's comparison of
    that reading's picks with what the reference proper says of them."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks import reference_qwen3next as ref
    from ray_tpu.models.qwen3_next import Qwen3NextConfig, build

    was, ref.LENGTHS = ref.LENGTHS, (256,)
    cfg = Qwen3NextConfig.tiny()
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
        return kind.judge(
            [{"tokens": p} for p in prompts], picks,
            ref.teacher_forced(params, prompts, answers, sizes, picks=picks))

    def carry(reading=None):
        return ref.carry_distance(
            ref.carried_states(params, prompts[1], answers[1], sizes,
                               reading=reading),
            ref.carried_states(params, prompts[1], answers[1], sizes))

    held.carry = carry
    yield held
    ref.LENGTHS = was


def _refs(n: int, dists, margin=1.0):
    """A reference's say of one canary of `n` tokens whose picks (ids 1)
    lie `dists[j]` bfloat16 spacings under its own choice (id 2) at the
    first positions and are its choice at the rest."""
    from benchmarks.kinds.serve import bf16_ulp

    top = 8.0
    under = list(dists) + [0.0] * (n - len(dists))
    return [{"top": [top] * n,
             "top_id": [2 if d else 1 for d in under],
             "picked": [top - d * bf16_ulp(top) for d in under],
             "margin": [margin] * n}]


@pytest.mark.parametrize("share,far,refused", [
    (0.8, 0.0, None), (1.3, 0.0, "bfloat16 spacings"),
    (0.5, 1.5, "farther than a rounding or a flipped expert goes"),
    (0.5, 0.5, None)],
    ids=["flips", "a-fault-everywhere", "a-fault-at-a-start",
         "inside-the-far-limit"])
def test_either_limit_refuses_alone(share, far, refused):
    n = 2000
    near = [6.0] * int(share * kind.MAX_OFF_SHARE * n)
    beyond = [2.5 * kind.FAR_TOL_ULPS] * int(far * kind.MAX_FAR_SHARE * n)
    got = kind.judge([{"tokens": [3, 4]}], [[1] * n],
                     _refs(n, beyond + near))
    assert got["judged"] == n
    assert got["off_share"] == pytest.approx((len(near) + len(beyond)) / n)
    assert got["far_share"] == pytest.approx(len(beyond) / n)
    if refused is None:
        assert got["off"] == []
    else:
        assert len(got["off"]) == 1 and refused in got["off"][0]
        assert "canary of 2 tokens, token 0" in got["off"][0]


def test_a_position_inside_the_routers_margin_is_set_aside_and_counted():
    n = 400
    near = _refs(n, [60.0] * 300)
    near[0]["margin"] = [kind.ROUTER_TIE_TAU] * 300 + [1.0] * 100
    got = kind.judge([{"tokens": [3, 4]}], [[1] * n], near)
    assert got["judged"] == 100 and got["near_tie_share"] == 0.75
    assert got["off_share"] == 0.0
    assert len(got["off"]) == 1 and "near ties" in got["off"][0]
    mixed = _refs(n, [60.0] * 100)
    mixed[0]["margin"] = [kind.ROUTER_TIE_TAU / 2] * 100 + [1.0] * 300
    got = kind.judge([{"tokens": [3, 4]}], [[1] * n], mixed)
    assert got["judged"] == 300 and got["off"] == []
    assert got["near_tie_share"] == pytest.approx(0.25)
    assert got["worst_ulps_near_ties"] == pytest.approx(60.0)


def test_the_float32_program_passes(small):
    got = small()
    assert got["off"] == [] and got["positions"] == 160
    assert got["judged"] >= 120
    assert got["off_share"] <= kind.MAX_OFF_SHARE
    assert got["far_share"] <= kind.MAX_FAR_SHARE


# what no share of positions shows, at this size as at the published one:
# a carry's rounding (the carry limit's to refuse)
UNDER_THE_LIMITS_HERE = ("bfloat16_state",)


@pytest.mark.parametrize("reading", kind.READINGS)
def test_what_the_comparison_says_of_each_reading_at_this_size(small,
                                                               reading):
    """Every reading of `reference_qwen3next.READINGS`, judged as a
    program with that fault would be.  What this size can and cannot
    show is a tested fact; what the comparison says of each at the
    published size is the chip's reading (PERF.md section 6, PR 55)."""
    got = small(reading)
    if reading in UNDER_THE_LIMITS_HERE:
        assert got["off"] == [], (reading, got["off_share"])
    else:
        assert got["off"], (reading, got["off_share"], got["far_share"])
        assert got["off_share"] > kind.MAX_OFF_SHARE \
            or got["far_share"] > kind.MAX_FAR_SHARE \
            or got["near_tie_share"] > 0.5 or got["judged"] < 32


def test_a_bfloat16_carry_shows_in_the_state_where_no_logit_shows_it(small):
    same = small.carry()
    assert len(same["layers"]) == 3 and len(same["heads"][0]) == 4
    assert max(same["layers"]) == 0.0
    got = kind.judge_carry(small.carry("bfloat16_state"))
    assert 1e-4 < got["carry_off"] < 3e-2
    assert got["carry_layer_off"] > 1e-4
    assert small("bfloat16_state")["off"] == []
    # ... and the limit stands between the chip's two readings, behind
    # the longest canary (PERF.md section 6, PR 55)
    assert 0.0052 < kind.MAX_CARRY_OFF < 0.0166
    # a stale slot is the SHORT canary's to refuse: behind 140 toy tokens
    # its state still stands
    stale = kind.judge_carry(small.carry("stale_slot"), "shortest")
    assert stale["off"] and "shortest canary" in stale["off"][0]


@pytest.mark.parametrize("first,refused", [
    (kind.MAX_CARRY_OFF * 0.5, False),
    (kind.MAX_CARRY_OFF * 2.0, True)], ids=["inside", "beyond"])
def test_the_carry_limit_reads_the_first_layers_worst_head(first, refused):
    deep = kind.MAX_CARRY_OFF * 5.0
    got = kind.judge_carry(
        {"layers": [first / 2, deep],
         "heads": [[first / 4, first], [deep, deep * 2]]})
    assert got["carry_off"] == first and got["carry_layer_off"] == deep
    assert got["carry_head_off"] == deep * 2
    assert bool(got["off"]) == refused
    if refused:
        assert "first linear layer" in got["off"][0]


def test_asked_readings_come_from_the_environment(monkeypatch):
    monkeypatch.delenv("QWEN3NEXT_READINGS", raising=False)
    assert kind.asked_readings() == []
    monkeypatch.setenv("QWEN3NEXT_READINGS", "bfloat16_state,stale_slot")
    assert kind.asked_readings() == ["bfloat16_state", "stale_slot"]
    monkeypatch.setenv("QWEN3NEXT_READINGS", "all")
    assert kind.asked_readings() == list(kind.READINGS)
    monkeypatch.setenv("QWEN3NEXT_READINGS", "float4")
    with pytest.raises(ValueError, match="float4"):
        kind.asked_readings()
