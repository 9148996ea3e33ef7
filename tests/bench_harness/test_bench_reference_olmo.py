"""The hybrid linear-attention configuration, its arithmetic, its
readers, and the comparison that decides `correct` in its cell — at a
small size on the CPU.  Every entry of BENCHMARK.json is found BY NAME:
a later PR appends its own behind them."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import model_math_olmo as mm  # noqa: E402
from benchmarks.kinds import serve_olmo  # noqa: E402
from benchmarks.spec import Spec  # noqa: E402

SPEC = Spec(REPO)
CELL = "serve-olmo-hybrid-longdoc-steady"
CFG = SPEC.config("olmo-hybrid-7b-serve")
NEW = ("gdn_chunk_kernel_busy_pct", "gdn_chunk_roofline_pct",
       "gdn_decode_kernel_busy_pct", "gdn_decode_roofline_pct",
       "decode_hbm_bound_pct.olmo", "kv_pages_fill_pct.tail")

# https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json,
# the numbers and switches of the catalog row
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_configuration_is_stage_0_of_the_published_model():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == {
        "num_hidden_layers", "layer_types", "max_position_embeddings"}
    assert CFG["published"]["num_hidden_layers"] == 32
    assert CFG["published"]["max_position_embeddings"] == 65536
    assert set(CFG["why_reduced"]) == {"num_hidden_layers",
                                       "max_position_embeddings"}
    assert CFG["num_hidden_layers"] == 16
    assert CFG["layer_types"] == PERIOD * 4 == PUBLISHED["layer_types"][:16]
    assert CFG["max_position_embeddings"] == 16384
    # every assumption names its one place in the program
    assert set(CFG["assumed"]) >= {
        "block_form", "qk_norm", "no_positions", "delta_rule",
        "state_dtype", "state_layout", "kv_row", "gate_initialisation"}
    for key, text in CFG["assumed"].items():
        assert key == "torch_dtype" or "models/" in text \
            or "ops/" in text, key
    dep = CFG["deployment"]
    assert "stage 0 of a two-stage pipeline" in dep["stands_for"]
    assert "whole vocabulary" in dep["stands_for"]
    assert dep["kind"] == "serve_olmo"
    assert dep["engine"] == {"max_batch": 32, "page_size": 16,
                             "num_pages": 4097}
    bench = _bench()
    entry = _named(bench["configs"], CFG["name"])
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/"
        "config.json")
    assert entry["file"] == "benchmarks/configs/olmo-hybrid-7b-serve.json"
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CFG["name"], "longdoc-steady", 1)
    for group in ("configs", "workloads"):
        for e in bench[group]:
            assert len(e["why"]) <= 200, e["name"]
    # every new metric lists this cell alone, wherever it stands
    for name in NEW:
        assert _named(bench["per_layer"], name)["workloads"] == [CELL]
    # one configuration, one cell of this family
    assert [c["name"] for c in bench["workloads"]
            if c["config"] == CFG["name"]] == [CELL]
    # the benchmark's time rule with one more cell
    cells = len(bench["workloads"])
    assert (2 + 14 * cells) * (bench["run_seconds"] + 60) \
        + 2 * 90 * cells + 1200 <= 43200


def test_the_engines_model_is_made_of_the_files_keys():
    from ray_tpu.models import resolve

    family, cfg = resolve(serve_olmo.model_kwargs(CFG))
    assert family.__name__ == "ray_tpu.models.olmo_hybrid"
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size,
            cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.kv_rows,
            cfg.linear_num_key_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.conv_dim, cfg.max_seq_len) == (
        3840, 16, 100352, 11008, 30, 30, 128, 32, 30, 96, 192, 11520, 16384)
    kinds = [layer.kind for layer in cfg.cache_spec()]
    assert kinds == ["state" if t == "linear_attention" else "full"
                     for t in CFG["layer_types"]]
    with pytest.raises(ValueError, match="layer_types"):
        serve_olmo.model_kwargs({**CFG, "num_hidden_layers": 15})
    with pytest.raises(ValueError, match="rope_parameters"):
        resolve({**serve_olmo.model_kwargs(CFG),
                 "rope_parameters": {"rope_theta": 10000.0}})
    toy = {**CFG, **{k: v for k, v in CFG["rehearsal"].items()
                     if k != "deployment"}}
    _family, small = resolve(serve_olmo.model_kwargs(toy))
    assert (small.hidden_size, small.head_dim, small.kv_rows,
            small.linear_key_head_dim, small.linear_value_head_dim) == (
        128, 32, 8, 24, 48)
    # the parent of a run fails at the kind's check of the model FILE
    assert serve_olmo._MODEL.endswith("ray_tpu/models/olmo_hybrid.py")
    assert os.path.isfile(serve_olmo._MODEL)


def test_parameters_and_bytes_against_the_issues_arithmetic():
    """ISSUE 50's arithmetic, by hand there: 215,570,172 parameters a
    linear layer (its mixer 88,750,332), 185,809,920 a full layer,
    4,100,788,944 on this chip (tests/test_olmo_hybrid_model.py counts
    the engine's tree); 27,371,520 B of state a sequence whatever its
    length; 15,360 B of the model's keys and values a token a full
    layer."""
    assert mm.mlp_params(CFG) == 126_812_160
    assert mm.linear_layer_params(CFG) == 215_570_172 \
        == 88_750_332 + 126_812_160 + 7_680
    assert mm.full_layer_params(CFG) == 185_809_920
    assert mm.embedding_params(CFG) == 385_351_680
    assert mm.total_params(CFG) == 4_100_788_944 == (
        12 * 215_570_172 + 4 * 185_809_920 + 770_707_200)
    whole = {**CFG, "layer_types": PUBLISHED["layer_types"]}
    assert mm.total_params(whole) == 7_430_870_688
    assert mm.state_row_numbers(CFG) == 30 * 96 * 192 == 552_960
    assert mm.state_bytes_per_sequence(CFG) == 27_371_520 == 12 * (
        2_211_840 + 3 * 11520 * 2)
    assert mm.kv_bytes_per_token(CFG) == 61_440 == 4 * 15_360
    # the chunk form: 196,608 operations a token a head, 5.9 M a layer;
    # q, k, v, o in bfloat16 and two float32 gates a head a token; a
    # state read and written a visit
    cost = mm.chunk_cost(CFG, tokens=1000, lane_passes=10)
    assert cost["flops"] == 1000 * 30 * 196_608 == 5_898_240_000
    assert cost["bytes"] == 1000 * (2 * (2880 + 2880 + 5760 + 5760) + 240) \
        + 10 * 2 * 2_211_840
    # bound by the bytes at the chip's peaks, whatever the pass's depth
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12
    # the decode kernel: a row read and written in float32
    assert mm.state_update_cost(CFG, rows=1000) == {
        "flops": 7.0 * 552_960_000, "bytes": 4_423_680_000.0}
    # a decode pass of 12 lanes at 4,400 tokens: the weights but the
    # embedding table, 3.2 GB of keys and values, 0.6 GB of state
    assert mm.decode_step_bytes(CFG, 2, 2, [4400] * 12, 12 * 12) == (
        2 * (4_100_788_944 - 385_351_680) + 12 * 4400 * 61_440
        + 12 * 12 * 4_423_680)
    dep = CFG["deployment"]
    assert "4,100,788,944 parameters" in dep["bytes"]["weights"]
    assert 33 * mm.state_bytes_per_sequence(CFG) == 903_260_160
    assert "903,260,160" in dep["bytes"]["state_pool"]
    assert 4097 * 16 * 4 * 2 * 32 * 128 * 2 == 4_296_015_872
    assert "4,296,015,872" in dep["bytes"]["pages"]


def _obs(rows=0, tokens=0, visits=0, chunk_s=0.0, update_s=0.0, **stats):
    first = {"state_decode_rows_total": 1000, "decode_steps": 10,
             "state_prefill_rows_total": 500,
             "delta_prefill_tokens_total": {"decode": 0, "prefill": 7000},
             "decode_lane_steps_total": 100, "decode_secs": 1.0,
             "used_pages": 1024, "free_pages": 3072, "max_batch": 32,
             "active": 12, "t": 0.0}
    last = {**first, "state_decode_rows_total": 1000 + rows,
            "state_prefill_rows_total": 500 + visits,
            "delta_prefill_tokens_total": {"decode": 0,
                                           "prefill": 7000 + tokens},
            **stats}
    return {"trace": {"busy_s": 2.0, "devices": 1,
                      "op_seconds": {
                          "gated_delta_chunk tpu_custom_call": chunk_s,
                          "gated_delta_update tpu_custom_call": update_s,
                          "paged_attention_decode tpu_custom_call": 0.5},
                      "span_stats": [[first, last]]},
            "polls": [[first, last]], "model": CFG,
            "engine": {"dtype": "bfloat16",
                       "param_bytes": 2 * mm.total_params(CFG)},
            "device": {"kind": "TPU v5 lite"},
            "summary": {"mean_context": 4400.0}}


def _params(name):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)["params"]


def test_the_new_readers_on_hand_made_observations():
    from benchmarks.readers import (decode_hbm_bound_olmo,
                                    gdn_chunk_roofline, gdn_decode_roofline,
                                    kv_pages_fill, trace_op_share)

    chunk, update = (_params("gdn_chunk_roofline_pct"),
                     _params("gdn_decode_roofline_pct"))
    assert chunk == _params("gdn_chunk_kernel_busy_pct")
    assert update == _params("gdn_decode_kernel_busy_pct")
    # 120,000 (token, layer) pairs in 600 visits: the bytes bound it
    obs = _obs(tokens=120_000, visits=600, chunk_s=0.4, rows=50_000,
               update_s=0.5)
    floor = (120_000 * 34_800 + 600 * 2 * 2_211_840) / 819e9
    assert floor > 120_000 * 5_898_240 / 197e12
    assert gdn_chunk_roofline.read(obs, chunk) == pytest.approx(
        100 * floor / 0.4)
    assert trace_op_share.read(obs, chunk) == pytest.approx(20.0)
    assert gdn_decode_roofline.read(obs, update) == pytest.approx(
        100 * (50_000 * 4_423_680 / 819e9) / 0.5)
    assert trace_op_share.read(obs, update) == pytest.approx(25.0)
    # neither pattern takes the other kernel, nor the paged one, in
    assert trace_op_share.read(
        obs, _params("paged_decode_kernel_busy_pct")) == pytest.approx(25.0)
    # no kernel in the trace (the interpreter's), no counter (the
    # parent's program): nothing, and nothing raised
    assert gdn_chunk_roofline.read(_obs(tokens=5, visits=1), chunk) is None
    assert gdn_decode_roofline.read(_obs(rows=5), update) is None
    bare = _obs(tokens=5, visits=1, rows=5, chunk_s=1.0, update_s=1.0)
    for s in bare["polls"][0]:    # the span's pair is the same two rows
        del s["state_decode_rows_total"], s["delta_prefill_tokens_total"]
        del s["used_pages"]
    assert gdn_chunk_roofline.read(bare, chunk) is None
    assert gdn_decode_roofline.read(bare, update) is None
    assert decode_hbm_bound_olmo.read(bare, {}) is None
    assert kv_pages_fill.read(bare, {}) is None
    assert kv_pages_fill.read({"polls": []}, {}) is None
    # 40 passes of 12 live lanes, 12 linear layers, 20 ms a pass
    obs = _obs(rows=12 * 40 * 12, decode_steps=50, decode_secs=1.8)
    assert kv_pages_fill.read(obs, {}) == pytest.approx(25.0)
    want = (2 * (4_100_788_944 - 385_351_680) + 12 * 4400 * 61_440
            + 12 * 12 * 4_423_680) / 819e9
    assert decode_hbm_bound_olmo.read(obs, {}) == pytest.approx(
        100 * want / 0.020)


def test_the_cell_reports_only_what_a_reader_finds_on_this_family():
    names = {m["name"] for m in SPEC.metrics_of("per_layer", CELL)}
    assert set(NEW) | {"paged_decode_kernel_busy_pct", "decode_step_ms.tail",
                       "prefill_pass_ms.tail", "paged_grid_live_pct.tail",
                       "prefill_deep_pass_pct.tail", "ready_s",
                       "gen_late_p95_ms"} <= names
    # three kinds of list are another test's and stay as they were
    assert not names & {"device_starved_pct.tail", "host_turnaround_ms.tail",
                        "host_off_cpu_pct.tail"}
    assert not names & {"state_pool_fill_pct.tail",
                        "state_lanes_per_decode_step.tail",
                        "ssm_decode_kernel_busy_pct",
                        "ssm_decode_roofline_pct",
                        "decode_hbm_bound_pct.granite",
                        "decode_hbm_bound_pct", "decode_hbm_bound_pct.laguna",
                        "decode_hbm_bound_pct.pangu",
                        "decode_hbm_bound_pct.glm",
                        "decode_hbm_bound_pct.sdar", "moe_busy_pct",
                        "latent_decode_kernel_busy_pct",
                        "attn_kernel_busy_pct.serve"}
    e2e = {m["name"] for m in SPEC.metrics_of("end_to_end", CELL)}
    assert e2e == {"ttft_p75_ms", "tpot_p95_ms", "setup_s"}
    traffic = SPEC.traffic("longdoc-steady")
    assert traffic["generator"] == "open_loop_cycle"
    assert traffic["cycle_requests"] >= 120
    assert traffic["prompt_len"] == {"median": 3072, "sigma": 0.9,
                                     "min": 256, "max": 15872}
    assert traffic["output_len"] == {"median": 128, "sigma": 0.6,
                                     "min": 16, "max": 512}
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= CFG["max_position_embeddings"]
    assert (traffic["lead_in_s"], traffic["end"], traffic["drain_s"],
            traffic["trace_s"]) == (15.0, "drain", 120.0, 4.0)
    assert "start_at" in traffic and "0.8 x" in traffic["rate_is"]
    # the canaries reach the mix's longest request: 248 chunks of state
    assert max(serve_olmo.CANARIES) == (15872, 512)
    assert 15872 == 248 * 64
    assert sum(m for _n, m in serve_olmo.CANARIES) == 1072
    assert set(serve_olmo.READINGS) == set(
        __import__("benchmarks.reference_olmo", fromlist=["READINGS"])
        .READINGS)


# ------------------------------------------------- the mix's generator

MIX = {"rate_rps": 1.0, "cycle_requests": 120, "start_at": 7,
       "lead_in_s": 15.0,
       "prompt_len": {"median": 3072, "sigma": 0.9, "min": 256, "max": 15872},
       "output_len": {"median": 128, "sigma": 0.6, "min": 16, "max": 512}}


def _plan(seed=5, seconds=50.0, scale=1.0, **over):
    return SPEC.generator("open_loop_cycle")({**MIX, **over}, seed, seconds,
                                             100352, scale)


def _shape(plan):
    return [(r["rid"].split("-")[1], len(r["tokens"]), r["max_new_tokens"],
             r["counted"]) for r in plan["requests"]]


def test_the_cycle_generator_replays_one_stretch_for_every_seed():
    a, b = _plan(seed=5), _plan(seed=3000000019)
    assert _shape(a) == _shape(b)
    assert [r["due_s"] for r in a["requests"]] \
        == [r["due_s"] for r in b["requests"]]
    assert a["requests"][0]["tokens"] != b["requests"][0]["tokens"]
    assert _plan(seed=5) == a                      # the same seed, the same
    counted = [r for r in a["requests"] if r["counted"]]
    # the window opens ON request `start_at`, holds rate x window of the
    # circle's 120 and the lead-in the stretch before it
    assert counted[0]["rid"] == "s5-7" and counted[0]["due_s"] \
        == pytest.approx(15.0, abs=1e-6)
    assert 40 <= len(counted) <= 60
    assert all(15.0 <= r["due_s"] < 65.0 for r in counted)
    lead = [r for r in a["requests"] if not r["counted"]]
    assert lead and all(0.0 <= r["due_s"] < 15.0 for r in lead)
    assert all(r["rid"].endswith("-lead1") for r in lead)
    assert [r["due_s"] for r in a["requests"]] \
        == sorted(r["due_s"] for r in a["requests"])
    rids = [r["rid"] for r in a["requests"]]
    firsts = [r["tokens"][0] for r in a["requests"]]
    assert len(set(rids)) == len(rids) and len(set(firsts)) == len(firsts)


@pytest.mark.parametrize("scale", [0.9, 1.3, 2.0])
def test_a_sweeps_rate_keeps_the_cycles_requests_and_order(scale):
    """Another rate shrinks the gaps: the same requests in the same
    order from the same one, further round the circle in a window."""
    base = [x[:3] for x in _shape(_plan()) if x[3]]
    other = _plan(scale=scale)
    got = [x[:3] for x in _shape(other) if x[3]]
    n = min(len(base), len(got))
    assert got[:n] == base[:n] and n >= 40
    assert len(got) == pytest.approx(50 * scale, abs=8)
    due = [r["due_s"] for r in other["requests"] if r["counted"]]
    was = [r["due_s"] for r in _plan()["requests"] if r["counted"]]
    assert [d - 15.0 for d in due[:n]] == pytest.approx(
        [(d - 15.0) / scale for d in was[:n]], abs=1e-6)


def test_a_window_longer_than_the_cycle_goes_round_again():
    plan = _plan(seconds=50.0, cycle_requests=30, start_at=0)
    counted = [r for r in plan["requests"] if r["counted"]]
    assert len(counted) == pytest.approx(50, abs=3)
    rids = [r["rid"] for r in plan["requests"]]
    assert len(set(rids)) == len(rids)
    assert "s5-0" in rids and "s5-0-turn1" in rids
    again = {r["rid"]: r for r in counted}
    assert again["s5-0-turn1"]["due_s"] - again["s5-0"]["due_s"] \
        == pytest.approx(30.0)
    assert len(again["s5-0-turn1"]["tokens"]) == len(again["s5-0"]["tokens"])


def test_the_cycle_holds_the_distributions_quantiles():
    from benchmarks.generators.open_loop_cycle import cycle

    c = cycle({**MIX, "cycle_requests": 144})
    assert c["period_s"] == pytest.approx(144.0)
    assert sorted(c["prompts"])[72] == pytest.approx(3072, rel=0.02)
    assert max(c["prompts"]) == 15872 and min(c["prompts"]) >= 256
    assert sorted(c["outputs"])[72] == pytest.approx(128, rel=0.02)
    assert all(0 < a < 144.0 for a in c["at"])


def test_the_mixs_window_holds_the_cycles_load():
    """`start_at` is chosen so: the stretch the window replays carries
    the circle's mean prompt load within a tenth, at the mix's rate."""
    from benchmarks.generators.open_loop_cycle import cycle

    mix = SPEC.traffic("longdoc-steady")
    c = cycle(mix)
    plan = SPEC.generator("open_loop_cycle")(mix, 1, 50.0, 100352)
    counted = [r for r in plan["requests"] if r["counted"]]
    per_s = sum(len(r["tokens"]) for r in counted) / 50.0
    mean = sum(c["prompts"]) / len(c["prompts"]) * mix["rate_rps"]
    assert per_s == pytest.approx(mean, rel=0.1)
    assert len(counted[0]["tokens"]) < 5000    # not ON a long prompt


# ----------------------------------------------- what the comparison sees


@pytest.fixture(scope="module")
def small():
    """The small model's weights, four prompts and the bfloat16
    program's greedy answers to them, and `held(reading)`: the kind's
    comparison of that reading's picks with what the reference proper
    says of them."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks import reference_olmo as ref
    from ray_tpu.models.olmo_hybrid import OlmoHybridConfig, build

    was, ref.LENGTHS = ref.LENGTHS, (256,)
    cfg = OlmoHybridConfig.tiny()
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
        return serve_olmo.judge(
            [{"tokens": p} for p in prompts], picks,
            ref.teacher_forced(params, prompts, answers, sizes, picks=picks))

    def carry(reading=None, lengths=(256,)):
        """`carry_distance` of a reading's states behind the second
        prompt and its answer from the reference proper's."""
        ref.LENGTHS = lengths
        try:
            return ref.carry_distance(
                ref.carried_states(params, prompts[1], answers[1], sizes,
                                   reading=reading),
                ref.carried_states(params, prompts[1], answers[1], sizes))
        finally:
            ref.LENGTHS = (256,)

    held.carry = carry
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
    ([6.0] * 15, None),
    ([6.0] * 25, "more than 4.0 bfloat16 spacings"),
    ([60.0] * 5, "farther than a rounding goes"),
    ([60.0] * 3, None),
], ids=["roundings", "a-fault-everywhere", "a-fault-at-a-start",
        "inside-the-far-limit"])
def test_either_limit_refuses_alone(dists, refused):
    n = 1000
    got = serve_olmo.judge([{"tokens": [3, 4]}], [[1] * n], _refs(n, dists))
    assert got["judged"] == n
    assert got["off_share"] == pytest.approx(len(dists) / n)
    assert got["far_share"] == pytest.approx(
        sum(d > serve_olmo.FAR_TOL_ULPS for d in dists) / n)
    if refused is None:
        assert got["off"] == []
    else:
        assert len(got["off"]) == 1 and refused in got["off"][0]
        assert "canary of 2 tokens, token 0" in got["off"][0]


def test_the_bfloat16_program_passes_and_every_position_is_judged(small):
    got = small()
    assert got["off"] == [] and got["judged"] == got["positions"] == 160
    assert got["near_tie_share"] == 0.0
    assert got["off_share"] <= serve_olmo.MAX_OFF_SHARE
    assert got["far_share"] <= serve_olmo.MAX_FAR_SHARE


@pytest.mark.parametrize("reading", serve_olmo.READINGS)
def test_what_the_comparison_says_of_each_reading_at_this_size(small,
                                                               reading):
    """Every reading of `reference_olmo.READINGS`, judged as a program
    with that fault would be.  REFUSED_HERE fail a limit at toy size
    too; the rest move a toy model's logits by less than the gap of its
    two largest at nearly every position and are pinned as under the
    limits HERE, so that what this size can and cannot show is a tested
    fact: what the comparison says of them at the published size is the
    chip's reading (PERF.md section 6, PR 50), and the program's own
    guards against them are tests/test_delta_rule_ops.py (padding,
    gates) and tests/test_olmo_hybrid_engine.py (a stale slot)."""
    got = small(reading)
    if reading in REFUSED_HERE:
        assert got["off"], reading
        assert got["off_share"] > serve_olmo.MAX_OFF_SHARE \
            or got["far_share"] > serve_olmo.MAX_FAR_SHARE
    else:
        assert got["off"] == [], (reading, got["off_share"],
                                  got["far_share"])


REFUSED_HERE = tuple(r for r in serve_olmo.READINGS
                     if r != "bfloat16_state")


# ------------------------------------------------------------ the carry


def test_the_reference_state_is_the_one_behind_the_last_token(small):
    """The padding behind a sequence leaves its state as it was: padded
    to 256 or to 512, the same state; and the proper reading lies at
    distance 0 from itself."""
    from benchmarks import reference_olmo as ref

    same = small.carry()
    assert len(same["layers"]) == 3 and len(same["heads"][0]) == 4
    assert max(same["layers"]) == 0.0
    ref.LENGTHS = (512,)
    try:
        longer = small.carry("bfloat16_state", lengths=(512,))
    finally:
        ref.LENGTHS = (256,)
    assert longer["layers"] == pytest.approx(
        small.carry("bfloat16_state")["layers"], rel=1e-5)


def test_a_bfloat16_carry_shows_in_the_state_where_no_logit_shows_it(small):
    """The ninth reading: under both shares at this size as at the
    published one (REFUSED_HERE), but its STATE lies 1e-3 to 1e-2 of
    its norm from the reference's behind 140 tokens — four orders above
    a float32 program's (test_bench_rehearse_olmo.py: under 1e-4).  At
    the published size, behind 16,383 tokens, its worst head of the
    first layer reads 0.0097 to 0.0193 against `MAX_CARRY_OFF` 0.008
    (the chip's readings, PERF.md section 6, PR 50); a toy sequence is
    too short for a rounding to accumulate that far."""
    got = serve_olmo.judge_carry(small.carry("bfloat16_state"))
    assert 1e-3 < got["carry_off"] < 3e-2
    assert got["carry_layer_off"] > 1e-3
    assert small("bfloat16_state")["off"] == []
    # ... and the limit stands between the chip's two readings
    assert 0.0053 < serve_olmo.MAX_CARRY_OFF < 0.0097


@pytest.mark.parametrize("first,refused", [
    (serve_olmo.MAX_CARRY_OFF * 0.5, False),
    (serve_olmo.MAX_CARRY_OFF * 2.0, True)], ids=["inside", "beyond"])
def test_the_carry_limit_reads_the_first_layers_worst_head(first, refused):
    """Deeper layers may lie farther (their inputs carry the layers'
    roundings): the limit is on the first linear layer's worst head."""
    deep = serve_olmo.MAX_CARRY_OFF * 5.0
    got = serve_olmo.judge_carry(
        {"layers": [first / 2, deep],
         "heads": [[first / 4, first], [deep, deep * 2]]})
    assert got["carry_off"] == first and got["carry_layer_off"] == deep
    assert got["carry_head_off"] == deep * 2
    assert bool(got["off"]) == refused
    if refused:
        assert "first linear layer" in got["off"][0]


def test_asked_readings_come_from_the_environment(monkeypatch):
    monkeypatch.delenv("OLMO_READINGS", raising=False)
    assert serve_olmo.asked_readings() == []
    monkeypatch.setenv("OLMO_READINGS", "bfloat16_state,stale_slot")
    assert serve_olmo.asked_readings() == ["bfloat16_state", "stale_slot"]
    monkeypatch.setenv("OLMO_READINGS", "all")
    assert serve_olmo.asked_readings() == list(serve_olmo.READINGS)
    monkeypatch.setenv("OLMO_READINGS", "float4")
    with pytest.raises(ValueError, match="float4"):
        serve_olmo.asked_readings()
