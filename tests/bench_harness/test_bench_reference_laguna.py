"""The Laguna configuration, its arithmetic, and the comparison that
decides `correct` in its cells — at a small size on the CPU."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import model_math_laguna as mm  # noqa: E402
from benchmarks.kinds import serve_laguna  # noqa: E402
from benchmarks.spec import Spec  # noqa: E402

SPEC = Spec(REPO)
CFG = SPEC.config("laguna-s-2.1-serve")

# https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json,
# the numbers and switches of the catalog row
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False,
    "gating": "per-head", "sliding_window": 512,
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
}
PERIOD = ["full_attention"] + ["sliding_attention"] * 3


def test_the_configuration_keeps_every_published_width():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings", "vocab_size",
        "num_experts"}
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    n = CFG["num_hidden_layers"]
    assert CFG["layer_types"] == (PERIOD * 12)[:n]          # first five
    assert CFG["mlp_layer_types"] == (["dense"] + ["sparse"] * 47)[:n]
    assert CFG["num_attention_heads_per_layer"] == ([48, 72, 72, 72] * 12)[:n]
    assert CFG["gating_types"] == ["per_head"] * n
    full = CFG["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["rope_theta"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"],
            full["partial_rotary_factor"]) == (
        "yarn", 128, 500000, 8192, 32, 1, 1.4852030263919618, 0.5)
    assert CFG["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert CFG["experts_held"] == [0, 128]
    assert CFG["num_experts_routed_over"] == PUBLISHED["num_experts"]
    assert set(CFG["assumed"]) >= {"gate_function", "router_scores",
                                   "shared_expert", "qk_norm"}
    assert "2 chips" in CFG["deployment"]["stands_for"]
    assert CFG["deployment"]["engine"] == {"max_batch": 32, "page_size": 16}


def test_the_engines_model_is_made_of_the_files_keys():
    from ray_tpu.models import resolve

    kw = serve_laguna.model_kwargs(CFG)
    family, cfg = resolve(kw)
    assert family.__name__ == "ray_tpu.models.laguna"
    assert (cfg.num_experts, cfg.experts_held, cfg.vocab_size) == (
        256, (0, 128), 50176)
    assert [layer.kind for layer in cfg.cache_spec()] == [
        "full", "window", "window", "window", "full"]
    assert cfg.rope("full_attention")["factor"] == 128
    with pytest.raises(ValueError, match="entries"):
        serve_laguna.model_kwargs({**CFG, "num_hidden_layers": 6})
    with pytest.raises(ValueError, match="held"):
        serve_laguna.model_kwargs({**CFG, "experts_held": [0, 64]})
    toy = {**CFG, **{k: v for k, v in CFG["rehearsal"].items()
                     if k != "deployment"}}
    _family, small = resolve(serve_laguna.model_kwargs(toy))
    assert (small.hidden_size, small.num_experts, small.experts_held,
            small.sliding_window) == (64, 8, (0, 4), 32)


def test_parameters_and_bytes_against_the_issues_table():
    """ISSUE 28's arithmetic, by hand there: layer 0 157,440,000; a
    sliding expert layer 1,281,325,056; the full one 1,262,376,960;
    embedding, head and final norm 308,284,416; 5,572,076,544 held."""
    assert mm.attention_params(CFG, 0) == 44_187_648
    assert mm.attention_params(CFG, 1) == 63_135_744
    assert mm.layer_params_outside_experts(CFG, 0) == 157_440_000
    assert mm.expert_params(CFG) == 9_437_184
    assert (mm.layer_params_outside_experts(CFG, 1)
            + 128 * mm.expert_params(CFG)) == 1_281_325_056
    assert (mm.layer_params_outside_experts(CFG, 4)
            + 128 * mm.expert_params(CFG)) == 1_262_376_960
    assert mm.total_params(CFG) == 5_572_076_544
    # a step reads 1.17 GB outside the experts and 18.87 MB an expert
    outside = mm.params_outside_experts(CFG) * 2
    assert round(outside / 1e9, 2) == 1.17
    assert mm.expert_params(CFG) * 2 == 18_874_368
    # one lane at context 2000: a full layer reads 2000 rows, a sliding
    # one 512, 4 KiB each
    assert mm.decode_kv_bytes(CFG, 2, [2000]) == 4096 * (2 * 2000 + 3 * 512)
    assert mm.decode_step_bytes(CFG, 2, 2, [2000], 10) == \
        outside + 10 * 18_874_368 + 4096 * (2 * 2000 + 3 * 512)
    cost = mm.expert_matmul_cost(CFG, assignments=100, experts_touched=40)
    assert cost["flops"] == 2.0 * 100 * 9_437_184
    assert cost["bytes"] == 40 * 18_874_368 + 100 * (3072 * 2 + 2 * 1024 * 2
                                                     + 3072 * 4)


def _ref(top_id, picked_under, margin, top=4.0):
    """One canary's reference record: every position's top is `top`,
    `picked_under[j]` bfloat16 spacings over the picked token's logit."""
    ulp = serve_laguna.ulps_below_top(top, top - 1.0) ** -1
    return {"top": [top] * len(top_id), "top_id": list(top_id),
            "picked": [top - u * ulp for u in picked_under],
            "margin": list(margin)}


def test_near_ties_are_set_aside_and_the_rest_is_held_to_a_share():
    n = 40
    canary = [{"tokens": [1, 2, 3]}]
    wide, tie = 0.1, serve_laguna.ROUTER_TIE_TAU / 2
    limit = serve_laguna.MAX_OFF_SHARE
    # picks off by 9 spacings at near ties only: set aside, correct
    margins = [tie] * 5 + [wide] * (n - 5)
    answers = [[7] * 5 + [1] * (n - 5)]
    held = serve_laguna.check_canaries(
        canary, answers, [_ref([1] * n, [9.0] * n, margins)])
    assert held["off"] == []
    assert (held["positions"], held["judged"], held["not_argmax"]) == (n, 35, 0)
    assert held["near_tie_share"] == pytest.approx(5 / 40)
    assert (held["worst_ulps"], held["off_share"]) == (0.0, 0.0)
    assert held["worst_ulps_near_ties"] == pytest.approx(9.0)
    # the same picks where the router had a margin: 5 of 40 beyond the
    # tolerance is a routing flip's doing (12.5 % <= 15 %) ...
    held = serve_laguna.check_canaries(
        canary, answers, [_ref([1] * n, [9.0] * n, [wide] * n)])
    assert held["off"] == [] and held["off_share"] == pytest.approx(0.125)
    assert held["worst_ulps"] == pytest.approx(9.0)
    assert held["largest_ulps"] == [pytest.approx(9.0)] * 5
    # ... 7 of 40 is not
    assert 7 / n > limit
    held = serve_laguna.check_canaries(
        canary, [[7] * 7 + [1] * (n - 7)],
        [_ref([1] * n, [9.0] * n, [wide] * n)])
    assert len(held["off"]) == 1 and "7 of 40 judged" in held["off"][0]
    assert "9.0 bfloat16 spacings" in held["off"][0]
    # within the tolerance: counted, not refused
    held = serve_laguna.check_canaries(
        canary, [[7] * n], [_ref([1] * n, [3.0] * n, [wide] * n)])
    assert held["off"] == [] and held["not_argmax"] == n
    assert held["worst_ulps"] == pytest.approx(3.0)
    # more than half set aside, or fewer than 32 judged: not correct
    held = serve_laguna.check_canaries(
        [{"tokens": [1]}], [[1] * 100],
        [_ref([1] * 100, [0.0] * 100, [tie] * 51 + [wide] * 49)])
    assert any("more than half" in p for p in held["off"])
    held = serve_laguna.check_canaries(
        canary, [[1] * 20], [_ref([1] * 20, [0.0] * 20, [wide] * 20)])
    assert any("only 20 positions judged" in p for p in held["off"])


# ------------------------------------------- lower precision must not pass


@pytest.fixture(scope="module")
def small():
    """The small model's weights, 4 x 120 random tokens, the reference's
    logits and margins there, and `held(picks)`: the kind's comparison
    of a pick at every position (its context being the tokens before
    it) with what the reference says of it."""
    import jax
    import jax.numpy as jnp

    from benchmarks import reference_laguna as ref
    from ray_tpu.models.laguna import LagunaConfig, build

    cfg = LagunaConfig.tiny()
    sizes = dict(layer_types=list(cfg.layer_types),
                 mlp_layer_types=list(cfg.mlp_layer_types),
                 sliding_window=cfg.sliding_window,
                 rope_parameters={k: dict(v) for k, v
                                  in dict(cfg.rope_parameters).items()},
                 num_experts_per_tok=2, norm_topk_prob=True,
                 moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6,
                 experts_held=[0, 4])
    tokens = jnp.asarray(np.random.RandomState(0).randint(1, 256, (4, 120)),
                         jnp.int32)
    params = jax.jit(build(cfg, 16).init)(
        jax.random.PRNGKey(0), tokens[:, :8])["params"]
    logits, margin = (np.asarray(x) for x in ref.logits(params, tokens,
                                                        sizes))

    def held(picks):
        picks = np.asarray(picks)
        refs = [{"top": logits[b].max(-1).tolist(),
                 "top_id": logits[b].argmax(-1).tolist(),
                 "picked": np.take_along_axis(
                     logits[b], picks[b][:, None], -1)[:, 0].tolist(),
                 "margin": margin[b].tolist()} for b in range(len(picks))]
        return serve_laguna.check_canaries(
            [{"tokens": row} for row in np.asarray(tokens).tolist()],
            picks.tolist(), refs)

    return cfg, params, tokens, sizes, held


def test_the_program_at_the_references_precision_passes_exactly(small):
    """First reading: float32 activations over the same stored matrices
    pick the reference's argmax at every judged position, distance 0."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.laguna import build

    cfg, params, tokens, _sizes, held = small
    model = build(dataclasses.replace(cfg, dtype=jnp.float32), 16)
    out = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
    got = held(np.asarray(out).argmax(-1))
    assert got["off"] == [] and got["judged"] >= 400
    assert (got["not_argmax"], got["worst_ulps"]) == (0, 0.0)


def test_the_reference_in_the_nearest_lower_precision_fails(small):
    """Second reading: the reference with every stored matrix rounded to
    float8_e4m3fn (the nearest precision below the stated bfloat16)
    picks tokens the reference proper puts beyond the tolerance at more
    judged positions than MAX_OFF_SHARE allows: not `correct`.  (At this
    size: measured off_share 0.41 against the 0.0 of the program at the
    reference's precision; the chip's readings are in PERF.md.)"""
    from benchmarks import reference_laguna as ref

    _cfg, params, tokens, sizes, held = small
    lower, _margin = ref.logits(params, tokens, sizes,
                                matrices=serve_laguna.LOWER_PRECISION)
    got = held(np.asarray(lower).argmax(-1))
    assert got["off_share"] > serve_laguna.MAX_OFF_SHARE
    assert got["off"] and "judged positions" in got["off"][0]


def _mutant_logits(small, patch):
    """The reference's logits with one of its functions replaced by a
    lower-precision one (`patch(ref)` returns what to restore)."""
    import jax

    from benchmarks import reference_laguna as ref

    _cfg, params, tokens, sizes, _held = small
    jax.clear_caches()        # `block` is jitted over the old function
    restore = patch(ref)
    try:
        return np.asarray(ref.logits(params, tokens, sizes)[0])
    finally:
        for name, fn in restore.items():
            setattr(ref, name, fn)
        jax.clear_caches()


def _router_in(dtype):
    def patch(ref):
        import jax
        import jax.numpy as jnp

        old = ref.router_scores
        ref.router_scores = lambda z: jax.nn.softmax(
            z.astype(dtype), axis=-1).astype(jnp.float32)
        return {"router_scores": old}
    return patch


def _experts_in_int8(ref):
    import jax.numpy as jnp

    old = ref._swiglu

    def quantized(w):
        w = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        return jnp.round(w / scale) * scale

    ref._swiglu = lambda h, w1, w3, w2, matrices=None: old(
        h, quantized(w1), quantized(w3), quantized(w2))
    return {"_swiglu": old}


@pytest.mark.parametrize("mutant,moved", [("float16_router", False),
                                          ("bfloat16_router", True),
                                          ("int8_experts", True)])
def test_what_is_finer_than_bfloat16_the_comparison_cannot_refuse(
        small, mutant, moved):
    """ISSUE 28 asked that a float16-router and an int8-expert mutant of
    the reference FAIL.  They cannot, by this or any comparison of
    outputs with a program whose activations are bfloat16 as stated:
    each is finer than that program's own noise (PERF.md section 6: on
    the chip the correct program had 13 % of its picks off the
    reference's argmax and 3 % beyond the tolerance; at the published
    width these mutants move 2 to 5 % and 0 to 0.4 %).  Pinned here so
    that the limit of the comparison is a tested fact: a float16 router
    (11 significant bits) changes no pick at all; a bfloat16 router and
    int8 expert matrices do move picks at this size, by flips — beyond
    the tolerance even — and stay under MAX_OFF_SHARE, as a correct
    bfloat16 program does.  What IS refused is the test above."""
    import jax.numpy as jnp

    patch = {"float16_router": _router_in(jnp.float16),
             "bfloat16_router": _router_in(jnp.bfloat16),
             "int8_experts": _experts_in_int8}[mutant]
    got = small[4](_mutant_logits(small, patch).argmax(-1))
    assert got["off"] == []
    assert (got["not_argmax"] > 0) == moved
    if moved:
        assert got["worst_ulps"] > serve_laguna.LOGIT_TOL_ULPS
        assert got["off_share"] <= serve_laguna.MAX_OFF_SHARE / 2
