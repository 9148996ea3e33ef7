"""The expert family's two settings told apart by their keys, the
Llama builder's older form, and the SHARE test of the Mellum cell's cut:
four chips that divide one layer by heads and by experts compute partial
results that add up to the uncut reference's layer."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import reference_mellum as ref  # noqa: E402
from ray_tpu import models  # noqa: E402
from ray_tpu.models import laguna, llama  # noqa: E402
from ray_tpu.parallel.mesh import MeshSpec, make_mesh  # noqa: E402
from ray_tpu.train.gspmd import (build_llama_train_state,  # noqa: E402
                                 build_train_state, param_count)


def _sizes(cfg):
    return dict(
        layer_types=list(cfg.layer_types),
        sliding_window=cfg.sliding_window,
        rope_parameters={k: dict(v)
                         for k, v in dict(cfg.rope_parameters).items()},
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob, rms_norm_eps=cfg.rms_norm_eps,
        experts_held=list(cfg.experts_held))


def test_model_type_mellum_is_a_setting_of_the_expert_family():
    family, cfg = models.resolve({
        "model_type": "mellum", "num_hidden_layers": 4,
        "num_attention_heads": 8, "num_key_value_heads": 1,
        "num_experts": 64, "experts_held": [0, 16],
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"]})
    assert family is laguna
    assert not cfg.gated and cfg.shared_expert_intermediate_size == 0
    assert cfg.moe_routed_scaling_factor == 1.0
    assert cfg.num_attention_heads_per_layer == (8,) * 4
    assert cfg.mlp_layer_types == ("sparse",) * 4
    assert cfg.experts_held == (0, 16) and cfg.num_experts == 64
    tiny = laguna.LagunaConfig.tiny()           # the other setting
    assert tiny.gated and tiny.shared_expert_intermediate_size == 32
    params = jax.eval_shape(
        lambda: laguna.train_build(laguna.LagunaConfig.tiny_ungated()).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    layer = params["layer_0"]
    assert "attn_gate" not in layer["attn"]
    assert set(layer["moe"]) == {"moe_router", "moe_experts_w1",
                                 "moe_experts_w3", "moe_experts_w2"}
    assert layer["moe"]["moe_experts_w1"].dtype == jnp.float32


@pytest.mark.parametrize("model, gated", [
    # a published config.json handed over as it is: no `gating`, no gate
    ({"model_type": "mellum", "num_attention_heads": 8}, False),
    ({"model_type": "mellum", "num_attention_heads": 8,
      "gating": "per-head"}, True),
    # the gated model's config class defaults the key, so a dictionary
    # of its type may leave it out (benchmarks/kinds/serve_laguna.py does)
    ({"model_type": "laguna"}, True),
    ({"model_type": "laguna", "gated": False}, False),
])
def test_the_gate_follows_the_published_key(model, gated):
    assert models.resolve(model)[1].gated is gated
    assert laguna.LagunaConfig().gated           # an instance's default
    assert not laguna.LagunaConfig.from_dict({}).gated
    assert laguna.LagunaConfig.from_dict({"gating": "per-head"}).gated


def test_the_llama_builder_keeps_its_three_value_step():
    mesh = make_mesh(MeshSpec(dp=-1), devices=jax.devices()[:1])
    cfg = llama.LlamaConfig.tiny()
    params, opt, step_fn, model = build_llama_train_state(
        cfg, mesh, batch_size=2, seq_len=32)
    assert param_count(params) == cfg.num_params()
    out = step_fn(params, opt, np.zeros((2, 32), np.int32))
    assert len(out) == 3 and np.isfinite(float(out[2]))
    state = build_train_state(cfg, mesh, batch_size=2, seq_len=32)
    assert state.counter_names == ()
    assert state.read(out[2], jnp.zeros((0,), jnp.int32))[1] == {}


def test_four_shares_add_up_to_the_uncut_layer():
    """The cut of the cell: chip s of 4 holds query heads 8s..8s+7 with
    KV head s and experts 16s..16s+15 (here 2s, 2s+1 of 8 heads with KV
    head s and experts 2s, 2s+1 of 8).  The four shares' partial results
    for one layer's attention, added, are the uncut reference's
    attention; with that sum carried on, their partial routed sums,
    added, are the uncut reference's expert layer."""
    d, hd, heads, kv, experts, f, s = 32, 8, 8, 4, 8, 16, 64
    whole = laguna.LagunaConfig.from_dict(dict(
        vocab_size=64, hidden_size=d, num_hidden_layers=1,
        num_attention_heads=heads, num_key_value_heads=kv, head_dim=hd,
        max_position_embeddings=s, num_experts=experts,
        num_experts_per_tok=2, moe_intermediate_size=f, sliding_window=16,
        layer_types=["sliding_attention"],
        rope_parameters={"sliding_attention": {"rope_type": "default",
                                               "rope_theta": 500000}},
        dtype=jnp.float32, param_dtype=jnp.float32))
    ks = jax.random.split(jax.random.PRNGKey(11), 9)
    norm = lambda k, *shape: jax.random.normal(k, shape) * shape[0] ** -0.5  # noqa: E731
    attn = {"wq": {"kernel": norm(ks[0], d, heads, hd)},
            "wk": {"kernel": norm(ks[1], d, kv, hd)},
            "wv": {"kernel": norm(ks[2], d, kv, hd)},
            "wo": {"kernel": norm(ks[3], heads, hd, d)}}
    moe = {"moe_router": norm(ks[4], d, experts),
           "moe_experts_w1": norm(ks[5], experts, d, f),
           "moe_experts_w3": norm(ks[6], experts, d, f),
           "moe_experts_w2": norm(ks[7], experts, f, d)}
    h = jax.random.normal(ks[8], (1, s, d))
    positions = jnp.arange(s)[None]
    sizes = _sizes(whole)
    static = ref._layer_static(sizes, 0, None)
    with jax.default_matmul_precision("highest"):
        want_attn = ref._attention({"attn": attn}, h, positions,
                                   window=static["window"],
                                   rope=static["rope"])
        got_attn = 0.0
        for share in range(4):
            cut = dataclasses.replace(
                whole, num_attention_heads_per_layer=(heads // 4,),
                num_key_value_heads=1)
            q = slice(2 * share, 2 * share + 2)
            part = {"wq": {"kernel": attn["wq"]["kernel"][:, q]},
                    "wk": {"kernel": attn["wk"]["kernel"][:, share:share + 1]},
                    "wv": {"kernel": attn["wv"]["kernel"][:, share:share + 1]},
                    "wo": {"kernel": attn["wo"]["kernel"][q]}}
            out, _ = laguna.GatedAttention(cut, 0).apply(
                {"params": part}, h, positions)
            got_attn = got_attn + out
        np.testing.assert_allclose(got_attn, want_attn, atol=2e-5,
                                   rtol=2e-5)
        h2 = h + want_attn
        want_moe, _, _ = ref._routed(moe, h2[0], None, top_k=2,
                                     normalize=True, lo=0, mutant=None)
        got_moe = 0.0
        valid = jnp.ones((1, s), bool)
        for share in range(4):
            lo = 2 * share
            cut = dataclasses.replace(whole, experts_held=(lo, lo + 2))
            part = {"moe_router": moe["moe_router"],
                    **{k: moe[k][lo:lo + 2] for k in (
                        "moe_experts_w1", "moe_experts_w3",
                        "moe_experts_w2")}}
            out, _ = laguna.ExpertLayer(cut).apply({"params": part}, h2,
                                                   valid)
            got_moe = got_moe + out[0]
        np.testing.assert_allclose(got_moe, want_moe, atol=2e-5, rtol=2e-5)
