"""models/laguna.py against the plain reference (seeded random weights,
small size, CPU), the model interface, and YaRN's frequencies against
values worked by hand."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_laguna as ref
from ray_tpu.models import cache as kv_cache, resolve
from ray_tpu.models.laguna import (LagunaConfig, build, rope_tables,
                                   yarn_inv_freq)
from ray_tpu.models.llama import LlamaConfig

CFG = LagunaConfig.tiny()
SIZES = dict(layer_types=list(CFG.layer_types),
             mlp_layer_types=list(CFG.mlp_layer_types),
             sliding_window=CFG.sliding_window,
             rope_parameters={k: dict(v)
                              for k, v in dict(CFG.rope_parameters).items()},
             num_experts_per_tok=CFG.num_experts_per_tok,
             norm_topk_prob=True, moe_routed_scaling_factor=2.5,
             rms_norm_eps=CFG.rms_norm_eps, experts_held=[0, 4])
TOKENS = jnp.asarray(np.random.RandomState(0).randint(1, 256, (2, 100)),
                     jnp.int32)


def _forward(cfg):
    model = build(cfg, 16)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 TOKENS[:, :8])["params"]
    out = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, TOKENS)
    return params, np.asarray(out.astype(jnp.float32))


def test_full_forward_is_the_references_in_float32():
    """Same mathematics: with float32 activations over the stored
    bfloat16 matrices the model's logits are the reference's to float32
    rounding (1e-4 of logits of size 4; measured 1.1e-5) — rotary of both
    kinds, the window's mask (100 tokens, window 32), the gate, the dense
    lead, routing, the held experts through the Pallas grouped matmul."""
    params, out = _forward(dataclasses.replace(CFG, dtype=jnp.float32))
    want, margin = ref.logits(params, TOKENS, SIZES)
    np.testing.assert_allclose(out, np.asarray(want), atol=1e-4)
    assert margin.shape == TOKENS.shape and float(margin.min()) > 0


def test_bfloat16_forward_agrees_but_for_routing_flips():
    """In bfloat16, at this size (hidden 64, 2 experts of 8), the
    activations' rounding moves a router logit by more than most gaps,
    and a position that routes one expert of two differently moves its
    logits by an expert, not by a rounding (up to 1.0 of logits of size
    4).  So the two are compared as populations: the median logit
    differs by roundings (under 0.05; measured 0.012) and at least 7 of
    10 positions have EVERY logit within 0.25 (measured 0.83) — a wrong
    mask, rotary or gate moves every position.  The exact comparison is
    the float32 one above."""
    params, out = _forward(CFG)
    want, _margin = ref.logits(params, TOKENS, SIZES)
    err = np.abs(out - np.asarray(want))
    assert np.median(err) < 0.05
    assert (err.max(axis=-1) < 0.25).mean() >= 0.7


def test_yarn_frequencies_by_hand():
    """dim 8 (4 pairs), theta 10000, factor 4, original length 64, beta
    32 and 1.  Pair i has f_i = 10000^(-i/4) = 1, 0.1, 0.01, 0.001.
    Correction dimensions: 8 ln(64 / (2 pi r)) / (2 ln 10000) = -0.499
    for r = 32 (floored, clamped: low 0) and 1.008 for r = 1 (ceiled:
    high 2).  Ramp (i - 0) / 2 clipped: 0, 0.5, 1, 1: pair 0 keeps its
    frequency, pair 1 is half way to f / 4, pairs 2 and 3 are f / 4."""
    got = yarn_inv_freq(8, 10000.0, 4.0, 64, 32.0, 1.0)
    want = [1.0, 0.5 * 0.1 + 0.5 * 0.1 / 4, 0.01 / 4, 0.001 / 4]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(8, 10000.0, 4.0, 64, 32.0,
                                                 1.0), want, rtol=1e-6)
    low = 8 * math.log(64 / (32 * 2 * math.pi)) / (2 * math.log(10000.0))
    assert math.floor(low) == -1      # the hand value above, clamped to 0
    # the published full-attention group rotates half the head, scaled
    dim, inv, factor = rope_tables(CFG.rope("full_attention"), 128)
    assert (dim, inv.shape, factor) == (64, (32,), 1.4852030263919618)
    dim, inv, factor = rope_tables(CFG.rope("sliding_attention"), 128)
    assert (dim, factor) == (128, 1.0)
    np.testing.assert_allclose(inv[1], 10000.0 ** (-2 / 128), rtol=1e-6)


def test_model_type_picks_the_family_and_the_cache_is_by_layer():
    family, cfg = resolve({"model_type": "laguna", "hidden_size": 64,
                           "layer_types": ["full_attention",
                                           "sliding_attention"],
                           "sliding_window": 32, "head_dim": 16,
                           "num_key_value_heads": 2,
                           "gating": "per-head"})   # unknown keys pass
    assert family.__name__ == "ray_tpu.models.laguna"
    assert cfg.cache_spec() == (kv_cache.LayerCache("full", 0, 2, 16),
                                kv_cache.LayerCache("window", 32, 2, 16))
    assert kv_cache.kinds_of(cfg.cache_spec()) == {"full": 0, "window": 32}
    # a dictionary without model_type, a preset's name and a config
    # instance of either family
    for model in ({"dim": 64, "n_layers": 2}, "tiny", LlamaConfig.tiny()):
        family, cfg = resolve(model)
        assert family.__name__ == "ray_tpu.models.llama"
        assert {layer.kind for layer in cfg.cache_spec()} == {"full"}
    assert resolve(CFG)[0].__name__ == "ray_tpu.models.laguna"
    with pytest.raises(ValueError, match="no model family"):
        resolve({"model_type": "nonesuch"})


def test_pools_are_sized_by_kind():
    pools = kv_cache.make_pools(CFG.cache_spec(),
                                {"full": 64, "window": 24}, jnp.bfloat16)
    assert [p.shape[0] for p in pools["k"]] == [64, 24, 24, 24, 64]
    kinds = [layer.kind for layer in CFG.cache_spec()]
    rows = {"k": [np.full((3, 2, 16), i, np.float32) for i in range(5)],
            "v": [np.full((3, 2, 16), -i, np.float32) for i in range(5)]}
    slots = {"full": [5, 6, 7], "window": [1, 2, 3]}
    pools = kv_cache.scatter_slots(pools, kinds, slots, rows)
    back = kv_cache.gather_slots(pools, kinds, slots)
    for name in ("k", "v"):
        for got, want in zip(back[name], rows[name]):
            np.testing.assert_array_equal(got, want)
    copied = kv_cache.copy_slots(pools, kinds, "full", [5], [9])
    assert float(copied["k"][4][9, 0, 0]) == 4.0       # a full layer
    assert float(copied["k"][1][9, 0, 0]) == 0.0       # a window layer
