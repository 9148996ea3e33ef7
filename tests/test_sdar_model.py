"""The block-diffusion setting of the expert-layer family
(`model_type: sdar_moe`, ray_tpu/models/laguna.py) against the plain
reference (benchmarks/reference_sdar.py): the layer under the block
mask, the keys the published config spells, and the sampler's rule on
constructed confidences."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_sdar as ref
from ray_tpu.models import FAMILIES, resolve
from ray_tpu.models import laguna
from ray_tpu.models.laguna import LagunaConfig

CFG = LagunaConfig.tiny_blocks()
SIZES = dict(num_hidden_layers=CFG.num_hidden_layers, head_dim=CFG.head_dim,
             rope_theta=1000000, rms_norm_eps=CFG.rms_norm_eps,
             num_experts_per_tok=CFG.num_experts_per_tok,
             norm_topk_prob=True,
             generation=dict(block_length=4, denoising_steps=4,
                             confidence_threshold=0.9, mask_token_id=255))
HERE = os.path.dirname(os.path.abspath(__file__))


def _params(seed=0):
    params = laguna.build(CFG, 16).init(
        jax.random.PRNGKey(seed), np.zeros((1, 8), np.int32))["params"]
    # norm weights are drawn as ones: move them, so that one dropped shows
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * jnp.asarray(
            rng.uniform(0.5, 1.5, x.shape), x.dtype)
        if "norm" in str(path) else x, params)


def test_the_setting_is_the_references_forward_under_the_block_mask():
    params = _params()
    tokens = np.random.RandomState(1).randint(0, 255, size=(1, 24))
    got = laguna.build(CFG, 16).apply({"params": params}, jnp.asarray(tokens))
    want, _margin = ref.logits(params, list(tokens[0]), SIZES,
                               at=list(range(24)))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("mutant", ["causal_in_block", "no_qk_norm"])
def test_a_mechanism_left_out_is_another_model(mutant):
    params = _params()
    tokens = list(np.random.RandomState(1).randint(0, 255, size=24))
    proper, _ = ref.logits(params, tokens, SIZES, at=list(range(24)))
    wrong, _ = ref.logits(params, tokens, SIZES, at=list(range(24)),
                          mutant=mutant)
    assert float(jnp.abs(proper - wrong).max()) > 1e-2


def test_the_published_keys_resolve_to_the_setting():
    """The benchmark's configuration file, as `kinds/serve_sdar.py` hands
    it to the engine: the family, the q/k norms its class defaults, the
    block's numbers, every layer full and sparse, nothing gated or
    shared."""
    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           "sdar-30b-a3b-chat-serve.json")) as f:
        file = json.load(f)
    assert FAMILIES["sdar_moe"] == "laguna"
    keys = ("model_type", "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "max_position_embeddings", "rms_norm_eps", "num_experts",
            "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
            "rope_theta", "sliding_window", "generation")
    family, cfg = resolve({k: file[k] for k in keys})
    assert family is laguna and cfg.qk_norm and not cfg.gated
    assert cfg.layer_types == (laguna.FULL,) * 6
    assert cfg.mlp_layer_types == ("sparse",) * 6
    assert cfg.num_attention_heads_per_layer == (32,) * 6
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size) == (128, (0, 128), 8, 768)
    assert not cfg.shared_expert_intermediate_size
    assert cfg.moe_routed_scaling_factor == 1.0 and cfg.norm_topk_prob
    assert cfg.rope(laguna.FULL) == {"rope_type": "default",
                                     "rope_theta": 1000000}
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id,
            cfg.confidence_threshold) == (4, 4, 151669, 0.9)
    assert [c.kind for c in cfg.cache_spec()] == ["full"] * 6


def test_the_other_settings_have_no_norm_on_q_and_k_and_no_blocks():
    for cfg in (LagunaConfig.tiny(), LagunaConfig.tiny_ungated()):
        assert not cfg.qk_norm and not cfg.block_length
        tree = jax.eval_shape(laguna.build(cfg, 16).init,
                              jax.random.PRNGKey(0),
                              np.zeros((1, 8), np.int32))["params"]
        assert not any("q_norm" in str(p) or "k_norm" in str(p) for p, _ in
                       jax.tree_util.tree_flatten_with_path(tree)[0])


def _logits(conf, ids, vocab=32):
    """Logits [1, B, V] whose softmax puts `conf[t]` on `ids[t]`."""
    out = np.zeros((1, len(conf), vocab), np.float32)
    for t, (c, z) in enumerate(zip(conf, ids)):
        out[0, t, z] = np.log(c * (vocab - 2) / (1.0 - c))   # the mask's
    return out                                    # column is no candidate


CASES = {
    # three masked, two clear 0.9 and the schedule asks for one: both go
    "the threshold moves two of three":
        ([31, 5, 31, 31], [0.95, 0.5, 0.3, 0.97], 1, [1, 0, 0, 1], 2),
    # none clears it: the schedule's one, the most confident
    "the schedule's count where the threshold moves none":
        ([31, 31, 31, 31], [0.2, 0.6, 0.5, 0.1], 1, [0, 1, 0, 0], 0),
    # one clears it and the schedule asks for two: the two largest
    "fewer over the threshold than the schedule asks":
        ([31, 31, 31, 31], [0.95, 0.6, 0.7, 0.1], 2, [1, 0, 1, 0], 1),
    # a tie goes to the lower position
    "a tie": ([31, 31, 31, 31], [0.4, 0.4, 0.4, 0.4], 2, [1, 1, 0, 0], 0),
    # more asked than masked: the masked ones, never a given token
    "more asked than masked": ([7, 31, 9, 31], [0.99, 0.2, 0.99, 0.3], 4,
                               [0, 1, 0, 1], 0),
    # no mask left: the commit changes nothing
    "the commit": ([7, 8, 9, 10], [0.99, 0.99, 0.99, 0.99], 1,
                   [0, 0, 0, 0], 0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_samplers_rule_on_constructed_confidences(case):
    tokens, conf, need, moves, over = CASES[case]
    cfg = dataclasses.replace(CFG, mask_token_id=31)
    ids = [11, 12, 13, 14]
    state, masked, moved, cleared = laguna.block_sample(
        cfg, jnp.asarray([tokens]), jnp.asarray(_logits(conf, ids)),
        jnp.asarray([need]))
    want = [z if m else t for t, z, m in zip(tokens, ids, moves)]
    assert state.tolist() == [want]
    assert (int(masked[0]), int(moved[0]), int(cleared[0])) == (
        tokens.count(31), sum(moves), over)
    # and the reference's rule on the same numbers
    z, log_c, _ = ref.confidences(_logits(conf, ids)[0], 31)
    got = ref.transfers([t == 31 for t in tokens], log_c, need, 0.9)
    assert got == [t for t, m in enumerate(moves) if m]


def test_the_masks_id_is_never_unmasked_to():
    cfg = dataclasses.replace(CFG, mask_token_id=31)
    logits = np.zeros((1, 4, 32), np.float32)
    logits[..., 31] = 50.0      # the mask's own id, by far the largest
    logits[..., 3] = 1.0
    state, *_ = laguna.block_sample(
        cfg, jnp.full((1, 4), 31), jnp.asarray(logits), jnp.asarray([4]))
    assert state.tolist() == [[3, 3, 3, 3]]
