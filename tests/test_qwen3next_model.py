"""models/qwen3_next.py — gated-delta-rule layers of 2 key heads under 4
value heads, a gated full layer whose KV row is FLAT (2 heads side by
side: models/cache.py, `FlatKVCache`), experts beside a gated shared
expert in every layer — against the plain reference
(benchmarks/reference_qwen3next.py: plain attention, the recurrence token
by token, a loop over the experts) on seeded weights at toy widths,
float32, CPU.  (The flat row's kernels and the share test:
tests/test_qwen3next_ops.py.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_qwen3next as ref
from ray_tpu.models import cache as kv_cache, resolve
from ray_tpu.models.qwen3_next import (FULL, LINEAR, Qwen3NextConfig, build)

CFG = Qwen3NextConfig.tiny()
PAGE = 16
SIZES = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
         if "dtype" not in f.name}
TOKENS = np.random.RandomState(0).randint(1, 256, (150,)).astype(np.int32)
ATOL = 1e-4    # float32 against float32, sums in another order

PUBLISHED = dict(
    model_type="qwen3_next", vocab_size=151936, hidden_size=2048,
    intermediate_size=5120, num_hidden_layers=48, num_attention_heads=16,
    num_key_value_heads=2, head_dim=256, hidden_act="silu",
    max_position_embeddings=262144, attention_bias=False, rms_norm_eps=1e-6,
    tie_word_embeddings=False, full_attention_interval=4,
    partial_rotary_factor=0.25, rope_theta=10000000, rope_scaling=None,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel_dim=4, num_experts=512, num_experts_per_tok=10,
    moe_intermediate_size=512, shared_expert_intermediate_size=512,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[])


@pytest.fixture(scope="module", autouse=True)
def short_reference():
    """The reference pads to 256 here, not to the chip's lengths."""
    was, ref.LENGTHS = ref.LENGTHS, (256, 512)
    yield
    ref.LENGTHS = was


@pytest.fixture(scope="module")
def params():
    return jax.jit(build(CFG, PAGE).init)(
        jax.random.PRNGKey(0), jnp.asarray(TOKENS[None, :8]))["params"]


def test_model_type_picks_the_family_and_the_cache_states_what_memory_holds():
    family, cfg = resolve(PUBLISHED)
    assert family.__name__.endswith("models.qwen3_next")
    assert cfg.layer_types == tuple([LINEAR] * 3 + [FULL]) * 12
    assert (cfg.key_dim, cfg.value_dim, cfg.conv_dim) == (2048, 4096, 8192)
    assert cfg.experts_held == (0, 512) and cfg.num_experts == 512
    spec = cfg.cache_spec()
    # the state in PAIRS of value heads, 256 lanes; the KV row FLAT: a
    # key of 2 x 256 = 512 numbers, whole tiles of the chip's lanes
    state = kv_cache.StateCache("state", 0, (3, 8192), (16, 128, 256))
    full = kv_cache.FlatKVCache("full", 0, 2, 256)
    assert spec == tuple([state] * 3 + [full]) * 12
    assert full.rows() == {"k": (512,), "v": (512,)}
    assert kv_cache.state_row_bytes(spec[:4], jnp.bfloat16) == 3 * (
        2_097_152 + 49_152)
    assert kv_cache.kinds_of(spec) == {"state": 0, "full": 0}
    pools = kv_cache.make_pools(spec[:4], {"state": 3, "full": 32},
                                jnp.bfloat16)
    assert pools["k"][3].shape == (32, 512) and pools["k"][0] is None
    # a token's bytes are what the shape says: 2 x 512 x 2 B
    assert pools["k"][3].nbytes + pools["v"][3].nbytes == 32 * 2048
    # the share: 256 experts held of a router 512 wide, half the rows
    _f, share = resolve({**PUBLISHED, "num_hidden_layers": 4,
                         "num_experts": 256, "experts_held": [0, 256],
                         "num_experts_routed_over": 512,
                         "vocab_size": 75968})
    assert share.share() == {"experts_held": [0, 256], "num_experts": 512,
                             "vocab_rows": 75968}
    assert share.layer_types == (LINEAR, LINEAR, LINEAR, FULL)


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("use_sliding_window", True), ("sliding_window", 4096),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("hidden_act", "gelu")])
def test_what_the_module_does_not_write_is_refused_by_its_key(key, value):
    with pytest.raises(ValueError, match=key):
        resolve({**PUBLISHED, key: value})


def test_a_share_that_does_not_count_its_experts_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        resolve({**PUBLISHED, "num_experts": 256, "experts_held": [0, 128],
                 "num_experts_routed_over": 512})
    with pytest.raises(ValueError, match="key heads"):
        resolve({**PUBLISHED, "linear_num_value_heads": 24})


def test_the_whole_sequence_is_the_references(params):
    got = build(CFG, PAGE).apply({"params": params},
                                 jnp.asarray(TOKENS[None]))[0]
    want = ref.logits(params, TOKENS, SIZES)
    assert float(jnp.abs(got - want).max()) < ATOL
    assert float(jnp.abs(want).max()) > 0.5


@pytest.mark.parametrize("reading", ref.READINGS)
def test_every_other_reading_moves_the_logits(params, reading):
    """Each mutant of the reference is another function of the same
    weights: what the comparison must refuse is not the reference by
    another name."""
    tokens = TOKENS[:96]
    want = ref.logits(params, tokens, SIZES)
    got = ref.logits(params, tokens, SIZES, reading=reading)
    moved = float(jnp.abs(got - want).max())
    if reading == "bfloat16_state":
        states, rounded = [], []
        ref.hidden(params, tokens, SIZES, states=states)
        ref.hidden(params, tokens, SIZES, reading=reading, states=rounded)
        dist = ref.carry_distance(rounded, states)
        assert max(dist["layers"]) > 1e-4
    else:
        assert moved > 100 * ATOL, (reading, moved)
