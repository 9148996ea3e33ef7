"""models/olmo_hybrid.py and the state kind of models/cache.py under a
second recurrence, against the plain reference (seeded random weights,
small size, float32, CPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_olmo as ref
from ray_tpu.models import cache as kv_cache, resolve
from ray_tpu.models.olmo_hybrid import (FULL, LINEAR, OlmoHybridConfig,
                                        build)

CFG = dataclasses.replace(OlmoHybridConfig.tiny(), dtype=jnp.float32,
                          param_dtype=jnp.float32)
PAGE = 16
SIZES = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
         if "dtype" not in f.name}
TOKENS = np.random.RandomState(0).randint(1, 256, (150,)).astype(np.int32)
# float32 against float32 under "highest": logits of size 3, the two
# differ by the order of their sums (the chunk form against the
# recurrence: measured 2e-5 over 150 tokens)
ATOL = 1e-4

PUBLISHED = dict(
    model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
    intermediate_size=11008, num_hidden_layers=32, num_attention_heads=30,
    num_key_value_heads=30, hidden_act="silu",
    max_position_embeddings=65536, attention_bias=False, rms_norm_eps=1e-6,
    tie_word_embeddings=False,
    layer_types=[LINEAR, LINEAR, LINEAR, FULL] * 8,
    linear_num_key_heads=30, linear_num_value_heads=30,
    linear_key_head_dim=96, linear_value_head_dim=192,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None})


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
    assert family.__name__.endswith("models.olmo_hybrid")
    assert cfg.layer_types == tuple([LINEAR] * 3 + [FULL]) * 8
    assert (cfg.head_dim, cfg.key_dim, cfg.value_dim, cfg.conv_dim) == (
        128, 2880, 5760, 11520)
    spec = cfg.cache_spec()
    # the state in PAIRS of heads, 384 lanes and no padding; the KV row
    # 32 heads tall, the model's 30 and two of zeros
    state = kv_cache.StateCache("state", 0, (3, 11520), (15, 96, 384))
    full = kv_cache.LayerCache("full", 0, 32, 128)
    assert spec == tuple([state] * 3 + [full]) * 8
    assert state.dtypes() == {"ssm": jnp.float32}
    assert np.prod(state.ssm) == 30 * 96 * 192
    assert kv_cache.state_row_bytes(spec[:4], jnp.bfloat16) == 3 * (
        2_211_840 + 69_120)
    assert kv_cache.kinds_of(spec) == {"state": 0, "full": 0}
    # no layer_types: three linear layers, then a full one
    _f, short = resolve({k: v for k, v in PUBLISHED.items()
                         if k != "layer_types"} | {"num_hidden_layers": 8})
    assert short.layer_types == tuple([LINEAR] * 3 + [FULL]) * 2


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("rope_parameters", {"rope_theta": 500000.0}),
    ("linear_num_value_heads", 60), ("linear_num_key_heads", 15),
    ("layer_types", [LINEAR] * 31), ("layer_types", ["mamba"] * 32)])
def test_what_the_module_does_not_write_is_refused_by_its_key(key, value):
    model = {**PUBLISHED, key: value}
    if key == "linear_num_key_heads":
        model["linear_num_value_heads"] = value
    with pytest.raises(ValueError, match="heads|layer_types|" + key):
        resolve(model)


def test_the_parameter_tree_is_the_issues_count(params):
    """ISSUE 50's arithmetic at the published widths, by shapes alone."""
    _family, cfg = resolve({**PUBLISHED, "num_hidden_layers": 16,
                            "layer_types": PUBLISHED["layer_types"][:16]})
    tree = jax.eval_shape(build(cfg, PAGE).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree.leaves(t))
    assert count(tree["layer_0"]) == 215_570_172
    assert count(tree["layer_3"]) == 185_809_920
    assert count(tree) == 4_100_788_944
    mixer = tree["layer_0"]["mixer"]
    assert mixer["qkv_proj"]["kernel"].shape == (3840, 11520)
    assert mixer["conv_w"].shape == (4, 11520)
    assert mixer["a_log"].dtype == mixer["norm_w"].dtype == jnp.float32
    assert mixer["qkv_proj"]["kernel"].dtype == jnp.bfloat16
    # the toy tree's gates are drawn as `assumed` says
    a = np.exp(np.asarray(params["layer_0"]["mixer"]["a_log"]))
    step = jax.nn.softplus(params["layer_0"]["mixer"]["dt_bias"])
    assert (a > 0).all() and (a < 16).all()
    assert (step > 9e-4).all() and (step < 0.11).all()


def test_the_cacheless_forward_is_the_reference(params):
    got = build(CFG, PAGE).apply({"params": params}, jnp.asarray(TOKENS[None]))
    want = np.asarray(ref.logits(params, TOKENS, SIZES))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=0, atol=ATOL)
    # a sequence that is not whole chunks is padded behind, not cut
    got = build(CFG, PAGE).apply({"params": params}, jnp.asarray(TOKENS[None, :70]))
    np.testing.assert_allclose(np.asarray(got[0]), want[:70], rtol=0,
                               atol=ATOL)


@pytest.mark.parametrize("reading", ref.READINGS)
def test_every_reading_of_the_reference_is_another_model(params, reading):
    """Each of the reference's other readings moves the logits of a
    150-token sequence by far more than the program differs from the
    reference proper: none is a no-op."""
    want = np.asarray(ref.logits(params, TOKENS, SIZES))
    tokens = TOKENS
    if reading in ("updating_pad", "stale_slot", "conv_edge_dropped"):
        # these depend on where the PROMPT ends: 100 of the 150 tokens
        x = ref.hidden(params, tokens, SIZES, prompt_len=100,
                       reading=reading)[:len(tokens)]
        base = ref.hidden(params, tokens, SIZES, prompt_len=100)[
            :len(tokens)]
        moved = float(jnp.abs(x - base)[100:].max())
        assert moved > 1e-2, reading
        return
    other = np.asarray(ref.logits(params, TOKENS, SIZES, reading=reading))
    # (keys at their own lengths with beta near 2 make the state's map
    # expansive: that reading's logits are not finite, which differs too)
    assert not np.abs(other - want).max() <= 100 * ATOL, reading
