"""models/granite.py, the state kind of models/cache.py and the engine's
state slots against the plain reference (seeded random weights, small
size, float32, CPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_granite as ref
from ray_tpu.models import cache as kv_cache, resolve
from ray_tpu.models.granite import (GraniteConfig, build, own_half,
                                    pair_queries)

CFG = dataclasses.replace(GraniteConfig.tiny(), dtype=jnp.float32,
                          param_dtype=jnp.float32)
PAGE = 16
SIZES = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
         if "dtype" not in f.name}
TOKENS = np.random.RandomState(0).randint(1, 256, (150,)).astype(np.int32)
# float32 against float32 under "highest": the logits are of size 0.05,
# and the two differ by the order of their sums (measured 5e-8 over the
# no-cache forward, 2e-7 through the pools)
ATOL = 2e-6


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


@pytest.fixture(scope="module")
def want(params):
    return np.asarray(ref.logits(params, TOKENS, SIZES))


def test_model_type_picks_the_family_and_a_mamba_layer_keeps_one_state():
    published = dict(
        model_type="granitemoehybrid", num_hidden_layers=10,
        layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
        num_local_experts=0, position_embedding_type="nope",
        tie_word_embeddings=True, max_position_embeddings=4096)
    family, cfg = resolve(published)
    assert family.__name__.endswith("models.granite")
    spec = cfg.cache_spec()
    state = kv_cache.StateCache("state", 0, (3, 4352), (64, 64, 128))
    # the 8 KV heads of 64 in 4 pairs of 128 lanes
    full = kv_cache.LayerCache("full", 0, 4, 128)
    assert spec == (state,) * 5 + (full,) + (state,) * 4
    assert kv_cache.kinds_of(spec) == {"state": 0, "full": 0}
    assert state.rows() == {"conv": (3, 4352), "ssm": (64, 64, 128)}
    assert state.dtypes() == {"ssm": jnp.float32} and full.dtypes() == {}
    # 36 such layers are the published model's 76,437,504 B a sequence
    assert kv_cache.state_row_bytes((state,) * 36 + (full,) * 4,
                                    jnp.bfloat16) == 76_437_504
    pools = kv_cache.make_pools(spec[4:7], {"state": 3, "full": 2 * PAGE},
                                jnp.bfloat16)
    assert [None if p is None else (p.shape, p.dtype)
            for p in pools["ssm"]] == [
        ((3, 64, 64, 128), jnp.float32), None,
        ((3, 64, 64, 128), jnp.float32)]
    assert pools["conv"][0].dtype == jnp.bfloat16
    assert pools["k"][1].shape == (32, 4, 128) and pools["k"][0] is None
    # a mechanism whose key is absent is not there
    assert cfg.embedding_multiplier == cfg.residual_multiplier == 1.0
    assert cfg.attention_multiplier == 0.125 and not cfg.mamba_conv_bias


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"),
    ("mamba_n_groups", 2), ("attention_bias", True),
    ("tie_word_embeddings", False)])
def test_the_unwritten_parts_of_the_family_are_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        resolve({"model_type": "granitemoehybrid", key: value})


def test_the_attentions_default_scale_is_what_it_was():
    """`scale=None` is the division by sqrt(d) every program before
    `scale` was compiled with, to the bit."""
    from ray_tpu.models import llama
    from ray_tpu.ops.paged_attention import paged_attention

    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 1, 4, 16), jnp.float32)
    pool = jnp.asarray(rng.randn(4 * PAGE, 2, 16), jnp.float32)
    table = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    lens = jnp.asarray([20, 9], jnp.int32)
    np.testing.assert_array_equal(
        paged_attention(q, pool, pool, table, lens, page_size=PAGE),
        paged_attention(q, pool, pool, table, lens, page_size=PAGE,
                        scale=0.25))
    k = jnp.asarray(rng.randn(2, 5, 2, 16), jnp.float32)
    q5 = jnp.asarray(rng.randn(2, 5, 4, 16), jnp.float32)
    np.testing.assert_allclose(llama.dense_attention(q5, k, k),
                               llama.dense_attention(q5, k, k, scale=0.25),
                               rtol=1e-6)


def test_paired_rows_ask_the_same_scores_and_keep_their_own_values():
    """Two KV heads of 64 side by side in a 128-lane row, queries zero
    on the other head's half: the attention of the unpaired layout."""
    from ray_tpu.models.llama import dense_attention

    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 6, 8, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 6, 4, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 6, 4, 64), jnp.float32)
    want = dense_attention(q, k, v, scale=1 / 64)
    got = own_half(dense_attention(
        pair_queries(q, 4), k.reshape(1, 6, 2, 128),
        v.reshape(1, 6, 2, 128), scale=1 / 64), 4)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(own_half(pair_queries(q, 4), 4), q)


def test_plain_forward_is_the_references_in_float32(params, want):
    """The cache-less pass (the chunk form, a chunk at a time from an
    empty state) gives the token-by-token reference's logits: the
    convolution, the gated norm, the multipliers, the tied head, the
    unrotated attention at the model's own scale."""
    out = jax.jit(lambda p, t: build(CFG, PAGE).apply({"params": p}, t))(
        params, jnp.asarray(TOKENS[None]))
    np.testing.assert_allclose(np.asarray(out[0]), want, atol=ATOL)
    assert float(np.abs(want).max()) > 0.02


def test_chunked_prefill_then_decode_is_the_references_every_position(
        params, want):
    """Through the pools: 140 tokens prefilled in chunks of 64 through
    the state pool beside a garbage lane (the last chunk ragged), then
    10 tokens one at a time through the interpreted kernel with the
    sequence CHANGING LANES, give the reference's logits at every
    position."""
    model = build(CFG, PAGE)
    pools = kv_cache.make_pools(
        CFG.cache_spec(), {"full": 17 * PAGE, "state": 4}, CFG.dtype)
    apply = jax.jit(lambda c, t: model.apply({"params": params}, t, c))
    got, n_prefill, width = [], 140, 256
    for lo in range(0, n_prefill, 64):
        hi = min(lo + 64, n_prefill)
        toks = np.zeros((2, 64), np.int32)
        slots = np.zeros((2, 64), np.int32)
        q_pos = np.zeros((2, 64), np.int32)
        toks[1, :hi - lo] = TOKENS[lo:hi]
        slots[1, :hi - lo] = PAGE + np.arange(lo, hi)
        q_pos[1, :hi - lo] = np.arange(lo, hi)
        ctx = np.zeros((2, width), np.int32)
        ctx[1, :hi] = PAGE + np.arange(hi)
        mask = np.zeros((2, width), bool)
        mask[1, :hi] = True
        logits, pools = apply({
            **pools, "q_pos": q_pos, "groups": {
                "full": {"slots": slots, "ctx": ctx, "ctx_mask": mask,
                         "ctx_pos": np.tile(np.arange(width,
                                                      dtype=np.int32), (2, 1))},
                "state": {"slots": np.asarray([0, 2], np.int32),
                          "lens": np.asarray([0, hi - lo], np.int32),
                          "fresh": np.asarray([False, lo == 0])}}}, toks)
        got.append(np.asarray(logits[1, :hi - lo]))
    table = np.zeros((3, 16), np.int32)
    for n in range(n_prefill, len(TOKENS)):
        lane = n % 3
        toks = np.zeros((3, 1), np.int32)
        toks[lane] = TOKENS[n]
        slots = np.zeros((3, 1), np.int32)
        slots[lane] = PAGE + n
        tables = table.copy()
        tables[lane, :10] = np.arange(1, 11)
        lens = np.zeros((3,), np.int32)
        lens[lane] = n + 1
        live = np.arange(3) == lane
        logits, pools = apply({
            **pools, "q_pos": np.where(live, n, 0)[:, None].astype(np.int32),
            "groups": {
                "full": {"slots": slots, "block_tables": tables,
                         "context_lens": lens},
                "state": {"slots": np.where(live, 2, 0).astype(np.int32),
                          "lens": live.astype(np.int32),
                          "fresh": np.zeros((3,), bool)}}}, toks)
        got.append(np.asarray(logits[lane]))
    np.testing.assert_allclose(np.concatenate(got), want, atol=ATOL)
    # the garbage slot and the slots nobody held are as they were made
    for pool in pools["ssm"] + pools["conv"]:
        if pool is not None:
            assert not np.asarray(pool)[[0, 1, 3]].any()
