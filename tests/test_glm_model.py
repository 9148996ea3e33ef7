"""The latent-attention family's SPARSE setting (`model_type:
glm_moe_dsa`, models/pangu.py): an indexer a layer, a second cache part
a token, interleaved rotary pairs, a router with a selection bias —
against the plain float32 reference the benchmark keeps
(benchmarks/reference_glm.py), at toy widths on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_glm as ref
from ray_tpu.models import cache as kv_cache, resolve
from ray_tpu.models.pangu import (SPARSE_COUNTERS, PanguConfig, PanguModel,
                                  build)

PAGE = 16
# `tiny_sparse` with 64 rows selected (whole pages) of contexts to 512
CFG = dataclasses.replace(PanguConfig.tiny_sparse(), dtype=jnp.float32,
                          index_topk=64, index_n_heads=4,
                          max_position_embeddings=512)


def sizes(cfg, held=None):
    return dict(num_hidden_layers=cfg.num_hidden_layers,
                first_k_dense_replace=cfg.first_k_dense_replace,
                kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                rope_parameters={"rope_theta": cfg.rope_theta},
                index_topk=cfg.index_topk,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                rms_norm_eps=cfg.rms_norm_eps,
                experts_held=list(held or cfg.experts_held))


SIZES = sizes(CFG)
TOKENS = np.random.RandomState(0).randint(1, 256, (230,)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return jax.jit(build(CFG, PAGE).init)(
        jax.random.PRNGKey(0), jnp.asarray(TOKENS[None, :8]))["params"]


@pytest.fixture(scope="module")
def want(params):
    logits, margin, picked = ref.logits(params, TOKENS, SIZES)
    return np.asarray(logits), np.asarray(margin), np.asarray(picked)


def test_model_type_picks_the_family_and_a_row_has_two_parts():
    family, cfg = resolve({
        "model_type": "glm_moe_dsa", "num_hidden_layers": 2,
        "kv_lora_rank": 512, "qk_rope_head_dim": 64, "index_n_heads": 32,
        "index_head_dim": 128, "index_topk": 2048,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "rope_interleave": True, "indexer_rope_interleave": True,
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1})
    assert family.__name__.endswith("models.pangu")
    assert cfg.rope_theta == 1000000 and not cfg.sandwich_norm
    layer = kv_cache.IndexedLatentCache("full", 0, 576, 128)
    assert cfg.cache_spec() == (layer, layer)
    assert kv_cache.kinds_of(cfg.cache_spec()) == {"full": 0}
    # the latent row stored 640 wide and, beside it, the index key
    assert layer.rows() == {"latent": (640,), "index": (128,)}
    pools = kv_cache.make_pools(cfg.cache_spec(), {"full": 4 * PAGE},
                                jnp.bfloat16)
    assert sorted(pools) == ["index", "latent"]
    assert [p.shape for p in pools["index"]] == [(64, 128)] * 2
    assert sum(p.nbytes for ps in pools.values() for p in ps) \
        == 2 * 64 * 1536
    assert build(cfg, PAGE).counters == PanguModel.counters + SPARSE_COUNTERS
    with pytest.raises(ValueError, match="rope_type"):
        resolve({"model_type": "glm_moe_dsa",
                 "rope_parameters": {"rope_type": "yarn"}})
    with pytest.raises(ValueError, match="groups"):
        resolve({"model_type": "glm_moe_dsa", "n_group": 8})


def test_plain_forward_is_the_references_dense_and_sparse(params, want):
    """The cache-less pass: 230 tokens, of which a query past position 63
    reads the 64 rows its indexer selects (interleaved rotary pairs, the
    LayerNorm'd index key, ReLU, head weights, ties to the lower
    position, the router's bias).  64 tokens take the dense path, and
    are the reference's with the indexer left out."""
    model = build(CFG, PAGE)
    out = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(TOKENS[None]))
    np.testing.assert_allclose(np.asarray(out[0]), want[0], atol=1e-4)
    assert np.isinf(want[2][:64]).all() and np.isfinite(want[2][64:]).all()
    assert float(want[1].min()) > 0
    short = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(TOKENS[None, :64]))
    dense, _, _ = ref.logits(params, TOKENS[:64], SIZES, variant="dense")
    np.testing.assert_allclose(np.asarray(short[0]), np.asarray(dense),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(short[0]), want[0][:64], atol=1e-4)


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_a_reference_with_one_mechanism_wrong_is_another_model(
        params, want, variant):
    got, _, _ = ref.logits(params, TOKENS, SIZES, variant=variant)
    got = np.asarray(got)
    assert np.abs(got[64:] - want[0][64:]).max() > 0.05
    if variant != "no_bias":
        # the attention of the first 64 positions is dense either way
        np.testing.assert_allclose(got[:32], want[0][:32], atol=1e-4)


def _cache(pools, slots, q_pos, **group):
    return {**pools, "q_pos": q_pos,
            "groups": {"full": {"slots": slots, **group}}}


def test_chunked_prefill_then_decode_is_the_references_every_position(
        params, want):
    """Through BOTH pools: 200 tokens prefilled in chunks of 16 — the
    first four over a 64-column context (the dense path: it writes the
    index keys the later chunks score), the rest over 256 columns (index
    scores, thresholds, the masked chunk kernel) — then 30 tokens one at
    a time over a table of 16 pages (the threshold, the lane's own pages
    under its mask: ops/sparse_decode.py): the reference's full-forward
    logits at every position."""
    model = build(CFG, PAGE)
    pools = kv_cache.make_pools(CFG.cache_spec(), {"full": 17 * PAGE},
                                CFG.dtype)
    apply = jax.jit(lambda c, t: model.apply({"params": params}, t, c))
    got, n_prefill, chunk, counted = [], 200, 16, 0
    for lo in range(0, n_prefill, chunk):
        hi = min(lo + chunk, n_prefill)
        width = 64 if hi <= 64 else 256
        toks = np.zeros((1, chunk), np.int32)
        slots = np.zeros((1, chunk), np.int32)
        q_pos = np.zeros((1, chunk), np.int32)
        toks[0, :hi - lo] = TOKENS[lo:hi]
        slots[0, :hi - lo] = PAGE + np.arange(lo, hi)
        q_pos[0, :hi - lo] = np.arange(lo, hi)
        ctx = np.zeros((1, width), np.int32)
        ctx[0, :hi] = PAGE + np.arange(hi)
        logits, pools, vec = apply(_cache(
            pools, slots, q_pos, ctx=ctx,
            ctx_pos=np.arange(width, dtype=np.int32)[None],
            ctx_mask=(np.arange(width) < hi)[None]), toks)
        got.append(np.asarray(logits[0, :hi - lo]))
        counted = counted + np.asarray(vec[-len(SPARSE_COUNTERS):])
    layers = CFG.num_hidden_layers
    visible = sum(range(1, n_prefill + 1))
    read = sum(min(t, 64) for t in range(1, n_prefill + 1))
    # (the index pages copied: those up to a scored chunk's last row)
    assert counted.tolist() == [
        layers * sum(range(65, n_prefill + 1)), layers * visible,
        layers * read, 0, layers * 64,
        layers * sum(-(-min(lo + chunk, n_prefill) // PAGE)
                     for lo in range(64, n_prefill, chunk))]
    table = np.zeros((1, 16), np.int32)
    table[0, :15] = np.arange(1, 16)
    for n in range(n_prefill, len(TOKENS)):
        logits, pools, vec = apply(_cache(
            pools, np.full((1, 1), PAGE + n, np.int32),
            np.full((1, 1), n, np.int32), block_tables=table,
            context_lens=np.full((1,), n + 1, np.int32)),
            TOKENS[None, n:n + 1])
        got.append(np.asarray(logits[0]))
        assert np.asarray(vec[-len(SPARSE_COUNTERS):]).tolist() == [
            layers * (n + 1), layers * (n + 1), layers * 64, layers * 64, 0,
            layers * -(-(n + 1) // PAGE)]
    np.testing.assert_allclose(np.concatenate(got), want[0], atol=3e-4)
    assert sorted(pools) == ["index", "latent"]


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The 16 shares' routed parts, with the shared expert counted once,
    are the uncut reference's expert layer — the router scoring all 16
    experts with its selection bias in every share."""
    from ray_tpu.models.laguna import ExpertLayer
    from ray_tpu.models.pangu import router_scores

    whole = dataclasses.replace(CFG, n_routed_experts=16,
                                experts_held=(0, 16))
    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(1, 40, whole.hidden_size), jnp.float32)
    valid = jnp.ones((1, 40), bool)
    layer = ExpertLayer(whole, scores=router_scores, selection_bias=True)
    moe = jax.jit(layer.init)(jax.random.PRNGKey(4), h, valid)["params"]
    assert moe["moe_router_bias"].shape == (16,)
    assert float(jnp.abs(moe["moe_router_bias"]).min()) > 0
    routed, _margin = ref._routed(h[0], moe, top_k=2, normalize=True, lo=0)
    shared = np.asarray(ref._swiglu(
        h[0], *(moe["moe_shared"][n]["kernel"] for n in ("w1", "w3", "w2"))))
    want = shared + 2.5 * np.asarray(routed)
    # the bias moves who is chosen: without it, another layer
    plain, _ = ref._routed(h[0], moe, top_k=2, normalize=True, lo=0,
                           bias=False)
    assert np.abs(np.asarray(plain) - np.asarray(routed)).max() > 1e-3
    total = -15 * shared
    for lo in range(16):
        share = {**moe, **{f"moe_experts_{n}":
                           moe[f"moe_experts_{n}"][lo:lo + 1]
                           for n in ("w1", "w3", "w2")}}
        cfg = dataclasses.replace(whole, experts_held=(lo, lo + 1))
        y, _counters = ExpertLayer(
            cfg, scores=router_scores, selection_bias=True).apply(
            {"params": share}, h, valid)
        total = total + np.asarray(y[0], np.float32)
    np.testing.assert_allclose(total, want, atol=3e-4)
