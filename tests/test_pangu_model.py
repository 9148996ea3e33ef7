"""models/pangu.py, the latent cache row and ops/latent_attention.py
against the plain reference (seeded random weights, small size, CPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import narrow_prefill_cases
import numpy as np
import pytest

from benchmarks import reference_pangu as ref
from ray_tpu.models import cache as kv_cache, resolve
from ray_tpu.models.pangu import PanguConfig, build
from ray_tpu.serve.llm import LLMEngine

CFG = dataclasses.replace(PanguConfig.tiny(), dtype=jnp.float32)
PAGE = 16


def _sizes(cfg, held=None):
    return dict(num_hidden_layers=cfg.num_hidden_layers,
                first_k_dense_replace=cfg.first_k_dense_replace,
                sandwich_norm=cfg.sandwich_norm,
                kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                rope_theta=cfg.rope_theta,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                rms_norm_eps=cfg.rms_norm_eps,
                experts_held=list(held or cfg.experts_held))


SIZES = _sizes(CFG)
TOKENS = np.random.RandomState(0).randint(1, 256, (150,)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return jax.jit(build(CFG, PAGE).init)(
        jax.random.PRNGKey(0), jnp.asarray(TOKENS[None, :8]))["params"]


@pytest.fixture(scope="module")
def want(params):
    """The reference's logits and margins of TOKENS."""
    logits, margin = ref.logits(params, TOKENS, SIZES)
    return np.asarray(logits), np.asarray(margin)


def test_model_type_picks_the_family_and_the_row_is_one_latent_vector():
    family, cfg = resolve({"model_type": "pangu_ultra_moe",
                           "num_hidden_layers": 2, "kv_lora_rank": 512,
                           "qk_rope_head_dim": 64})
    assert family.__name__.endswith("models.pangu")
    layer = kv_cache.LayerCache("full", 0, 0, 0, 576)
    assert cfg.cache_spec() == (layer, layer)
    assert kv_cache.kinds_of(cfg.cache_spec()) == {"full": 0}
    # one pool a layer and no `v`: 576 numbers a token, allocated at the
    # next multiple of the chip's 128 lanes
    assert layer.rows() == {"latent": (640,)}
    pools = kv_cache.make_pools(cfg.cache_spec(), {"full": 4 * PAGE},
                                jnp.bfloat16)
    assert list(pools) == ["latent"]
    assert [p.shape for p in pools["latent"]] == [(64, 640)] * 2
    assert sum(p.nbytes for p in pools["latent"]) == 2 * 64 * 640 * 2
    # a key-and-value layer's are what they were
    kv = kv_cache.LayerCache("full", 0, 2, 16)
    assert kv.rows() == {"k": (2, 16), "v": (2, 16)} and kv.latent == 0
    # a mechanism whose key is absent is not there
    assert not cfg.sandwich_norm and cfg.n_shared_experts == 0
    assert cfg.first_k_dense_replace == 0


def test_plain_forward_is_the_references_in_float32(params, want):
    """(ii, first half) the model's cache-less pass, the plain form,
    gives the reference's logits to float32 rounding (measured 4e-6 of
    logits of size 3): down-projections and their norms, the shared
    rotated key, the sandwich norms, the dense lead, sigmoid routing,
    the held experts through the Pallas grouped matmul."""
    out = jax.jit(lambda p, t: build(CFG, PAGE).apply({"params": p}, t))(
        params, jnp.asarray(TOKENS[None]))
    np.testing.assert_allclose(np.asarray(out[0]), want[0], atol=1e-4)
    assert want[1].shape == TOKENS.shape and float(want[1].min()) > 0


def _cache(pools, slots, q_pos, **group):
    return {**pools, "q_pos": q_pos,
            "groups": {"full": {"slots": slots, **group}}}


def test_chunked_prefill_then_decode_is_the_references_every_position(
        params, want):
    """(i) and (ii): the ABSORBED form through the latent pages — 140
    tokens prefilled in chunks of 64 over a gathered context of 256
    columns (blocks past the context are not walked), then 10 tokens one
    at a time through the kernel — gives the reference's full-forward
    logits at every position to float32 rounding."""
    model = build(CFG, PAGE)
    pools = kv_cache.make_pools(CFG.cache_spec(), {"full": 17 * PAGE},
                                CFG.dtype)
    apply = jax.jit(lambda c, t: model.apply({"params": params}, t, c))
    got, n_prefill, width = [], 140, 256
    for lo in range(0, n_prefill, 64):
        hi = min(lo + 64, n_prefill)
        toks = np.zeros((1, 64), np.int32)
        slots = np.zeros((1, 64), np.int32)
        q_pos = np.zeros((1, 64), np.int32)
        toks[0, :hi - lo] = TOKENS[lo:hi]
        slots[0, :hi - lo] = PAGE + np.arange(lo, hi)
        q_pos[0, :hi - lo] = np.arange(lo, hi)
        ctx = np.zeros((1, width), np.int32)
        ctx[0, :hi] = PAGE + np.arange(hi)
        logits, pools, _counted = apply(_cache(
            pools, slots, q_pos, ctx=ctx,
            ctx_pos=np.arange(width, dtype=np.int32)[None],
            ctx_mask=(np.arange(width) < hi)[None]), toks)
        got.append(np.asarray(logits[0, :hi - lo]))
    table = np.zeros((1, 16), np.int32)
    table[0, :10] = np.arange(1, 11)
    for n in range(n_prefill, len(TOKENS)):
        logits, pools, _counted = apply(_cache(
            pools, np.full((1, 1), PAGE + n, np.int32),
            np.full((1, 1), n, np.int32), block_tables=table,
            context_lens=np.full((1,), n + 1, np.int32)),
            TOKENS[None, n:n + 1])
        got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.concatenate(got), want[0], atol=2e-4)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """(iv) the two shares' routed parts, with the shared expert counted
    once, are the uncut reference's expert layer: share (lo, hi) of the
    model computes its own experts' part of the routed sum and what
    every share computes alike."""
    from ray_tpu.models.laguna import ExpertLayer
    from ray_tpu.models.pangu import router_scores

    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(1, 40, CFG.hidden_size), jnp.float32)
    whole = dataclasses.replace(CFG, experts_held=(0, 8))
    layer = ExpertLayer(whole, scores=router_scores)
    moe = jax.jit(layer.init)(jax.random.PRNGKey(4), h,
                              jnp.ones((1, 40), bool))["params"]
    want, _margin = ref._routed(
        h[0], moe, top_k=2, normalize=True, lo=0)
    shared = ref._swiglu(h[0], *(moe["moe_shared"][n]["kernel"]
                                 for n in ("w1", "w3", "w2")))
    want = np.asarray(ref.combine_shared(shared, want, 2.5))
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        share = {**moe, **{f"moe_experts_{n}": moe[f"moe_experts_{n}"][lo:hi]
                           for n in ("w1", "w3", "w2")}}
        cfg = dataclasses.replace(CFG, experts_held=(lo, hi))
        y, counters = ExpertLayer(cfg, scores=router_scores).apply(
            {"params": share}, h, jnp.ones((1, 40), bool))
        parts.append(np.asarray(y[0], np.float32))
        assert int(counters["assignments"]) > 0
    shared = np.asarray(shared)
    # each share's output holds the shared expert once: take it off one
    np.testing.assert_allclose(parts[0] + parts[1] - shared, want,
                               atol=2e-4)


def _drain(eng):
    while eng.step():
        pass
    eng.drain()


def _engine(**kw):
    return LLMEngine(CFG, seed=5, page_size=PAGE, max_batch=4, **kw)


def test_latent_pools_ship_copy_and_share(params):
    """(v) `gather_slots` -> `scatter_slots` and `copy_slots` round-trip
    a latent pool; a request that shares a live prefix (two whole pages
    and 15 rows of a third by a copy-on-write split) gives the tokens of an unshared one; rows
    shipped from a prefill engine decode as local ones do."""
    spec = CFG.cache_spec()
    kinds = [layer.kind for layer in spec]
    rng = np.random.RandomState(6)
    pools = {"latent": [jnp.asarray(rng.randn(4 * PAGE, 128), jnp.float32)
                        for _ in spec]}
    at = {"full": np.asarray([3, 17, 40, 41])}
    rows = kv_cache.gather_slots(pools, kinds, at)
    assert list(rows) == ["latent"] and rows["latent"][0].shape == (4, 128)
    blank = kv_cache.make_pools(spec, {"full": 4 * PAGE}, jnp.float32)
    back = kv_cache.scatter_slots(blank, kinds, at, rows)
    for a, b in zip(back["latent"], pools["latent"]):
        np.testing.assert_array_equal(np.asarray(a)[at["full"]],
                                      np.asarray(b)[at["full"]])
        assert not np.asarray(a)[[0, 2, 63]].any()
    copied = kv_cache.copy_slots(pools, kinds, "full", [3, 17], [50, 51])
    for a, b in zip(copied["latent"], pools["latent"]):
        np.testing.assert_array_equal(np.asarray(a)[[50, 51]],
                                      np.asarray(b)[[3, 17]])

    prompt = [int(t) for t in TOKENS[:48]]
    alone = _engine(prefix_sharing=False)
    want = alone.generate_batch(
        [{"tokens": prompt, "max_new_tokens": 6}])[0]
    eng = _engine()
    first = eng.submit({"tokens": prompt, "max_new_tokens": 6})
    for _ in range(3):
        eng.step()
    second = eng.submit({"tokens": prompt, "max_new_tokens": 6})
    _drain(eng)
    st = eng.stats()
    assert st["prefix_sharing"] and st["prefix_hits"] == 1
    assert st["cow_splits"] == 1 and st["prefix_tokens_shared"] == 47
    assert list(first.generated) == list(second.generated) == want
    assert st["latent_pool_bytes"] == 3 * eng.num_pages * PAGE * 128 * 4
    assert st["latent_decode_calls_total"] == 3 * st["decode_steps"]
    assert st["latent_prefill_rows_total"] == 3 * (48 + 48)
    assert st["latent_decode_rows_total"] > 0
    assert st["latent_pages_in_use"] == 0

    payload = alone.prefill_request({"tokens": prompt, "max_new_tokens": 6,
                                     "request_id": "ship"})
    assert list(payload["rows"]) == ["latent"]
    decoder = _engine(params=alone._params)
    shipped = decoder.submit(
        {"tokens": prompt, "max_new_tokens": 6, "request_id": "ship"},
        kv_pack=(payload["meta"], payload["rows"]))
    _drain(decoder)
    assert list(shipped.generated) == want
    assert decoder.stats()["prefill_steps"] == 0


def test_the_prefill_pass_carries_each_lanes_block_table():
    """What `latent_chunk_attention` reads its table from: every
    `page_size`-th column of the pass's `ctx`, by whole pages, is the
    sequence's pages (`seq.cache["full"].pages`) as far as its context reaches and its
    length is the mask's count — for every pass an engine builds, the
    passes of a prompt that shares a live prefix's pages (and holds a
    copy-on-write page of its own behind them) among them."""
    eng = _engine()
    passes, tables, calls = [], {}, []
    dispatch, forward = eng._dispatch_prefill, eng._forward

    def spy_dispatch(step, prefill_args, shape):
        lanes = [(seq.cache["full"].pages.tolist(), hi)
                 for seq, _lo, hi, *_rest in prefill_args]
        tables.update((seq, seq.cache["full"].pages.tolist())
                      for seq, *_rest in prefill_args)
        out = dispatch(step, prefill_args, shape)
        # the pass's own call is the last (warm-up's come before it)
        passes.append((lanes, *calls[-1]))
        return out

    def spy_forward(tokens, q_pos, last_idx, groups, **kw):
        full = groups["full"]
        if "ctx" in full:
            calls.append((np.asarray(full["ctx"]),
                          np.asarray(full["ctx_mask"])))
        return forward(tokens, q_pos, last_idx, groups, **kw)

    eng._dispatch_prefill, eng._forward = spy_dispatch, spy_forward
    prompt = [int(t) for t in TOKENS[:96]]
    first = eng.submit({"tokens": prompt, "max_new_tokens": 4})
    for _ in range(4):
        eng.step()
    second = eng.submit({"tokens": prompt, "max_new_tokens": 4})
    third = eng.submit({"tokens": [5] + prompt[:70], "max_new_tokens": 4})
    _drain(eng)
    assert eng.stats()["prefix_hits"] == 1 and eng.stats()["cow_splits"] == 1
    # five whole pages shared, the sixth a copy of 15 of its rows
    assert tables[first][:5] == tables[second][:5]
    assert tables[first][5] != tables[second][5]
    assert len(passes) >= 4 and third.generated
    for lanes, ctx, mask in passes:
        table = ctx[:, ::PAGE] // PAGE
        np.testing.assert_array_equal(mask.sum(-1)[:len(lanes)],
                                      [hi for _bt, hi in lanes])
        assert not mask[len(lanes):].any()
        for lane, (block_table, hi) in enumerate(lanes):
            used = -(-hi // PAGE)
            assert list(table[lane, :used]) == block_table[:used]
            # positions in order: a column's slot is its page's row
            np.testing.assert_array_equal(
                ctx[lane, :hi], np.repeat(table[lane, :used] * PAGE,
                                          PAGE)[:hi] + np.arange(hi) % PAGE)
            # behind the length: the garbage page, a valid address
            assert not ctx[lane, hi:].any()


class _NarrowKit:
    """This family's kit for `narrow_prefill_cases`: chunk 16 under a
    context of 384 gives the prefill pass the widths 64, 256 and 384;
    the prefill kernel's grid follows the pass's lanes."""

    @staticmethod
    def make(max_len=384, **kw):
        return LLMEngine(
            dataclasses.replace(CFG, max_position_embeddings=max_len),
            seed=5, page_size=PAGE, max_batch=4, prefill_chunk=16, **kw)

    @staticmethod
    def make_one_width():
        return _NarrowKit.make(max_len=64)

    @staticmethod
    def prompt(n, salt=0):
        rs = np.random.RandomState(2000 + 7 * n + salt)
        return [int(t) for t in rs.randint(1, 256, n)]

    check = staticmethod(narrow_prefill_cases.teacher_forced_check(
        ref, SIZES))


@pytest.mark.parametrize("case", narrow_prefill_cases.CASES,
                         ids=lambda case: case.__name__)
def test_narrow_prefill_pass(case):
    case(_NarrowKit)


def _routers_zeroed(params):
    """`params` with every router's matrix zero: all scores tie, so every
    token chooses experts 0 and 1 (the top-k keeps the lowest of a tie),
    both of the share (0, 4)."""
    def zero(path, leaf):
        return jnp.zeros_like(leaf) if "moe_router" in jax.tree_util.keystr(
            path) else leaf
    return jax.tree_util.tree_map_with_path(zero, params)


def test_row_tiles_active_is_the_dispatchs_over_the_layers(params):
    """`moe_row_tiles_active_total` on a hand-built routing: every token
    on experts 0 and 1, so a pass of n tokens fills ceil(n / row tile)
    tiles of each in both expert layers.  One pass through the model —
    64 slots, 40 of them tokens, tiles of 16: 2 x 2 x 3 — and an engine's
    `stats()` by phase: a decode pass of at most 4 lanes fills one tile
    an expert, a prefill pass of four prompts four tiles an expert."""
    from ray_tpu.ops import moe

    fixed = _routers_zeroed(params)
    model = build(CFG, PAGE)
    names = model.counters
    assert names[:4] == tuple(f"moe_{n}_total" for n in moe.COUNTERS)
    assert names[3] == "moe_row_tiles_active_total" and len(names) == 6
    pools = kv_cache.make_pools(CFG.cache_spec(), {"full": 17 * PAGE},
                                CFG.dtype)
    toks = np.zeros((1, 64), np.int32)
    slots = np.zeros((1, 64), np.int32)
    q_pos = np.zeros((1, 64), np.int32)
    toks[0, :40], slots[0, :40] = TOKENS[:40], PAGE + np.arange(40)
    q_pos[0, :40] = np.arange(40)
    ctx = np.zeros((1, 64), np.int32)
    ctx[0, :40] = PAGE + np.arange(40)
    _logits, _pools, vec = model.apply({"params": fixed}, toks, _cache(
        pools, slots, q_pos, ctx=ctx,
        ctx_pos=np.arange(64, dtype=np.int32)[None],
        ctx_mask=(np.arange(64) < 40)[None]))
    assert moe.row_tile(64, 2, 8) == 16
    assert dict(zip(names, np.asarray(vec).tolist())) == {
        "moe_assignments_total": 2 * 2 * 40, "moe_expert_calls_total": 2 * 2,
        "moe_max_load_total": 2 * 40, "moe_row_tiles_active_total": 2 * 2 * 3,
        "moe_layer_passes_total": 2, "moe_expert_slots_total": 2 * 4}

    eng = _engine(params=fixed)
    eng.generate_batch([{"tokens": [int(t) for t in TOKENS[n:n + 60]],
                         "max_new_tokens": 5} for n in range(4)])
    st = eng.stats()
    tiles, calls = st["moe_row_tiles_active_total"], \
        st["moe_expert_calls_total"]
    assert set(tiles) == {"decode", "prefill"}
    assert tiles["decode"] == calls["decode"] == 2 * 2 * st["decode_steps"]
    assert calls["prefill"] == 2 * 2 * st["prefill_steps"]
    # the four prompts in one pass of 4 x 64 slots, tiles of 64: 240
    # tokens an expert
    assert st["prefill_steps"] == 1 and moe.row_tile(256, 2, 8) == 64
    assert tiles["prefill"] == 2 * 2 * 4
