"""Paged-attention kernel tests (ops/paged_attention.py).

The kernel runs in Pallas interpret mode on CPU — same numerics as the
TPU compilation — so these tests pin the decode kernel against the
dense gather-then-softmax reference (models/llama.py cached_attention)
across batch, context length, GQA grouping, and page size, including
ragged lengths, all-garbage lanes, and non-contiguous / shuffled
physical page assignment.  The engine-level A/B at the bottom proves
the two attention_impl settings generate token-identical streams.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from ray_tpu.models import cache as kv_cache
from ray_tpu.models.llama import LlamaConfig, cached_attention
from ray_tpu.ops.paged_attention import paged_attention


def _rand_paged_case(rng, batch, ctx_lens, n_heads, n_kv_heads, head_dim,
                     page_size, num_pages):
    """Random pools + a shuffled (non-contiguous) page assignment per
    lane; returns everything both the paged kernel and the dense
    reference need.  Page 0 is the garbage page, never assigned."""
    t = num_pages * page_size
    pool_k = jnp.asarray(rng.normal(size=(t, n_kv_heads, head_dim)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(t, n_kv_heads, head_dim)),
                         jnp.float32)
    q = jnp.asarray(rng.normal(size=(batch, 1, n_heads, head_dim)),
                    jnp.float32)
    used = [-(-c // page_size) for c in ctx_lens]
    width = max(max(used), 1)
    assert sum(used) <= num_pages - 1, "case needs more pages"
    pages = list(rng.permutation(np.arange(1, num_pages)))
    bt = np.zeros((batch, width), np.int32)
    for b in range(batch):
        for p in range(used[b]):
            bt[b, p] = pages.pop()
    return q, pool_k, pool_v, bt, np.asarray(ctx_lens, np.int32)


def _dense_reference(q, pool_k, pool_v, bt, ctx_lens, page_size):
    """cached_attention over ctx/ctx_pos/ctx_mask arrays derived from
    the same block tables — the exact arrays the dense engine path
    builds each decode step."""
    batch = q.shape[0]
    length = bt.shape[1] * page_size
    ctx = np.zeros((batch, length), np.int32)
    ctx_pos = np.zeros((batch, length), np.int32)
    ctx_mask = np.zeros((batch, length), bool)
    for b in range(batch):
        for pos in range(int(ctx_lens[b])):
            ctx[b, pos] = bt[b, pos // page_size] * page_size \
                + pos % page_size
            ctx_pos[b, pos] = pos
            ctx_mask[b, pos] = True
    q_pos = np.maximum(ctx_lens.astype(np.int32) - 1, 0)[:, None]
    return cached_attention(q, pool_k, pool_v, jnp.asarray(ctx),
                            jnp.asarray(ctx_pos), jnp.asarray(ctx_mask),
                            jnp.asarray(q_pos))


@pytest.mark.parametrize("batch,ctx_lens,heads,kv_heads,page_size", [
    (1, [1], 4, 2, 8),                 # single token, single lane
    (2, [5, 16], 4, 4, 8),             # MHA (group=1), page-exact length
    (3, [13, 1, 9], 4, 2, 4),          # GQA group=2, ragged
    (4, [31, 8, 17, 2], 8, 2, 8),      # GQA group=4, multi-page ragged
    (2, [7, 23], 4, 2, 16),            # bigger pages than one context
])
def test_kernel_matches_dense_reference(batch, ctx_lens, heads, kv_heads,
                                        page_size):
    rng = np.random.default_rng(hash((batch, heads, page_size)) % 2**32)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, batch, ctx_lens, heads, kv_heads, head_dim=16,
        page_size=page_size, num_pages=24)
    out = paged_attention(q, pk, pv, jnp.asarray(bt), jnp.asarray(cl),
                          page_size=page_size)
    ref = _dense_reference(q, pk, pv, bt, cl, page_size)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ragged_with_garbage_lanes():
    """Inactive lanes (context length 0, table pointing at the garbage
    page) must produce finite zeros — never NaNs from an all-masked
    softmax — while live lanes stay exact."""
    rng = np.random.default_rng(7)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, 4, [11, 0, 3, 0], 4, 2, head_dim=8, page_size=4,
        num_pages=16)
    out = np.asarray(paged_attention(q, pk, pv, jnp.asarray(bt),
                                     jnp.asarray(cl), page_size=4))
    assert np.all(np.isfinite(out))
    assert np.all(out[1] == 0) and np.all(out[3] == 0)
    ref = np.asarray(_dense_reference(q, pk, pv, bt, cl, 4))
    np.testing.assert_allclose(out[0], ref[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[2], ref[2], rtol=1e-5, atol=1e-5)


def test_all_garbage_batch_is_zero():
    rng = np.random.default_rng(11)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, 3, [0, 0, 0], 4, 2, head_dim=8, page_size=8, num_pages=8)
    out = np.asarray(paged_attention(q, pk, pv, jnp.asarray(bt),
                                     jnp.asarray(cl), page_size=8))
    assert np.all(out == 0) and np.all(np.isfinite(out))


def test_kernel_under_jit_and_wide_table():
    """The engine calls the kernel inside jit with a bucketed table
    width that can exceed any lane's used pages — trailing table
    entries must not perturb the result."""
    rng = np.random.default_rng(3)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, 2, [9, 4], 4, 2, head_dim=16, page_size=4, num_pages=16)
    ref = paged_attention(q, pk, pv, jnp.asarray(bt), jnp.asarray(cl),
                          page_size=4)
    wide = np.zeros((2, 8), np.int32)           # width 3 -> 8
    wide[:, :bt.shape[1]] = bt
    fn = jax.jit(lambda *a: paged_attention(*a, page_size=4))
    out = fn(q, pk, pv, jnp.asarray(wide), jnp.asarray(cl))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_shared_pages_between_lanes():
    """Prefix sharing: two lanes whose tables alias the SAME physical
    pages must each read the shared KV — the kernel only ever addresses
    pages through the table, so aliasing is invisible to it."""
    rng = np.random.default_rng(5)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, 2, [12, 12], 4, 2, head_dim=8, page_size=4, num_pages=16)
    bt[1] = bt[0]                                # full alias
    out = paged_attention(q, pk, pv, jnp.asarray(bt), jnp.asarray(cl),
                          page_size=4)
    ref = _dense_reference(q, pk, pv, bt, cl, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------ slot-pool round trips


def test_gather_scatter_copy_round_trip():
    """Property test over the KV slot-pool plumbing the paged cache
    rides on: scatter(gather(x)) is identity on the touched slots, a
    gather after shipping through numpy equals the original rows, and
    copy_kv_slots makes dst rows literally equal src rows (the CoW
    split primitive)."""
    cfg = LlamaConfig(vocab_size=16, dim=16, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=16, max_seq_len=32,
                      dtype=jnp.float32)
    spec = cfg.cache_spec()
    kinds = [layer.kind for layer in spec]

    def make_kv_pools(cfg, num_slots):
        return kv_cache.make_pools(spec, {"full": num_slots}, cfg.dtype)

    def gather_kv_slots(pools, slots):
        return kv_cache.gather_slots(pools, kinds, {"full": slots})

    def scatter_kv_slots(pools, slots, rows):
        return kv_cache.scatter_slots(pools, kinds, {"full": slots}, rows)

    def copy_kv_slots(pools, src, dst):
        return kv_cache.copy_slots(pools, kinds, "full", src, dst)

    rng = np.random.default_rng(13)
    for trial in range(5):
        num_slots = 40
        pools = make_kv_pools(cfg, num_slots)
        pools = {"k": [jnp.asarray(rng.normal(size=p.shape), p.dtype)
                       for p in pools["k"]],
                 "v": [jnp.asarray(rng.normal(size=p.shape), p.dtype)
                       for p in pools["v"]]}
        n = int(rng.integers(1, 12))
        slots = rng.choice(np.arange(1, num_slots), size=n, replace=False)
        rows = gather_kv_slots(pools, slots)
        # round trip into a fresh zeroed pool set
        fresh = make_kv_pools(cfg, num_slots)
        fresh = scatter_kv_slots(fresh, slots, rows)
        back = gather_kv_slots(fresh, slots)
        for side in ("k", "v"):
            for a, b in zip(rows[side], back[side]):
                np.testing.assert_array_equal(a, b)
        # copy: dst slots must equal src slots afterwards
        free = [s for s in range(1, num_slots) if s not in set(slots)]
        dst = np.asarray(free[:n], np.int32)
        copied = copy_kv_slots(pools, slots, dst)
        after_src = gather_kv_slots(copied, slots)
        after_dst = gather_kv_slots(copied, dst)
        for side in ("k", "v"):
            for a, b in zip(after_src[side], after_dst[side]):
                np.testing.assert_array_equal(a, b)


# ------------------------------------------------ engine-level A/B


def _make_engine(impl, params=None):
    from ray_tpu.serve.llm import LLMEngine

    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=64, max_seq_len=64,
                      dtype=jnp.float32)
    return LLMEngine(cfg, page_size=8, num_pages=33, max_batch=4,
                     prefill_chunk=8, max_queue=8,
                     attention_impl=impl, params=params)


def test_engine_paged_vs_dense_identical_tokens():
    """The serving A/B: the same prompts decoded greedily through the
    paged kernel and through the dense reference produce identical
    token streams (fp32 keeps argmax bit-stable)."""
    paged = _make_engine("paged")
    dense = _make_engine("dense", params=paged._params)
    assert paged.stats()["attention_impl"] == "paged"
    assert dense.stats()["attention_impl"] == "dense"
    reqs = [{"tokens": [5, 9, 3], "max_new_tokens": 6},
            {"tokens": [7, 11, 2, 4, 8, 1, 9, 10, 3, 2],
             "max_new_tokens": 6},
            {"tokens": [3] * 13, "max_new_tokens": 6}]
    out_p = paged.generate_batch([dict(r) for r in reqs])
    out_d = dense.generate_batch([dict(r) for r in reqs])
    assert out_p == out_d, (out_p, out_d)


def test_attention_impl_validation():
    with pytest.raises(ValueError, match="auto\\|paged\\|dense"):
        _make_engine("flashier")
