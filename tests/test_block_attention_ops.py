"""The block form of the paged kernel (`paged_attention_block`: B queries
a lane that all see the rows below `context_lens`, the block's own among
them) and the block-causal mask of the gathered and the whole-sequence
routes, against plain attention written out here."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (cached_attention, default_attention,
                                  dense_attention)
from ray_tpu.ops.paged_attention import paged_attention

PAGE, BLOCK, H, HKV, D = 16, 4, 8, 2, 32


def _plain(q, k, v, seen):
    """q [S, H, D], k/v [T, Hkv, D], seen [S, T] -> [S, H, D]."""
    k = np.repeat(k, q.shape[1] // k.shape[1], axis=1)
    v = np.repeat(v, q.shape[1] // v.shape[1], axis=1)
    scores = np.einsum("shd,thd->hst", q, k) / np.sqrt(q.shape[-1])
    scores = np.where(seen[None], scores, -np.inf)
    p = np.exp(scores - scores.max(axis=-1, keepdims=True))
    return np.einsum("hst,thd->shd", p / p.sum(axis=-1, keepdims=True), v)


@pytest.mark.parametrize("rows", [1, 17, 4000])
def test_block_kernel_is_plain_attention_over_the_rows_and_the_block(rows):
    """Three lanes in one call: `rows` committed rows (rounded down to
    whole blocks, as a block's first position is), a lane one block in,
    and an empty lane; pages shuffled over the pool."""
    rng = np.random.RandomState(rows)
    lens = [rows // BLOCK * BLOCK + BLOCK, BLOCK, 0]
    used = [-(-n // PAGE) for n in lens]
    width = max(4, max(used))
    num_pages = sum(used) + 3
    pool_k = rng.normal(size=(num_pages * PAGE, HKV, D)).astype(np.float32)
    pool_v = rng.normal(size=(num_pages * PAGE, HKV, D)).astype(np.float32)
    q = rng.normal(size=(3, BLOCK, H, D)).astype(np.float32)
    pages = list(rng.permutation(np.arange(1, num_pages)))
    tables = np.zeros((3, width), np.int32)
    for lane, n in enumerate(used):
        tables[lane, :n] = [pages.pop() for _ in range(n)]
    out = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(tables), jnp.asarray(lens, jnp.int32), page_size=PAGE))
    assert out.shape == q.shape
    for lane, n in enumerate(lens):
        if not n:
            assert not out[lane].any()
            continue
        slots = (tables[lane, :used[lane], None] * PAGE
                 + np.arange(PAGE)).reshape(-1)[:n]
        want = _plain(q[lane], pool_k[slots], pool_v[slots],
                      np.ones((BLOCK, n), bool))
        np.testing.assert_allclose(out[lane], want, rtol=2e-5, atol=2e-5)


def test_one_query_a_lane_is_the_decode_kernel_it_was():
    """S = 1 takes the path it took: the same numbers as a block of one
    row repeated would give, and the decode kernel's name."""
    import jax

    rng = np.random.RandomState(5)
    pool = jnp.asarray(rng.normal(size=(8 * PAGE, HKV, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 1, H, D)), jnp.float32)
    tables = jnp.asarray([[3, 5, 0, 0], [1, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([20, 7], jnp.int32)
    one = paged_attention(q, pool, pool, tables, lens, page_size=PAGE)
    four = paged_attention(jnp.repeat(q, 4, axis=1), pool, pool, tables,
                           lens, page_size=PAGE)
    np.testing.assert_allclose(np.asarray(four[:, :1]), np.asarray(one),
                               rtol=1e-6, atol=1e-6)

    def names(queries):
        return str(jax.make_jaxpr(lambda x: paged_attention(
            x, pool, pool, tables, lens, page_size=PAGE))(queries))

    assert "paged_attention_decode" in names(q)
    assert "paged_attention_block" not in names(q)
    assert "paged_attention_block" in names(jnp.repeat(q, 4, axis=1))


@pytest.mark.parametrize("lo,hi", [(0, 16), (16, 24), (8, 40)])
def test_gathered_route_masks_by_blocks(lo, hi):
    """A prefill chunk at [lo, hi) (whole blocks) over a gathered
    context: each query sees its whole block and every earlier one."""
    rng = np.random.RandomState(hi)
    pool_k = rng.normal(size=(64, HKV, D)).astype(np.float32)
    pool_v = rng.normal(size=(64, HKV, D)).astype(np.float32)
    q = rng.normal(size=(1, hi - lo, H, D)).astype(np.float32)
    width = 48
    ctx = np.zeros((1, width), np.int32)
    ctx_pos = np.zeros((1, width), np.int32)
    ctx_mask = np.zeros((1, width), bool)
    ctx[0, :hi] = 8 + np.arange(hi)          # position p lives in slot 8 + p
    ctx_pos[0, :hi], ctx_mask[0, :hi] = np.arange(hi), True
    q_pos = np.arange(lo, hi)[None]
    out = np.asarray(cached_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(ctx), jnp.asarray(ctx_pos), jnp.asarray(ctx_mask),
        jnp.asarray(q_pos), block=BLOCK))
    seen = np.arange(hi)[None, :] // BLOCK <= q_pos[0][:, None] // BLOCK
    want = _plain(q[0], pool_k[8:8 + hi], pool_v[8:8 + hi], seen)
    np.testing.assert_allclose(out[0], want, rtol=2e-5, atol=2e-5)
    causal = np.asarray(cached_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(ctx), jnp.asarray(ctx_pos), jnp.asarray(ctx_mask),
        jnp.asarray(q_pos)))
    assert np.abs(causal[0] - want).max() > 1e-3   # the mask is another


def test_whole_sequence_route_masks_by_blocks():
    rng = np.random.RandomState(2)
    s = 24
    q = rng.normal(size=(1, s, H, D)).astype(np.float32)
    k = rng.normal(size=(1, s, HKV, D)).astype(np.float32)
    v = rng.normal(size=(1, s, HKV, D)).astype(np.float32)
    at = np.arange(s)
    want = _plain(q[0], k[0], v[0], at[None, :] // BLOCK <= at[:, None] // BLOCK)
    for route in (default_attention, dense_attention):
        out = np.asarray(route(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), block=BLOCK))
        np.testing.assert_allclose(out[0], want, rtol=2e-5, atol=2e-5)
