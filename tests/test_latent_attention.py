"""ops/latent_attention.py against plain numpy: the decode kernel and
the prefill chunk's kernel, both interpreted."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_attention as la

PAGE = 16


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_latent_decode_kernel_against_plain_numpy(dtype, tol):
    """(iii) the kernel (interpreted) over ragged context lengths, a
    lane of 0 and a table wider than any lane uses: every head's
    softmax over the lane's rows, the value the row's first 32."""
    rng = np.random.RandomState(1)
    lanes, heads, width, value, pages = 5, 4, 128, 32, 40
    lens = np.asarray([0, 1, 37, 200, 16], np.int32)
    pool = rng.randn(pages * PAGE, width).astype(np.float32)
    q = rng.randn(lanes, 1, heads, width).astype(np.float32)
    table = np.zeros((lanes, 16), np.int32)
    free = list(rng.permutation(np.arange(1, pages)))
    for b, n in enumerate(lens):
        for p in range(-(-n // PAGE)):
            table[b, p] = free.pop()
    out = la.latent_paged_attention(
        jnp.asarray(q, dtype), jnp.asarray(pool, dtype), table, lens,
        page_size=PAGE, value_width=value, scale=0.1)
    assert out.shape == (lanes, 1, heads, value) and out.dtype == dtype
    out = np.asarray(out.astype(jnp.float32))
    assert not out[0].any()
    for b, n in enumerate(lens[1:], 1):
        slots = (table[b, np.arange(n) // PAGE] * PAGE
                 + np.arange(n) % PAGE)
        rows = np.asarray(jnp.asarray(pool[slots], dtype), np.float32)
        qb = np.asarray(jnp.asarray(q[b, 0], dtype), np.float32)
        s = qb @ rows.T * 0.1
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, :value]
        np.testing.assert_allclose(out[b, 0], want, atol=tol, rtol=tol)


def _engine_pass(rng, lanes, chunk, heads, width, tables, pages,
                 cols=128, behind=None):
    """A prefill pass as `LLMEngine._dispatch_prefill` builds it: `lanes`
    = (lo, hi) a lane ((0, 0): an empty one), `tables` its pages; `ctx`
    the slot of positions 0..hi-1 in order, `ctx_mask` those columns,
    `q_pos` lo..hi-1 then zeros (padded queries).  The pool holds
    numbers in the rows of a lane's used pages and NaN in every other
    page but page 0, the engine's garbage page; the columns behind `hi`
    name `behind` (default: slot 0, as the engine's)."""
    pool = np.full((pages * PAGE, width), np.nan, np.float32)
    pool[:PAGE] = rng.randn(PAGE, width)
    q = rng.randn(len(lanes), chunk, heads, width).astype(np.float32)
    ctx = np.zeros((len(lanes), cols), np.int32)
    mask = np.zeros((len(lanes), cols), bool)
    q_pos = np.zeros((len(lanes), chunk), np.int32)
    for b, ((lo, hi), table) in enumerate(zip(lanes, tables)):
        table = np.asarray(table)
        used = table[:-(-hi // PAGE)]
        for page in used:
            # whole pages: the rows of the last one behind `hi` hold
            # what an earlier owner left, large and finite
            pool[page * PAGE:(page + 1) * PAGE] = 50 * rng.randn(PAGE, width)
        slots = np.repeat(table * PAGE, PAGE) + np.tile(np.arange(PAGE),
                                                        len(table))
        pool[slots[:hi]] = rng.randn(hi, width)
        if behind is not None:
            ctx[b] = behind
        ctx[b, :hi] = slots[:hi]
        mask[b, :hi] = True
        q_pos[b, :hi - lo] = np.arange(lo, hi)
    pos = np.broadcast_to(np.arange(cols, dtype=np.int32), ctx.shape)
    return q, pool, ctx, pos, mask, q_pos


def _check_chunk(out, q, pool, ctx, q_pos, lanes, value, scale, dtype, tol):
    """`out` against one softmax a query over the rows at positions up
    to its own; an empty lane reads zeros."""
    def rounded(x):
        return np.asarray(jnp.asarray(x, dtype), np.float32)

    assert out.shape == q.shape[:3] + (value,) and out.dtype == dtype
    out = np.asarray(out.astype(jnp.float32))
    for b, (_lo, hi) in enumerate(lanes):
        if hi == 0:
            assert not out[b].any()
            continue
        rows = rounded(pool[ctx[b, :hi]])
        for i in range(q.shape[1]):
            seen = rows[:q_pos[b, i] + 1]
            s = rounded(q[b, i]) @ seen.T * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ seen[:, :value]
            np.testing.assert_allclose(out[b, i], want, atol=tol, rtol=tol)


# (lo, hi) a lane and its pages, at a chunk of 8 queries, 4 heads and
# blocks of 32 rows (2 pages) under query tiles of 2 heads
_CASES = {
    # one context ends in the first block beside one that walks four
    "ragged": ([(5, 13), (112, 120)], [[3], [9, 4, 5, 6, 7, 8, 2, 1]]),
    # an empty lane between two live ones: its table names a NaN page
    "empty_lane": ([(0, 8), (0, 0), (32, 40)], [[2], [11], [5, 6, 7]]),
    # a length that is a multiple neither of the page nor of the block
    "odd_length": ([(69, 77), (30, 37)], [[1, 2, 3, 4, 5], [6, 7, 8]]),
    # a chunk at lo > 0 whose causal edge crosses a block's edge
    "chunk_at_lo": ([(60, 68), (28, 36)], [[1, 2, 3, 4, 5], [6, 7, 8]]),
    # a last chunk of 3 and of 1 tokens: 5 and 7 padded queries
    "padded_queries": ([(40, 43), (0, 1)], [[1, 2, 3], [4]]),
    # two prompts share their first two pages; the second's own pages
    # lie below them: a table is in no order, positions are
    "shared_prefix": ([(40, 48), (32, 40)], [[9, 10, 3], [9, 10, 2]]),
    # the narrow pass: `PREFILL_NARROW_LANES` = 2 lanes, one prompt
    "narrow_two_lanes": ([(16, 24), (0, 0)], [[7, 8], [12]]),
    # a table of 7 pages under blocks of 2: the last block holds one
    "odd_table": ([(100, 108), (90, 97)],
                  [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14]], 112),
    # the deep pass: as many lanes of 4 x the chunk, a head a query tile
    # (the tile's rows are the same); a full lane that crosses two
    # blocks' edges beside a last chunk of 17 tokens, 15 padded queries
    "deep_two_lanes": ([(40, 72), (3, 20)], [[1, 2, 3, 4, 5], [6, 7]],
                       128, 32),
    # ... and alone at lo = 0: every query's context is the chunk's own
    "deep_first_chunk": ([(0, 32), (0, 0)], [[4, 9], [12]], 128, 32),
}


@pytest.mark.parametrize("case", sorted(_CASES))
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_latent_prefill_kernel_against_plain_numpy(monkeypatch, dtype,
                                                   tol, case):
    """The prefill chunk's kernel (interpreted) over passes built as the
    engine builds them: every query's softmax over the rows at positions
    up to its own, whatever the lane's table, length and chunk; NaN
    stands in every page no lane uses and is never read."""
    monkeypatch.setattr(la, "_PREFILL_BLOCK_ROWS", 32)
    monkeypatch.setattr(la, "_PREFILL_QUERY_ROWS", 16)
    lanes, tables, cols, chunk = (*_CASES[case], 128, 8)[:4]
    heads, width, value = 4, 128, 32
    assert la._prefill_tiles(heads, chunk, 128 // PAGE, PAGE) \
        == (max(1, 16 // chunk), 2)
    q, pool, ctx, pos, mask, q_pos = _engine_pass(
        np.random.RandomState(3), lanes, chunk, heads, width, tables,
        pages=16, cols=cols)
    out = la.latent_chunk_attention(
        jnp.asarray(q, dtype), jnp.asarray(pool, dtype), ctx, pos, mask,
        q_pos, page_size=PAGE, value_width=value, scale=0.1)
    _check_chunk(out, q, pool, ctx, q_pos, lanes, value, 0.1, dtype, tol)


def test_chunk_attention_walks_only_the_blocks_that_hold_context(
        monkeypatch):
    """The prefill kernel over a context wider than a block: equal to
    one softmax over the valid columns, lane by lane, and a NaN planted
    in the pool behind the columns of a block a lane does not reach is
    never read by it (the block is not computed, not masked): the second
    lane's context ends in the first block, so its second, which the
    first lane walks, is not its to walk."""
    monkeypatch.setattr(la, "_PREFILL_BLOCK_ROWS", 32)
    rng = np.random.RandomState(2)
    lanes = [(32, 40), (5, 13)]
    # the columns behind a lane's context name a NaN page
    q, pool, ctx, pos, mask, q_pos = _engine_pass(
        rng, lanes, 8, 3, 128, [[1, 2, 3], [5]], pages=13,
        behind=12 * PAGE)
    assert np.isnan(pool[12 * PAGE:]).all()
    out = la.latent_chunk_attention(
        jnp.asarray(q), jnp.asarray(pool), ctx, pos, mask, q_pos,
        page_size=PAGE, value_width=32, scale=0.1)
    _check_chunk(out, q, pool, ctx, q_pos, lanes, 32, 0.1, jnp.float32,
                 2e-5)
    # a lane with nothing valid walks nothing and reads zeros
    out = np.asarray(la.latent_chunk_attention(
        jnp.asarray(q), jnp.asarray(pool), ctx, pos, np.zeros_like(mask),
        q_pos, page_size=PAGE, value_width=32, scale=0.1))
    assert not out.any()
