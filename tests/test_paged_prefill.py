"""ops/paged_prefill.py (interpreted) against `llama.cached_attention`,
the XLA form it takes the place of, on the same paged pools and the same
`ctx`: passes built as `LLMEngine._dispatch_prefill` builds them."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import cached_attention
from ray_tpu.ops import paged_prefill as pp

PAGE = 16


def _engine_pass(rng, lanes, chunk, heads, stored, d, tables, pages, cols,
                 behind=None, poison=False):
    """`lanes` = (lo, hi) a lane ((0, 0): an empty one), `tables` its
    pages: `ctx` the slot of positions 0..hi-1 in order, `ctx_mask` those
    columns, `q_pos` lo..hi-1 then zeros (padded queries); the columns
    behind `hi` name `behind` (default: slot 0, as the engine's).  The
    pools hold numbers in every row — with `poison`, NaN in every page
    but page 0 and those a lane uses, whose rows behind `hi` hold what an
    earlier owner left, large and finite."""
    shape = (pages * PAGE, stored, d)
    pool_k, pool_v = rng.randn(*shape), rng.randn(*shape)
    if poison:
        pool_k[PAGE:], pool_v[PAGE:] = np.nan, np.nan
    q = rng.randn(len(lanes), chunk, heads, d).astype(np.float32)
    ctx = np.zeros((len(lanes), cols), np.int32)
    mask = np.zeros((len(lanes), cols), bool)
    q_pos = np.zeros((len(lanes), chunk), np.int32)
    for b, ((lo, hi), table) in enumerate(zip(lanes, tables)):
        table = np.asarray(table)
        for page in table[:-(-hi // PAGE)]:
            rows = slice(page * PAGE, (page + 1) * PAGE)
            pool_k[rows] = 50 * rng.randn(PAGE, stored, d)
            pool_v[rows] = 50 * rng.randn(PAGE, stored, d)
        slots = np.repeat(table * PAGE, PAGE) + np.tile(np.arange(PAGE),
                                                        len(table))
        pool_k[slots[:hi]] = rng.randn(hi, stored, d)
        pool_v[slots[:hi]] = rng.randn(hi, stored, d)
        if behind is not None:
            ctx[b] = behind
        ctx[b, :hi] = slots[:hi]
        mask[b, :hi] = True
        q_pos[b, :hi - lo] = np.arange(lo, hi)
    pos = np.broadcast_to(np.arange(cols, dtype=np.int32), ctx.shape)
    return (q, pool_k.astype(np.float32), pool_v.astype(np.float32), ctx,
            pos, mask, q_pos)


def _check(lanes, q, pool_k, pool_v, ctx, pos, mask, q_pos, kv_heads, dtype,
           tol):
    """The kernel against the XLA form over the pools' first `kv_heads`
    heads; an empty lane reads zeros (the XLA form's mean of nothing is
    not a number anybody reads)."""
    q, pool_k, pool_v = (jnp.asarray(t, dtype) for t in (q, pool_k, pool_v))
    got = pp.paged_prefill_attention(q, pool_k, pool_v, ctx, mask, q_pos,
                                     page_size=PAGE, kv_heads=kv_heads)
    assert got.shape == q.shape and got.dtype == dtype
    clean = jnp.nan_to_num(pool_k[:, :kv_heads]), jnp.nan_to_num(
        pool_v[:, :kv_heads])
    want = cached_attention(q, *clean, ctx, pos, mask, q_pos)
    got, want = (np.asarray(t.astype(jnp.float32)) for t in (got, want))
    for b, (_lo, hi) in enumerate(lanes):
        if hi == 0:
            assert not got[b].any()
            continue
        np.testing.assert_allclose(got[b], want[b], atol=tol, rtol=tol)
    return got


# (lo, hi) a lane and its pages; then the chunk, (query heads, KV heads,
# heads a cache row stores, head width), the columns of `ctx`, and the
# keys of a block under test (the kernel's own at the larger shapes)
_CASES = {
    # chunk 64, a query head a KV head, a narrow row: one context ends
    # inside a page in the first block beside one that walks three
    "chunk64_group1": ([(5, 69), (200, 264)],
                       [list(range(3, 8)), list(range(10, 27))],
                       64, (4, 4, 4, 32), 512, 96),
    # chunk 256, four query heads a KV head: a lane that crosses two
    # blocks' edges beside a last chunk of 100 tokens, 156 padded queries
    "chunk256_group4": ([(256, 512), (0, 100)],
                        [list(range(1, 33)), list(range(40, 47))],
                        256, (8, 2, 2, 32), 512, 128),
    # the hybrid cell's row: 30 heads in a row of 32 x 128, group 1, the
    # kernel's own blocks of 512 keys
    "row_of_32_heads": ([(236, 300), (0, 64)],
                        [list(range(1, 20)), list(range(20, 24))],
                        64, (30, 30, 32, 128), 1024, 512),
    # group 4 over a row of 8 heads x 128 (the dense family's row)
    "row_of_8_heads_group4": ([(100, 164), (17, 81)],
                              [list(range(1, 12)), list(range(12, 18))],
                              64, (32, 8, 8, 128), 512, 256),
    # an empty lane between two live ones: its table names a NaN page
    "empty_lane": ([(0, 64), (0, 0), (64, 128)],
                   [[2, 3, 4, 5], [11], list(range(12, 20))],
                   64, (4, 4, 4, 32), 256, 64),
    # a length that is a multiple neither of the page nor of the block,
    # the causal edge crossing a block's edge
    "ends_inside_a_page": ([(13, 77), (110, 141)],
                           [list(range(1, 6)), list(range(6, 15))],
                           64, (4, 2, 2, 32), 256, 64),
    # two prompts share their first three pages; the second's own pages
    # lie below them: a table is in no order, positions are
    "shared_prefix": ([(48, 112), (48, 100)],
                      [[20, 21, 22, 3, 4, 5, 6], [20, 21, 22, 2, 1, 9, 8]],
                      64, (4, 4, 8, 32), 256, 64),
    # a table of 128 pages of which the lanes use 5 and 2
    "wide_table": ([(6, 70), (0, 20)], [list(range(1, 6)), [7, 8]],
                   64, (4, 4, 4, 32), 2048, 256),
    # the wide pass, 8 x 64: a lane whose only rows are its chunk's own,
    # a last chunk of 9 tokens, an empty lane, contexts of one block and
    # of five, every table drawn from one shuffled pool (scattered, in no
    # order)
    "eight_lanes_scattered": (
        [(0, 64), (64, 128), (311, 375), (40, 49), (0, 0), (130, 194),
         (0, 3), (17, 81)], "shuffled", 64, (4, 4, 4, 32), 512, 64),
    # the widest bucket, 16,384 columns, at toy head counts and the
    # kernel's own 512 keys: 4,900 rows (ten blocks, the last ends inside
    # a page) beside a lane of 40 — what the lanes hold, not the bucket
    "bucket_16384_unequal": ([(4836, 4900), (0, 40)], "shuffled", 64,
                             (2, 2, 2, 32), 16384, 512),
    # a lane's KV heads in tiles of 2 (grid lanes x 3): each tile's heads
    # against its own rows of the head-major block
    "head_tiles": ([(30, 94), (0, 7)], [list(range(1, 7)), [9]],
                   64, (12, 6, 8, 32), 256, 64),
}


# every case in float32, where the two forms differ by the order of their
# sums; the stated precision (bfloat16 operands, float32 sums) at the two
# rows of 128-wide heads, the chunk of 256 and the shared pages
_BF16 = ("chunk256_group4", "row_of_32_heads", "row_of_8_heads_group4",
         "shared_prefix")


@pytest.mark.parametrize("dtype,tol,case", [
    *((jnp.float32, 2e-5, case) for case in sorted(_CASES)),
    *((jnp.bfloat16, 2e-2, case) for case in _BF16)],
    ids=lambda v: v if isinstance(v, str) else getattr(v, "__name__", ""))
def test_prefill_kernel_is_cached_attention(monkeypatch, dtype, tol, case):
    """Every query's softmax over the rows at positions up to its own,
    whatever the lane's table, length, chunk and group; NaN stands in
    every page no lane uses and is never read."""
    lanes, tables, chunk, (heads, kv, stored, d), cols, keys = _CASES[case]
    monkeypatch.setattr(pp, "_BLOCK_KEYS", keys)
    if case == "head_tiles":
        monkeypatch.setattr(pp, "_QUERY_ROWS", 2 * chunk * heads // kv)
        assert pp._tile_heads(kv, chunk * heads // kv) == 2
    rng, pages = np.random.RandomState(3), 48
    if tables == "shuffled":
        need = [-(-hi // PAGE) for _lo, hi in lanes]
        pages = sum(need) + 9
        free = list(rng.permutation(np.arange(1, pages)))
        tables = [[free.pop() for _ in range(max(1, n))] for n in need]
    passed = _engine_pass(rng, lanes, chunk, heads, stored, d, tables,
                          pages=pages, cols=cols, poison=True)
    _check(lanes, *passed, kv, dtype, tol)


def test_pages_behind_the_last_visible_block_are_never_read(monkeypatch):
    """A NaN planted in the pool behind the columns of a block a lane
    does not reach is never read by it (the block is not walked, not
    masked): the second lane's context ends in the first block, so its
    second, which the first lane walks, is not its to walk — and a
    lane whose chunk lies BELOW its context's end (its queries see less
    than the mask allows) stops at what they see."""
    monkeypatch.setattr(pp, "_BLOCK_KEYS", 32)
    rng = np.random.RandomState(2)
    lanes = [(32, 40), (5, 13)]
    q, pool_k, pool_v, ctx, pos, mask, q_pos = _engine_pass(
        rng, lanes, 8, 4, 4, 32, [[1, 2, 3], [5]], pages=13, cols=128,
        behind=12 * PAGE, poison=True)
    assert np.isnan(pool_k[12 * PAGE:]).all()
    got = _check(lanes, q, pool_k, pool_v, ctx, pos, mask, q_pos, 4,
                 jnp.float32, 2e-5)
    assert np.isfinite(got).all()
    # the first lane's mask allows 96 columns, two NaN pages behind its
    # 40 rows among them; its queries end at 39
    ctx[0, 40:96] = np.repeat([10, 11, 10, 11], PAGE)[:56] * PAGE \
        + np.tile(np.arange(PAGE), 4)[:56]
    mask[0, :96] = True
    out = pp.paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v), ctx, mask,
        q_pos, page_size=PAGE)
    np.testing.assert_allclose(np.asarray(out), got, atol=2e-5, rtol=2e-5)
    # a pass with nothing valid walks nothing and reads zeros
    out = pp.paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v), ctx,
        np.zeros_like(mask), q_pos, page_size=PAGE)
    assert not np.asarray(out).any()


def test_tiles_at_the_shapes_the_families_ask_for():
    """Heads a grid step and pages a block from the static shapes: the
    hybrid cell's three passes hold every head in one step and walk 512
    keys a block at every bucket; a row four times as wide walks fewer."""
    row = jnp.zeros((PAGE, 32, 128), jnp.bfloat16)
    for chunk in (64, 256):
        assert pp._tile_heads(30, chunk) == 30
    for cols in (1024, 4096, 16384):
        assert pp._block_pages(row, cols // PAGE, PAGE) == 512 // PAGE
    assert pp._block_pages(row, 8, PAGE) == 8
    assert pp._block_pages(jnp.zeros((PAGE, 128, 128), jnp.float32), 1024,
                           PAGE) == 5
    # the dense family's deep pass: 8 KV heads x (4 x 256) query rows
    assert pp._tile_heads(8, 4 * 256) == 8
    assert pp._tile_heads(32, 512) == 16
    assert pp._tile_heads(30, 1024) == 6
