"""ops/sparse_index.py and the selection inside the latent kernels: the
indexer's scores, the exact selection of the `top_k` largest (a tie to
the lower position), and the two sparse forms of the attention — the
chunk kernel that masks what was not selected, and gather-then-decode —
against plain attention over the same set of rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_attention as la
from ray_tpu.ops import sparse_index as si

PAGE = 16


def _top_k_mask(scores, k):
    """The oracle: `lax.top_k` puts equal values lower index first."""
    at = np.asarray(jax.lax.top_k(jnp.asarray(scores), k)[1])
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, at, True, axis=-1)
    return mask


@pytest.mark.parametrize("k", [1, 7, 32, 100])
def test_threshold_selection_is_top_k_with_ties_to_the_lower_position(k):
    rng = np.random.RandomState(k)
    scores = rng.randn(6, 100).astype(np.float32)
    # a constructed tie that straddles the cut: row 0's k-th largest
    # value five times over, at scattered positions
    order = np.argsort(-scores[0])
    scores[0, rng.choice(100, 5, replace=False)] = scores[0, order[k - 1]]
    # many equal values (a ReLU's zeros)
    scores[1, ::3] = 0.0
    # a row that sees fewer than k positions, and one that sees none
    scores[2, 5:] = -np.inf
    scores[3, :] = -np.inf
    # every value equal
    scores[4, :] = 1.5
    # -0.0 is +0.0 to a comparison (a sort would put it lower; the score
    # kernel hands out one zero, so `top_k` never meets the other)
    scores[5, 2::4] = 0.0
    both = scores.copy()
    both[5, 2::8] = -0.0
    np.testing.assert_array_equal(
        np.asarray(si.selected(jnp.asarray(scores),
                               *si.select_threshold(jnp.asarray(both), k))),
        np.asarray(si.selected(jnp.asarray(scores),
                               *si.select_threshold(jnp.asarray(scores),
                                                    k))))
    thr, tie = si.select_threshold(jnp.asarray(scores), k)
    got = np.asarray(si.selected(jnp.asarray(scores), thr, tie))
    seen = scores > -np.inf
    want = _top_k_mask(scores, k)
    np.testing.assert_array_equal(got & seen, want & seen)
    assert (got & seen).sum(-1).tolist() == [
        min(k, int(n)) for n in seen.sum(-1)]
    # a row that sees no more than k rows takes all of them
    assert (got & seen)[2].sum() == min(k, 5)


def test_threshold_of_a_batch_of_queries_and_a_width_below_k():
    rng = np.random.RandomState(0)
    scores = jnp.asarray(rng.randn(2, 3, 64), jnp.float32)
    got = si.selected(scores, *si.select_threshold(scores, 16))
    np.testing.assert_array_equal(np.asarray(got),
                                  _top_k_mask(np.asarray(scores), 16))
    # k past the width: everything
    assert bool(si.selected(scores, *si.select_threshold(scores, 500)).all())
    rows = np.asarray(si.select_rows(scores[0], 16))
    np.testing.assert_array_equal(
        rows, np.asarray(jax.lax.top_k(scores[0], 16)[1]))


def _pool_case(rng, lanes, lens, pages, width, dtype=jnp.float32):
    """A pool of `width`-wide rows, each lane's pages scattered over it,
    and the rows of each lane in order."""
    total = 1 + lanes * pages
    pool = rng.randn(total * PAGE, width).astype(np.float32)
    perm = 1 + rng.permutation(lanes * pages).reshape(lanes, pages)
    table = perm.astype(np.int32)
    slots = (table[:, :, None] * PAGE + np.arange(PAGE)).reshape(lanes, -1)
    return jnp.asarray(pool, dtype), table, slots


@pytest.mark.parametrize("chunk", [1, 8])
def test_index_scores_kernel_is_the_plain_sum(chunk):
    rng = np.random.RandomState(1)
    lanes, heads, dim, pages = 3, 4, 16, 6
    lens = np.asarray([70, 0, 96])
    pool, table, slots = _pool_case(rng, lanes, lens, pages, dim)
    q = jnp.asarray(rng.randn(lanes, chunk, heads, dim), jnp.float32)
    w = jnp.asarray(rng.randn(lanes, chunk, heads), jnp.float32)
    q_pos = np.maximum(lens[:, None] - chunk + np.arange(chunk), 0)
    got = np.asarray(si.index_scores(
        q, w, pool, jnp.asarray(table), jnp.asarray(lens),
        jnp.asarray(q_pos), page_size=PAGE))
    keys = np.asarray(pool)[slots]                       # [B, L, d]
    dots = np.einsum("bsjd,bld->bsjl", np.asarray(q), keys)
    want = (np.maximum(dots, 0) * np.asarray(w)[..., None]).sum(2)
    pos = np.arange(pages * PAGE)
    seen = (pos[None, None] <= q_pos[..., None]) \
        & (pos[None, None] < lens[:, None, None])
    assert got.shape == (lanes, chunk, pages * PAGE)
    assert np.all(got[~seen] == -np.inf)
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-5, atol=1e-5)


def _gathered_scores(q, w, pool, table, lens, q_pos):
    """The parent's form (PR 42): a lane's index rows gathered by page
    into a copy, `[pages, page_size x d]` rows, and the same sum."""
    b, s, j, d = q.shape
    rows = pool.reshape(-1, PAGE * d)[table].reshape(b, -1, d)
    dots = jnp.einsum("bsjd,bld->bjsl", q, rows,
                      preferred_element_type=jnp.float32)
    total = jnp.sum(jnp.maximum(dots, 0.0)
                    * w.transpose(0, 2, 1)[..., None], axis=1)
    total = jnp.where(total == 0.0, 0.0, total)
    pos = jnp.arange(rows.shape[1])
    seen = (pos <= q_pos[..., None]) & (pos < lens[:, None, None])
    return jnp.where(seen, total, -jnp.inf)


def _small_integers(rng, *shape):
    """Whole numbers of -3..3: their products and sums are exact in
    float32 in whatever order a backend takes them, so two forms that
    read the same rows agree to the bit."""
    return rng.randint(-3, 4, shape).astype(np.float32)


TABLES = ["falling", "shuffled", "shared_page", "empty_lane", "mid_block",
          "dead_zero", "dead_garbage", "q_behind"]


def _table_case(case, chunk, rng, heads=4):
    """Three lanes over a table two blocks of the kernel's walk wide:
    (pool, table, lens, q_pos) with the pages of `case`.  By default a
    lane's pages are shuffled, its dead entries 0, its queries the last
    `chunk` positions of its length; lane 0 ends mid-page in the second
    block, lane 1 four pages and three rows past its chunk, lane 2 a row
    short of the first block's end."""
    lanes, dim = 3, 32
    step = si._score_block_pages(chunk, 1 << 20, PAGE, heads)
    # (two blocks, or where a chunk is longer than a block — the deep
    # pass's 256 queries over 128 keys a step — as many as hold it twice)
    pages = 2 * step * max(1, -(-(chunk + 5 * PAGE) // (step * PAGE)))
    block = pages * PAGE // 2
    short = chunk + 4 * PAGE + 3
    lens = np.asarray([block + 5 * PAGE + 7, short, block - 1])
    if case == "empty_lane":
        lens[1] = 0
    if case in ("dead_zero", "dead_garbage"):
        # a table twice as wide as its live part
        lens = np.asarray([block - 9, short, max(block // 2 + 3, chunk + 1)])
    poison = 1 + lanes * pages + np.arange(8)
    pool = _small_integers(
        rng, (1 + lanes * pages + len(poison)) * PAGE, dim)
    used = -(-lens // PAGE)
    table = np.zeros((lanes, pages), np.int32)
    for b in range(lanes):
        own = 1 + b * pages + np.arange(used[b])
        table[b, :used[b]] = own[::-1] if case == "falling" \
            else rng.permutation(own)
    if case == "shared_page":
        # a shared prefix: lane 1's first two pages are lane 0's
        table[1, :2] = table[0, :2]
    if case == "dead_garbage":
        # in range and never read: those pages hold NaN
        pool[(poison[:, None] * PAGE + np.arange(PAGE)).reshape(-1)] = np.nan
        for b in range(lanes):
            table[b, used[b]:] = rng.choice(poison, pages - used[b])
    behind = 40 if case == "q_behind" else 0
    q_pos = np.maximum(
        lens[:, None] - behind - chunk + np.arange(chunk), 0)
    return (jnp.asarray(pool), jnp.asarray(table), jnp.asarray(lens),
            jnp.asarray(q_pos, jnp.int32))


@pytest.mark.parametrize("case", TABLES)
@pytest.mark.parametrize("chunk,heads", [(1, 4), (64, 4), (256, 32)],
                         ids=["1", "64", "deep"])
def test_in_place_kernel_is_the_gathered_form_bit_for_bit(chunk, heads,
                                                          case):
    """The kernel that walks the table against the parent's gathered
    copy (bit for bit: a key's score does not depend on the block that
    held it) and against `plain_scores` over the lane's rows in order —
    a decode lane's one query, the wide pass's 64, and the deep pass's
    256 under 32 heads, whose step is 128 keys and not 512."""
    rng = np.random.RandomState(TABLES.index(case))
    pool, table, lens, q_pos = _table_case(case, chunk, rng, heads)
    lanes, width = table.shape[0], table.shape[1] * PAGE
    q = jnp.asarray(_small_integers(rng, lanes, chunk, heads, pool.shape[1]))
    w = jnp.asarray(_small_integers(rng, lanes, chunk, heads) / 8)
    got = np.asarray(si.index_scores(q, w, pool, table, lens, q_pos,
                                     page_size=PAGE))
    assert got.shape == (lanes, chunk, width) and not np.isnan(got).any()
    np.testing.assert_array_equal(
        got, np.asarray(_gathered_scores(q, w, pool, table, lens, q_pos)))
    pos = np.arange(width)
    seen = (pos <= np.asarray(q_pos)[..., None]) \
        & (pos < np.asarray(lens)[:, None, None])
    assert np.all(got[~seen] == -np.inf) and np.all(got[seen] > -np.inf)
    # the plain form: a lane's rows in order as one sequence, the
    # chunk's queries at their own positions of it
    reach = int(np.asarray(lens).max())
    slots = (np.asarray(table)[:, :, None] * PAGE
             + np.arange(PAGE)).reshape(lanes, -1)[:, :reach]
    for b in range(lanes):
        if not int(lens[b]):
            continue
        at = np.asarray(q_pos)[b]
        full_q = jnp.zeros((1, reach, heads, pool.shape[1]), jnp.float32
                           ).at[0, at].set(q[b])
        full_w = jnp.zeros((1, reach, heads), jnp.float32).at[0, at].set(w[b])
        want = np.asarray(si.plain_scores(
            full_q, full_w, jnp.nan_to_num(pool)[slots[b]][None]))[0, at]
        mine = seen[b][:, :reach].nonzero()
        np.testing.assert_array_equal(got[b][mine], want[mine])


def test_two_lanes_of_256_score_as_eight_lanes_of_64_do():
    """The deep prefill pass's call against the wide pass's on the SAME
    rows: two sequences' 256 queries each as `[2, 256]` (128 keys a
    step) and as `[8, 64]` (four lanes a sequence under one table, 512
    keys a step) — every score equal to the last bit, random float32
    operands: a key's score is one product over the key's width and one
    sum over heads whatever block held the key and whatever lane the
    query — and both the plain sum."""
    rng = np.random.RandomState(47)
    heads, dim, far, chunk = 32, 32, 256, 64
    assert si._score_block_pages(far, 1 << 20, PAGE, heads) * PAGE == 128
    assert si._score_block_pages(chunk, 1 << 20, PAGE, heads) * PAGE == 512
    pages = 64                                    # 1,024 positions a lane
    lens = np.asarray([300 + far, 13 + far])      # the chunks end there
    pool, table, slots = _pool_case(rng, 2, lens, pages, dim)
    q = jnp.asarray(rng.randn(2, far, heads, dim), jnp.float32)
    w = jnp.asarray(rng.randn(2, far, heads), jnp.float32)
    q_pos = lens[:, None] - far + np.arange(far)
    deep = np.asarray(si.index_scores(
        q, w, pool, jnp.asarray(table), jnp.asarray(lens),
        jnp.asarray(q_pos), page_size=PAGE))
    split = far // chunk
    wide_pos = q_pos.reshape(2 * split, chunk)
    wide = np.asarray(si.index_scores(
        q.reshape(2 * split, chunk, heads, dim),
        w.reshape(2 * split, chunk, heads), pool,
        jnp.asarray(np.repeat(table, split, axis=0)),
        jnp.asarray(wide_pos[:, -1] + 1), jnp.asarray(wide_pos),
        page_size=PAGE))
    assert deep.shape == (2, far, pages * PAGE)
    np.testing.assert_array_equal(deep.reshape(wide.shape), wide)
    keys = np.asarray(pool)[slots]
    dots = np.einsum("bsjd,bld->bsjl", np.asarray(q), keys)
    want = (np.maximum(dots, 0) * np.asarray(w)[..., None]).sum(2)
    seen = np.arange(pages * PAGE) <= q_pos[..., None]
    assert np.all(deep[~seen] == -np.inf)
    np.testing.assert_allclose(deep[seen], want[seen], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("heads", [4, 32, 64])
def test_a_score_steps_products_stay_within_the_kernels_vmem(heads):
    """`_score_block_pages` x heads x chunk float32 products for every
    chunk a pass may carry, 1 to 1,024 queries a lane: within the VMEM
    the call asks for, a whole number of pages that divides the table;
    a decode lane's step and the wide pass's are what they were."""
    for page in (8, 16, 64):
        for chunk in [1, 2, 3, 16, 63, 64, 65, 128, 255, 256, 512, 1000,
                      1024]:
            for table in (1, 4, 48, 64, 2048):
                pages = si._score_block_pages(chunk, table, page, heads)
                assert 1 <= pages <= table and table % pages == 0
                keys = pages * page
                assert heads * chunk * keys * 4 <= si._SCORE_VMEM_BYTES
                if chunk == 1:
                    assert pages == min(
                        table, si._SCORE_BLOCK_KEYS_ONE_QUERY // page) \
                        or table % (si._SCORE_BLOCK_KEYS_ONE_QUERY // page)
                elif heads * chunk <= 32 * 64 and table == 2048:
                    assert keys == si._SCORE_BLOCK_KEYS
    assert si._score_block_pages(256, 2048, 16, 32) * 16 == 128
    assert si._score_block_pages(64, 2048, 16, 32) * 16 == 512
    assert si._score_block_pages(1, 2048, 16, 32) * 16 == 4096


def test_pages_read_are_those_a_lanes_queries_see():
    lens = jnp.asarray([0, 1, 16, 17, 100, 100])
    q_pos = jnp.asarray([[0, 0], [0, 0], [14, 15], [15, 16], [98, 99],
                         [30, 31]])
    assert np.asarray(si.pages_read(lens, q_pos, PAGE)).tolist() \
        == [0, 1, 1, 2, 7, 2]


def _plain(q, rows, mask, value_width, scale):
    """softmax over the masked rows of q [S, H, W] . rows [L, W]."""
    s = np.einsum("shw,lw->shl", q, rows) * scale
    s = np.where(mask[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("shl,lv->shv", p, rows[:, :value_width])


def test_masked_chunk_kernel_is_plain_attention_over_the_selected_rows():
    rng = np.random.RandomState(2)
    lanes, chunk, heads, width, vw, pages, k = 2, 8, 4, 128, 32, 40, 48
    lens = np.asarray([600, 130])
    pool, table, slots = _pool_case(rng, lanes, lens, pages, width)
    q = rng.randn(lanes, chunk, heads, width).astype(np.float32) * 0.3
    q_pos = lens[:, None] - chunk + np.arange(chunk)
    marks = rng.randn(lanes, chunk, pages * PAGE).astype(np.float32)
    marks[0, :, 7] = marks[0, :, 300] = marks[0, :, 301] = 0.25   # ties
    pos = np.arange(pages * PAGE)
    seen = (pos[None, None] <= q_pos[..., None]) \
        & (pos[None, None] < lens[:, None, None])
    marks = np.where(seen, marks, -np.inf)
    thr, tie = si.select_threshold(jnp.asarray(marks), k)
    ctx = np.where(pos[None] < lens[:, None], slots, 0)
    got = np.asarray(la.latent_chunk_attention(
        jnp.asarray(q), pool, jnp.asarray(ctx), None,
        jnp.asarray(pos[None] < lens[:, None]), jnp.asarray(q_pos),
        page_size=PAGE, value_width=vw, scale=0.1,
        select=(jnp.asarray(marks), thr, tie)))
    chosen = _top_k_mask(marks, k) & seen
    assert chosen.sum(-1).tolist() == [[k] * chunk] * lanes
    for b in range(lanes):
        want = _plain(q[b], np.asarray(pool)[slots[b]], chosen[b], vw, 0.1)
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-5)
    # without a selection the same call reads every visible row
    dense = np.asarray(la.latent_chunk_attention(
        jnp.asarray(q), pool, jnp.asarray(ctx), None,
        jnp.asarray(pos[None] < lens[:, None]), jnp.asarray(q_pos),
        page_size=PAGE, value_width=vw, scale=0.1))
    want = _plain(q[0], np.asarray(pool)[slots[0]], seen[0], vw, 0.1)
    np.testing.assert_allclose(dense[0], want, rtol=2e-4, atol=2e-5)
    assert np.abs(dense - got).max() > 1e-3


def test_gather_then_decode_is_plain_attention_over_the_selected_rows():
    rng = np.random.RandomState(3)
    lanes, heads, width, vw, pages, k = 3, 4, 128, 32, 12, 32
    lens = np.asarray([150, 20, 0])
    pool, table, slots = _pool_case(rng, lanes, lens, pages, width)
    q = rng.randn(lanes, 1, heads, width).astype(np.float32) * 0.3
    pos = np.arange(pages * PAGE)
    marks = np.where(pos[None] < lens[:, None],
                     rng.randn(lanes, pages * PAGE), -np.inf
                     ).astype(np.float32)
    at = si.select_rows(jnp.asarray(marks), k)
    rows = si.gather_rows(pool, jnp.asarray(table), at, page_size=PAGE)
    assert rows.shape == (lanes * k, width)
    got = np.asarray(la.latent_paged_attention(
        jnp.asarray(q), rows,
        jnp.arange(lanes * k // PAGE, dtype=jnp.int32).reshape(lanes, -1),
        jnp.minimum(jnp.asarray(lens), k), page_size=PAGE, value_width=vw,
        scale=0.1))
    chosen = _top_k_mask(marks, k) & (marks > -np.inf)
    assert chosen.sum(-1).tolist() == [k, 20, 0]
    for b in range(2):
        want = _plain(q[b], np.asarray(pool)[slots[b]], chosen[b][None],
                      vw, 0.1)
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-5)
    assert not got[2].any()          # an empty lane: zeros
