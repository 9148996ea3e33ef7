"""ops/sparse_decode.py: a decode lane's attention over the pages it
holds under the selection's mask, against plain float32 softmax over the
`top_k` rows and against the form it takes the place of (`select_rows`,
`gather_rows`, `latent_paged_attention` over the copy) — and the
three-way branch of `models/pangu.LatentAttention._selected` that picks
between them by the table's width."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_attention as la
from ray_tpu.ops import sparse_decode as sd
from ray_tpu.ops import sparse_index as si
from test_sparse_index_ops import _top_k_mask

PAGE, HEADS, WIDTH, VW, K = 16, 4, 128, 32, 48
KERNEL = dict(page_size=PAGE, value_width=VW, scale=0.1)

# name -> (pages of the table, the lanes' lengths, the pool's dtype).  A
# table of 40 pages is two blocks of the walk (the second a quarter
# full), one of 72 three.
CASES = {
    "longer_than_k": (40, [600, 130, 49], jnp.float32),
    "exactly_k": (40, [K, K, 2 * K], jnp.float32),
    "shorter_in_a_wide_table": (72, [K - 1, 20, 1], jnp.float32),
    "empty_lane": (40, [0, 333, 0], jnp.float32),
    "ends_inside_a_page": (40, [16 * 33 + 7, 16 * 32 + 1, 511], jnp.float32),
    "equal_scores_across_the_cut": (40, [600, 200, 64], jnp.float32),
    "shuffled_with_a_shared_prefix": (40, [600, 580, 100], jnp.float32),
    "bfloat16_pool": (72, [1100, 130, 0], jnp.bfloat16),
    "float32_pool": (72, [1100, 130, 0], jnp.float32),
}


def _case(name):
    """(q, pool, table, lens, marks, the rows of each lane in order): a
    lane's pages scattered over the pool, its dead table entries on pages
    of NaN that nothing may read."""
    pages, lens, dtype = CASES[name]
    rng = np.random.RandomState(sorted(CASES).index(name))
    lens = np.asarray(lens)
    lanes, poison = len(lens), 4
    total = 1 + lanes * pages + poison
    pool = rng.randn(total * PAGE, WIDTH).astype(np.float32)
    pool[(1 + lanes * pages) * PAGE:] = np.nan
    table = 1 + rng.permutation(lanes * pages).reshape(lanes, pages)
    if name == "shuffled_with_a_shared_prefix":
        table[1, :3] = table[0, :3]
    used = -(-lens // PAGE)
    for b in range(lanes):
        table[b, used[b]:] = 1 + lanes * pages + rng.randint(
            poison, size=pages - used[b])
    pos = np.arange(pages * PAGE)
    marks = rng.randn(lanes, pages * PAGE).astype(np.float32)
    if name == "equal_scores_across_the_cut":
        # a ReLU's zeros, more of them than the cut leaves room for; the
        # k-th largest value of lane 1 five times over; every score equal
        marks[0] = np.maximum(marks[0], 0.0) * (pos % 3 == 0)
        order = np.argsort(-marks[1, :lens[1]])
        marks[1, rng.choice(lens[1], 5, replace=False)] \
            = marks[1, order[K - 1]]
        marks[2] = 1.5
    marks = np.where(pos[None] < lens[:, None], marks, -np.inf)
    q = rng.randn(lanes, 1, HEADS, WIDTH).astype(np.float32) * 0.3
    slots = (table[:, :, None] * PAGE + np.arange(PAGE)).reshape(lanes, -1)
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32),
            jnp.asarray(marks, jnp.float32), slots)


def _plain(q, rows, mask):
    """float32 softmax over the masked rows of q [H, W] . rows [L, W]."""
    s = np.einsum("hw,lw->hl", q, rows) * KERNEL["scale"]
    s = np.where(mask[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hl,lv->hv", p / p.sum(-1, keepdims=True),
                     rows[:, :VW])


@pytest.mark.parametrize("name", list(CASES))
def test_masked_walk_is_plain_attention_over_the_top_k_rows(name):
    q, pool, table, lens, marks, slots = _case(name)
    lanes = len(lens)
    thr, tie = si.select_threshold(marks, K)
    got = np.asarray(sd.latent_selected_attention(
        q, pool, table, lens, marks[:, None], thr, tie, **KERNEL
    ).astype(jnp.float32))
    assert got.shape == (lanes, 1, HEADS, VW) and np.isfinite(got).all()
    # what it takes the place of, on the same inputs
    at = si.select_rows(marks, K)
    rows = si.gather_rows(jnp.nan_to_num(pool), table, at, page_size=PAGE)
    gathered = np.asarray(la.latent_paged_attention(
        q, rows, jnp.arange(lanes * K // PAGE, dtype=jnp.int32
                            ).reshape(lanes, -1),
        jnp.minimum(lens, K), **KERNEL).astype(jnp.float32))
    # bfloat16 operands and probabilities: a rounding of 2^-8 a product
    tol = dict(rtol=2e-4, atol=2e-5) if pool.dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(got, gathered, **tol)
    seen = np.asarray(marks) > -np.inf
    chosen = _top_k_mask(np.asarray(marks), K) & seen
    assert chosen.sum(-1).tolist() == np.minimum(lens, K).tolist()
    # the pair stands for exactly those rows: the lower position wins a
    # tie, the count is `K`, a lane of no more than `K` sees all it holds
    np.testing.assert_array_equal(
        np.asarray(si.selected(marks, thr, tie)) & seen, chosen)
    full = np.asarray(jnp.nan_to_num(pool).astype(jnp.float32))
    for b in range(lanes):
        if not int(lens[b]):
            # an empty lane: zeros, and nothing of its table's NaN pages
            assert not got[b].any()
            continue
        want = _plain(np.asarray(q[b, 0].astype(jnp.float32)),
                      full[slots[b]], chosen[b])
        np.testing.assert_allclose(got[b, 0], want, **tol)


def test_the_lengths_mask_is_the_kernels_own():
    """A lane of no more than `K` rows has threshold -inf and a `tie`
    that lets positions it does not hold through; marks that say nothing
    of the length (finite past it) still read only the rows the lane
    holds."""
    q, pool, table, lens, marks, slots = _case("shorter_in_a_wide_table")
    thr, tie = si.select_threshold(marks, K)
    assert np.all(np.asarray(thr) == -np.inf)
    past = np.arange(marks.shape[1]) >= np.asarray(lens)[:, None]
    assert (np.asarray(si.selected(marks, thr, tie)) & past).any(-1).all()
    loud = jnp.where(marks > -jnp.inf, marks, 7.0)
    got = np.asarray(sd.latent_selected_attention(
        q, pool, table, lens, loud, thr, tie, **KERNEL))
    dense = np.asarray(la.latent_paged_attention(q, pool, table, lens,
                                                 **KERNEL))
    np.testing.assert_allclose(got, dense, rtol=2e-4, atol=2e-5)


def _decode_pass(pages, top_k):
    """`_selected`'s decode pass of two lanes over tables of `pages`
    pages (of 32 a lane in the pool): its traced text, and its output
    and counters."""
    from ray_tpu.models.cache import latent_row_width
    from ray_tpu.models.pangu import LatentAttention, PanguConfig

    cfg = dataclasses.replace(PanguConfig.tiny_sparse(), dtype=jnp.float32,
                              index_topk=top_k)
    layer = LatentAttention(cfg, PAGE)
    rng = np.random.RandomState(5)
    lanes, most = 2, 32
    slots = (1 + lanes * most) * PAGE
    lens = np.asarray([min(pages * PAGE, 70), 9], np.int32)
    first = 1 + np.arange(lanes) * most
    x = jnp.asarray(rng.randn(lanes, 1, cfg.hidden_size), jnp.float32)
    row = latent_row_width(cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    cache = {
        "latent": jnp.asarray(rng.randn(slots, row), jnp.float32),
        "index": jnp.asarray(rng.randn(slots, cfg.index_head_dim),
                             jnp.float32),
        "slots": jnp.asarray(first * PAGE + lens - 1)[:, None],
        "block_tables": jnp.asarray(first[:, None] + np.arange(pages),
                                    jnp.int32),
        "context_lens": jnp.asarray(lens)}
    positions = jnp.asarray(lens - 1)[:, None]
    wide = dict(cache, block_tables=jnp.asarray(
        first[:, None] + np.arange(most), jnp.int32))
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x, positions, wide)

    def call(p, x, c):
        return layer.apply(p, x, positions, c)
    out, _pools, counted = jax.jit(call)(params, x, cache)
    return (str(jax.make_jaxpr(call)(params, x, cache)), np.asarray(out),
            np.asarray(counted), lens)


def test_the_branch_follows_the_tables_width():
    """`_selected` at P x 16 <= k, P <= k and P > k: every row through
    the dense decode kernel; the lane's own pages under the mask; the
    sort, the gathered copy and the decode kernel over it — decided by
    the table's width, the page and `index_topk` alone, output and the
    six counters equal across the two sparse forms on one input."""
    import re

    def kernels(text):
        return set(re.findall(r"name=(latent_attention_\w+)", text))

    select, decode = "latent_attention_decode_select", \
        "latent_attention_decode"
    dense = _decode_pass(pages=2, top_k=32)[0]
    assert kernels(dense) == {decode}
    assert "sparse_index_scores" not in dense
    masked, out, counted, lens = _decode_pass(pages=16, top_k=16)
    assert kernels(masked) == {select}
    assert "sparse_index_scores" in masked
    assert "name=select_threshold" in masked
    assert "name=select_rows" not in masked and "gather_rows" not in masked
    copied, same, as_many, lens_too = _decode_pass(pages=32, top_k=16)
    assert kernels(copied) == {decode}
    assert "name=select_rows" in copied and "name=gather_rows" in copied
    assert "name=select_threshold" not in copied
    assert lens.tolist() == lens_too.tolist() == [70, 9]
    np.testing.assert_allclose(out, same, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(counted, as_many)
    assert counted.tolist() == [79, 79, 16 + 9, 16 + 9, 1, 5 + 1]
