"""The indexer of learned sparse attention: which cached rows a query's
attention reads (models/pangu.py, the `index_*` keys).

Beside its latent row a token keeps an INDEX KEY `k` of `d` numbers
(models/cache.py, the `"index"` part).  A query has J index heads `q_j`
and a weight a head `w_j`, and scores every row it can see:

    I_ts = sum_j w_tj ReLU(q_tj . k_s)         float32, bfloat16 operands

Its attention then runs over the `top_k` rows of largest I alone (all of
them while it sees no more than that); a tie goes to the lower position.

`index_scores`   the kernel `sparse_index_scores`: every query of a pass
    (a chunk's, or a decode lane's one) against the lane's pages of the
    index pool, a block of keys a grid step (the fewer, the more query
    rows the chunk has: `_score_block_pages`); what a query does not see
    (past its own position, past the lane's length) reads -inf.  The
    pool stays where it lies, `[T, d]` in HBM: the kernel walks the
    lane's block table itself as the attention kernels do
    (`latent_attention._page_copies`: a block's pages copied into one
    half of a double buffer while the other half is scored), and a
    block past the last position any of the lane's queries sees copies
    nothing.
`select_threshold`  a chunk's selection as a THRESHOLD a query, exact
    and without a sort: the `top_k`-th largest score, by bisection over
    the order-preserving integer image of float32 (32 counts), and the
    last position a score EQUAL to it is still taken at (a second
    bisection, over positions, run only where a tie straddles the
    cut).  The prefill kernel masks with the pair
    (ops/latent_attention.py).
`select_rows`    a decode lane's selection as POSITIONS (`lax.top_k`:
    equal scores come lower index first).
`gather_rows`    those positions' rows of a pool, through the lane's
    block table, laid out a lane after a lane: a pool the decode kernel
    reads with an identity table.
`selected`       the mask the pair (threshold, tie) stands for.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# keys a grid step of the score kernel covers, by what the call scores a
# lane: at 32 heads x 64 queries the float32 products of 512 keys are 4
# MB; one query a lane has products of 32 rows, and its step is as wide
# as its page copies pay for (256 of them, 21 ns a page unrolled; on the
# v5e at the cell's shapes 4096 beats 2048 by 2-11 % and 1024 by 10-19 %
# below 30k rows a lane: PERF.md, PR 43)
_SCORE_BLOCK_KEYS = 512
_SCORE_BLOCK_KEYS_ONE_QUERY = 4096
# ... and the float32 products a step of a chunk may hold, those 4 MB:
# a chunk of more query rows than 32 x 64 takes fewer keys a step (128 at
# 32 heads x 256 queries, the deep prefill pass's), the product the same
_SCORE_BLOCK_PRODUCTS = 32 * 64 * _SCORE_BLOCK_KEYS
# what the kernel may take of VMEM: the compiler's own allowance
_SCORE_VMEM_BYTES = 16 << 20


def _score_block_pages(chunk: int, table_width: int, page_size: int,
                       heads: int) -> int:
    """Pages a grid step covers: the most that divide the table and keep
    the step within its block of keys, and its `heads` x `chunk` x keys
    products within theirs (a page at the least)."""
    keys = _SCORE_BLOCK_KEYS_ONE_QUERY if chunk == 1 else min(
        _SCORE_BLOCK_KEYS, _SCORE_BLOCK_PRODUCTS // (heads * chunk))
    pages = max(1, min(table_width, keys // page_size))
    while table_width % pages:
        pages -= 1
    return pages


def _scores_kernel(bt_ref, n_ref, q_ref, w_ref, qpos_ref, pool_hbm, o_ref,
                   buf, sem, *, heads: int, chunk: int, page_size: int,
                   pages: int):
    """q [1, J x S, d] head-major; w [1, J x S, 1] float32; qpos [1, S,
    1]; the pool `[num_pages, page_size, d]` in HBM; o [1, S, keys]
    float32: a block of `pages` pages of the lane's positions; `buf` [2,
    pages, page_size, d] and its DMA semaphores [2] (a buffer half
    each).  `bt_ref` [B, P]: the lanes' pages; `n_ref` [B]: how far the
    lane's queries see (no further than its length)."""
    from jax.experimental import pallas as pl

    from ray_tpu.ops.latent_attention import _page_copies

    b = pl.program_id(0)
    ki = pl.program_id(1)
    n = n_ref[b]
    keys = pages * page_size
    # never past the table, whatever the lengths say
    used = jnp.minimum((n + page_size - 1) // page_size, bt_ref.shape[1])
    blocks = (used + pages - 1) // pages
    # (unrolled: a copy's scalar work in a loop is twice the time)
    fetch, wait = _page_copies(bt_ref, pool_hbm, buf, sem, b, used, pages,
                               unroll=True)

    @pl.when(ki < blocks)
    def _live():
        half = ki % 2

        @pl.when(ki == 0)
        def _first():
            fetch(0, 0)

        @pl.when(ki + 1 < blocks)
        def _next():
            fetch(ki + 1, 1 - half)

        wait(half)
        rows = buf[half].reshape(keys, buf.shape[-1])    # [keys, d]
        s = jax.lax.dot_general(
            q_ref[0], rows, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [J x S, keys]
        s = jnp.maximum(s, 0.0) * w_ref[0]
        if chunk == 1:
            total = jnp.sum(s, axis=0, keepdims=True)    # [1, keys]
        else:
            total = jnp.sum(s.reshape(heads, chunk, keys), axis=0)
        pos = ki * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        # (the block's pages past the lane's last are that page again)
        seen = (pos <= qpos_ref[0]) & (pos < n)          # [S, keys]
        # (a sum of -0.0's is -0.0, which a sort puts under +0.0 and a
        # comparison does not: one zero leaves here)
        total = jnp.where(total == 0.0, 0.0, total)
        o_ref[0] = jnp.where(seen, total, -jnp.inf)

    @pl.when(ki >= blocks)
    def _dead():
        o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, o_ref.dtype)


def index_scores(q: jax.Array, w: jax.Array, pool: jax.Array,
                 table: jax.Array, lens: jax.Array, q_pos: jax.Array, *,
                 page_size: int, interpret: Optional[bool] = None
                 ) -> jax.Array:
    """q [B, S, J, d] index queries, w [B, S, J] float32 head weights,
    pool [T, d] the index pool (the pass's own rows already written),
    table [B, P] the lanes' pages, lens [B] their lengths, q_pos [B, S].
    Returns I [B, S, P x page_size] float32: a query's score of every
    position of its lane, -inf where it does not see it (a position
    past its own, or at or past the lane's length)."""
    from ray_tpu.ops import interpret_default

    return _scores_call(q, w, pool, table, lens, q_pos,
                        page_size=page_size,
                        interpret=interpret_default(interpret))


def _seen(lens: jax.Array, q_pos: jax.Array) -> jax.Array:
    """[B] int32: how far a lane's queries see — the positions below its
    length up to the last query's own."""
    return jnp.minimum(lens.astype(jnp.int32),
                       jnp.max(q_pos.astype(jnp.int32), axis=-1) + 1)


def pages_read(lens: jax.Array, q_pos: jax.Array, page_size: int
               ) -> jax.Array:
    """[B] int32: the pages of a lane that `index_scores` copies — those
    that hold a position one of the lane's queries sees."""
    return (_seen(lens, q_pos) + page_size - 1) // page_size


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _scores_call(q, w, pool, table, lens, q_pos, *, page_size: int,
                 interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, j, d = q.shape
    width = table.shape[1]
    pages = _score_block_pages(s, width, page_size, j)
    keys = pages * page_size

    def _lane(bi, ki, *_scalars):
        return (bi, 0, 0)

    def _out(bi, ki, *_scalars):
        return (bi, 0, ki)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, width // pages),
        in_specs=[pl.BlockSpec((1, j * s, d), _lane),
                  pl.BlockSpec((1, j * s, 1), _lane),
                  pl.BlockSpec((1, s, 1), _lane),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, s, keys), _out),
        scratch_shapes=[pltpu.VMEM((2, pages, page_size, d), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=j, chunk=s,
                          page_size=page_size, pages=pages),
        out_shape=jax.ShapeDtypeStruct((b, s, width * page_size),
                                       jnp.float32),
        grid_spec=grid_spec, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SCORE_VMEM_BYTES),
        name="sparse_index_scores",
    # (the mask `pos < lens` under `pos <= q_pos` is `pos < _seen`)
    )(table.astype(jnp.int32), _seen(lens, q_pos),
      q.transpose(0, 2, 1, 3).reshape(b, j * s, d),
      w.astype(jnp.float32).transpose(0, 2, 1).reshape(b, j * s, 1),
      q_pos.astype(jnp.int32)[..., None],
      # a split of the major dimension: a page is a tile, nothing moves
      pool.reshape(pool.shape[0] // page_size, page_size, d))


def plain_scores(q: jax.Array, w: jax.Array, k: jax.Array) -> jax.Array:
    """The same scores over a whole sequence with no cache: q [B, S, J,
    d], w [B, S, J], k [B, S, d] -> I [B, S, S], -inf past the causal
    edge."""
    s = jnp.einsum("bsjd,btd->bsjt", q, k,
                   preferred_element_type=jnp.float32)
    total = jnp.sum(jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None],
                    axis=2)
    n = q.shape[1]
    total = jnp.where(total == 0.0, 0.0, total)
    return jnp.where(jnp.tril(jnp.ones((n, n), bool)), total, -jnp.inf)


def _ordered(scores: jax.Array) -> jax.Array:
    """float32 -> uint32 in the same order (-0.0 as +0.0, as a float
    comparison has them)."""
    scores = jnp.where(scores == 0.0, 0.0, scores)
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    top = jnp.uint32(1 << 31)
    return jnp.where(bits >= top, ~bits, bits | top)


def _unordered(key: jax.Array) -> jax.Array:
    top = jnp.uint32(1 << 31)
    return jax.lax.bitcast_convert_type(
        jnp.where(key >= top, key ^ top, ~key), jnp.float32)


@functools.partial(jax.jit, static_argnames=("top_k",))
def select_threshold(scores: jax.Array, top_k: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """scores [..., L] float32 (-inf: not seen) -> (threshold [...]
    float32, tie [...] int32): the `top_k` largest of a row, a tie to
    the lower position, are the positions p with

        scores_p > threshold  or  (scores_p == threshold and p <= tie)

    (`selected`).  A row that sees no more than `top_k` positions gets
    threshold -inf: everything it sees, and of what it does not see
    whatever `tie` lets through — the caller masks the unseen anyway."""
    with jax.named_scope("sparse_index_select"):
        width = scores.shape[-1]
        k = min(int(top_k), width)
        key = _ordered(scores)

        def bit(i, t):
            cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
            count = jnp.sum(key >= cand[..., None], axis=-1,
                            dtype=jnp.int32)
            return jnp.where(count >= k, cand, t)

        # the largest t that at least k keys reach: the k-th largest key
        t = jax.lax.fori_loop(0, 32, bit,
                              jnp.zeros(scores.shape[:-1], jnp.uint32))
        above = jnp.sum(key > t[..., None], axis=-1, dtype=jnp.int32)
        equal = key == t[..., None]
        need = k - above      # >= 1 of the `equal` are taken, lowest first
        pos = jnp.arange(width, dtype=jnp.int32)

        def cut(_):
            def bit(i, p):
                cand = p | (jnp.int32(1) << (pos_bits - 1 - i))
                count = jnp.sum(equal & (pos < cand[..., None]), axis=-1,
                                dtype=jnp.int32)
                return jnp.where(count < need, cand, p)

            # the largest p with fewer than `need` equals before it
            return jax.lax.fori_loop(
                0, pos_bits, bit, jnp.zeros(scores.shape[:-1], jnp.int32))

        pos_bits = max(1, int(width).bit_length())
        straddles = jnp.any(jnp.sum(equal, axis=-1, dtype=jnp.int32) > need)
        tie = jax.lax.cond(
            straddles, cut,
            lambda _: jnp.full(scores.shape[:-1], width, jnp.int32), None)
        return _unordered(t), tie


def selected(scores: jax.Array, threshold: jax.Array, tie: jax.Array
             ) -> jax.Array:
    """The mask `select_threshold`'s pair stands for, [..., L] bool."""
    pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    thr = threshold[..., None]
    return (scores > thr) | ((scores == thr) & (pos <= tie[..., None]))


@functools.partial(jax.jit, static_argnames=("top_k",))
def select_rows(scores: jax.Array, top_k: int) -> jax.Array:
    """scores [B, L] (-inf: not seen) -> positions [B, top_k] int32 of
    the largest, in falling order of score, equal scores lower position
    first: what a lane sees comes before what it does not."""
    with jax.named_scope("sparse_index_select"):
        return jax.lax.top_k(scores, top_k)[1].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("page_size",))
def gather_rows(pool: jax.Array, table: jax.Array, positions: jax.Array,
                *, page_size: int) -> jax.Array:
    """pool [T, W]; table [B, P] the lanes' pages; positions [B, K] of
    each lane's context -> [B x K, W]: the rows at those positions, a
    lane after a lane."""
    with jax.named_scope("sparse_gather"):
        page = jnp.take_along_axis(table.astype(jnp.int32),
                                   positions // page_size, axis=1)
        slots = page * page_size + positions % page_size
        return pool[slots.reshape(-1)]
