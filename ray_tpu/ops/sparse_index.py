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
    index pool, a block of keys a grid step; what a query does not see
    (past its own position, past the lane's length) reads -inf.  The
    lane's index rows are gathered by PAGE first (`[pages, page_size x
    d]` rows of 4 KB): the plain form; the kernel could walk the table
    itself as the attention kernels do.
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

# keys a grid step of the score kernel covers: at 32 heads x 64 queries
# the float32 products of a block are 4 MB
_SCORE_BLOCK_KEYS = 512


def _scores_kernel(n_ref, q_ref, w_ref, qpos_ref, k_ref, o_ref, *,
                   heads: int, chunk: int, keys: int):
    """q [1, J x S, d] head-major; w [1, J x S, 1] float32; qpos [1, S,
    1]; k [1, keys, d]: a block of the lane's index rows; o [1, S, keys]
    float32.  `n_ref` [B]: the lane's length."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    start = pl.program_id(1) * keys
    n = n_ref[b]

    @pl.when(start < n)
    def _live():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [J x S, keys]
        s = jnp.maximum(s, 0.0) * w_ref[0]
        if chunk == 1:
            total = jnp.sum(s, axis=0, keepdims=True)    # [1, keys]
        else:
            total = jnp.sum(s.reshape(heads, chunk, keys), axis=0)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        seen = (pos <= qpos_ref[0]) & (pos < n)          # [S, keys]
        # (a sum of -0.0's is -0.0, which a sort puts under +0.0 and a
        # comparison does not: one zero leaves here)
        total = jnp.where(total == 0.0, 0.0, total)
        o_ref[0] = jnp.where(seen, total, -jnp.inf)

    @pl.when(start >= n)
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


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _scores_call(q, w, pool, table, lens, q_pos, *, page_size: int,
                 interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, j, d = q.shape
    pages = table.shape[1]
    width = pages * page_size
    keys = min(_SCORE_BLOCK_KEYS, width)
    assert width % keys == 0, (width, keys)
    rows = pool.reshape(pool.shape[0] // page_size, page_size * d)[
        table.astype(jnp.int32)].reshape(b, width, d)

    def _lane(bi, ki, *_scalars):
        return (bi, 0, 0)

    def _block(bi, ki, *_scalars):
        return (bi, ki, 0)

    def _out(bi, ki, *_scalars):
        return (bi, 0, ki)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, width // keys),
        in_specs=[pl.BlockSpec((1, j * s, d), _lane),
                  pl.BlockSpec((1, j * s, 1), _lane),
                  pl.BlockSpec((1, s, 1), _lane),
                  pl.BlockSpec((1, keys, d), _block)],
        out_specs=pl.BlockSpec((1, s, keys), _out))
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=j, chunk=s, keys=keys),
        out_shape=jax.ShapeDtypeStruct((b, s, width), jnp.float32),
        grid_spec=grid_spec, interpret=interpret,
        name="sparse_index_scores",
    )(lens.astype(jnp.int32),
      q.transpose(0, 2, 1, 3).reshape(b, j * s, d),
      w.astype(jnp.float32).transpose(0, 2, 1).reshape(b, j * s, 1),
      q_pos.astype(jnp.int32)[..., None], rows)


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
