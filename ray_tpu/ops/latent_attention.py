"""Attention over a LATENT cache: one row a token, from which keys and
values are both made (models/cache.py, `LayerCache.latent`).

The row is `(c, k_rope)`: a compressed vector `c` of `value_width`
numbers and, behind it, the one rotated key part every head shares.  In
the ABSORBED form a head's query has been multiplied into the row's own
space (`q~ = q_nope Wkvb_k^T`, then `q_rope` behind it), so that

    score_ij = q_i . row_j * scale         over the whole row
    out_i    = softmax_j(score_ij) c_j     the row's first `value_width`

and the caller multiplies `out` by the value half of the expansion.  No
per-token key or value exists anywhere: every head reads the SAME row,
once, as key and as value.  Per lane that is H query heads against one
key head — 2 H (W + value_width) operations for W numbers read, at the
chip's ridge for 128 heads, where grouped-query attention (a few query
heads a key head) is bound by memory alone.

Two passes:

`latent_paged_attention`  one query a lane (decode) over the lane's
    pages, a Pallas kernel named `latent_attention_decode`.  It shares
    the paged decode kernel's walk (ops/paged_attention.py: the grid of
    lanes x blocks of `pages_per_step` pages, the pool left in HBM, a
    block's pages copied into one half of a double buffer while the
    other is computed on, the table clamp) and differs where the row
    form does: ONE pool and one copy a page, the value a slice of the
    key's buffer, and operands left in the pool's dtype with float32
    accumulation — at 128 heads a lane the products are as much of the
    call as the copies, and a float32 product takes the bfloat16 unit
    several passes.

`latent_chunk_attention`  a chunk of queries a lane (chunked prefill)
    over a gathered context, in blocks of the context with a running
    maximum and denominator: the `[lanes, heads, chunk, context]` score
    array of `llama.cached_attention` (2.1 GB at 8 lanes x 128 heads x
    64 x 8192 in float32) is never held.  Lane by lane, each over the
    blocks its OWN context fills: a pass costs what its lanes read, not
    what its width bucket — or its longest lane — could hold.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import pages_per_step

_NEG_INF = -1e30
# context rows a block of `latent_chunk_attention` covers
CHUNK_CTX_BLOCK = 512


def _decode_kernel(bt_ref, cl_ref, q_ref, pool_hbm, o_ref, buf, sem,
                   acc_ref, m_ref, l_ref, *, page_size: int, pages: int,
                   scale: float, value_width: int):
    """q [1, H, W]; the pool `[num_pages, page_size, W]` in HBM; o [1,
    H, value_width]; `buf` [2, pages, page_size, W] and its DMA
    semaphores [2] (a buffer half each); float32 scratch: acc [H,
    value_width], running max and denominator [H, 128]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    pi = pl.program_id(1)
    n_p = pl.num_programs(1)
    ctx = cl_ref[b]
    # never past the table, whatever the lengths say
    used = jnp.minimum((ctx + page_size - 1) // page_size, bt_ref.shape[1])
    blocks = (used + pages - 1) // pages
    keys = pages * page_size

    def fetch(block, half):
        """Start the copies of `block`'s pages into buffer `half`; a
        page past the lane's last is its last again."""
        def page(j, carry):
            at = bt_ref[b, jnp.minimum(block * pages + j, used - 1)]
            pltpu.make_async_copy(pool_hbm.at[at], buf.at[half, j],
                                  sem.at[half]).start()
            return carry
        jax.lax.fori_loop(0, pages, page, 0)

    def wait(half):
        def page(j, carry):
            pltpu.make_async_copy(pool_hbm.at[0], buf.at[half, j],
                                  sem.at[half]).wait()
            return carry
        jax.lax.fori_loop(0, pages, page, 0)

    @pl.when(pi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(pi < blocks)
    def _update():
        half = pi % 2

        @pl.when(pi == 0)
        def _first():
            fetch(0, 0)

        @pl.when(pi + 1 < blocks)
        def _next():
            fetch(pi + 1, 1 - half)

        wait(half)
        q = q_ref[0]                                     # [H, W]
        rows = buf[half].reshape(keys, buf.shape[-1])    # [keys, W]
        s = jax.lax.dot_general(
            q, rows, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, keys]
        # rows of the last used page beyond the context length hold
        # garbage, and the block's pages past it are that page again
        pos = pi * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        valid = pos < ctx
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:, :1]                            # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
        m_ref[:, :1] = m_new
        # the value is the row's head: the same fetched bytes
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_width],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [H, value_width]

    @pl.when(pi == n_p - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def latent_paged_attention(q: jax.Array, pool: jax.Array,
                           block_tables: jax.Array, context_lens: jax.Array,
                           *, page_size: int, value_width: int,
                           scale: float, interpret: Optional[bool] = None
                           ) -> jax.Array:
    """Single-token decode attention over latent pages.

    q: [B, 1, H, W] absorbed queries (the current token's row must
    already be in the pool); pool: [T, W]; block_tables: [B, pages];
    context_lens: [B] (0: an inactive lane, zeros out).  Returns [B, 1,
    H, value_width] in q's dtype: per head the softmax-weighted mean of
    the rows' first `value_width` numbers."""
    from ray_tpu.ops import interpret_default

    return _decode_call(q, pool, block_tables, context_lens,
                        page_size=page_size, value_width=value_width,
                        scale=float(scale),
                        interpret=interpret_default(interpret))


# a jit of its own, as `paged_attention._paged_call`: traced and lowered
# once a program, not once a layer
@functools.partial(jax.jit, static_argnames=("page_size", "value_width",
                                             "scale", "interpret"))
def _decode_call(q, pool, block_tables, context_lens, *, page_size: int,
                 value_width: int, scale: float, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, w = q.shape
    assert s == 1, f"latent_paged_attention is decode-only, got S={s}"
    num_slots = pool.shape[0]
    assert pool.shape == (num_slots, w) and num_slots % page_size == 0
    pages_total = num_slots // page_size
    width = block_tables.shape[1]
    paged = pool.reshape(pages_total, page_size, w)
    bt = block_tables.astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)
    if interpret:
        # as `_paged_call`: the interpreter carries whole operands
        # through its grid loop, so hand it the table's pages only
        paged = paged[bt.reshape(-1)]
        bt = jnp.arange(b * width, dtype=jnp.int32).reshape(b, width)

    def _lane(bi, pi, *_scalars):
        return (bi, 0, 0)

    pages = pages_per_step(width, page_size)
    kernel = functools.partial(_decode_kernel, page_size=page_size,
                               pages=pages, scale=scale,
                               value_width=value_width)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, -(-width // pages)),
        in_specs=[pl.BlockSpec((1, h, w), _lane),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, value_width), _lane),
        scratch_shapes=[
            pltpu.VMEM((2, pages, page_size, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((h, value_width), jnp.float32),   # acc
            pltpu.VMEM((h, 128), jnp.float32),           # running max
            pltpu.VMEM((h, 128), jnp.float32),           # running denom
        ])
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        grid_spec=grid_spec, interpret=interpret,
        name="latent_attention_decode",
    )(bt, cl, q.reshape(b, h, w), paged)
    return out.reshape(b, 1, h, value_width)


def latent_chunk_attention(q: jax.Array, pool: jax.Array, ctx: jax.Array,
                           ctx_pos: jax.Array, ctx_mask: jax.Array,
                           q_pos: jax.Array, *, value_width: int,
                           scale: float) -> jax.Array:
    """A chunk of queries a lane over a gathered latent context.

    q: [B, S, H, W] absorbed queries; pool: [T, W] (this call's rows
    already written); ctx: [B, L] slot of each context entry, ctx_pos:
    [B, L] its position, ctx_mask: [B, L] its validity; q_pos: [B, S].
    A query sees the valid entries at positions up to its own.  Returns
    [B, S, H, value_width] in q's dtype."""
    _b, s, h, _w = q.shape
    length = ctx.shape[1]
    blk = min(CHUNK_CTX_BLOCK, length)
    assert length % blk == 0, (length, blk)

    def lane(args):
        """One lane's chunk [S, H, W] over its own context: the blocks
        that hold a valid entry of THIS lane, so a short context beside
        a long one in the pass costs its own length (and an empty lane
        nothing)."""
        q, ctx, ctx_pos, ctx_mask, q_pos = args
        last = jnp.max(jnp.where(ctx_mask, jnp.arange(length) + 1, 0))

        def take(a, i):
            return jax.lax.dynamic_slice_in_dim(a, i * blk, blk)

        def block(i, carry):
            m, l, acc = carry            # [S, H, 1], [S, H, 1], [S, H, V]
            rows = pool[take(ctx, i)]                        # [blk, W]
            sc = jnp.einsum("shw,kw->shk", q, rows,
                            preferred_element_type=jnp.float32) * scale
            seen = ((take(ctx_pos, i)[None, :] <= q_pos[:, None])
                    & take(ctx_mask, i)[None, :])[:, None, :]
            sc = jnp.where(seen, sc, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.where(seen, jnp.exp(sc - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum(
                "shk,kv->shv", p.astype(rows.dtype),
                rows[:, :value_width], preferred_element_type=jnp.float32)
            return m_new, l, acc

        m0 = jnp.full((s, h, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((s, h, 1), jnp.float32)
        acc0 = jnp.zeros((s, h, value_width), jnp.float32)
        _m, l, acc = jax.lax.fori_loop(0, (last + blk - 1) // blk, block,
                                       (m0, l0, acc0))
        return (acc / jnp.maximum(l, 1e-20)).astype(q.dtype)

    return jax.lax.map(lane, (q, ctx, ctx_pos, ctx_mask, q_pos))
