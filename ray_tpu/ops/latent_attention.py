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

Two kernels over the lane's pages.  Both leave the pool `[T, W]` in HBM
and walk a lane's block table a block of pages at a time: a block's
pages are copied into one half of a double buffer while the other half
is computed on (`_page_copies`; a page past the lane's last is its last
again, masked by position), a block past the lane's last page is not
computed, an empty lane reads nothing and writes zeros.  Both fetch a
page ONCE as key and as value (the value is a slice of the key's
buffer), leave operands in the pool's dtype and keep scores, running
maximum, denominator and accumulator in float32 in VMEM — at 128 heads
the products are as much of a call as the copies, and a float32 product
takes the bfloat16 unit several passes.  Each is a `jax.jit` of its
own: traced and lowered once a program, not once a layer.

`latent_paged_attention`  one query a lane (decode), the kernel
    `latent_attention_decode`.  Its grid is the paged decode kernel's
    (ops/paged_attention.py): lanes x blocks of `pages_per_step` pages,
    all 128 heads of the lane's one query in a step.

`latent_chunk_attention`  a chunk of queries a lane (chunked prefill),
    the kernel `latent_attention_prefill`.  It differs where a chunk
    does:
    - QUERY TILES.  A lane's 64 queries x 128 heads are 8,192 query
      rows of 640 numbers, 10.5 MB: not one VMEM block.  The grid is
      lanes x tiles of whole heads (`_prefill_tiles`: 32 heads x 64
      queries = 2,048 rows), head-major so that a tile's rows share one
      `[queries, keys]` mask.  A lane's rows are read again once a tile
      — 1,280 B a row against 4.7 MFLOP of products a row and tile: the
      kernel is the MXU's.
    - THE WALK IS A LOOP INSIDE A GRID STEP, over the lane's own blocks
      of 512 rows: a grid axis over the table's width would pay a grid
      step (0.2 us, measured) for every block a lane does not have,
      1,024 of them at the widest table.  The copies of a block's pages
      are issued unrolled, so that their scalar work packs beside the
      products.
    - THE CAUSAL EDGE runs inside the chunk: a query sees the positions
      up to its own (`q_pos`) below the lane's length; a padded query
      (`q_pos` 0) sees position 0 and stays finite.
    - THE TABLE IS READ FROM `ctx`.  The engine's prefill pass hands a
      layer the slot of every context position 0..n-1 in order
      (`serve/llm.py`, `_dispatch_prefill`: `seq.slot_cache[:hi]`, zeros
      behind).  A page holds `page_size` consecutive positions, so every
      `page_size`-th column names a page — the sequence's block table as
      far as its context reaches, shared prefix pages included — and the
      mask's count is the length: two integer operations, no gather of
      rows, no score array `[lanes, heads, chunk, context]` in HBM (2.1
      GB at 8 x 128 x 64 x 8192 in float32), and a pass costs what its
      lanes hold, not what its width bucket could.
    - A SELECTION (`select=`: learned sparse attention,
      ops/sparse_index.py).  Given every query's index scores of the
      lane's positions and its (threshold, tie), a block's unselected
      columns are masked like the causal edge: the same pages, the same
      grid, a block of scores `[queries, keys]` copied beside a block's
      pages.  The plain first form: the products of the masked columns
      are still made.

`latent_paged_attention` over SELECTED rows is itself: the caller
gathers a lane's rows (`sparse_index.gather_rows`) into a pool of their
own, a lane after a lane, and hands an identity table.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import pages_per_step

_NEG_INF = -1e30


def _page_copies(bt_ref, pool_hbm, buf, sem, lane, used, pages: int,
                 unroll: bool = False):
    """(fetch, wait) over blocks of `pages` pages of `lane`'s table, of
    which it uses `used`: `fetch(block, half)` starts the copies of a
    block's pages into buffer half `half` (a page past the lane's last
    is its last again), `wait(half)` waits for them.  Unrolled, the
    copies' scalar work is straight-line code that the compiler packs
    beside the vector work around it."""
    from jax.experimental.pallas import tpu as pltpu

    def fetch(block, half):
        def page(j, carry):
            at = bt_ref[lane, jnp.minimum(block * pages + j, used - 1)]
            pltpu.make_async_copy(pool_hbm.at[at], buf.at[half, j],
                                  sem.at[half]).start()
            return carry
        jax.lax.fori_loop(0, pages, page, 0, unroll=unroll)

    def wait(half):
        def page(j, carry):
            pltpu.make_async_copy(pool_hbm.at[0], buf.at[half, j],
                                  sem.at[half]).wait()
            return carry
        jax.lax.fori_loop(0, pages, page, 0, unroll=unroll)

    return fetch, wait


def _softmax_block(s, masked, rows, acc_ref, m_ref, l_ref,
                   value_width: int):
    """One block of the running softmax: scores `s` [R, keys] (float32)
    of R query rows against the block's `rows` [keys, W], `masked(x,
    fill)` putting `fill` where a row does not see a key; the running
    maximum and denominator [R, 128] and the accumulator [R,
    value_width] move on.  The value is the row's head: the same fetched
    bytes."""
    s = masked(s, _NEG_INF)
    m_prev = m_ref[:, :1]                                # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = masked(jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=-1,
                                                 keepdims=True)
    m_ref[:, :1] = m_new
    acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
        p.astype(rows.dtype), rows[:, :value_width],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [R, value_width]


def _decode_kernel(bt_ref, cl_ref, q_ref, pool_hbm, o_ref, buf, sem,
                   acc_ref, m_ref, l_ref, *, page_size: int, pages: int,
                   scale: float, value_width: int):
    """q [1, H, W]; the pool `[num_pages, page_size, W]` in HBM; o [1,
    H, value_width]; `buf` [2, pages, page_size, W] and its DMA
    semaphores [2] (a buffer half each); float32 scratch: acc [H,
    value_width], running max and denominator [H, 128]."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    pi = pl.program_id(1)
    n_p = pl.num_programs(1)
    ctx = cl_ref[b]
    # never past the table, whatever the lengths say
    used = jnp.minimum((ctx + page_size - 1) // page_size, bt_ref.shape[1])
    blocks = (used + pages - 1) // pages
    keys = pages * page_size
    fetch, wait = _page_copies(bt_ref, pool_hbm, buf, sem, b, used, pages)

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
        _softmax_block(s, lambda x, fill: jnp.where(valid, x, fill), rows,
                       acc_ref, m_ref, l_ref, value_width)

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

    pages = pages_per_step(width, page_size, w)
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


# query rows (heads x the chunk's queries) a tile of the prefill kernel
# holds, and context rows a block of its walk covers.  Measured on the
# v5e at the cell's shapes (PERF.md, PR 41): 2048 x 512 beats 1024 x 512
# by 4 % and 512-row tiles lose 14 %; blocks of 256 or 1024 rows lose 8
# to 19 %; query sub-tiles inside a block lose too (the block's two
# products run at the MXU's rate as they stand).  At 2048 x 512 the
# float32 scores and the accumulator are 4 MB each, and a block's
# products (2.4 GFLOP at a row of 640) hide its 0.66 MB of page copies
_PREFILL_QUERY_ROWS = 2048
_PREFILL_BLOCK_ROWS = 512
# a tile's blocks, scratch and temporaries are some 30 MB at the cell's
# shapes, over the compiler's default allowance
_PREFILL_VMEM_BYTES = 48 << 20


def _prefill_kernel(bt_ref, cl_ref, q_ref, qpos_ref, *refs, page_size: int,
                    pages: int, scale: float, value_width: int,
                    select: bool = False):
    """q [1, Ht, S, W]: a tile of Ht heads' queries of one lane's chunk;
    qpos [1, S, 1]; the pool in HBM; o [1, Ht, S, value_width]; `buf`
    and `sem` as the decode kernel's; float32 scratch by query row (head
    by head, Ht x S of them): acc [rows, value_width], running max and
    denominator [rows, 128].  A grid step is one tile; it walks the
    lane's blocks itself.  With `select`, before the pool: thr [1, S, 1]
    float32, tie [1, S, 1] and the index scores in HBM [B, blocks, S,
    keys]; before acc: their double buffer [2, S, keys] and its
    semaphores."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if select:
        (thr_ref, tie_ref, scores_hbm, pool_hbm, o_ref, buf, sem, sbuf,
         ssem, acc_ref, m_ref, l_ref) = refs
    else:
        pool_hbm, o_ref, buf, sem, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    ctx = cl_ref[b]
    used = jnp.minimum((ctx + page_size - 1) // page_size, bt_ref.shape[1])
    blocks = (used + pages - 1) // pages
    keys = pages * page_size
    _one, heads, chunk, width = q_ref.shape
    fetch_pages, wait_pages = _page_copies(bt_ref, pool_hbm, buf, sem, b,
                                           used, pages, unroll=True)
    if select:
        def scores_copy(block, half):
            return pltpu.make_async_copy(scores_hbm.at[b, block],
                                         sbuf.at[half], ssem.at[half])

        def fetch(block, half):
            fetch_pages(block, half)
            scores_copy(block, half).start()

        def wait(half):
            wait_pages(half)
            scores_copy(0, half).wait()
    else:
        fetch, wait = fetch_pages, wait_pages
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(blocks > 0)
    def _first():
        fetch(0, 0)

    def block(ci, carry):
        half = ci % 2

        @pl.when(ci + 1 < blocks)
        def _next():
            fetch(ci + 1, 1 - half)

        wait(half)
        q = q_ref[0].reshape(heads * chunk, width)       # [rows, W]
        rows = buf[half].reshape(keys, width)            # [keys, W]
        s = jax.lax.dot_general(
            q, rows, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rows, keys]
        # the causal edge runs inside the chunk: a query sees positions
        # up to its own, and none at or past the lane's length (the last
        # page's rows there hold garbage); one mask for every head
        pos = ci * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        seen = (pos <= qpos_ref[0]) & (pos < ctx)        # [S, keys]
        if select:
            # ... and of those only the rows its indexer selected
            index = sbuf[half]                           # [S, keys]
            seen &= (index > thr_ref[0]) | (
                (index == thr_ref[0]) & (pos <= tie_ref[0]))

        def by_head(x, fill):
            x = x.reshape(heads, chunk, keys)
            return jnp.where(seen[None], x, fill).reshape(heads * chunk,
                                                          keys)
        _softmax_block(s, by_head, rows, acc_ref, m_ref, l_ref, value_width)
        return carry

    # a lane's own blocks and no more: an empty lane runs none
    jax.lax.fori_loop(0, blocks, block, 0)
    inv = 1.0 / jnp.maximum(l_ref[:, :1], 1e-20)
    o_ref[0] = (acc_ref[:] * inv).astype(o_ref.dtype).reshape(
        heads, chunk, value_width)


def latent_chunk_attention(q: jax.Array, pool: jax.Array, ctx: jax.Array,
                           ctx_pos: jax.Array, ctx_mask: jax.Array,
                           q_pos: jax.Array, *, page_size: int,
                           value_width: int, scale: float,
                           interpret: Optional[bool] = None,
                           select=None) -> jax.Array:
    """A chunk of queries a lane (chunked prefill) over the lane's
    latent pages.

    q: [B, S, H, W] absorbed queries; pool: [T, W] (this call's rows
    already written); q_pos: [B, S].  The context is what the engine's
    prefill pass hands a full layer: ctx [B, L] the slot of context
    position 0, 1, ... in order (`ctx_pos` says so and is not read),
    ctx_mask [B, L] true on the lane's first n columns.  A page holds
    `page_size` consecutive positions, so every `page_size`-th column
    names a page: the lane's block table, read here with no gather of
    rows.  A query sees the positions up to its own below n — with
    `select` = (index scores [B, S, L] float32, threshold [B, S], tie
    [B, S]: `sparse_index.select_threshold`'s) only those of them its
    indexer selected.  Returns [B, S, H, value_width] in q's dtype; a
    lane of n = 0 zeros."""
    from ray_tpu.ops import interpret_default

    del ctx_pos
    table = ctx[:, ::page_size] // page_size
    heads, pages = _prefill_tiles(q.shape[2], q.shape[1], table.shape[1],
                                  page_size)
    return _prefill_call(q, pool, table, ctx_mask.sum(-1), q_pos, select,
                         page_size=page_size, value_width=value_width,
                         scale=float(scale), tile_heads=heads,
                         block_pages=pages,
                         interpret=interpret_default(interpret))


def _prefill_tiles(heads: int, chunk: int, table_width: int,
                   page_size: int):
    """(heads a query tile, pages a context block) of the prefill
    kernel, from the call's static shapes: the most heads that divide
    `heads` and keep a tile within _PREFILL_QUERY_ROWS query rows, and
    _PREFILL_BLOCK_ROWS context rows or the whole table if narrower."""
    tile = max(1, min(heads, _PREFILL_QUERY_ROWS // chunk))
    while heads % tile:
        tile -= 1
    return tile, min(table_width, max(1, _PREFILL_BLOCK_ROWS // page_size))


# a jit of its own, as `_decode_call`
@functools.partial(jax.jit, static_argnames=(
    "page_size", "value_width", "scale", "tile_heads", "block_pages",
    "interpret"))
def _prefill_call(q, pool, block_tables, context_lens, q_pos, select=None,
                  *, page_size: int, value_width: int, scale: float,
                  tile_heads: int, block_pages: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, w = q.shape
    num_slots = pool.shape[0]
    assert pool.shape == (num_slots, w) and num_slots % page_size == 0
    assert h % tile_heads == 0, (h, tile_heads)
    width = block_tables.shape[1]
    paged = pool.reshape(num_slots // page_size, page_size, w)
    bt = block_tables.astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)
    if interpret:
        # as `_decode_call`: the table's pages only
        paged = paged[bt.reshape(-1)]
        bt = jnp.arange(b * width, dtype=jnp.int32).reshape(b, width)

    def _tile(bi, hi, *_scalars):
        return (bi, hi, 0, 0)

    def _lane(bi, hi, *_scalars):
        return (bi, 0, 0)

    rows = tile_heads * s
    kernel = functools.partial(_prefill_kernel, page_size=page_size,
                               pages=block_pages, scale=scale,
                               value_width=value_width,
                               select=select is not None)
    chosen, chosen_specs, chosen_scratch = (), [], []
    if select is not None:
        # the scores a block of the walk at a time, [B, blocks, S, keys]
        scores, thr, tie = select
        keys = block_pages * page_size
        blocks = -(-width // block_pages)
        scores = jnp.pad(scores.astype(jnp.float32), (
            (0, 0), (0, 0), (0, blocks * keys - scores.shape[-1])),
            constant_values=-jnp.inf)
        chosen = (thr.astype(jnp.float32)[..., None],
                  tie.astype(jnp.int32)[..., None],
                  scores.reshape(b, s, blocks, keys).transpose(0, 2, 1, 3))
        chosen_specs = [pl.BlockSpec((1, s, 1), _lane),
                        pl.BlockSpec((1, s, 1), _lane),
                        pl.BlockSpec(memory_space=pl.ANY)]
        chosen_scratch = [pltpu.VMEM((2, s, keys), jnp.float32),
                          pltpu.SemaphoreType.DMA((2,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // tile_heads),
        in_specs=[pl.BlockSpec((1, tile_heads, s, w), _tile),
                  pl.BlockSpec((1, s, 1), _lane), *chosen_specs,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tile_heads, s, value_width), _tile),
        scratch_shapes=[
            pltpu.VMEM((2, block_pages, page_size, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)), *chosen_scratch,
            pltpu.VMEM((rows, value_width), jnp.float32),   # acc
            pltpu.VMEM((rows, 128), jnp.float32),           # running max
            pltpu.VMEM((rows, 128), jnp.float32),           # running denom
        ])
    # head-major, so that a tile's query rows share one [S, keys] mask;
    # the transposes fold into the products on either side
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, s, value_width), q.dtype),
        grid_spec=grid_spec, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        name="latent_attention_prefill",
    )(bt, cl, q.transpose(0, 2, 1, 3),
      q_pos.astype(jnp.int32)[..., None], *chosen, paged)
    return out.transpose(0, 2, 1, 3)
