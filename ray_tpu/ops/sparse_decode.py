"""Decode attention of learned sparse attention over the pages the lane
HOLDS: one absorbed query a lane against the `[T, W]` latent pool where
it lies, the selection a MASK, the kernel
`latent_attention_decode_select`.

What it takes the place of, where a lane's table has no more pages than
the selection has rows (models/pangu.py, `LatentAttention._selected`):
`sparse_index.select_rows` (a sort of every lane's scores),
`sparse_index.gather_rows` (the block table taken along the positions,
the slot arithmetic, and `pool[slots]`: a COPY of lanes x `top_k` rows,
42 MB a layer at 16 lanes x 2,048 rows of 640, made for every lane
whatever it holds) and `latent_paged_attention` over the copy.  A copy
out of the pool costs its issue, not its bytes, up to a page
(`latent_attention._page_copies` unrolled: 21 ns a page of 16 rows), so a
lane's whole table of P pages is never more copies than its `top_k` rows
while P <= `top_k` — and every one of them a whole aligned page, which
the chip's compiler takes where it refuses a one-row slice.

- THE SELECTION IS `sparse_index.select_threshold`'s pair, as the chunk
  kernel's (`latent_attention._prefill_kernel`, `select=`): a row enters
  the running maximum, the denominator and the sum only where
  `sparse_index.selected(marks, threshold, tie)` holds and its position
  is below the lane's length.  The length's mask is the kernel's own: a
  lane that holds no more than `top_k` rows has threshold -inf, its
  unseen positions' marks are -inf too and `tie` may let them through.
  The products of the rows not selected are still made: the same plain
  first form as the chunk kernel's.
- THE WALK IS A LOOP INSIDE A GRID STEP, a step a lane, over the lane's
  OWN blocks of `_BLOCK_KEYS` rows: a block's pages are copied into one
  half of a double buffer while the other half is multiplied; a lane's
  marks ride in beside its query as one VMEM block `[blocks, keys]`, of
  which a turn of the loop reads a row.  A grid axis over the table's
  width (the form of `latent_attention._decode_kernel`) would pay a grid
  step for every block a lane does not have: 16 lanes x 64 blocks at the
  widest table, of which two lanes of 14 are live.  An empty lane reads
  nothing and writes zeros.
- Operands in the pool's dtype, scores, running maximum, denominator and
  accumulator in float32 in VMEM, a page fetched ONCE as key and as value:
  `latent_attention._softmax_block`, as the two kernels of that file.
  The sums run in position order where the gathered pool's ran in score
  order: the same set of rows, last-bit differences.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.latent_attention import (_NEG_INF, _page_copies,
                                          _softmax_block)

# context rows a block of the walk covers.  Read on the v5e at the sparse
# cell's shapes (PERF.md section 6, PR 56: 16 lanes of 64 heads over a row
# of 640, twenty calls inside one program with the threshold search, each
# behind the one before): two live lanes of 7,168 rows 0.127 / 0.125 /
# 0.129 ms at 512 / 1,024 / 2,048 rows a block, two of 30,720 rows 0.281 /
# 0.253 / 0.247, sixteen of 7,168 rows 0.403 / 0.350 / 0.372 (a lane's
# last block is copied whole, and its first is overlapped by nothing),
# sixteen of 30,720 rows 1.460 / 1.235 / 1.130
_BLOCK_KEYS = 1024
# what the kernel may take of VMEM: the compiler's own allowance (a
# block's double buffer is 2.6 MB at a row of 640, a lane's marks 128 KB
# at the widest table, twice)
_VMEM_BYTES = 16 << 20


def _kernel(bt_ref, cl_ref, q_ref, thr_ref, tie_ref, marks_ref, pool_hbm,
            o_ref, buf, sem, acc_ref, m_ref, l_ref, *, page_size: int,
            pages: int, scale: float, value_width: int):
    """q [1, H, W]: one lane's query; thr [1, 1, 1] float32 and tie [1,
    1, 1] int32: its selection; marks [1, blocks, keys] float32: its
    index scores, a row a block of the walk; the pool `[num_pages,
    page_size, W]` in HBM; o [1, H, value_width]; `buf` [2, pages,
    page_size, W] and its DMA semaphores [2] (a buffer half each);
    float32 scratch: acc [H, value_width], running max and denominator
    [H, 128].  A grid step is one lane; it walks the lane's blocks
    itself."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    ctx = cl_ref[b]
    # never past the table, whatever the lengths say
    used = jnp.minimum((ctx + page_size - 1) // page_size, bt_ref.shape[1])
    blocks = (used + pages - 1) // pages
    keys = pages * page_size
    fetch, wait = _page_copies(bt_ref, pool_hbm, buf, sem, b, used, pages,
                               unroll=True)
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
        rows = buf[half].reshape(keys, buf.shape[-1])    # [keys, W]
        s = jax.lax.dot_general(
            q_ref[0], rows, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, keys]
        # of the positions below the lane's length (the last page's rows
        # past it hold garbage, and the block's pages past that page are
        # it again) only those the indexer selected; one mask for every
        # head
        pos = ci * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        index = marks_ref[0, pl.ds(ci, 1), :]            # [1, keys]
        seen = (pos < ctx) & ((index > thr_ref[0]) | (
            (index == thr_ref[0]) & (pos <= tie_ref[0])))
        _softmax_block(s, lambda x, fill: jnp.where(seen, x, fill), rows,
                       acc_ref, m_ref, l_ref, value_width)
        return carry

    # a lane's own blocks and no more: an empty lane runs none
    jax.lax.fori_loop(0, blocks, block, 0)
    denom = jnp.maximum(l_ref[:, :1], 1e-20)
    o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def latent_selected_attention(q: jax.Array, pool: jax.Array,
                              block_tables: jax.Array,
                              context_lens: jax.Array, marks: jax.Array,
                              threshold: jax.Array, tie: jax.Array, *,
                              page_size: int, value_width: int,
                              scale: float,
                              interpret: Optional[bool] = None
                              ) -> jax.Array:
    """Single-token decode attention over the SELECTED rows of a lane's
    latent pages.

    q: [B, 1, H, W] absorbed queries (the current token's row must
    already be in the pool); pool: [T, W]; block_tables: [B, P];
    context_lens: [B] (0: an inactive lane, zeros out); marks: [B, 1, P x
    page_size] or [B, P x page_size] float32, the query's index score of
    every position of its lane (`sparse_index.index_scores`); threshold,
    tie: [B], `sparse_index.select_threshold`'s pair over `marks`.
    Returns [B, 1, H, value_width] in q's dtype: per head the
    softmax-weighted mean of the first `value_width` numbers of the rows
    at the positions p below the lane's length with

        marks_p > threshold  or  (marks_p == threshold and p <= tie)."""
    from ray_tpu.ops import interpret_default

    return _call(q, pool, block_tables, context_lens, marks, threshold, tie,
                 page_size=page_size, value_width=value_width,
                 scale=float(scale), interpret=interpret_default(interpret))


# a jit of its own, as `latent_attention._decode_call`: traced and
# lowered once a program, not once a layer
@functools.partial(jax.jit, static_argnames=("page_size", "value_width",
                                             "scale", "interpret"))
def _call(q, pool, block_tables, context_lens, marks, threshold, tie, *,
          page_size: int, value_width: int, scale: float, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, w = q.shape
    assert s == 1, f"latent_selected_attention is decode-only, got S={s}"
    num_slots = pool.shape[0]
    assert pool.shape == (num_slots, w) and num_slots % page_size == 0
    width = block_tables.shape[1]
    assert marks.size == b * width * page_size, (marks.shape, width)
    paged = pool.reshape(num_slots // page_size, page_size, w)
    bt = block_tables.astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)
    if interpret:
        # as `_decode_call`: the interpreter carries whole operands
        # through its grid loop, so hand it the table's pages only
        paged = paged[bt.reshape(-1)]
        bt = jnp.arange(b * width, dtype=jnp.int32).reshape(b, width)

    def _lane(bi, *_scalars):
        return (bi, 0, 0)

    # a block's pages: _BLOCK_KEYS rows, or the whole table if narrower
    pages = min(width, max(1, _BLOCK_KEYS // page_size))
    keys = pages * page_size
    blocks = -(-width // pages)
    # a row a block of the walk, -inf behind the table's last page
    marks = jnp.pad(marks.astype(jnp.float32).reshape(b, -1), (
        (0, 0), (0, blocks * keys - width * page_size)),
        constant_values=-jnp.inf).reshape(b, blocks, keys)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), _lane),
                  pl.BlockSpec((1, 1, 1), _lane),
                  pl.BlockSpec((1, 1, 1), _lane),
                  pl.BlockSpec((1, blocks, keys), _lane),
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
        functools.partial(_kernel, page_size=page_size, pages=pages,
                          scale=scale, value_width=value_width),
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        grid_spec=grid_spec, interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
        name="latent_attention_decode_select",
    )(bt, cl, q.reshape(b, h, w),
      threshold.astype(jnp.float32).reshape(b, 1, 1),
      tie.astype(jnp.int32).reshape(b, 1, 1), marks, paged)
    return out.reshape(b, 1, h, value_width)
