"""Pallas TPU paged attention for single-token decode with GQA.

Decode attention over the serving tier's paged KV cache
(serve/llm.py): instead of gathering the whole ``[B, L]`` slot-table
context out of the flat pools and softmaxing over ``-1e30``-masked
garbage (``models/llama.py cached_attention``), the kernel walks each
sequence's **used pages only** — the grid's sequential block dimension
carries flash-style online-softmax scratch (running max / denominator)
so no dense context copy or score matrix ever materializes.

A grid step covers ``K = pages_per_step(W, page_size)`` pages of one
lane, not one: the grid is ``(B, ceil(W / K))``.  A grid step costs a
tenth of a microsecond and up whatever it computes, and a page is 16
keys, so at one page a step a call was its grid (1.63 ms at 32 lanes x
256 pages on a v5e, 0.10 ms with 32 pages a step).  A lane's pages are
not adjacent in the pool, so the pools stay in HBM (``pl.ANY``) and a
step copies its K pages itself (`make_async_copy`, the physical page
read from the scalar-prefetched block table) into one half of a
double-buffered VMEM scratch, after starting the NEXT block's copies
into the other half; a lane's first block is fetched in its first step.
A step past a lane's last block fetches and computes nothing.  The K
block specs of the pipelined alternative were measured and dropped:
their 2K index maps a step are scalar work the block table's whole
width pays again (1.21 ms for the same call).

A block's pages past the lane's last are the last used page again (a
valid address, finite rows) and are masked by position.  All KV heads
ride in one block: one page fetch serves every head, and the per-head
attention math batches over the leading head dim inside the kernel.
Prefix-shared and CoW-split pages need no special handling: the kernel
only ever addresses physical pages through the table, exactly like the
dense gather it replaces.

Compiled on TPU, ``interpret=True`` on CPU (same numerics, pure jax)
so tier-1 validates the kernel path end to end.

Layout: q [B, 1, H, D]; pools [T, Hkv, D] flat slot pools with
T = num_pages * page_size; block_tables [B, W] physical page ids
(unused entries may point anywhere valid, e.g. the garbage page 0);
context_lens [B] tokens of live context per lane (0 = inactive lane,
output is zeros).

A layer that attends over a WINDOW of the last `window` positions
(`paged_attention(..., window=W, starts=...)`) hands the kernel a table
of the pages that cover that window only: `starts[b]` is the position
the table's first page begins at, and a key at position p counts when
`context_lens[b] - window <= p < context_lens[b]`.  The kernel is then
named `paged_attention_decode_window`, so a trace tells the two kinds of
layer apart.

A BLOCK of S > 1 queries a lane (`q` [B, S, H, D]: a model that
generates by diffusion over blocks, models/laguna.py) is the same walk
with S x G query rows a KV head where a decode step has G: every query
of the block sees every row below `context_lens[b]`, the block's own
rows among them (the caller wrote them first), so there is no mask
among the last S positions and nothing else changes.  The kernel is
then named `paged_attention_block`.

A FLAT pool `[T, Hkv x D]` (models/cache.py, `FlatKVCache`: few wide
heads, 2 x 256, whose `[T, 2, 256]` pool the chip would store sixteen
heads tall) is the same walk: a page is copied `[page_size, Hkv x D]`,
whole tiles, and a head's keys are a D-lane slice of the block instead
of a row of its transpose.  Nothing else changes, the kernel's names
included.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


STEP_VMEM_BYTES = 50 << 20   # what one grid step's pages may take of
# the kernel's fast memory: half the 100.38 MB the chip's compiler allows
VMEM_BYTES_A_NUMBER = 48     # ... at its own count of the kernel at 512
# keys of 32 heads x 128 (100.42 MB asked for 2 Mi cached numbers of a
# step's keys, as many values: the two double-buffered bfloat16 page
# buffers are 8 B of it, the float32 copies and the products' staged
# operands the rest)


def pages_per_step(width: int, page_size: int, row: int) -> int:
    """K, the pages of a lane one grid step covers, from the table's
    static width: a quarter of the table, held between 128 and 512 keys,
    and never more than the table.  Measured on the v5e at every width
    the engine asks for (PERF.md, PR 33): fewer pages a step and a wide
    table is grid overhead again; more and a lane's first block, which
    nothing overlaps, and the copies past its last page cost more than
    the steps saved.  `row`: the numbers of one cache row (KV heads x
    head width), which bound the keys by `STEP_VMEM_BYTES`: every row
    of 2,048 numbers or fewer keeps 512, a row of 32 heads x 128 holds
    256 (at 512 the chip's compiler refuses the kernel by 48 KB)."""
    fit = STEP_VMEM_BYTES // (VMEM_BYTES_A_NUMBER * max(1, row))
    least = max(1, 128 // page_size)
    most = max(least, min(512, fit) // page_size)
    return min(width, max(least, min(most, width // 4)))


def _paged_kernel(bt_ref, cl_ref, *refs, page_size: int, pages: int,
                  scale: float, window: Optional[int] = None,
                  flat_heads: int = 0):
    """`refs`: with a window the scalar `starts`, then q, the k and v
    pools (in HBM), o, the k and v page buffers `[2, pages, page_size,
    Hkv, D]` (`flat_heads` > 0: `[2, pages, page_size, Hkv x D]`, that
    many heads side by side), their DMA semaphores `[2 (k, v), 2
    (buffer half)]` and the three softmax scratch buffers."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    pi = pl.program_id(1)
    n_p = pl.num_programs(1)
    ctx = cl_ref[b]
    if window is None:
        start = 0
    else:
        start, refs = refs[0][b], refs[1:]
    (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, acc_ref, m_ref,
     l_ref) = refs
    # never past the table, whatever the lengths say: a page id read
    # beyond it would be an address
    used = jnp.minimum((ctx - start + page_size - 1) // page_size,
                       bt_ref.shape[1])
    blocks = (used + pages - 1) // pages
    keys = pages * page_size

    def fetch(block, half):
        """Start the copies of `block`'s pages into buffer `half`; a
        page past the lane's last is its last again.  A loop and not K
        copies written out: the same time on the chip, a fraction of
        the program for its compiler and for the interpreter."""
        def page(j, carry):
            at = bt_ref[b, jnp.minimum(block * pages + j, used - 1)]
            pltpu.make_async_copy(k_hbm.at[at], k_buf.at[half, j],
                                  sem.at[0, half]).start()
            pltpu.make_async_copy(v_hbm.at[at], v_buf.at[half, j],
                                  sem.at[1, half]).start()
            return carry
        jax.lax.fori_loop(0, pages, page, 0)

    def wait(half):
        """One wait a copy started into `half`; a wait takes its size
        from the descriptor, so any page stands for the source."""
        def page(j, carry):
            pltpu.make_async_copy(k_hbm.at[0], k_buf.at[half, j],
                                  sem.at[0, half]).wait()
            pltpu.make_async_copy(v_hbm.at[0], v_buf.at[half, j],
                                  sem.at[1, half]).wait()
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
        rows = (keys,) + k_buf.shape[3:]
        q = q_ref[0].astype(jnp.float32)               # [Hkv, G, D]

        def head_major(buf):                            # [Hkv, keys, D]
            block = buf[half].reshape(rows)
            if not flat_heads:
                return block.transpose(1, 0, 2).astype(jnp.float32)
            d = rows[1] // flat_heads
            return jnp.stack([block[:, i * d:(i + 1) * d]
                              for i in range(flat_heads)]
                             ).astype(jnp.float32)

        k, v = head_major(k_buf), head_major(v_buf)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [Hkv, G, keys]
        # rows of the last used page beyond the context length hold
        # garbage (or another sequence's data on a shared page tail),
        # and the block's pages past it are that page again
        pos = start + pi * keys + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, keys), 2)
        valid = pos < ctx                               # [1, 1, keys]
        if window is not None:
            valid = valid & (pos >= ctx - window)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:, :, :1]                        # [Hkv, G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                  # [Hkv, G, 1]
        l_ref[:, :, :1] = l_ref[:, :, :1] * corr \
            + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, :, :1] = m_new
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)         # [Hkv, G, D]

    @pl.when(pi == n_p - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :, :1], 1e-20)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def paged_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array,
                    *, page_size: int,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None,
                    starts: Optional[jax.Array] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Decode attention over paged KV pools: one query a lane, or a
    block of S that all see the same rows (the module's text).

    q: [B, 1, H, D] (or [B, S, H, D]) post-rope queries (the current token's k/v must
    already be written into the pools); pool_k/pool_v: [T, Hkv, D] (or
    flat, [T, Hkv x D]: the module's text);
    block_tables: [B, W] physical page of each logical page; and
    context_lens: [B] live tokens per lane (position < context_lens[b]
    attends — causality for decode, since the query sits at position
    context_lens[b] - 1).  Returns [B, 1, H, D] in q's dtype.

    Cost scales with the pages the lanes use and, a tenth of a
    microsecond a step, with ``B * ceil(W / pages_per_step(W,
    page_size))``: callers shrink W to the max used pages across the
    batch.

    With ``window``, ``block_tables[b]`` lists the pages from position
    ``starts[b]`` (a multiple of the page size) on, and only the last
    ``window`` positions before ``context_lens[b]`` attend.

    ``scale`` is the factor on the scores; None is 1 / sqrt(D).  The
    chip's compiler copies whole 128-lane tiles only, so a model whose
    heads are 64 wide stores two of them side by side in one row and
    asks with queries that are zero on the other head's half
    (models/granite.py): D is then 128 here and the scale the model's.
    """
    from ray_tpu.ops import interpret_default

    return _paged_call(q, pool_k, pool_v, block_tables, context_lens,
                       starts, page_size=page_size, window=window,
                       scale=scale, interpret=interpret_default(interpret))


# A jit of its own: a model's layers call with the same shapes, and so
# the kernel is traced and lowered once a program, not once a layer (a
# tenth of a second each, 48 times in the warm-up of a 12-layer engine's
# four table widths: PERF.md, PR 33).
@functools.partial(jax.jit,
                   static_argnames=("page_size", "window", "scale",
                                    "interpret"))
def _paged_call(q, pool_k, pool_v, block_tables, context_lens, starts, *,
                page_size: int, window: Optional[int],
                scale: Optional[float] = None, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    assert s == 1 or window is None, "a block of queries has no window"
    num_slots, stored = pool_k.shape[0], pool_k.shape[1:]
    flat_row = len(stored) == 1
    hkv = stored[0] // d if flat_row else stored[0]
    assert num_slots % page_size == 0, "pool not page-aligned"
    num_pages = num_slots // page_size
    g = h // hkv
    w = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    if s == 1:
        qr = q.reshape(b, hkv, g, d)                 # GQA head grouping
    else:
        # a block's queries are more rows of their KV head: [B, Hkv,
        # S x G, D]
        qr = q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
            b, hkv, s * g, d)
        g = s * g
    kp = pool_k.reshape(num_pages, page_size, *stored)
    vp = pool_v.reshape(num_pages, page_size, *stored)
    bt = block_tables.astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)

    if interpret:
        # interpret mode carries whole operands through its grid loop,
        # making every step O(pool size) on CPU no matter how narrow
        # the table is.  Gather the table-reachable pages into a
        # compact pool and rebase the table: the kernel sees identical
        # content (shared pages arrive as duplicated rows — same
        # numerics), the gather itself is O(used context), and step
        # cost stays independent of the pool/max-context capacity.
        # The compiled TPU path never takes this branch — it copies
        # single pages straight from the flat pool.
        flat = bt.reshape(-1)
        kp = kp[flat]                                 # [B*W, P, Hkv, D]
        vp = vp[flat]
        bt = jnp.arange(b * w, dtype=jnp.int32).reshape(b, w)

    scalars = (bt, cl) if window is None \
        else (bt, cl, starts.astype(jnp.int32))

    def _q_index(bi, pi, *_scalars):
        return (bi, 0, 0, 0)

    pages = pages_per_step(w, page_size, hkv * d)
    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               pages=pages, scale=scale, window=window,
                               flat_heads=hkv if flat_row else 0)
    page_buf = pltpu.VMEM((2, pages, page_size, *stored), pool_k.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, -(-w // pages)),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), _q_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), _q_index),
        scratch_shapes=[
            page_buf, page_buf,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hkv, g, d), jnp.float32),     # acc
            pltpu.VMEM((hkv, g, 128), jnp.float32),   # running max
            pltpu.VMEM((hkv, g, 128), jnp.float32),   # running denom
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_attention_block" if s > 1
        else "paged_attention_decode" if window is None
        else "paged_attention_decode_window",
    )(*scalars, qr, kp, vp)
    if s == 1:
        return out.reshape(b, 1, h, d)
    return out.reshape(b, hkv, s, h // hkv, d).transpose(
        0, 2, 1, 3, 4).reshape(b, s, h, d)
