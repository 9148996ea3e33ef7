"""Pallas TPU paged attention for single-token decode with GQA.

Decode attention over the serving tier's paged KV cache
(serve/llm.py): instead of gathering the whole ``[B, L]`` slot-table
context out of the flat pools and softmaxing over ``-1e30``-masked
garbage (``models/llama.py cached_attention``), the kernel walks each
sequence's **used pages only** — the grid's sequential page dimension
carries flash-style online-softmax scratch (running max / denominator)
so no dense context copy or score matrix ever materializes.

Page indirection happens in the BlockSpec index maps via scalar
prefetch: the block table and context lengths arrive as
``PrefetchScalarGridSpec`` scalar operands, so the KV block fetched at
grid step ``(b, p)`` is the *physical* page ``block_tables[b, p]``
read straight from the flat pool.  Pages past a sequence's used count
are clamped to its last used page — the same index as the previous
grid step, which Pallas recognizes and skips the redundant copy — and
their compute is predicated off with ``pl.when``.  All KV heads ride
in one block (the grid is ``(B, W)``, not ``(B * Hkv, W)``): one page
fetch serves every head, and the per-head attention math batches over
the leading head dim inside the kernel.  Prefix-shared and CoW-split
pages need no special handling: the kernel only ever addresses
physical pages through the table, exactly like the dense gather it
replaces.

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
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _paged_kernel(bt_ref, cl_ref, *refs, page_size: int, scale: float,
                  window: Optional[int] = None):
    """`refs`: with a window the scalar `starts`, then q, k, v, o and
    the three scratch buffers."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    pi = pl.program_id(1)
    n_p = pl.num_programs(1)
    ctx = cl_ref[b]
    if window is None:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        start = 0
    else:
        st_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        start = st_ref[b]
    used = (ctx - start + page_size - 1) // page_size

    @pl.when(pi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(pi < used)
    def _update():
        q = q_ref[0].astype(jnp.float32)               # [Hkv, G, D]
        k = k_ref[0].transpose(1, 0, 2).astype(jnp.float32)  # [Hkv, P, D]
        v = v_ref[0].transpose(1, 0, 2).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [Hkv, G, P]
        # rows of the last used page beyond the context length hold
        # garbage (or another sequence's data on a shared page tail)
        pos = start + pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)
        valid = pos < ctx                               # [1, 1, P]
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
                    starts: Optional[jax.Array] = None) -> jax.Array:
    """Single-token decode attention over paged KV pools.

    q: [B, 1, H, D] post-rope queries (the current token's k/v must
    already be written into the pools); pool_k/pool_v: [T, Hkv, D];
    block_tables: [B, W] physical page of each logical page; and
    context_lens: [B] live tokens per lane (position < context_lens[b]
    attends — causality for decode, since the query sits at position
    context_lens[b] - 1).  Returns [B, 1, H, D] in q's dtype.

    Cost scales with ``W`` (the block-table width), not the pool or max
    context: callers shrink W to the max used pages across the batch.

    With ``window``, ``block_tables[b]`` lists the pages from position
    ``starts[b]`` (a multiple of the page size) on, and only the last
    ``window`` positions before ``context_lens[b]`` attend.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import interpret_default

    interpret = interpret_default(interpret)
    b, s, h, d = q.shape
    assert s == 1, f"paged_attention is decode-only (S=1), got S={s}"
    num_slots, hkv, _ = pool_k.shape
    assert num_slots % page_size == 0, "pool not page-aligned"
    num_pages = num_slots // page_size
    g = h // hkv
    w = block_tables.shape[1]
    scale = 1.0 / (d ** 0.5)

    qr = q.reshape(b, hkv, g, d)                     # GQA head grouping
    kp = pool_k.reshape(num_pages, page_size, hkv, d)
    vp = pool_v.reshape(num_pages, page_size, hkv, d)
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
        # The compiled TPU path never takes this branch — it DMAs
        # single pages straight from the flat pool via the index map.
        flat = bt.reshape(-1)
        kp = kp[flat]                                 # [B*W, P, Hkv, D]
        vp = vp[flat]
        bt = jnp.arange(b * w, dtype=jnp.int32).reshape(b, w)

    scalars = (bt, cl) if window is None \
        else (bt, cl, starts.astype(jnp.int32))

    def _kv_index(bi, pi, bt, cl, *st):
        # clamp unused grid steps to the last used page: same index as
        # the previous step, so the pipeline skips the redundant copy
        first = st[0][bi] if st else 0
        used = (cl[bi] - first + page_size - 1) // page_size
        p = jnp.minimum(pi, jnp.maximum(used - 1, 0))
        return (bt[bi, p], 0, 0, 0)

    def _q_index(bi, pi, *_scalars):
        return (bi, 0, 0, 0)

    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               scale=scale, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), _q_index),
            pl.BlockSpec((1, page_size, hkv, d), _kv_index),
            pl.BlockSpec((1, page_size, hkv, d), _kv_index),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), _q_index),
        scratch_shapes=[
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
        name="paged_attention_decode" if window is None
        else "paged_attention_decode_window",
    )(*scalars, qr, kp, vp)
    return out.reshape(b, 1, h, d)
