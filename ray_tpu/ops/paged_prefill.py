"""Pallas TPU attention of a PREFILL CHUNK over the paged `[T, Hkv, D]`
pools: S queries a lane against the pages the lane holds, the kernel
`paged_attention_prefill`.

What it takes the place of is the XLA form `models/llama.cached_attention`
of a prefill pass: a gather of every lane's context at the width of the
pass's bucket (`pool_k[ctx]`, `pool_v[ctx]`: at 32 heads of 128 and 16,384
columns 134 MB a lane, written and read back, twice) and float32 scores
`[heads, S, L]` in HBM, of which the mask then drops the columns the lane
does not have — a pass paid for what its bucket could hold.  Here it pays
for what its lanes hold: the same mathematics (bfloat16 operands, float32
scores and sums, probabilities rounded to the pool's dtype before `p v`),
in flash form.

- THE TABLE IS READ FROM `ctx`, as `ops/latent_attention.py`'s prefill
  kernel reads it: the engine's prefill pass hands a full layer the slot
  of every context position 0..n-1 in order (`serve/llm.py`,
  `_dispatch_prefill`), a page holds `page_size` consecutive positions, so
  every `page_size`-th column names a page and the mask's count is the
  length.  No row is gathered by XLA.
- THE POOLS STAY IN HBM.  A grid step is one lane (and one tile of its KV
  heads, where a lane's query rows are more than a step holds:
  `_tile_heads`); it walks the lane's blocks of `keys` positions in a loop of
  its own, and copies a block's pages by the scalar-prefetched table into
  one half of a double buffer while the other half is computed on
  (`latent_attention._page_copies`, once for the keys and once for the
  values).  THE WALK ENDS at the last block any query of the chunk can
  see — position `max(q_pos)` below the lane's length — and a lane of
  n = 0 walks nothing and reads zeros.
- A BLOCK IS TURNED HEAD-MAJOR ONCE (a page arrives `[page_size, Hkv, D]`,
  a head's keys a row in every tile), then a loop over the tile's KV
  heads multiplies each head's S x G query rows into its `[keys, D]`
  block: float32 scores `[S x G, keys]`, the running maximum, denominator
  and accumulator by head in VMEM.  A cache row may hold more heads than
  the model has (`kv_heads`: the hybrid family stores 30 as 32, zeros
  behind): those are copied with their page and never multiplied.
- THE CAUSAL EDGE runs inside the chunk: a query sees the positions up
  to its own (`q_pos`).  The blocks wholly below the chunk's first
  position are seen by every query and take no mask at all; a padded
  query (`q_pos` 0) sees position 0 and stays finite.  Every query sees
  position 0, which lies in the first block: a row's running maximum is
  a real score from the first block on, so a masked score's probability
  is exp(-1e30 - m) = 0 by itself.

- A FLAT POOL `[T, Hkv x D]` (models/cache.py, `FlatKVCache`: 2 heads of
  256 side by side, whole tiles where `[T, 2, 256]` is stored sixteen
  heads tall) takes the same walk: a page arrives `[page_size, Hkv x D]`
  and a head's keys are a D-lane slice of the block, copied to its row of
  the head-major scratch without a transpose.  With 8 query heads a KV
  head a lane's 256 queries are 2,048 rows a head, and both heads ride in
  one grid step (`_tile_heads(2, 2048)`).

No `window=` and no `block=` yet: their callers still run the XLA form.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.latent_attention import _page_copies

_NEG_INF = -1e30

# the keys a block of the walk covers, the heads a turn of the loop inside
# a block multiplies together, the query rows (KV heads x S x G) a grid
# step holds, and the VMEM the call asks for.  Read on the v5e at the
# hybrid cell's shapes (PERF.md section 6, PR 54: 30 heads over a row of
# 32 x 128, one layer's call ten times inside one program, each behind
# the one before): two lanes of 256 queries over 4,900 rows 0.50 ms at 512
# keys a block and 0.58 at 256, over 15,000 rows 1.32 and 1.51, eight
# lanes of 64 over 4,900 rows 1.09 and 1.40 (a head's turn costs its
# latencies whatever the block holds); under 1,000 rows 256 keys are 0.02
# ms ahead, half a block less past the lane's end.  Three heads a turn
# beat one by 5 and 31 %, and 1,024 keys gained nothing (the refused PR
# 51's readings of this kernel).  At the deep pass a step holds: q and o,
# double-buffered by the pipeline, 7.7 MB; the accumulator 3.9 MB, running
# maximum and denominator 3.9 MB each; the two double-buffered page
# buffers 32 KB a key and the head-major block 16 KB a key, 25 MB at 512
# keys; three heads' scores and probabilities 3 MB
_BLOCK_KEYS = 512
_HEADS_A_TURN = 3            # at most, of the head loop inside a block
_QUERY_ROWS = 8192
_VMEM_BYTES = 64 << 20
_BUFFER_BYTES = 32 << 20     # ... of which a block's buffers may take


def _tile_heads(kv_heads: int, rows: int) -> int:
    """The KV heads a grid step holds: the most that divide `kv_heads`
    and keep a step within _QUERY_ROWS query rows (`rows`: S x G, the
    query rows a KV head)."""
    tile = max(1, min(kv_heads, _QUERY_ROWS // rows))
    while kv_heads % tile:
        tile -= 1
    return tile


def _block_pages(pool, table_width: int, page_size: int) -> int:
    """The pages a block of the walk covers, from the call's static
    shapes: _BLOCK_KEYS keys — fewer where a row of `pool` (the stored
    heads x D) is so wide that the buffers (six rows a key: k and v, two
    halves, the head-major copy) would pass _BUFFER_BYTES — or the whole
    table if narrower."""
    row_bytes = math.prod(pool.shape[1:]) * pool.dtype.itemsize
    keys = min(_BLOCK_KEYS, _BUFFER_BYTES // (6 * row_bytes))
    return min(table_width, max(1, keys // page_size))


def _lanes(x, width: int):
    """`x` [rows, 128], a row's value in every lane, at `width` lanes."""
    from jax.experimental.pallas import tpu as pltpu

    if width % 128:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], width))
    return x if width == 128 else pltpu.repeat(x, width // 128, axis=1)


def _prefill_kernel(bt_ref, seen_ref, lo_ref, q_ref, qpos_ref, k_hbm, v_hbm,
                    o_ref, k_buf, v_buf, k_sem, v_sem, kt_ref, vt_ref,
                    acc_ref, m_ref, l_ref, *, page_size: int, pages: int,
                    scale: float, group: int, flat: bool = False):
    """q [1, Ht, G x S, D]: a tile of Ht KV heads' query rows of one
    lane's chunk, group-major; qpos [1, S, 1]; the pools `[num_pages,
    page_size, R, D]` in HBM (`flat`: `[num_pages, page_size, R x D]`);
    o as q; `k_buf`, `v_buf` [2, pages, page_size, R, D] (or R x D) and
    their DMA semaphores [2] (a buffer half each);
    `kt_ref`, `vt_ref` [R, keys, D]: the block head-major; float32
    scratch by head: acc [Ht, G x S, D], running max and denominator
    [Ht, G x S, 128].  `seen_ref` [B]: the positions the lane's chunk
    can see; `lo_ref` [B]: the chunk's lowest query position."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    first = pl.program_id(1) * q_ref.shape[1]
    seen = seen_ref[b]
    used = jnp.minimum((seen + page_size - 1) // page_size, bt_ref.shape[1])
    blocks = (used + pages - 1) // pages
    keys = pages * page_size
    _one, heads, rows, d = q_ref.shape
    chunk = rows // group
    together = max(u for u in range(1, _HEADS_A_TURN + 1) if heads % u == 0)
    fetch_k, wait_k = _page_copies(bt_ref, k_hbm, k_buf, k_sem, b, used,
                                   pages, unroll=True)
    fetch_v, wait_v = _page_copies(bt_ref, v_hbm, v_buf, v_sem, b, used,
                                   pages, unroll=True)

    def fetch(block, half):
        fetch_k(block, half)
        fetch_v(block, half)

    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(blocks > 0)
    def _first():
        fetch(0, 0)

    def block(masked: bool):
        def body(ci, carry):
            half = ci % 2

            @pl.when(ci + 1 < blocks)
            def _next():
                fetch(ci + 1, 1 - half)

            wait_k(half)
            wait_v(half)
            stored = (keys,) + k_buf.shape[3:]
            if flat:
                for buf, turned in ((k_buf, kt_ref), (v_buf, vt_ref)):
                    rows_of = buf[half].reshape(stored)
                    for h in range(turned.shape[0]):
                        turned[h] = rows_of[:, h * d:(h + 1) * d]
            else:
                kt_ref[:] = k_buf[half].reshape(stored).transpose(1, 0, 2)
                vt_ref[:] = v_buf[half].reshape(stored).transpose(1, 0, 2)
            if masked:
                pos = ci * keys + jax.lax.broadcasted_iota(
                    jnp.int32, (1, keys), 1)
                # the causal edge, and nothing at or past what the chunk
                # can see (the last page's rows there may be another
                # owner's); one mask for every head and group
                sees = (pos <= qpos_ref[0]) & (pos < seen)   # [S, keys]

            def turn(i, carry):
                # `together` heads a turn, stage by stage: a head's chain
                # (product, maximum, exponential, sum, product) is one
                # latency after another, and the next head's products
                # fill the matrix units while this one's softmax runs
                hs = [i * together + u for u in range(together)]
                s = [jax.lax.dot_general(
                    q_ref[0, h], kt_ref[first + h],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                    for h in hs]                         # [G x S, keys]
                if masked:
                    s = [jnp.where(sees[None],
                                   x.reshape(group, chunk, keys),
                                   _NEG_INF).reshape(rows, keys) for x in s]
                # the running maximum and denominator keep a row's value
                # in every lane: a row's reduction comes out that way, and
                # against a block of scores it is repeated by whole
                # tiles, with no broadcast along the lanes
                m_prev = [m_ref[h] for h in hs]          # [G x S, 128]
                m_new = [jnp.maximum(m, jnp.max(x, axis=-1, keepdims=True))
                         for m, x in zip(m_prev, s)]
                p = [jnp.exp(x - _lanes(m, keys)) for x, m in zip(s, m_new)]
                corr = [jnp.exp(a - b) for a, b in zip(m_prev, m_new)]
                for h, x, c, m in zip(hs, p, corr, m_new):
                    l_ref[h] = l_ref[h] * c + jnp.sum(x, axis=-1,
                                                      keepdims=True)
                    m_ref[h] = m
                for h, x, c in zip(hs, p, corr):
                    acc_ref[h] = acc_ref[h] * _lanes(c, d) \
                        + jax.lax.dot_general(
                            x.astype(vt_ref.dtype), vt_ref[first + h],
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                return carry

            jax.lax.fori_loop(0, heads // together, turn, 0)
            return carry
        return body

    # the blocks every query sees whole: those that end at or below the
    # chunk's lowest position (none where a query is padded: position 0)
    free = jnp.minimum(jnp.minimum(lo_ref[b] + 1, seen) // keys, blocks)
    jax.lax.fori_loop(0, free, block(False), 0)
    jax.lax.fori_loop(free, blocks, block(True), 0)
    inv = 1.0 / jnp.maximum(l_ref[:, :, :1], 1e-20)
    o_ref[0] = (acc_ref[:] * inv).astype(o_ref.dtype)


def paged_prefill_attention(q: jax.Array, pool_k: jax.Array,
                            pool_v: jax.Array, ctx: jax.Array,
                            ctx_mask: jax.Array, q_pos: jax.Array, *,
                            page_size: int, kv_heads: Optional[int] = None,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None) -> jax.Array:
    """A chunk of queries a lane (chunked prefill) over the lane's pages.

    q: [B, S, H, D]; pool_k / pool_v: [T, R, D] flat slot pools (this
    call's rows already written), of whose R stored heads the first
    `kv_heads` are the model's (None: all of them); H = G x kv_heads.
    A pool `[T, R x D]` holds a row's heads side by side (the module's
    text).
    The context is what the engine's prefill pass hands a full layer:
    ctx [B, L] the slot of context position 0, 1, ... in order, ctx_mask
    [B, L] true on the lane's first n columns, q_pos [B, S] the queries'
    positions (a padded query: 0).  A query sees the positions up to its
    own below n.  `scale`: the factor on the scores, None 1 / sqrt(D).
    Returns [B, S, H, D] in q's dtype; a lane of n = 0 zeros."""
    from ray_tpu.ops import interpret_default

    # every `page_size`-th column of `ctx` names a page, and the walk ends
    # behind the last position any query sees
    table = (ctx[:, ::page_size] // page_size).astype(jnp.int32)
    seen = jnp.minimum(ctx_mask.sum(-1), q_pos.max(-1) + 1).astype(jnp.int32)
    d = q.shape[-1]
    stored = pool_k.shape[1] // d if pool_k.ndim == 2 else pool_k.shape[1]
    kv_heads = stored if kv_heads is None else kv_heads
    group = q.shape[2] // kv_heads
    return _prefill_call(q, pool_k, pool_v, table, seen, q_pos,
                         page_size=page_size, kv_heads=kv_heads,
                         scale=float(d ** -0.5 if scale is None else scale),
                         tile_heads=_tile_heads(kv_heads, q.shape[1] * group),
                         block_pages=_block_pages(pool_k, table.shape[1],
                                                  page_size),
                         interpret=interpret_default(interpret))


# a jit of its own, as `paged_attention._paged_call`: traced and lowered
# once a program, not once a layer
@functools.partial(jax.jit, static_argnames=(
    "page_size", "kv_heads", "scale", "tile_heads", "block_pages",
    "interpret"))
def _prefill_call(q, pool_k, pool_v, table, seen, q_pos, *, page_size: int,
                  kv_heads: int, scale: float, tile_heads: int,
                  block_pages: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    num_slots, flat = pool_k.shape[0], pool_k.ndim == 2
    stored = pool_k.shape[1] // d if flat else pool_k.shape[1]
    assert num_slots % page_size == 0, "pool not page-aligned"
    assert h % kv_heads == 0 and kv_heads <= stored, (h, kv_heads, stored)
    assert kv_heads % tile_heads == 0, (kv_heads, tile_heads)
    group, width = h // kv_heads, table.shape[1]
    paged = (num_slots // page_size, page_size, *pool_k.shape[1:])
    kp, vp = pool_k.reshape(paged), pool_v.reshape(paged)
    if interpret:
        # as `_paged_call`: the interpreter carries whole operands
        # through its grid loop, so hand it the table's pages only
        kp, vp = kp[table.reshape(-1)], vp[table.reshape(-1)]
        table = jnp.arange(b * width, dtype=jnp.int32).reshape(b, width)

    def _tile(bi, hi, *_scalars):
        return (bi, hi, 0, 0)

    def _lane(bi, hi, *_scalars):
        return (bi, 0, 0)

    rows, keys = group * s, block_pages * page_size
    kernel = functools.partial(_prefill_kernel, page_size=page_size,
                               pages=block_pages, scale=scale, group=group,
                               flat=flat)
    page_buf = pltpu.VMEM((2, block_pages, page_size, *pool_k.shape[1:]),
                          pool_k.dtype)
    head_major = pltpu.VMEM((stored, keys, d), pool_k.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, kv_heads // tile_heads),
        in_specs=[pl.BlockSpec((1, tile_heads, rows, d), _tile),
                  pl.BlockSpec((1, s, 1), _lane),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tile_heads, rows, d), _tile),
        scratch_shapes=[
            page_buf, page_buf,
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
            head_major, head_major,
            pltpu.VMEM((tile_heads, rows, d), jnp.float32),    # acc
            pltpu.VMEM((tile_heads, rows, 128), jnp.float32),  # running max
            pltpu.VMEM((tile_heads, rows, 128), jnp.float32),  # ... denom
        ])
    # a KV head's query rows group-major ([B, Hkv, G x S, D]), so that
    # they share one [S, keys] mask; the transposes fold into the
    # products on either side
    qr = q.reshape(b, s, kv_heads, group, d).transpose(0, 2, 3, 1, 4)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, rows, d), q.dtype),
        grid_spec=grid_spec, interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
        name="paged_attention_prefill",
    )(table, seen, q_pos.min(-1).astype(jnp.int32),
      qr.reshape(b, kv_heads, rows, d), q_pos.astype(jnp.int32)[..., None],
      kp, vp)
    return out.reshape(b, kv_heads, group, s, d).transpose(
        0, 3, 1, 2, 4).reshape(b, s, h, d)
