"""ops/latent_attention.py against plain numpy: the decode kernel
(interpreted) and the blockwise pass of a prefill chunk."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_attention as la

PAGE = 16


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_latent_decode_kernel_against_plain_numpy(dtype, tol):
    """(iii) the kernel (interpreted) over ragged context lengths, a
    lane of 0 and a table wider than any lane uses: every head's
    softmax over the lane's rows, the value the row's first 32."""
    rng = np.random.RandomState(1)
    lanes, heads, width, value, pages = 5, 4, 128, 32, 40
    lens = np.asarray([0, 1, 37, 200, 16], np.int32)
    pool = rng.randn(pages * PAGE, width).astype(np.float32)
    q = rng.randn(lanes, 1, heads, width).astype(np.float32)
    table = np.zeros((lanes, 16), np.int32)
    free = list(rng.permutation(np.arange(1, pages)))
    for b, n in enumerate(lens):
        for p in range(-(-n // PAGE)):
            table[b, p] = free.pop()
    out = la.latent_paged_attention(
        jnp.asarray(q, dtype), jnp.asarray(pool, dtype), table, lens,
        page_size=PAGE, value_width=value, scale=0.1)
    assert out.shape == (lanes, 1, heads, value) and out.dtype == dtype
    out = np.asarray(out.astype(jnp.float32))
    assert not out[0].any()
    for b, n in enumerate(lens[1:], 1):
        slots = (table[b, np.arange(n) // PAGE] * PAGE
                 + np.arange(n) % PAGE)
        rows = np.asarray(jnp.asarray(pool[slots], dtype), np.float32)
        qb = np.asarray(jnp.asarray(q[b, 0], dtype), np.float32)
        s = qb @ rows.T * 0.1
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, :value]
        np.testing.assert_allclose(out[b, 0], want, atol=tol, rtol=tol)


def test_chunk_attention_walks_only_the_blocks_that_hold_context(
        monkeypatch):
    """The prefill pass's attention over a context wider than a block:
    equal to one softmax over the valid columns, lane by lane, and a NaN
    planted in the pool behind the columns of a block a lane does not
    reach is never read by it (the block is not computed, not masked):
    the second lane's context ends in the first block, so its second,
    which the first lane walks, is not its to walk."""
    monkeypatch.setattr(la, "CHUNK_CTX_BLOCK", 32)
    rng = np.random.RandomState(2)
    lanes, chunk, heads, width, value, length = 2, 8, 3, 128, 32, 128
    pool = rng.randn(200, width).astype(np.float32)
    pool[150:] = np.nan
    q = rng.randn(lanes, chunk, heads, width).astype(np.float32)
    his = (40, 13)
    ctx = np.full((lanes, length), 150, np.int32)
    mask = np.zeros((lanes, length), bool)
    q_pos = np.zeros((lanes, chunk), np.int32)
    for b, hi in enumerate(his):
        # the lane's walked blocks end at the next multiple of 32: its
        # masked columns there point at the garbage slot, as the
        # engine's do; every column behind them at a NaN row
        ctx[b, :-(-hi // 32) * 32] = 0
        ctx[b, :hi] = 10 + 50 * b + np.arange(hi)
        mask[b, :hi] = True
        q_pos[b] = hi - chunk + np.arange(chunk)
    pos = np.broadcast_to(np.arange(length, dtype=np.int32), ctx.shape)
    out = np.asarray(la.latent_chunk_attention(
        jnp.asarray(q), jnp.asarray(pool), ctx, pos, mask, q_pos,
        value_width=value, scale=0.1))
    for b, hi in enumerate(his):
        rows = pool[ctx[b, :hi]]
        for i in range(chunk):
            seen = rows[:q_pos[b, i] + 1]
            s = q[b, i] @ seen.T * 0.1
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ seen[:, :value]
            np.testing.assert_allclose(out[b, i], want, atol=2e-5)
    # a lane with nothing valid walks nothing and reads zeros
    out = np.asarray(la.latent_chunk_attention(
        jnp.asarray(q), jnp.asarray(pool), ctx, pos, np.zeros_like(mask),
        q_pos, value_width=value, scale=0.1))
    assert not out.any()
