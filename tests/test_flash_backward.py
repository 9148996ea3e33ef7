"""ops/flash_attention.py with a window and with its blockwise backward,
against masked dense attention, at a small size on the CPU (the Pallas
kernels run in the interpreter)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import default_attention, dense_attention
from ray_tpu.ops import flash_attention as fa

B, S, D = 2, 128, 16


def _qkv(heads, kv_heads, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, S, heads, D)),
            jax.random.normal(ks[1], (B, S, kv_heads, D)),
            jax.random.normal(ks[2], (B, S, kv_heads, D)),
            jax.random.normal(ks[3], (B, S, heads, D)))


# Mellum's group of 8 (8 query heads on one KV head) and Mistral's of 4;
# no window, a window inside one block, across blocks, of one position
@pytest.mark.parametrize("window", [0, 20, 48, 1])
@pytest.mark.parametrize("heads,kv_heads", [(8, 1), (8, 2)],
                         ids=["group8", "group4"])
def test_forward_and_backward_are_masked_dense_attention(heads, kv_heads,
                                                         window):
    q, k, v, w = _qkv(heads, kv_heads)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, True, 32, 32, True, window)

    def dense(q, k, v):
        return dense_attention(q, k, v, True, window)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("blocks", [(16, 32), (32, 16), (64, 64)])
def test_unequal_blocks_cover_the_band(blocks):
    q, k, v, w = _qkv(4, 1, seed=1)
    window = 40
    got = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, True, *blocks, True, window) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(dense_attention(
        q, k, v, True, window) * w), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


def test_the_grids_cover_the_band_only():
    """A query block of a window layer visits the kv blocks that can
    hold a visible key, not the sequence: 8192 positions, window 1024,
    blocks of 128 — 10 steps, not 64; without a window all 64."""
    assert fa._band_steps(64, 128, 128, 1024) == 10
    assert fa._band_steps(64, 128, 128, 0) == 64
    assert fa._band_steps(4, 32, 32, 1024) == 4       # never past the end
    # step j of query block 40: kv blocks 32..40, then past the diagonal
    blocks = [fa._kv_block(40, j, block_q=128, block_k=128, window=1024)
              for j in range(10)]
    assert [int(b) for b, _ in blocks] == list(range(32, 41)) + [40]
    assert [bool(inside) for _, inside in blocks] == [True] * 9 + [False]
    # kv block 32 is seen by query blocks 32..40
    seen = [fa._q_block(32, j, block_q=128, block_k=128, window=1024,
                        n_q=64) for j in range(10)]
    assert [int(b) for b, _ in seen] == list(range(32, 41)) + [40]
    assert [bool(inside) for _, inside in seen] == [True] * 9 + [False]


def test_default_blocks_are_large_for_long_sequences_and_fit_the_window():
    assert fa.default_block(8192) == 512 and fa.default_block(2048) == 512
    assert fa.default_block(768) == 256 and fa.default_block(640) == 128
    assert fa.default_block(8192, window=1024) == 512
    assert fa.default_block(8192, window=512) == 256
    assert fa.default_block(8192, window=64) == 128
    # 8192 positions, window 1024, blocks of 512: 4 steps a query block
    assert fa._band_steps(16, 512, 512, 1024) == 4
    q, k, v, w = _qkv(4, 1, seed=2)
    got = fa.flash_attention(q, k, v, True, None, None, True, 40)
    np.testing.assert_allclose(got, dense_attention(q, k, v, True, 40),
                               atol=2e-5, rtol=2e-5)


def test_window_and_full_calls_carry_different_kernel_names():
    q, k, v, _ = _qkv(4, 1)

    def text(window):
        return jax.jit(jax.grad(lambda q, k: jnp.sum(fa.flash_attention(
            q, k, v, True, 32, 32, True, window)), (0, 1))).lower(
                q, k).as_text(debug_info=True)

    full, banded = text(0), text(32)
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        assert name in full and name + "_window" in banded
        assert name + "_window" not in full


def test_default_attention_is_the_one_route_with_a_window(monkeypatch):
    from ray_tpu.models import llama

    q, k, v, w = _qkv(8, 1)
    short = default_attention(q, k, v, causal=True, window=24)
    monkeypatch.setattr(llama, "FLASH_PREFILL_MIN_SEQ", 128)
    calls = []
    real = fa.flash_attention

    def spy(*a, **kw):
        calls.append(kw.get("window", 0))
        return real(*a, **kw)

    monkeypatch.setattr(fa, "flash_attention", spy)
    routed = llama.default_attention(q, k, v, causal=True, window=24)
    llama.default_attention(q, k, v, causal=True)
    assert calls == [24, 0]
    np.testing.assert_allclose(routed, short, atol=2e-5, rtol=2e-5)
    with pytest.raises(NotImplementedError):
        jax.grad(lambda q: jnp.sum(real(q, k, v, False, 32, 32, True)))(q)
