"""Kernel tests: ring attention (sequence parallel) and flash attention.

Green-field coverage (the reference has no SP/CP — SURVEY §5.7); the
correctness oracle is the dense reference attention.
"""

import numpy as np
import pytest

from tests.conftest import force_cpu_jax


def _qkv(jax, B=2, S=64, H=4, Hkv=2, D=16):
    import jax.numpy as jnp

    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), dtype=jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D), dtype=jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D), dtype=jnp.float32)
    return q, k, v


def test_ring_attention_matches_dense():
    jax = force_cpu_jax()
    import jax.numpy as jnp

    from ray_tpu.models.llama import default_attention
    from ray_tpu.ops.ring_attention import ring_attention
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(dp=2, sp=4), devices=jax.devices()[:8])
    q, k, v = _qkv(jax)
    dense = default_attention(q, k, v, causal=True)
    with mesh:
        ring = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, head_axis=None)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_non_causal():
    jax = force_cpu_jax()
    from ray_tpu.models.llama import default_attention
    from ray_tpu.ops.ring_attention import ring_attention
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(sp=8), devices=jax.devices()[:8])
    q, k, v = _qkv(jax)
    dense = default_attention(q, k, v, causal=False)
    with mesh:
        ring = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=False, head_axis=None))(q, k, v)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grad_matches_dense():
    jax = force_cpu_jax()
    import jax.numpy as jnp

    from ray_tpu.models.llama import default_attention
    from ray_tpu.ops.ring_attention import ring_attention
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(sp=4), devices=jax.devices()[:4])
    q, k, v = _qkv(jax, S=32)
    with mesh:
        g_ring = jax.jit(jax.grad(lambda q: ring_attention(
            q, k, v, mesh, head_axis=None).sum()))(q)
    g_dense = jax.grad(lambda q: default_attention(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g_dense), np.asarray(g_ring),
                               atol=5e-5, rtol=5e-5)


def test_flash_attention_matches_dense():
    jax = force_cpu_jax()
    from ray_tpu.models.llama import default_attention
    from ray_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(jax, S=128, D=64)
    dense = default_attention(q, k, v, causal=True)
    flash = flash_attention(q, k, v, True, 32, 32, True)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(flash),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_backward():
    jax = force_cpu_jax()
    from ray_tpu.models.llama import default_attention
    from ray_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(jax, S=64, D=32)
    g1 = jax.grad(lambda q: flash_attention(q, k, v, True, 32, 32, True).sum())(q)
    g2 = jax.grad(lambda q: default_attention(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=2e-4, rtol=2e-4)


def test_default_attention_routes_long_prefill_through_flash(monkeypatch):
    """A/B equivalence for the length-threshold routing: at or above
    FLASH_PREFILL_MIN_SEQ (and a multiple of the flash block),
    default_attention must go through the Pallas flash kernel and agree
    with the dense math it replaces."""
    jax = force_cpu_jax()
    from ray_tpu.models import llama
    from ray_tpu.ops import flash_attention as fa

    calls = []
    real = fa.flash_attention

    def spy(q, k, v, *a, **kw):
        calls.append(tuple(q.shape))
        return real(q, k, v, *a, **kw)

    monkeypatch.setattr(fa, "flash_attention", spy)
    monkeypatch.setattr(llama, "FLASH_PREFILL_MIN_SEQ", 128)
    q, k, v = _qkv(jax, S=128, D=32)
    routed = llama.default_attention(q, k, v, causal=True)
    assert calls, "long causal prefill did not route through flash"
    dense = llama.dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)
    # grad traces through the routed path: flash carries its own
    # blockwise backward kernels (numerics: tests/test_flash_backward.py;
    # tracing alone proves the wiring without a second kernel compile)
    jax.make_jaxpr(
        jax.grad(lambda q: llama.default_attention(q, k, v).sum()))(q)


def test_default_attention_short_or_unaligned_stays_dense(monkeypatch):
    """Below the threshold, non-causal, cross-attention (s != t), or
    non-128-multiple sequences keep the XLA dense path."""
    jax = force_cpu_jax()
    from ray_tpu.models import llama
    from ray_tpu.ops import flash_attention as fa

    def boom(*a, **kw):
        raise AssertionError("flash kernel must not be used here")

    monkeypatch.setattr(fa, "flash_attention", boom)
    monkeypatch.setattr(llama, "FLASH_PREFILL_MIN_SEQ", 128)
    q, k, v = _qkv(jax, S=64, D=32)
    llama.default_attention(q, k, v, causal=True)       # short
    llama.default_attention(q, k, v, causal=False)      # non-causal
    q2, k2, v2 = _qkv(jax, S=192, D=32)
    monkeypatch.setattr(llama, "FLASH_PREFILL_MIN_SEQ", 200)
    llama.default_attention(q2, k2, v2, causal=True)    # below threshold


def test_llama_trains_with_sequence_parallelism():
    jax = force_cpu_jax()
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train.gspmd import build_llama_train_state

    mesh = make_mesh(MeshSpec(dp=2, sp=2, tp=2), devices=jax.devices()[:8])
    cfg = LlamaConfig.tiny()
    params, opt, step, _ = build_llama_train_state(cfg, mesh, batch_size=2,
                                                   seq_len=64)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    losses = []
    for _ in range(3):
        params, opt, loss = step(params, opt, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]
