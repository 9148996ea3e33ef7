"""Pallas TPU flash attention (forward) with GQA and causal masking.

Online-softmax blockwise attention: the KV sequence never materializes a
[S, S] score matrix in HBM — scores live in VMEM one (block_q, block_k)
tile at a time with running max/denominator scratch carried across the
sequential kv grid dimension (guide: scratch persists across grid steps).

The backward pass recomputes through the reference dense attention via
custom_vjp: training paths use ring/default attention (pure jax,
autodiff-friendly); this kernel targets the serving/prefill path where
activation memory dominates.

Layout: q [B, S, H, D]; k/v [B, T, Hkv, D] (GQA groups = H // Hkv).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, block_q: int, block_k: int, causal: bool, scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _update():
        q = q_ref[0, 0].astype(jnp.float32)           # [BQ, D]
        k = k_ref[0, 0].astype(jnp.float32)           # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[:, :1]                          # [BQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                 # [BQ, 1]
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # kv blocks entirely above the diagonal contribute nothing
        @pl.when(ki * block_k <= qi * block_q + (block_q - 1))
        def _():
            _update()
    else:
        _update()

    @pl.when(ki == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    t = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, "seq not divisible by block"
    qt = q.transpose(0, 2, 1, 3)   # [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)   # [B, Hkv, T, D]
    vt = v.transpose(0, 2, 1, 3)
    grid = (b * h, s // block_q, t // block_k)
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bh, qi, ki: (bh // h, bh % h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bh, qi, ki: (bh // h, (bh % h) // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bh, qi, ki: (bh // h, (bh % h) // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bh, qi, ki: (bh // h, bh % h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    """Flash attention with a dense-recompute backward."""
    if interpret is None:
        from ray_tpu.ops import kernel_mode

        interpret = kernel_mode() == "interpret"
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    out = flash_attention(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v)


def _bwd(causal, block_q, block_k, interpret, res, g_out):
    # dense_attention, not default_attention: the latter routes long
    # sequences back into this kernel, which would recurse at trace time
    from ray_tpu.models.llama import dense_attention

    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: dense_attention(q, k, v, causal=causal),
                     q, k, v)
    return vjp(g_out)


flash_attention.defvjp(_fwd, _bwd)
