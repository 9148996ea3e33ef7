"""Pallas TPU flash attention, forward and backward, with GQA, causal
masking and an optional sliding window.

Online-softmax blockwise attention: neither direction materializes a
[S, S] score matrix in HBM — scores live in VMEM one (block_q, block_k)
tile at a time.  The forward carries running max/denominator scratch
across the sequential kv grid dimension and, under a gradient, also
writes each row's log-sum-exp.  The backward is two blockwise kernels
that recompute a tile's probabilities from that log-sum-exp: one
accumulates dq over a query block's kv blocks, the other dk and dv over
a kv block's query blocks and over the query heads of its GQA group.
They work on the TRANSPOSED tile (k q^T, [block_k, block_q]) so that the
per-row statistics are lane vectors that broadcast without a transpose.

With `window` = W > 0 a query sees the last W positions up to its own
(i - W < j <= i).  The grids then cover only the band: a query block
visits the ~W / block_k kv blocks that can hold a visible key, a kv
block the query blocks that can see it; steps past the diagonal are
predicated off and fetch nothing.  Without a window the grids are the
whole square, the half above the diagonal predicated off the same way.

Every caller comes through `models.llama.default_attention`, which
routes causal self-attention of 512 positions or more here: a family's
whole-sequence forward (no cache; forward only) and the training steps
(both directions).  Window and full calls carry different kernel names
(`flash_attention_fwd` / `flash_attention_fwd_window`, and so for
`_bwd_dq` and `_bwd_dkv`), so a device trace tells them apart.

Layout: q [B, S, H, D]; k/v [B, T, Hkv, D] (GQA groups = H // Hkv).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import interpret_default

_NEG_INF = -1e30
_LANES = 128


def _band_steps(n_inner: int, block_outer: int, block_inner: int,
                window: int) -> int:
    """Grid steps of the inner dimension: every inner block without a
    window; with one, the most inner blocks a single outer block's band
    can touch (the band spans window - 1 + block_outer positions, which
    may straddle one block more than it fills)."""
    if not window:
        return n_inner
    return min(n_inner, (window + block_outer - 2) // block_inner + 2)


def _kv_block(qi, j, *, block_q: int, block_k: int, window: int):
    """(kv block, inside the band) of step j of query block qi."""
    last = (qi * block_q + block_q - 1) // block_k      # the diagonal's
    if window:
        first = jnp.maximum(qi * block_q - (window - 1), 0) // block_k
    else:
        first = 0
    ki = first + j
    # past the diagonal: keep the last block's index, so nothing is fetched
    return jnp.minimum(ki, last), ki <= last


def _q_block(ki, j, *, block_q: int, block_k: int, window: int, n_q: int):
    """(query block, inside the band) of step j of kv block ki."""
    first = (ki * block_k) // block_q                    # the diagonal's
    last = n_q - 1
    if window:
        last = jnp.minimum(
            (ki * block_k + block_k - 1 + window - 1) // block_q, last)
    qi = first + j
    return jnp.minimum(qi, last), qi <= last


def _visible(q_pos, k_pos, window: int):
    mask = q_pos >= k_pos
    if window:
        mask = mask & (k_pos > q_pos - window)
    return mask


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, block_q: int,
                  block_k: int, causal: bool, window: int, scale: float,
                  with_lse: bool):
    from jax.experimental import pallas as pl

    if with_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if causal:
        ki, inside = _kv_block(qi, j, block_q=block_q, block_k=block_k,
                               window=window)
    else:
        ki, inside = j, None

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
            seen = _visible(q_pos, k_pos, window)
            s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_ref[:, :1]                          # [BQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(seen, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                 # [BQ, 1]
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # kv blocks past the diagonal contribute nothing
        pl.when(inside)(_update)
    else:
        _update()

    @pl.when(j == n_j - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = jnp.broadcast_to(
                m_ref[:, :1] + jnp.log(denom), lse_ref.shape[2:])


def default_block(s: int, window: int = 0) -> int:
    """The block side where the caller names none.  A grid step costs
    about a third of a microsecond whatever it computes, so a long
    sequence takes the largest side that divides it, up to 512 (a
    [512, 512] float32 tile is 1 MiB of VMEM; on the v5e, forward and
    backward of 8 heads x 8192 positions take 29.2 ms at 128, 11.0 at
    256, 5.7 at 512: PERF.md section 6, PR 32).  A window layer at most
    half its window, because the band is covered in whole blocks and a
    wider block computes mostly masked pairs (window 1024: 6.9 ms at
    128, 3.3 at 256, 2.3 at 512)."""
    for side in (512, 256, 128):
        if s % side == 0 and (not window or side <= max(window // 2, 128)):
            return side
    return 128


def _blocks(s: int, t: int, block_q: Optional[int], block_k: Optional[int],
            window: int = 0):
    block_q = min(block_q or default_block(s, window), s)
    block_k = min(block_k or default_block(t, window), t)
    assert s % block_q == 0 and t % block_k == 0, "seq not divisible by block"
    return block_q, block_k


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    # the backward's [block, block] float32 tiles at 512 pass the
    # default scoped limit
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _suffix(window: int) -> str:
    return "_window" if window else ""


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool, window: int = 0, with_lse: bool = False):
    """out [B, S, H, D]; with `with_lse` also each row's log-sum-exp
    [B, H, S] float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    t = k.shape[1]
    block_q, block_k = _blocks(s, t, block_q, block_k, window)
    assert not window or (causal and s == t), "a window is causal"
    qt = q.transpose(0, 2, 1, 3)   # [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)   # [B, Hkv, T, D]
    vt = v.transpose(0, 2, 1, 3)
    grid = (b * h, s // block_q,
            _band_steps(t // block_k, block_q, block_k, window))
    scale = 1.0 / (d ** 0.5)

    def kv_map(bh, qi, j):
        ki = (_kv_block(qi, j, block_q=block_q, block_k=block_k,
                        window=window)[0] if causal else j)
        return (bh // h, (bh % h) // g, ki, 0)

    def q_map(bh, qi, j):
        return (bh // h, bh % h, qi, 0)

    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, causal=causal,
                               window=window, scale=scale,
                               with_lse=with_lse)
    out_shape = [jax.ShapeDtypeStruct((b, h, s, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, block_q, d), q_map)]
    if with_lse:
        # a row's statistic fills its 128 lanes; the backward reads lane 0
        out_shape.append(jax.ShapeDtypeStruct((b, h, s, _LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, block_q, _LANES), q_map))
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),       # acc
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
        ],
        compiler_params=_compiler_params(), interpret=interpret,
        name="flash_attention_fwd" + _suffix(window),
    )(qt, kt, vt)
    out = outs[0].transpose(0, 2, 1, 3)
    if with_lse:
        return out, outs[1][..., 0]
    return out


# ---------------------------------------------------------------- backward
#
# With P = softmax(S) row-wise, S = scale q k^T, O = P v and
# delta_i = sum_d dO_id O_id:
#     dV = P^T dO        dP = dO V^T        dS = P * (dP - delta)
#     dQ = scale dS K    dK = scale dS^T Q
# Both kernels hold the tile transposed: s_t = scale k q^T [BK, BQ], with
# the rows' log-sum-exp and delta as [1, BQ] lane vectors.

_NT = (((1,), (1,)), ((), ()))   # a b^T
_NN = (((1,), (0,)), ((), ()))   # a b
_TN = (((0,), (0,)), ((), ()))   # a^T b


def _tile_t(q, k, v, do, lse, delta, qi, ki, *, block_q, block_k, window,
            scale):
    """(p_t, ds_t) [BK, BQ] float32 of one tile, from operands in their
    stored dtype (float32 accumulation)."""
    s_t = jax.lax.dot_general(
        k, q, _NT, preferred_element_type=jnp.float32) * scale
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    seen = _visible(q_pos, k_pos, window)
    p_t = jnp.where(seen, jnp.exp(jnp.where(seen, s_t, _NEG_INF) - lse), 0.0)
    dp_t = jax.lax.dot_general(
        v, do, _NT, preferred_element_type=jnp.float32)
    return p_t, p_t * (dp_t - delta) * scale


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, block_q, block_k, window, scale):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ki, inside = _kv_block(qi, j, block_q=block_q, block_k=block_k,
                           window=window)

    @pl.when(inside)
    def _():
        k = k_ref[0, 0]
        _, ds_t = _tile_t(q_ref[0, 0], k, v_ref[0, 0], do_ref[0, 0],
                          lse_ref[0, 0], delta_ref[0, 0], qi, ki,
                          block_q=block_q, block_k=block_k, window=window,
                          scale=scale)
        acc_ref[:] += jax.lax.dot_general(
            ds_t.astype(k.dtype), k, _TN,
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, block_q, block_k, window, scale,
                n_q, steps):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    j = pl.program_id(2)      # (query head of the group, band step)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    qi, inside = _q_block(ki, j % steps, block_q=block_q, block_k=block_k,
                          window=window, n_q=n_q)

    @pl.when(inside)
    def _():
        q, do = q_ref[0, 0], do_ref[0, 0]
        p_t, ds_t = _tile_t(q, k_ref[0, 0], v_ref[0, 0], do,
                            lse_ref[0, 0], delta_ref[0, 0], qi, ki,
                            block_q=block_q, block_k=block_k,
                            window=window, scale=scale)
        dv_acc[:] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, _NN,
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, _NN,
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g_out, block_q: int, block_k: int,
                    interpret: bool, window: int = 0):
    """(dq, dk, dv) of causal attention, blockwise."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    block_q, block_k = _blocks(s, s, block_q, block_k, window)
    n_q, n_k = s // block_q, s // block_k
    scale = 1.0 / (d ** 0.5)
    qt, kt, vt, dot = (x.transpose(0, 2, 1, 3) for x in (q, k, v, g_out))
    delta = jnp.sum(g_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)           # [B, H, S]
    rows = (b, h, 1, s)
    lse, delta = lse.reshape(rows), delta.reshape(rows)
    band = dict(block_q=block_q, block_k=block_k, window=window)

    # ---- dq: a query block over its kv blocks
    def q_map(bh, qi, j):
        return (bh // h, bh % h, qi, 0)

    def row_map(bh, qi, j):
        return (bh // h, bh % h, 0, qi)

    def kv_map(bh, qi, j):
        return (bh // h, (bh % h) // g, _kv_block(qi, j, **band)[0], 0)

    q_spec = pl.BlockSpec((1, 1, block_q, d), q_map)
    row_spec = pl.BlockSpec((1, 1, 1, block_q), row_map)
    kv_spec = pl.BlockSpec((1, 1, block_k, d), kv_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, **band),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        grid=(b * h, n_q, _band_steps(n_k, block_q, block_k, window)),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="flash_attention_bwd_dq" + _suffix(window),
    )(qt, kt, vt, dot, lse, delta)

    # ---- dk, dv: a kv block over the query blocks that see it, for
    # each query head of its group in turn
    steps = _band_steps(n_q, block_k, block_q, window)

    def head_block(bk, ki, j):
        qi = _q_block(ki, j % steps, n_q=n_q, **band)[0]
        return bk // hkv, (bk % hkv) * g + j // steps, qi

    def q_map2(bk, ki, j):
        bi, hi, qi = head_block(bk, ki, j)
        return (bi, hi, qi, 0)

    def row_map2(bk, ki, j):
        bi, hi, qi = head_block(bk, ki, j)
        return (bi, hi, 0, qi)

    def kv_map2(bk, ki, j):
        return (bk // hkv, bk % hkv, ki, 0)

    q_spec = pl.BlockSpec((1, 1, block_q, d), q_map2)
    row_spec = pl.BlockSpec((1, 1, 1, block_q), row_map2)
    kv_spec = pl.BlockSpec((1, 1, block_k, d), kv_map2)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, n_q=n_q, steps=steps,
                          **band),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b, hkv, s, d), v.dtype)],
        grid=(b * hkv, n_k, g * steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="flash_attention_bwd_dkv" + _suffix(window),
    )(qt, kt, vt, dot, lse, delta)
    return tuple(x.transpose(0, 2, 1, 3) for x in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None, window: int = 0):
    """Flash attention; `window` > 0 keeps the last `window` positions
    up to the query's own.  Blocks of `default_block` where none is
    named.  Differentiable where causal (the blockwise backward
    above)."""
    return _flash_forward(q, k, v, causal, block_q, block_k,
                          interpret_default(interpret), window)


def _fwd(q, k, v, causal, block_q, block_k, interpret, window):
    if not causal or q.shape[1] != k.shape[1]:
        raise NotImplementedError(
            "flash_attention's backward is causal self-attention's")
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k,
                              interpret_default(interpret), window, with_lse=True)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, interpret, window, res, g_out):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g_out, block_q, block_k,
                           interpret_default(interpret), window)


flash_attention.defvjp(_fwd, _bwd)
