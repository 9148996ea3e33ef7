"""The selective state-space recurrence of a Mamba-2 layer over the
serving tier's STATE pool (models/cache.py, kind `state`): one
fixed-size state a sequence instead of a row a token.

For head n with input x_t [P], step dt_t > 0, A_n < 0 and the layer's
B_t, C_t [N] (one group: every head's):

    H_t = exp(dt_t A_n) H_{t-1} + dt_t x_t (x) B_t        H: [P, N]
    y_t = H_t C_t + D_n x_t

Two forms of it, one a kind of pass:

`ssm_chunk` — a PREFILL pass's: S tokens of a lane at once from the
lane's state H_0, as matrix products and no loop (the published chunked
form, one chunk).  With a_t = dt_t A, c_t = sum_{s<=t} a_s:

    y_t = exp(c_t) C_t.H_0 + sum_{s<=t} exp(c_t - c_s) (C_t.B_s) dt_s x_s
          + D x_t
    H_S = exp(c_S) H_0 + sum_s exp(c_S - c_s) dt_s x_s (x) B_s

A padded position has dt = 0 (the caller masks it by the lane's valid
length): it adds nothing and leaves the state as it was, so a lane of
fewer than S tokens, or none, ends with the state its tokens made.  In
XLA: its fusions carry no kernel's name in a trace, only the scope's.

`ssm_state_update` — a DECODE pass's: one token a lane, the Pallas
kernel `ssm_state_update` over the pool IN PLACE.  The state is the
largest thing a decode pass of many lanes touches (2 MiB a lane a layer
in float32, read and written), and XLA's gather -> update -> scatter
crosses HBM about six times for it; the kernel walks the pool by the
lane's slot (scalar-prefetched: the block's index IS the slot), reads a
lane's state once, writes it once into the aliased pool, and yields y.
A dead lane (slot 0, the garbage slot) is SKIPPED: its grid steps name
the block of the live lane before it (the first live lane's first block
where none is), which the pipeline then neither fetches again nor
writes, and the body does nothing.  Interpreted on the CPU as the other
kernels are.

`conv_chunk` is a recurrent layer's causal depthwise convolution over a
pass, its last `taps - 1` VALID inputs kept as the `conv` part of the
state: this recurrence's x, B and C channels, and the q, k and v
channels of a gated-delta-rule layer (ops/delta_rule.py), which has no
bias and hands in zeros.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

HEAD_BLOCK = 64   # heads of a lane one grid step of the decode kernel
# covers.  Measured on the v5e (PERF.md section 6, PR 40: 64 lanes of 64
# x 64 x 128 float32, 12 calls in a row): 518 us a call at 16 heads a
# step, 480 at 32, 465 at 64 — the published 64 heads are ONE step a
# lane, 2 MiB in and 2 MiB out, 8 MiB of the kernel's double buffers; a
# grid step costs a third of a microsecond beside the 1.3 us a quarter
# of a lane's bytes take


def conv_chunk(u: jax.Array, state: jax.Array, weight: jax.Array,
               bias: jax.Array, lens: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Causal depthwise convolution of a pass, whatever recurrence
    reads it (the module's text).  u: [L, S, C] this pass's
    inputs; state: [L, K-1, C] the lane's last K-1 inputs before them
    (zeros at a sequence's start); weight: [K, C], tap K-1 on the
    newest input; bias: [C]; lens: [L] valid tokens a lane.  Returns
    (the convolution at every position [L, S, C], the new state: the
    last K-1 inputs up to the lane's last VALID one — the old state
    where the lane has none)."""
    taps, s = weight.shape[0], u.shape[1]
    seen = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    out = bias.astype(jnp.float32)
    for k in range(taps):
        out = out + seen[:, k:k + s].astype(jnp.float32) \
            * weight[k].astype(jnp.float32)
    last = lens[:, None] + jnp.arange(taps - 1)[None, :]     # [L, K-1]
    new = jnp.take_along_axis(seen, last[:, :, None], axis=1)
    return out.astype(u.dtype), new.astype(state.dtype)


def ssm_chunk(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
              c: jax.Array, d: jax.Array, h0: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """One chunk of the recurrence for every lane of a pass.

    x: [L, S, H, P]; dt: [L, S, H] float32, 0 at a padded position;
    a: [H] (negative); b, c: [L, S, N]; d: [H]; h0: [L, H, P, N]
    float32.  Returns (y [L, S, H, P] in x's dtype, H_S [L, H, P, N]
    float32).  Sums in float32."""
    f32 = jnp.float32
    s = x.shape[1]
    xf, bf, cf = x.astype(f32), b.astype(f32), c.astype(f32)
    cum = jnp.cumsum(dt * a.astype(f32), axis=1)              # c_t [L,S,H]
    # exp(c_t - c_s) for s <= t; the exponent is masked, not the result:
    # above the diagonal it is positive and may overflow
    diff = cum[:, :, None, :] - cum[:, None, :, :]            # [L,t,s,H]
    lower = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    cb = jnp.einsum("ltn,lsn->lts", cf, bf)                   # C_t . B_s
    m = cb[..., None] * decay * dt[:, None, :, :]             # [L,t,s,H]
    y = jnp.einsum("ltsh,lshp->lthp", m, xf)
    y = y + jnp.einsum("ltn,lhpn->lthp", cf, h0) \
        * jnp.exp(cum)[..., None]
    y = y + d.astype(f32)[None, None, :, None] * xf
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dt               # [L,S,H]
    h = jnp.exp(cum[:, -1])[:, :, None, None] * h0 \
        + jnp.einsum("lsh,lshp,lsn->lhpn", to_end, xf, bf)
    return y.astype(x.dtype), h


def ssm_step(x, dt, a, b, c, d, h):
    """The recurrence itself, one token: x [L, H, P], dt [L, H], b, c
    [L, N], h [L, H, P, N] float32 -> (y [L, H, P] float32, the new h).
    What `ssm_state_update` computes for its live lanes."""
    f32 = jnp.float32
    xf = x.astype(f32)
    h = jnp.exp(dt * a.astype(f32))[:, :, None, None] * h \
        + (dt[:, :, None] * xf)[..., None] \
        * b.astype(f32)[:, None, None, :]
    y = jnp.einsum("lhpn,ln->lhp", h, c.astype(f32)) \
        + d.astype(f32)[None, :, None] * xf
    return y, h


def _update_kernel(row_ref, hold_ref, da_ref, dtx_ref, b_ref, c_ref,
                   h_ref, y_ref, o_ref, *, heads: int):
    """One (lane, head block) step.  da: [1, 1, 1, HB] each head's
    exp(dt A); dtx: [1, 1, P, HB] dt x, a head a COLUMN (so that it
    spreads along the state's lanes); b, c: [1, 1, N]; h, o: the lane's
    block of the pool [1, HB, P, N]; y: [1, 1, P, HB]."""
    from jax.experimental import pallas as pl

    lane, blk = pl.program_id(0), pl.program_id(1)
    live = hold_ref[lane] < 0

    @pl.when(live)
    def _update():
        da, dtx = da_ref[0, 0], dtx_ref[0, 0]           # [1, HB], [P, HB]
        b, c = b_ref[0], c_ref[0]                       # [1, N]
        for j in range(heads):
            h = da[:, j:j + 1] * h_ref[0, j] + dtx[:, j:j + 1] * b
            o_ref[0, j] = h
            y_ref[0, 0, :, j:j + 1] = jnp.sum(h * c, axis=-1,
                                              keepdims=True)

    # no lane of the pass is live: every step names the garbage slot's
    # first block, which must go back as it came
    @pl.when(jnp.logical_not(live) & (lane == 0) & (blk == 0))
    def _through():
        o_ref[...] = h_ref[...]


def ssm_state_update(pool: jax.Array, slots: jax.Array, x: jax.Array,
                     dt: jax.Array, a: jax.Array, b: jax.Array,
                     c: jax.Array, d: jax.Array, *,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence for every lane, the pool updated in
    place.  pool: [slots, H, P, N] float32 (DONATE it: the result
    aliases it); slots: [L] the lane's slot, 0 = a dead lane; x: [L, H,
    P]; dt: [L, H] float32; a, d: [H]; b, c: [L, N].  Returns (y [L, H,
    P] in x's dtype — a dead lane's is D x, read by nobody — and the
    pool)."""
    from ray_tpu.ops import interpret_default

    return _update_call(pool, slots, x, dt, a, b, c, d,
                        interpret=interpret_default(interpret))


# a jit of its own, as `paged_attention._paged_call`: the model's 36
# state layers call with the same shapes, one trace and lowering
@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def _update_call(pool, slots, x, dt, a, b, c, d, *, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    lanes, heads, p = x.shape
    n = b.shape[-1]
    hb = min(HEAD_BLOCK, heads)
    assert heads % hb == 0, f"{heads} heads in blocks of {hb}"
    nb = heads // hb
    xf = x.astype(f32)
    slots = slots.astype(jnp.int32)
    # a dead lane names the block of the live lane before it, held at
    # that lane's last block; before the first live lane, that lane's
    # first block; with no live lane, the garbage slot's first
    live = slots != 0
    at = jnp.arange(lanes, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, at, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    row = slots[src]
    hold = jnp.where(live, -1, jnp.where(before >= 0, nb - 1, 0)
                     ).astype(jnp.int32)

    def by_block(v):                     # [L, H, ...] -> [L, NB, ..., HB]
        v = v.reshape(lanes, nb, hb, -1)
        return v.transpose(0, 1, 3, 2)

    da = by_block(jnp.exp(dt * a.astype(f32)))              # [L,NB,1,HB]
    dtx = by_block(dt[:, :, None] * xf)                      # [L,NB,P,HB]
    b3 = b.astype(f32)[:, None, :]
    c3 = c.astype(f32)[:, None, :]

    def small(li, bi, *_s):
        return (li, bi, 0, 0)

    def vec(li, bi, *_s):
        return (li, 0, 0)

    def state(li, bi, row_ref, hold_ref):
        held = hold_ref[li]
        return (row_ref[li], jnp.where(held < 0, bi, held), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lanes, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hb), small),
            pl.BlockSpec((1, 1, p, hb), small),
            pl.BlockSpec((1, 1, n), vec),
            pl.BlockSpec((1, 1, n), vec),
            pl.BlockSpec((1, hb, p, n), state),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, p, hb), small),
            pl.BlockSpec((1, hb, p, n), state),
        ],
    )
    y, pool = pl.pallas_call(
        functools.partial(_update_kernel, heads=hb),
        out_shape=[jax.ShapeDtypeStruct((lanes, nb, p, hb), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # operands count from the scalars: row, hold, da, dtx, b, c, pool
        input_output_aliases={6: 1},
        interpret=interpret,
        name="ssm_state_update",
    )(row, hold, da, dtx, b3, c3, pool)
    # a dead lane's y is whatever the output's buffer held: zeroed, so
    # that nothing not finite reaches the garbage slots its pass writes
    y = jnp.where(live[:, None, None],
                  y.transpose(0, 1, 3, 2).reshape(lanes, heads, p), 0.0) \
        + d.astype(f32)[None, :, None] * xf
    return y.astype(x.dtype), pool


def ssm_state_update_xla(pool, slots, x, dt, a, b, c, d):
    """`ssm_state_update` as XLA's gather -> update -> scatter: what the
    kernel is tested against (and what it replaces).  A dead lane
    leaves the garbage slot as it was too."""
    live = (slots != 0)[:, None, None, None]
    h0 = pool[slots]
    y, h = ssm_step(x, dt, a, b, c, d, h0)
    pool = pool.at[slots].set(jnp.where(live, h, h0))
    y = jnp.where(live[:, :, :, 0], y, d.astype(jnp.float32)[None, :, None]
                  * x.astype(jnp.float32))
    return y.astype(x.dtype), pool
