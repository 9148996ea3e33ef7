"""The gated delta rule of a linear-attention layer (Gated DeltaNet:
Yang, Kautz, Hatamizadeh, arXiv:2412.06464) over the serving tier's
STATE pool (models/cache.py, kind `state`): one fixed-size state a
sequence, as `ops/ssm.py`'s, under another recurrence.

For a head with key k_t [dk] (unit length), query q_t [dk], value v_t
[dv], decay alpha_t = exp(g_t) in (0, 1] and step beta_t in [0, 2):

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
        = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                      S: [dk, dv]

The state is not decayed and added to, as a Mamba-2 state is: it is
multiplied by a map with eigenvalues down to 1 - beta (negative past
beta = 1), so a chunk is not one masked product and a decode step reads
`S^T k` before it writes.  `gated_delta_step` is the definition, one
token; `gated_delta_recurrent` scans it.  Two forms, one a kind of pass:

`gated_delta_chunk` — a PREFILL pass's: S tokens a lane in chunks of
`CHUNK` from the lane's state, the Pallas kernel `gated_delta_chunk`.
The published chunk form (the WY representation), for one chunk with
G_i = sum_{s<=i} g_s:

    A = strict_lower(diag(beta) (K K^T * exp(G_i - G_j)))
    T = (I + A)^-1
    W = T diag(beta) (K * exp G),   U = T diag(beta) V
    V' = U - W S_0
    O  = (Q * exp G) S_0 + (Q K^T * exp(G_i - G_j) * lower) V'
    S_C = exp(G_C) S_0 + (K * exp(G_C - G))^T V'

The kernel's grid is (lane, pair of heads, chunk), the chunks of a lane
in turn with the pair's state held in VMEM across them (its output
block, written back once).  T, the inverse of a unit lower triangle,
has no primitive on the chip: it is built by DOUBLING — the inverse of
the diagonal blocks of width 2b from those of width b,
T_2b = T_b - T_b (A * [lower-left b x b of each 2b block]) T_b, six
products for 64 rows — which is block forward substitution and as
stable (the Neumann product (I - A)(I + A^2)... is the same count and
is not: with beta near 2 and keys that repeat, A's powers reach 1e20
before they vanish).  Every product is float32 at the highest
precision: the state is the carry of 248 chunks at the cell's longest
prompt.  A padded position has beta = 0 and g = 0 (the caller masks by
the lane's valid length): its row of A and its V' are zero, and it
leaves the state as it was.

`gated_delta_update` — a DECODE pass's: one token a lane, the Pallas
kernel `gated_delta_update` over the pool IN PLACE by the lane's slot,
as `ssm.ssm_state_update` (a state is read once and written once into
the aliased pool; a dead lane, slot 0, is skipped).  Elementwise on the
vector unit in float32: S^T k and S^T q are sums over the state's rows.

**The stored layout.**  A head's state is [dk, dv] = [96, 192] in the
published model, and neither width is a multiple of the chip's 128
lanes: a pool `[slots, 30, 96, 192]` float32 is stored 256 lanes wide,
a third larger than its shape says.  The pool holds the heads in PAIRS
side by side, `[slots, heads / 2, dk, 2 dv]` (`pair_state`): 384 lanes,
the same bytes and no padding.  Both kernels work on a pair at once.
The chunk kernel stacks the pair's tokens, head 2p's 64 rows over head
2p + 1's, so every product has 128 rows and the products with the state
are block-diagonal (half their arithmetic is on zeros, on a matrix unit
that a 64-row product would leave as idle); the decode kernel spreads
each head's k and q over its own half of the lanes.

**A second head shape** (models/qwen3_next.py): 16 key heads under 32
value heads of 128 x 128, key head j serving value heads 2j and 2j + 1.
The caller repeats q and k to the value heads' number, so the kernels see
32 heads and nothing in this file changes but that map: the state is
paired to (16, 128, 256), 256 lanes, whole tiles by itself.  A PAIR of
value heads then holds the same key head twice: the chunk kernel's
stacked `K K^T` and `Q K^T` compute one 64 x 64 block two times over
(a kernel that read a shared key head once is not written: PERF.md
section 7).

`gated_delta_chunk_xla` and `gated_delta_update_xla` are the plain XLA
twins the kernels are tested against (per-head layout, a triangular
solve).  Interpreted on the CPU as the other kernels are.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

CHUNK = 64        # tokens a chunk (the published kernels'); a power of 2
PAIR_BLOCK = 15   # pairs of heads one grid step of the decode kernel
# covers, where the heads divide by it: the published 30 heads are ONE
# step a lane, 2.2 MB in and 2.2 MB out (`ssm.HEAD_BLOCK` has the
# measurement this follows).  15 does not divide the second shape's 16
# pairs (32 value heads): those get ALL their pairs in one step a lane,
# 2.1 MB in and 2.1 MB out, the same one step a lane
_VMEM_BYTES = 32 * 1024 * 1024
F32 = jnp.float32


# ---------------------------------------------------- the recurrence itself


def gated_delta_step(q, k, v, g, beta, s):
    """One token.  q, k: [L, H, dk]; v: [L, H, dv]; g (log alpha), beta:
    [L, H]; s: [L, H, dk, dv] float32 -> (o [L, H, dv] float32, the new
    s).  What `gated_delta_update` computes for its live lanes."""
    qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
    s = jnp.exp(g.astype(F32))[..., None, None] * s
    u = jnp.einsum("lhkv,lhk->lhv", s, kf)
    s = s + kf[..., :, None] * (beta.astype(F32)[..., None]
                                * (vf - u))[..., None, :]
    return jnp.einsum("lhkv,lhk->lhv", s, qf), s


def gated_delta_recurrent(q, k, v, g, beta, s0):
    """`gated_delta_step` over q, k [L, S, H, dk], v [L, S, H, dv], g,
    beta [L, S, H] from s0 -> (o [L, S, H, dv] float32, S_S)."""
    def step(s, x):
        o, s = gated_delta_step(*x, s)
        return s, o

    s1, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s1


# ------------------------------------------------------------ the layout


def pair_state(s: jax.Array) -> jax.Array:
    """[..., H, dk, dv] -> [..., H / 2, dk, 2 dv]: heads 2p and 2p + 1
    side by side (the module's text)."""
    *lead, h, dk, dv = s.shape
    assert h % 2 == 0, f"{h} heads do not pair"
    s = s.reshape(*lead, h // 2, 2, dk, dv)
    return jnp.moveaxis(s, -3, -2).reshape(*lead, h // 2, dk, 2 * dv)


def unpair_state(s: jax.Array) -> jax.Array:
    """`pair_state`'s inverse."""
    *lead, p, dk, dv2 = s.shape
    s = s.reshape(*lead, p, dk, 2, dv2 // 2)
    return jnp.moveaxis(s, -2, -3).reshape(*lead, 2 * p, dk, dv2 // 2)


# -------------------------------------------------------- the chunk form


def gated_delta_chunk_xla(q, k, v, g, beta, s0, chunk: int = CHUNK):
    """The chunk form in plain XLA, a head at a time in the per-head
    layout: q, k [L, S, H, dk]; v [L, S, H, dv]; g, beta [L, S, H]
    float32 (0 at a padded position); s0 [L, H, dk, dv] float32; S a
    multiple of `chunk` -> (o [L, S, H, dv] float32, S_S).  What the
    kernel is tested against, and the cache-less path of the model."""
    lanes, s, h, dk = q.shape
    assert s % chunk == 0, f"{s} tokens in chunks of {chunk}"
    hi = jax.lax.Precision.HIGHEST

    def per_chunk(x):      # [L, S, H, ...] -> [nc, L, H, C, ...]
        x = x.astype(F32).reshape(lanes, s // chunk, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    eye = jnp.eye(chunk, dtype=F32)

    def one(state, x):
        qc, kc, vc, gc, bc = x                 # [L, H, C, ...]; g, b [L,H,C]
        cum = jnp.cumsum(gc, axis=-1)
        decay = jnp.exp(jnp.where(lower, cum[..., :, None]
                                  - cum[..., None, :], -jnp.inf))
        kk = jnp.einsum("lhik,lhjk->lhij", kc, kc, precision=hi)
        a = jnp.where(strict, bc[..., None] * kk * decay, 0.0)
        rhs = jnp.concatenate(
            [kc * jnp.exp(cum)[..., None], vc], axis=-1) * bc[..., None]
        wu = jax.scipy.linalg.solve_triangular(
            eye + a, rhs, lower=True, unit_diagonal=True)
        w, u = wu[..., :dk], wu[..., dk:]
        vn = u - jnp.einsum("lhik,lhkv->lhiv", w, state, precision=hi)
        qk = jnp.where(lower, jnp.einsum("lhik,lhjk->lhij", qc, kc,
                                         precision=hi) * decay, 0.0)
        o = jnp.einsum("lhik,lhkv->lhiv", qc * jnp.exp(cum)[..., None],
                       state, precision=hi) \
            + jnp.einsum("lhij,lhjv->lhiv", qk, vn, precision=hi)
        to_end = jnp.exp(cum[..., -1:] - cum)
        state = jnp.exp(cum[..., -1])[..., None, None] * state \
            + jnp.einsum("lhik,lhiv->lhkv", kc * to_end[..., None], vn,
                         precision=hi)
        return state, o

    s1, o = jax.lax.scan(one, s0.astype(F32), tuple(
        per_chunk(x) for x in (q, k, v, g, beta)))
    # [nc, L, H, C, dv] -> [L, S, H, dv]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4)
    return o.reshape(lanes, s, h, -1), s1


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


_NT = (((1,), (1,)), ((), ()))     # a @ b^T


def _chunk_kernel(q_ref, k_ref, kt_ref, v_ref, cols_ref, rows_ref, gl_ref,
                  h0_ref, o_ref, h_ref, *, chunk: int, dv: int):
    """One (lane, pair, chunk) step.  q, k: [1, 1, 1, 2C, dk], the
    pair's tokens stacked (head 2p's C rows, then head 2p + 1's); kt:
    k's transpose [1, 1, 1, dk, 2C]; v, o: [1, C, 2 dv], the pair's
    values side by side; cols: [.., 2C, 2] = (G_i, beta_i) a row; rows:
    [.., 2, 2C] = (G_j, G_C - G_j) a column; gl: [.., 1, 2 dv] = G_C a
    lane; h0, h: the pair's state [1, 1, dk, 2 dv]."""
    from jax.experimental import pallas as pl

    c2 = 2 * chunk

    @pl.when(pl.program_id(2) == 0)
    def _load():
        h_ref[...] = h0_ref[...]

    state = h_ref[0, 0]                                  # [dk, 2 dv]
    q, k, kt = q_ref[0, 0, 0], k_ref[0, 0, 0], kt_ref[0, 0, 0]
    v = v_ref[0].astype(F32)
    g_i, b_i = cols_ref[0, 0, 0][:, 0:1], cols_ref[0, 0, 0][:, 1:2]
    g_j, to_end = rows_ref[0, 0, 0][0:1, :], rows_ref[0, 0, 0][1:2, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (c2, c2), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c2, c2), 1)
    same = (row >= chunk) == (col >= chunk)              # one head's block
    lower = same & (row >= col)
    decay = jnp.exp(jnp.where(lower, g_i - g_j, -jnp.inf))
    a = jnp.where(same & (row > col), b_i * _dot(k, k, _NT) * decay, 0.0)
    # T = (I + A)^-1 by doubling (the module's text)
    t = jnp.where(row == col, 1.0, 0.0).astype(F32)
    b = 1
    while b < chunk:
        block = ((row & b) != 0) & ((col & b) == 0) \
            & ((row & -(2 * b)) == (col & -(2 * b)))
        t = t - _dot(_dot(t, jnp.where(block, a, 0.0)), t)
        b *= 2
    rv = jax.lax.broadcasted_iota(jnp.int32, (c2, 2 * dv), 0)
    lv = jax.lax.broadcasted_iota(jnp.int32, (c2, 2 * dv), 1)
    own = (rv >= chunk) == (lv >= dv)       # a head's rows, its own lanes
    vbd = jnp.where(own, jnp.concatenate([v, v], axis=0), 0.0)
    w = _dot(t, b_i * (k * jnp.exp(g_i)))                # [2C, dk]
    u = _dot(t, b_i * vbd)                               # [2C, 2 dv]
    vn = jnp.where(own, u - _dot(w, state), 0.0)
    qk = jnp.where(lower, _dot(q, k, _NT) * decay, 0.0)
    o = _dot(q * jnp.exp(g_i), state) + _dot(qk, vn)     # [2C, 2 dv]
    # (an iota of its own: the chip's compiler refuses a slice of one)
    first = jax.lax.broadcasted_iota(jnp.int32, (chunk, 2 * dv), 1) < dv
    o_ref[0] = jnp.where(first, o[:chunk], o[chunk:]).astype(o_ref.dtype)
    h_ref[0, 0] = jnp.exp(gl_ref[0, 0, 0]) * state + _dot(kt * jnp.exp(to_end), vn)


def gated_delta_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                      g: jax.Array, beta: jax.Array, s0: jax.Array, *,
                      chunk: int = CHUNK, interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """A prefill pass's tokens through the recurrence, chunk by chunk.

    q, k: [L, S, H, dk] (k of unit length, q scaled); v: [L, S, H, dv];
    g (log alpha), beta: [L, S, H] float32, both 0 at a padded
    position; s0: [L, H / 2, dk, 2 dv] float32, the lanes' states in
    the PAIRED layout (zeros for a lane that starts its sequence); S a
    multiple of `chunk`.  Returns (o [L, S, H, dv] in v's dtype, S_S in
    the paired layout)."""
    from ray_tpu.ops import interpret_default

    return _chunk_call(q, k, v, g, beta, s0, chunk=chunk,
                       interpret=interpret_default(interpret))


# a jit of its own: the model's linear layers call with the same shapes
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _chunk_call(q, k, v, g, beta, s0, *, chunk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, s, h, dk = q.shape
    dv = v.shape[-1]
    assert s % chunk == 0 and chunk & (chunk - 1) == 0, \
        f"{s} tokens in chunks of {chunk}"
    assert h % 2 == 0, f"{h} heads do not pair"
    pairs, nc, c2 = h // 2, s // chunk, 2 * chunk

    def stacked(x):        # [L, S, H, ...] -> [L, P, nc, 2C, ...]
        x = x.astype(F32).reshape(lanes, nc, chunk, pairs, 2, *x.shape[3:])
        x = jnp.moveaxis(jnp.moveaxis(x, 3, 1), 4, 3)  # L, P, nc, 2, C
        return x.reshape(lanes, pairs, nc, c2, *x.shape[5:])

    qs, ks = stacked(q), stacked(k)
    gs, bs = stacked(g), stacked(beta)                   # [L, P, nc, 2C]
    cum = jnp.cumsum(gs.reshape(lanes, pairs, nc, 2, chunk), axis=-1)
    end = cum[..., -1:]                                  # G_C a head
    cols = jnp.stack([cum.reshape(gs.shape), bs], axis=-1)
    rows = jnp.stack([cum.reshape(gs.shape),
                      (end - cum).reshape(gs.shape)], axis=-2)
    gl = jnp.repeat(end[..., 0], dv, axis=-1)[..., None, :]  # [L,P,nc,1,2dv]
    v2 = v.reshape(lanes, s, h * dv)

    def tokens(li, pi, ci):
        return (li, pi, ci, 0, 0)

    def values(li, pi, ci):
        return (li, ci, pi)

    def state(li, pi, ci):
        return (li, pi, 0, 0)

    o, s1 = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk, dv=dv),
        out_shape=[jax.ShapeDtypeStruct((lanes, s, h * dv), v.dtype),
                   jax.ShapeDtypeStruct(s0.shape, F32)],
        grid=(lanes, pairs, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, c2, dk), tokens),
            pl.BlockSpec((1, 1, 1, c2, dk), tokens),
            pl.BlockSpec((1, 1, 1, dk, c2), tokens),
            pl.BlockSpec((1, chunk, 2 * dv), values),
            pl.BlockSpec((1, 1, 1, c2, 2), tokens),
            pl.BlockSpec((1, 1, 1, 2, c2), tokens),
            pl.BlockSpec((1, 1, 1, 1, 2 * dv), tokens),
            pl.BlockSpec((1, 1, dk, 2 * dv), state),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 2 * dv), values),
            pl.BlockSpec((1, 1, dk, 2 * dv), state),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="gated_delta_chunk",
    )(qs, ks, jnp.swapaxes(ks, -1, -2), v2, cols, rows, gl,
      s0.astype(F32))
    return o.reshape(lanes, s, h, dv), s1


# ------------------------------------------------------- the decode form


def _update_kernel(row_ref, hold_ref, kq_ref, vab_ref, h_ref, o_ref,
                   out_ref, *, pairs: int, dv: int):
    """One (lane, block of pairs) step.  kq: [1, PB, dk, 4], columns
    (k, k', q, q') of a pair's two heads; vab: [1, PB, 3, 2 dv], rows
    (v, alpha, beta) a lane of the pair's state; h, out: the lane's
    block of the pool [1, PB, dk, 2 dv]; o: [1, PB, 1, 2 dv]."""
    from jax.experimental import pallas as pl

    lane, blk = pl.program_id(0), pl.program_id(1)
    live = hold_ref[lane] < 0

    @pl.when(live)
    def _update():
        dk = h_ref.shape[2]
        first = jax.lax.broadcasted_iota(jnp.int32, (dk, 2 * dv), 1) < dv

        def pair(j, carry):
            cols, rows = kq_ref[0, j], vab_ref[0, j]
            kk = jnp.where(first, cols[:, 0:1], cols[:, 1:2])
            qq = jnp.where(first, cols[:, 2:3], cols[:, 3:4])
            v, alpha, beta = rows[0:1], rows[1:2], rows[2:3]
            s = alpha * h_ref[0, j]
            u = jnp.sum(s * kk, axis=0, keepdims=True)
            s = s + kk * (beta * (v - u))
            out_ref[0, j] = s
            o_ref[0, j] = jnp.sum(s * qq, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, pairs, pair, 0)

    # no lane of the pass is live: every step names the garbage slot's
    # first block, which must go back as it came
    @pl.when(jnp.logical_not(live) & (lane == 0) & (blk == 0))
    def _through():
        out_ref[...] = h_ref[...]


def gated_delta_update(pool: jax.Array, slots: jax.Array, q: jax.Array,
                       k: jax.Array, v: jax.Array, g: jax.Array,
                       beta: jax.Array, *, interpret: Optional[bool] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence for every lane, the pool updated in
    place.  pool: [slots, H / 2, dk, 2 dv] float32, the PAIRED layout
    (DONATE it: the result aliases it); slots: [L] the lane's slot, 0 =
    a dead lane; q, k: [L, H, dk]; v: [L, H, dv]; g (log alpha), beta:
    [L, H] float32.  Returns (o [L, H, dv] in v's dtype — a dead lane's
    is 0 — and the pool)."""
    from ray_tpu.ops import interpret_default

    return _update_call(pool, slots, q, k, v, g, beta,
                        interpret=interpret_default(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def _update_call(pool, slots, q, k, v, g, beta, *, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, h, dk = q.shape
    dv = v.shape[-1]
    assert h % 2 == 0, f"{h} heads do not pair"
    pairs = h // 2
    pb = PAIR_BLOCK if pairs % PAIR_BLOCK == 0 else pairs
    nb = pairs // pb
    slots = slots.astype(jnp.int32)
    # a dead lane names the block of the live lane before it, held at
    # that lane's last block; before the first live lane, that lane's
    # first block; with no live lane, the garbage slot's first
    # (`ssm._update_call`)
    live = slots != 0
    at = jnp.arange(lanes, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, at, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    row = slots[src]
    hold = jnp.where(live, -1, jnp.where(before >= 0, nb - 1, 0)
                     ).astype(jnp.int32)

    def columns(x):        # [L, H, dk] -> [L, P, dk, 2]
        return jnp.swapaxes(x.astype(F32).reshape(lanes, pairs, 2, dk),
                            -1, -2)

    def by_lane(x):        # [L, H] -> [L, P, 2 dv]
        return jnp.repeat(x.astype(F32), dv, axis=-1
                          ).reshape(lanes, pairs, 2 * dv)

    kq = jnp.concatenate([columns(k), columns(q)], axis=-1)
    vab = jnp.stack([v.astype(F32).reshape(lanes, pairs, 2 * dv),
                     by_lane(jnp.exp(g)), by_lane(beta)], axis=2)

    def small(li, bi, *_s):
        return (li, bi, 0, 0)

    def state(li, bi, row_ref, hold_ref):
        held = hold_ref[li]
        return (row_ref[li], jnp.where(held < 0, bi, held), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lanes, nb),
        in_specs=[
            pl.BlockSpec((1, pb, dk, 4), small),
            pl.BlockSpec((1, pb, 3, 2 * dv), small),
            pl.BlockSpec((1, pb, dk, 2 * dv), state),
        ],
        out_specs=[
            pl.BlockSpec((1, pb, 1, 2 * dv), small),
            pl.BlockSpec((1, pb, dk, 2 * dv), state),
        ],
    )
    o, pool = pl.pallas_call(
        functools.partial(_update_kernel, pairs=pb, dv=dv),
        out_shape=[jax.ShapeDtypeStruct((lanes, pairs, 1, 2 * dv), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # operands count from the scalars: row, hold, kq, vab, pool
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="gated_delta_update",
    )(row, hold, kq, vab, pool)
    # a dead lane's o is whatever the output's buffer held: zeroed, so
    # that nothing not finite reaches the garbage slots its pass writes
    o = jnp.where(live[:, None, None], o.reshape(lanes, h, dv), 0.0)
    return o.astype(v.dtype), pool


def gated_delta_update_xla(pool, slots, q, k, v, g, beta):
    """`gated_delta_update` as XLA's gather -> update -> scatter over
    the same paired pool: what the kernel is tested against.  A dead
    lane leaves the garbage slot as it was too."""
    live = slots != 0
    s0 = unpair_state(pool[slots])
    o, s1 = gated_delta_step(q, k, v, g, beta, s0)
    s1 = jnp.where(live[:, None, None, None], s1, s0)
    pool = pool.at[slots].set(pair_state(s1))
    return jnp.where(live[:, None, None], o, 0.0).astype(v.dtype), pool
