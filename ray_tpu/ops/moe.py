"""A mixture-of-experts layer that holds a share of the experts.

The layer is told which experts live here (`experts_held`, a contiguous
range of the router's `num_experts`).  It routes every token over ALL
experts — the router keeps its published width and its experts a token —
and computes the part of the result its own experts give: what the
absent experts would add is another chip's to compute (and, on one chip,
is simply not there: no code stands in for the exchange).

    route      logits in float32, softmax over all experts, the top-k
               probabilities divided by their sum           (moe_router)
    dispatch   the (token, expert) assignments whose expert is held
               here, grouped by expert; each group padded to whole row
               tiles so that a tile belongs to one expert  (moe_dispatch)
    experts    SwiGLU of every row tile under its expert's matrices, a
               Pallas grouped matmul that visits the ACTIVE tiles only:
               an expert no token chose is never read      (moe_experts)
    combine    each token's rows times their routing weights, summed
                                                           (moe_combine)

No token is dropped and there is no capacity factor: the row buffer is
as long as the worst case (every assignment held here, every expert's
group ending in a nearly empty tile), and the grid's unused tiles are
predicated off and fetch nothing.  So the layer's weight traffic follows
the routed tokens — a decode step of 16 lanes reads the experts those
lanes chose, not every expert held.

`valid` masks tokens that are padding (an empty decode lane, the tail of
a prefill chunk): they are routed nowhere, touch no expert and count in
no counter.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["route", "dispatch", "grouped_swiglu", "combine", "moe_layer",
           "row_tile", "COUNTERS"]

# what `moe_layer` counts, in the order of its counter vector
COUNTERS = ("assignments", "expert_calls", "max_load")


class Dispatch(NamedTuple):
    """Where every held assignment's row lies, and whose every tile is."""
    row_token: jax.Array     # [R] token of each row (n_tokens = padding)
    dest: jax.Array          # [T, k] row of each assignment (R = not held)
    tile_expert: jax.Array   # [R / tm] local expert of each row tile
    active_tiles: jax.Array  # [] tiles that hold a row
    counts: jax.Array        # [E_held] tokens of each held expert


def row_tile(n_tokens: int, top_k: int, num_experts: int) -> int:
    """Rows of a tile: the power of two at or over the mean group
    (tokens x k / experts), between 16 (a bfloat16 tile's sublanes) and
    128 (the MXU's side).  Decode batches take 16, a prefill pass 32."""
    mean = max(1, -(-n_tokens * top_k // num_experts))
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def softmax_scores(logits: jax.Array) -> jax.Array:
    return jax.nn.softmax(logits, axis=-1)


def route(x: jax.Array, w_router: jax.Array, top_k: int,
          normalize: bool = True,
          scores: Callable[[jax.Array], jax.Array] = softmax_scores
          ) -> Tuple[jax.Array, jax.Array]:
    """x [T, D], w_router [D, E] -> (expert ids [T, k] int32, weights
    [T, k] float32).  Logits and `scores` (softmax over all experts
    unless the model says otherwise) in float32 whatever the stored
    dtype: a logit moved by a bfloat16 rounding changes who is chosen."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32),
                         w_router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = scores(logits)
        weights, ids = jax.lax.top_k(probs, top_k)
        if normalize:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return ids.astype(jnp.int32), weights


def dispatch(ids: jax.Array, valid: jax.Array, held: Tuple[int, int],
             tm: int) -> Dispatch:
    """Group the assignments whose expert lies in `held` = (lo, hi) by
    expert.  ids [T, k]; valid [T] bool.  Static sizes: R = T*k rounded
    up to tiles + one tile an expert (each group may end in a nearly
    empty tile)."""
    with jax.named_scope("moe_dispatch"):
        t, k = ids.shape
        lo, hi = held
        e = hi - lo
        a = t * k
        n_tiles = -(-a // tm) + e
        rows = n_tiles * tm
        local = ids.reshape(a) - lo
        here = (local >= 0) & (local < e) & jnp.repeat(valid, k)
        key = jnp.where(here, local, e)                 # e = not here
        counts = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
        padded = -(-counts // tm) * tm
        ends = jnp.cumsum(padded)
        starts = ends - padded
        first = jnp.cumsum(counts) - counts             # in sorted order
        order = jnp.argsort(key, stable=True)
        skey = key[order]
        safe = jnp.minimum(skey, e - 1)
        dest_sorted = jnp.where(
            skey < e,
            starts[safe] + jnp.arange(a, dtype=jnp.int32) - first[safe],
            rows)
        dest = jnp.zeros((a,), jnp.int32).at[order].set(dest_sorted)
        row_token = jnp.full((rows,), t, jnp.int32).at[dest].set(
            jnp.arange(a, dtype=jnp.int32) // k, mode="drop")
        tile_start = jnp.arange(n_tiles, dtype=jnp.int32) * tm
        tile_expert = jnp.minimum(
            jnp.searchsorted(ends, tile_start, side="right"),
            e - 1).astype(jnp.int32)
        return Dispatch(row_token, dest.reshape(t, k), tile_expert,
                        (ends[-1] // tm).astype(jnp.int32), counts)


def _up_kernel(te_ref, na_ref, x_ref, w1_ref, w3_ref, h_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) < na_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
        h_ref[...] = (jax.nn.silu(gate) * up).astype(h_ref.dtype)


def _down_kernel(te_ref, na_ref, h_ref, w2_ref, y_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) < na_ref[0])
    def _():
        y_ref[...] = jnp.dot(
            h_ref[...], w2_ref[0],
            preferred_element_type=jnp.float32).astype(y_ref.dtype)


def grouped_swiglu(xs: jax.Array, w1: jax.Array, w3: jax.Array,
                   w2: jax.Array, tile_expert: jax.Array,
                   active_tiles: jax.Array, *, tm: int,
                   interpret: Optional[bool] = None) -> jax.Array:
    """xs [R, D] rows grouped by expert in whole tiles of `tm`; w1, w3
    [E, D, F], w2 [E, F, D]; tile_expert [R / tm]; active_tiles [].
    Returns [R, D] float32: SwiGLU of each active tile under its
    expert's matrices (rows of inactive tiles are left unwritten — the
    combine never reads them).

    Two `pallas_call`s, both named `moe_experts`: gate and up with the
    activation, then down.  One grid step a row tile; the expert's whole
    matrices are one block, so consecutive tiles of one expert fetch it
    once, an inactive tile keeps the last active tile's indices (no
    fetch) and its body is predicated off."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        from ray_tpu.ops import kernel_mode

        interpret = kernel_mode() == "interpret"
    rows, d = xs.shape
    f = w1.shape[-1]
    n_tiles = rows // tm
    na = jnp.reshape(active_tiles, (1,)).astype(jnp.int32)
    te = tile_expert.astype(jnp.int32)

    def tile(i, te, na):
        return jnp.minimum(i, jnp.maximum(na[0] - 1, 0))

    def row_map(i, te, na):
        return (tile(i, te, na), 0)

    def w_map(i, te, na):
        return (te[tile(i, te, na)], 0, 0)

    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=96 * 1024 * 1024)
    h = pl.pallas_call(
        _up_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, f), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tm, d), row_map),
                      pl.BlockSpec((1, d, f), w_map),
                      pl.BlockSpec((1, d, f), w_map)],
            out_specs=pl.BlockSpec((tm, f), row_map)),
        compiler_params=params, interpret=interpret,
        name="moe_experts",
    )(te, na, xs, w1, w3)
    return pl.pallas_call(
        _down_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tm, f), row_map),
                      pl.BlockSpec((1, f, d), w_map)],
            out_specs=pl.BlockSpec((tm, d), row_map)),
        compiler_params=params, interpret=interpret,
        name="moe_experts",
    )(te, na, h, w2)


def combine(y_rows: jax.Array, dest: jax.Array, weights: jax.Array
            ) -> jax.Array:
    """y_rows [R, D]; dest, weights [T, k] -> [T, D] float32: each
    token's held rows times their routing weights, summed.  An
    assignment that is not held (dest = R) adds nothing."""
    with jax.named_scope("moe_combine"):
        rows = y_rows.shape[0]
        here = dest < rows
        picked = y_rows[jnp.minimum(dest, rows - 1)]          # [T, k, D]
        w = jnp.where(here, weights, 0.0)[..., None]
        # a row of an inactive tile was never written: select, not scale
        return jnp.sum(jnp.where(here[..., None], picked, 0.0) * w, axis=1)


def moe_layer(x: jax.Array, w_router: jax.Array, w1: jax.Array,
              w3: jax.Array, w2: jax.Array, *, top_k: int,
              held: Tuple[int, int], valid: Optional[jax.Array] = None,
              normalize: bool = True,
              scores: Callable[[jax.Array], jax.Array] = softmax_scores,
              interpret: Optional[bool] = None
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The routed part of an expert layer for the experts held here.

    x [T, D]; w_router [D, num_experts]; w1, w3 [E_held, D, F], w2
    [E_held, F, D] (the held experts' matrices only).  Returns (y [T, D]
    float32 — the sum over this share's chosen experts of routing weight
    x SwiGLU_e(x), unscaled; the caller applies the model's factor and
    adds what every share computes alike — and the counters of
    `COUNTERS` as int32 scalars)."""
    t = x.shape[0]
    num_experts = w_router.shape[-1]
    if valid is None:
        valid = jnp.ones((t,), bool)
    ids, weights = route(x, w_router, top_k, normalize, scores)
    tm = row_tile(t, top_k, num_experts)
    d = dispatch(ids, valid, held, tm)
    with jax.named_scope("moe_dispatch"):
        xpad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
        xs = xpad[d.row_token]
    with jax.named_scope("moe_experts"):
        y_rows = grouped_swiglu(xs, w1, w3, w2, d.tile_expert,
                                d.active_tiles, tm=tm, interpret=interpret)
    y = combine(y_rows, d.dest, weights)
    counters = {"assignments": jnp.sum(d.counts),
                "expert_calls": jnp.sum(d.counts > 0).astype(jnp.int32),
                "max_load": jnp.max(d.counts)}
    return y, counters
