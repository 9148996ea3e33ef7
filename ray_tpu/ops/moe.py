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
               tiles so that a tile belongs to one expert  (moe_dispatch).
               The ROWS are moved by a Pallas call, `moe_dispatch_rows`:
               a grid step a row tile copies its tokens' rows out of x
               by index, HBM to VMEM, and writes the tile
    experts    SwiGLU of every row tile under its expert's matrices, a
               Pallas grouped matmul that visits the ACTIVE tiles only:
               an expert no token chose is never read      (moe_experts)
    combine    each token's rows times their routing weights, summed
               (moe_combine) — a Pallas call, `moe_combine_rows`, that
               walks the row tiles and adds each row into its token's
               float32 sum, which stays in VMEM meanwhile

No token is dropped and there is no capacity factor: the row buffer is
as long as the worst case (every assignment held here, every expert's
group ending in a nearly empty tile), and the grid's unused tiles are
predicated off and fetch nothing — in the row kernels as in the grouped
matmuls: an INACTIVE tile is neither read nor written by the dispatch,
the combine or their transposes, and of the T x k assignments only
those held here move a row (a chip that holds 16 of 64 experts fills a
quarter of its buffer).  So the layer's weight traffic follows the
routed tokens — a decode step of 16 lanes reads the experts those
lanes chose, not every expert held — and its row traffic the rows that
exist.

`valid` masks tokens that are padding (an empty decode lane, the tail of
a prefill chunk): they are routed nowhere, touch no expert and count in
no counter.

The layer is differentiable in x, the router and the three expert
tensors, so a training step runs the same `moe_layer` as the engine.
`route` is plain jax and differentiates itself (float32, through the
top-k's chosen probabilities and the softmax).  Dispatch, experts and
combine are one `jax.custom_vjp` (`_routed`) whose backward is written
by hand: a row belongs to one assignment, so the transpose of the
combine is the dispatch's walk over dy (`moe_combine_rows_bwd`: in one
visit of an active tile each row takes its token's dy times its routing
weight, and its dot with the row's output is the weight's gradient) and
that of the dispatch the combine's over dx's rows with weights of one
(`moe_combine_rows` again).  Between them two Pallas calls, named
`moe_experts_bwd_dx` and `moe_experts_bwd_dw`, visit the active tiles
as the forward does: the first recomputes gate and up and
gives dh, dgate, dup and dx a row tile; the second accumulates dW1,
dW3 and dW2 over each expert's row tiles in a block that stays in VMEM
while the expert lasts.  An expert no token chose is not visited and
its gradient is a written zero (a select on its count, not an unwritten
block).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import interpret_default

__all__ = ["route", "dispatch", "dispatch_rows", "grouped_swiglu", "combine",
           "combine_rows", "combine_rows_bwd", "moe_layer", "row_tile",
           "COUNTERS", "TRAIN_COUNTERS"]

# what `moe_layer` counts for the engine, in the order of its counter
# vector — the last the row tiles that held a row: over `expert_calls`,
# the tiles a touched expert filled — and a training step reads
# TRAIN_COUNTERS, with the row tiles its dropless buffer had
COUNTERS = ("assignments", "expert_calls", "max_load", "row_tiles_active")
TRAIN_COUNTERS = COUNTERS + ("row_tiles",)


class Dispatch(NamedTuple):
    """Where every held assignment's row lies, and whose every tile is."""
    row_assign: jax.Array    # [R] assignment t * k + j of each row (T * k
                             # = padding); its token is row_assign // k
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
          scores: Callable[[jax.Array], jax.Array] = softmax_scores,
          bias: Optional[jax.Array] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """x [T, D], w_router [D, E] -> (expert ids [T, k] int32, weights
    [T, k] float32).  Logits and `scores` (softmax over all experts
    unless the model says otherwise) in float32 whatever the stored
    dtype: a logit moved by a bfloat16 rounding changes who is chosen.
    With a selection `bias` [E] the experts are CHOSEN by score + bias
    and weighted by the score alone."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32),
                         w_router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = scores(logits)
        if bias is None:
            weights, ids = jax.lax.top_k(probs, top_k)
        else:
            _, ids = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
            weights = jnp.take_along_axis(probs, ids, axis=-1)
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
        row_assign = jnp.full((rows,), a, jnp.int32).at[dest].set(
            jnp.arange(a, dtype=jnp.int32), mode="drop")
        tile_start = jnp.arange(n_tiles, dtype=jnp.int32) * tm
        tile_expert = jnp.minimum(
            jnp.searchsorted(ends, tile_start, side="right"),
            e - 1).astype(jnp.int32)
        return Dispatch(row_assign, dest.reshape(t, k), tile_expert,
                        (ends[-1] // tm).astype(jnp.int32), counts)


def _up_kernel(te_ref, na_ref, x_ref, w1_ref, w3_ref, h_ref, *,
               tile_axis: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(tile_axis) < na_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
        h_ref[...] = (jax.nn.silu(gate) * up).astype(h_ref.dtype)


def _down_kernel(te_ref, na_ref, h_ref, w2_ref, y_ref, *, tile_axis: int):
    """The product over the whole hidden width in one float32 `dot`,
    whatever columns of the output the step's block of w2 holds."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(tile_axis) < na_ref[0])
    def _():
        y_ref[...] = jnp.dot(
            h_ref[...], w2_ref[0],
            preferred_element_type=jnp.float32).astype(y_ref.dtype)


# VMEM the blocks of an expert's matrices may take in one grouped call,
# both halves of the pipeline's double buffer together
_EXPERT_BLOCK_BYTES = 48 * 1024 * 1024


def _column_tile(depth: int, width: int, matrices: int, itemsize: int) -> int:
    """Columns of `matrices` blocks `[depth, width]` a grid step holds:
    all of them where the blocks, double buffered, fit
    `_EXPERT_BLOCK_BYTES`, else `width` halved until they do, in
    multiples of 128."""
    tile = width
    while (2 * matrices * depth * tile * itemsize > _EXPERT_BLOCK_BYTES
           and tile % 256 == 0):
        tile //= 2
    return tile


def hidden_tile(d: int, f: int, itemsize: int) -> int:
    """Columns of an expert's hidden width a grid step of the gate/up
    call covers: all `f` where the gate and up blocks of `[d, f]` fit
    `_column_tile`'s budget (3072 x 1024 and every expert the repo ran
    before the 7680 x 2048 ones: 25 MB), else a slice of it (7680 x
    2048: 512, four slices — whole, the up call asked for 120 MB of
    VMEM under a limit of 96; 6144 x 2048: 1024, two slices, which fill
    the budget to the byte)."""
    return _column_tile(d, f, 2, itemsize)


def out_tile(d: int, f: int, itemsize: int) -> int:
    """Columns of the OUTPUT a grid step of the down call covers, by the
    same budget for its one block of `[f, d]`: all `d`, or a slice of it
    (2048 x 7680: 3840, two slices; 2048 x 6144 fits whole).  The cut is
    over w2's columns and not over the sum, so a step's product is the
    unsliced form's one `dot` and no step adds to another's."""
    return _column_tile(f, d, 1, itemsize)


def _tile(i, na):
    """The row tile grid step i stands on: itself, or past the active
    tiles the last active one (no fetch, no write-back)."""
    return jnp.minimum(i, jnp.maximum(na[0] - 1, 0))


def _tile_plumbing(tile_expert: jax.Array, active_tiles: jax.Array):
    """The scalar-prefetch operands, index maps and compiler parameters
    every grouped kernel shares: grid step i is row tile i; past the
    active tiles a step keeps the last active tile's indices (no fetch,
    no write-back) and its body is predicated off."""
    from jax.experimental.pallas import tpu as pltpu

    na = jnp.reshape(active_tiles, (1,)).astype(jnp.int32)
    te = tile_expert.astype(jnp.int32)

    def row_map(i, te, na):
        return (_tile(i, na), 0)

    def w_map(i, te, na):
        return (te[_tile(i, na)], 0, 0)

    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=96 * 1024 * 1024)
    return te, na, row_map, w_map, params


# What a step of a grouped forward call reads and writes, as
# block(row tile, slice, tile_expert): a row tile at its full width, a
# row tile's slice of the columns, the tile's expert's slice of columns
def _rows_whole(tile, part, te):
    return (tile, 0)


def _rows_cut(tile, part, te):
    return (tile, part)


def _expert_cut(tile, part, te):
    return (te[tile], 0, part)


def _walk(n_tiles: int, slices: int):
    """The grid of a grouped forward call whose weight blocks are cut
    into `slices` of columns, and `at(block)`: the index map that puts a
    grid step on `block(row tile, slice, tile_expert)`.

    One slice: the grid is the row tiles alone, the program every expert
    that fits one block has always had — consecutive tiles of one expert
    stand on one weight block, which is fetched once.  More: the slices
    are walked OUTSIDE the row tiles, `(slices, row tiles)`, so that the
    same holds of every (expert, slice) block and an expert's bytes
    cross HBM once a call however many tiles it fills (inside, a tile
    ended on the last slice and the next tile of the SAME expert began
    on the first: every tile re-read its expert whole).  Past the
    active tiles a step keeps the last active tile's indices in its own
    slice (no fetch, no write-back); with no active tile every step
    stands on slice 0, and the call fetches the one block its first
    step cannot avoid."""
    if slices == 1:
        def at(block):
            return lambda i, te, na: block(_tile(i, na), 0, te)
        return (n_tiles,), at

    def at(block):
        return lambda j, i, te, na: block(
            _tile(i, na), jnp.where(na[0] > 0, j, 0), te)
    return (slices, n_tiles), at


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
    activation, then down.  One grid step a row tile where an expert's
    whole matrices are one block, so that consecutive tiles of one
    expert fetch it once.  Where they are too large for that the
    matrices are cut by COLUMNS — gate and up over the hidden width
    (`hidden_tile`), down over its output (`out_tile`) — and the grid is
    (slices, row tiles): every (expert, slice) block is still fetched
    once, and each step writes its own block of the result (`_walk`).
    An inactive tile keeps the last active tile's indices (no fetch)
    and its body is predicated off."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = interpret_default(interpret)
    rows = xs.shape[0]
    na = jnp.reshape(active_tiles, (1,)).astype(jnp.int32)
    te = tile_expert.astype(jnp.int32)

    def call(kernel, out_dtype, tile, x, *ws):
        """x [R, depth] under each tile's expert's `[depth, width]`
        matrices `ws`, `tile` columns a block -> [R, width]."""
        _e, depth, width = ws[0].shape
        grid, at = _walk(rows // tm, width // tile)
        w_spec = pl.BlockSpec((1, depth, tile), at(_expert_cut))
        return pl.pallas_call(
            functools.partial(kernel, tile_axis=len(grid) - 1),
            out_shape=jax.ShapeDtypeStruct((rows, width), out_dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=grid,
                in_specs=[pl.BlockSpec((tm, depth), at(_rows_whole))]
                + [w_spec] * len(ws),
                out_specs=pl.BlockSpec((tm, tile), at(_rows_cut))),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * len(grid),
                vmem_limit_bytes=96 * 1024 * 1024),
            interpret=interpret, name="moe_experts",
        )(te, na, x, *ws)

    d, f = w1.shape[1:]
    itemsize = w1.dtype.itemsize
    h = call(_up_kernel, xs.dtype, hidden_tile(d, f, itemsize), xs, w1, w3)
    return call(_down_kernel, jnp.float32, out_tile(d, f, itemsize), h, w2)


_NT = (((1,), (1,)), ((), ()))   # a b^T
_TN = (((0,), (0,)), ((), ()))   # a^T b


def _bwd_dx_kernel(te_ref, na_ref, x_ref, dy_ref, w1_ref, w3_ref, w2_ref,
                   dx_ref, h_ref, dgate_ref, dup_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) < na_ref[0])
    def _():
        x, dy = x_ref[...], dy_ref[...]
        w1, w3 = w1_ref[0], w3_ref[0]
        gate = jnp.dot(x, w1, preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3, preferred_element_type=jnp.float32)
        sig = jax.nn.sigmoid(gate)
        act = gate * sig                                   # silu(gate)
        dh = jax.lax.dot_general(dy, w2_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
        dgate = (dh * up * sig * (1.0 + gate * (1.0 - sig))).astype(x.dtype)
        dup = (dh * act).astype(x.dtype)
        h_ref[...] = (act * up).astype(h_ref.dtype)
        dgate_ref[...] = dgate
        dup_ref[...] = dup
        dx_ref[...] = (
            jax.lax.dot_general(dgate, w1, _NT,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(dup, w3, _NT,
                                  preferred_element_type=jnp.float32)
        ).astype(dx_ref.dtype)


def _bwd_dw_kernel(te_ref, na_ref, x_ref, dy_ref, h_ref, dgate_ref, dup_ref,
                   dw1_ref, dw3_ref, dw2_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i < na_ref[0])
    def _():
        # the first tile of an expert's group zeroes the block that then
        # stays in VMEM until the expert changes
        @pl.when((i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)]))
        def _():
            dw1_ref[...] = jnp.zeros_like(dw1_ref)
            dw3_ref[...] = jnp.zeros_like(dw3_ref)
            dw2_ref[...] = jnp.zeros_like(dw2_ref)

        x = x_ref[...]
        dw1_ref[0] += jax.lax.dot_general(
            x, dgate_ref[...], _TN, preferred_element_type=jnp.float32)
        dw3_ref[0] += jax.lax.dot_general(
            x, dup_ref[...], _TN, preferred_element_type=jnp.float32)
        dw2_ref[0] += jax.lax.dot_general(
            h_ref[...], dy_ref[...], _TN,
            preferred_element_type=jnp.float32)


def grouped_swiglu_bwd(xs: jax.Array, dy_rows: jax.Array, w1: jax.Array,
                       w3: jax.Array, w2: jax.Array, tile_expert: jax.Array,
                       active_tiles: jax.Array, *, tm: int,
                       interpret: Optional[bool] = None):
    """The transpose of `grouped_swiglu` at rows xs [R, D] for the
    cotangent dy_rows [R, D] (both in the dtype the products run in):
    (dxs [R, D], dW1, dW3 [E, D, F], dW2 [E, F, D] float32).  Rows of
    inactive tiles and the blocks of experts with no tile are left
    unwritten, as in the forward: the caller selects them away."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = interpret_default(interpret)
    rows, d = xs.shape
    e, _, f = w1.shape
    n_tiles = rows // tm
    te, na, row_map, w_map, params = _tile_plumbing(tile_expert,
                                                    active_tiles)
    wide = pl.BlockSpec((tm, d), row_map)
    narrow = pl.BlockSpec((tm, f), row_map)
    up_w = pl.BlockSpec((1, d, f), w_map)
    down_w = pl.BlockSpec((1, f, d), w_map)
    hidden = jax.ShapeDtypeStruct((rows, f), xs.dtype)
    dxs, h, dgate, dup = pl.pallas_call(
        _bwd_dx_kernel,
        out_shape=[jax.ShapeDtypeStruct((rows, d), xs.dtype),
                   hidden, hidden, hidden],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[wide, wide, up_w, up_w, down_w],
            out_specs=[wide, narrow, narrow, narrow]),
        compiler_params=params, interpret=interpret,
        name="moe_experts_bwd_dx",
    )(te, na, xs, dy_rows, w1, w3, w2)
    dw1, dw3, dw2 = pl.pallas_call(
        _bwd_dw_kernel,
        out_shape=[jax.ShapeDtypeStruct((e, d, f), jnp.float32),
                   jax.ShapeDtypeStruct((e, d, f), jnp.float32),
                   jax.ShapeDtypeStruct((e, f, d), jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[wide, wide, narrow, narrow, narrow],
            out_specs=[up_w, up_w, down_w]),
        compiler_params=params, interpret=interpret,
        name="moe_experts_bwd_dw",
    )(te, na, xs, dy_rows, h, dgate, dup)
    return dxs, dw1, dw3, dw2


def combine(y_rows: jax.Array, dest: jax.Array, weights: jax.Array
            ) -> jax.Array:
    """y_rows [R, D]; dest, weights [T, k] -> [T, D] float32: each
    token's held rows times their routing weights, summed.  An
    assignment that is not held (dest = R) adds nothing.  The plain
    form: a gather of all T x k assignments, held or not."""
    with jax.named_scope("moe_combine"):
        rows = y_rows.shape[0]
        here = dest < rows
        picked = y_rows[jnp.minimum(dest, rows - 1)]          # [T, k, D]
        w = jnp.where(here, weights, 0.0)[..., None]
        # a row of an inactive tile was never written: select, not scale
        return jnp.sum(jnp.where(here[..., None], picked, 0.0) * w, axis=1)


def _lanes(d: int) -> int:
    """Columns of a row's slab: a vector register's 128 lanes, or the
    whole of a row narrower than that (a toy model's)."""
    return 128 if d % 128 == 0 else d


def _row_slabs(x: jax.Array) -> jax.Array:
    """x [T, D] -> [T, D / 128, 128] float32: a token's row as a slab of
    whole tiles, which a copy can address by the token (the chip's
    compiler refuses a one-row slice of a `[T, D]` array, whose rows lie
    eight to a tile: "Slice shape along dimension 0 must be aligned to
    tiling (8), but is 1").  One pass over the T tokens, not the rows."""
    t, d = x.shape
    return x.astype(jnp.float32).reshape(t, d // _lanes(d), _lanes(d))


def _row_operands(row_assign, weights, active_tiles, n_assign, top_k, tm):
    """The row kernels' scalar operands, made here so that a kernel's
    scalar work a row is a load (the copies are issued by the scalar
    core: a `min` and a shift a row cost a sixth more at Mellum's
    shapes — 662 against 568 us a call; my chip runs, PR 44): each
    row's token and, where there are weights, its assignment — a
    padding row's the last, masked in the kernel — the routing weights
    by assignment, the rows of each tile that hold a token (the FIRST
    ones: a group starts on a tile and its padding ends it) and the
    active tiles."""
    held = jnp.sum((row_assign < n_assign).reshape(-1, tm), axis=1,
                   dtype=jnp.int32)
    assign = jnp.minimum(row_assign, n_assign - 1)
    token = assign // top_k
    if weights is None:
        assign, w = assign[:1], jnp.zeros((1,), jnp.float32)
    else:
        w = weights.reshape(-1).astype(jnp.float32)
    return (token, assign, w, held,
            jnp.reshape(active_tiles, (1,)).astype(jnp.int32))


def _row_map(i, rt, ra, w, nv, na):
    return (_tile(i, na), 0)


# Rows of a tile a row kernel handles as straight-line code
_ROW_GROUP = 8


def _for_rows(tm: int, unroll: bool, body) -> None:
    """body(r) for the `tm` rows of a tile.  On the chip: a loop over
    groups of `_ROW_GROUP` rows, a group straight-line code — a copy
    issued from a loop of single rows costs twice the time (PR 43), and
    the whole tile unrolled, 128 rows, is dear to TRACE: the Mellum
    cell's warm `setup_s` went 48 -> 95 s for 1 % more tokens a second
    than groups of 8 give (52,900-53,330 against 52,440-52,470; 567
    against 603 us a dispatch call alone; my chip runs, PR 44).  In the
    interpreter a loop of single rows (unrolled, the tests of every
    family that runs the layer took half as long again)."""
    group = min(tm, _ROW_GROUP) if unroll else 1

    def rows(q, carry):
        for j in range(group):
            body(q * group + j)
        return carry
    jax.lax.fori_loop(0, tm // group, rows, 0)


def _gather_kernel(rt_ref, ra_ref, w_ref, nv_ref, na_ref, src_hbm, *refs,
                   tm: int, backward: bool, unroll: bool):
    """Grid step i: the slabs of row tile i's tokens copied from
    `src_hbm` [T, chunks, lanes] into a buffer half — the NEXT active
    tile's started before this one's are waited for — and written out
    as rows.  Forward: out [tm, D] = the rows, a padding row zeros.
    Backward (src = dy): y [tm, D] in; out = dy rows x their routing
    weights, dot [tm, 1] = sum(dy row x y row) in float32.

    Every row of an active tile copies, a padding row the last token's
    slab, masked after: a branch a row to spare a group's last few
    copies cost a third more at Mellum's shapes (765 against 568 us a
    call; my chip runs, PR 44)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if backward:
        y_ref, out_ref, dot_ref, buf, sem, wcol = refs
    else:
        out_ref, buf, sem = refs
    i = pl.program_id(0)
    na = na_ref[0]
    chunks, lanes = buf.shape[2:]

    def start(tile, half):
        _for_rows(tm, unroll, lambda r: pltpu.make_async_copy(
            src_hbm.at[rt_ref[tile * tm + r]], buf.at[half, r],
            sem.at[half]).start())

    @pl.when(i < na)
    def _():
        half = i % 2

        @pl.when(i == 0)
        def _first():
            start(0, 0)

        @pl.when(i + 1 < na)
        def _next():
            start(i + 1, 1 - half)

        def landed(r):
            pltpu.make_async_copy(src_hbm.at[0], buf.at[half, r],
                                  sem.at[half]).wait()
            if backward:   # the row's routing weight, on all its lanes
                wcol[pl.ds(r, 1), :] = jnp.full(
                    (1, lanes), w_ref[ra_ref[i * tm + r]])
        _for_rows(tm, unroll, landed)
        held = jax.lax.broadcasted_iota(jnp.int32, (tm, lanes), 0) < nv_ref[i]
        dot = jnp.zeros((tm, lanes), jnp.float32)
        for c in range(chunks):
            cols = slice(c * lanes, (c + 1) * lanes)
            # a slab's c-th sublane of every row: one strided load
            piece = jnp.where(held, buf[half, :, c, :], 0.0)
            if backward:
                dot += piece * y_ref[:, cols]
                piece = piece * wcol[...]
            out_ref[:, cols] = piece.astype(out_ref.dtype)
        if backward:
            dot_ref[...] = jnp.sum(dot, axis=-1, keepdims=True)


# The row kernels' calls are `jax.jit`s of their own: a model's program
# then traces each ONCE for all its layers, their recomputation under
# remat and the backward, not a time a call (PR 33 found the same of the
# paged kernel: cheap on the device, dear to trace)
@functools.partial(jax.jit, static_argnames=("top_k", "tm", "dtype",
                                             "interpret"))
def _gather_call(src, row_assign, active_tiles, y_rows=None, weights=None, *,
                 top_k, tm, dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    backward = y_rows is not None
    t, d = src.shape
    rows = row_assign.shape[0]
    chunks, lanes = d // _lanes(d), _lanes(d)
    wide = pl.BlockSpec((tm, d), _row_map)
    thin = pl.BlockSpec((tm, 1), _row_map)
    out = jax.ShapeDtypeStruct((rows, d), dtype)
    return pl.pallas_call(
        functools.partial(_gather_kernel, tm=tm, backward=backward,
                          unroll=not interpret),
        out_shape=([out, jax.ShapeDtypeStruct((rows, 1), jnp.float32)]
                   if backward else out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(rows // tm,),
            in_specs=([pl.BlockSpec(memory_space=pl.ANY)]
                      + ([wide] if backward else [])),
            out_specs=[wide, thin] if backward else wide,
            scratch_shapes=(
                [pltpu.VMEM((2, tm, chunks, lanes), jnp.float32),
                 pltpu.SemaphoreType.DMA((2,))]
                + ([pltpu.VMEM((tm, lanes), jnp.float32)] if backward
                   else []))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_combine_rows_bwd" if backward else "moe_dispatch_rows",
    )(*_row_operands(row_assign, weights, active_tiles, t * top_k, top_k,
                     tm), _row_slabs(src), *((y_rows,) if backward else ()))


def dispatch_rows(x: jax.Array, row_assign: jax.Array,
                  active_tiles: jax.Array, *, top_k: int, tm: int,
                  interpret: Optional[bool] = None) -> jax.Array:
    """x [T, D], row_assign [R] -> xs [R, D]: each row's token, a padding
    row of an active tile zeros (the expert kernels multiply it).  A
    Pallas call, `moe_dispatch_rows`, that visits the ACTIVE tiles: an
    inactive tile is neither read nor written."""
    return _gather_call(x, row_assign, active_tiles, top_k=top_k, tm=tm,
                        dtype=x.dtype, interpret=interpret_default(interpret))


def combine_rows_bwd(dy: jax.Array, y_rows: jax.Array, weights: jax.Array,
                     row_assign: jax.Array, active_tiles: jax.Array, *,
                     tm: int, dtype, interpret: Optional[bool] = None):
    """The combine's transpose in one visit of every active tile
    (`moe_combine_rows_bwd`): dy [T, D] float32 (already rounded to
    `dtype`, the products'), y_rows [R, D] float32, weights [T, k] ->
    (dy_rows [R, D] = each row's token's dy x its routing weight, in
    `dtype`; row_dot [R] float32 = sum over D of that dy x y_rows, the
    weight's gradient)."""
    dy_rows, row_dot = _gather_call(
        dy, row_assign, active_tiles, y_rows, weights,
        top_k=weights.shape[1], tm=tm, dtype=jnp.dtype(dtype),
        interpret=interpret_default(interpret))
    return dy_rows, row_dot[:, 0]


def _combine_kernel(rt_ref, ra_ref, w_ref, nv_ref, na_ref, rows_ref, out_ref,
                    stage, *, tm: int, weighted: bool, unroll: bool):
    """Grid step i: row tile i, [tm, D], added row by row — times the
    row's routing weight if `weighted` — into its tokens' sums: out
    [T, D] float32, which stays in VMEM from the first step to the
    last.  A padding row adds zeros to the last token (no branch a row:
    see `_gather_kernel`)."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < na_ref[0])
    def _():
        held = jax.lax.broadcasted_iota(jnp.int32, stage.shape, 0) < nv_ref[i]
        stage[...] = jnp.where(held, rows_ref[...].astype(jnp.float32), 0.0)

        def add(r):
            term = stage[pl.ds(r, 1), :]
            if weighted:
                term = w_ref[ra_ref[i * tm + r]] * term
            out_ref[pl.ds(rt_ref[i * tm + r], 1), :] += term
        _for_rows(tm, unroll, add)


# VMEM the combine's [T, D] float32 sums may take (the chip has 128 MiB;
# Mellum's 8192 x 2304 are 72 of them); over it `_combined` takes the
# plain form
_COMBINE_SUM_BYTES = 80 * 1024 * 1024


@functools.partial(jax.jit, static_argnames=("n_tokens", "top_k", "tm",
                                             "interpret"))
def combine_rows(rows: jax.Array, row_assign: jax.Array,
                 active_tiles: jax.Array, n_tokens: int, *, top_k: int,
                 tm: int, weights: Optional[jax.Array] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """rows [R, D], row_assign [R], weights [T, k] or None (ones) ->
    [T, D] float32: each token's held rows times their weights, summed
    in float32 in the order of the ROWS (by expert, where `combine` sums
    in the order of the token's k choices).  A Pallas call,
    `moe_combine_rows`, over the ACTIVE tiles: a row belongs to one
    assignment, so a walk over the rows that exist meets every held
    assignment once and no other; rows of inactive tiles are not
    read."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = interpret_default(interpret)
    n_rows, d = rows.shape
    if n_tokens * d * 4 > _COMBINE_SUM_BYTES:
        raise ValueError(
            f"combine_rows keeps [{n_tokens}, {d}] float32 sums in VMEM: "
            f"over {_COMBINE_SUM_BYTES} bytes; split the tokens")
    return pl.pallas_call(
        functools.partial(_combine_kernel, tm=tm,
                          weighted=weights is not None,
                          unroll=not interpret),
        out_shape=jax.ShapeDtypeStruct((n_tokens, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(n_rows // tm,),
            in_specs=[pl.BlockSpec((tm, d), _row_map)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="moe_combine_rows",
    )(*_row_operands(row_assign, weights, active_tiles, n_tokens * top_k,
                     top_k, tm), rows)


# Rows of the buffer a pick (R / (T x k)) from which the plain `combine`
# is taken: many held experts and few tokens — Laguna's decode pass,
# 2,368 rows for 320 picks, 90 active tiles of a row or two — make the
# walk over the row tiles dearer than a gather of the picks (82.5
# against 56.4 us a call at 7.4 rows a pick; at 3.0, GLM-5's decode
# pass, 42.3 against 56.2, and every other pass the cells compile lies
# below: PERF.md section 6, my chip runs, PR 44)
_PLAIN_COMBINE_ROWS_A_PICK = 4


def _combined(rows, d: Dispatch, weights, tm, interpret, *, ones=False):
    """Each token's held rows of `rows` [R, D] times their routing
    weights (`ones`: times one), summed: [T, D] float32."""
    t, k = weights.shape
    n_rows, width = rows.shape
    if (n_rows >= _PLAIN_COMBINE_ROWS_A_PICK * t * k
            or t * width * 4 > _COMBINE_SUM_BYTES):
        return combine(rows, d.dest, jnp.ones_like(weights) if ones
                       else weights)
    return combine_rows(rows, d.row_assign, d.active_tiles, t, top_k=k,
                        tm=tm, weights=None if ones else weights,
                        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _routed(x, weights, w1, w3, w2, d: Dispatch, tm: int,
            interpret: Optional[bool]):
    """Dispatch's gather, the experts and the combine: x [T, D], routing
    weights [T, k] -> y [T, D] float32.  The matrices are multiplied in
    x's dtype whatever they are stored in (a trainer's float32 masters
    are rounded here, and their gradients come back float32)."""
    return _routed_fwd(x, weights, w1, w3, w2, d, tm, interpret)[0]


def _routed_fwd(x, weights, w1, w3, w2, d, tm, interpret):
    w1c, w3c, w2c = (w.astype(x.dtype) for w in (w1, w3, w2))
    top_k = weights.shape[1]
    with jax.named_scope("moe_dispatch"):
        xs = dispatch_rows(x, d.row_assign, d.active_tiles, top_k=top_k,
                           tm=tm, interpret=interpret)
    with jax.named_scope("moe_experts"):
        y_rows = grouped_swiglu(xs, w1c, w3c, w2c, d.tile_expert,
                                d.active_tiles, tm=tm, interpret=interpret)
    with jax.named_scope("moe_combine"):
        y = _combined(y_rows, d, weights, tm, interpret)
    # residuals are arrays: the masters' dtypes ride as empty ones
    masters = tuple(jnp.zeros((0,), w.dtype) for w in (w1, w3, w2))
    return y, (xs, weights, w1c, w3c, w2c, d, y_rows, masters)


def _routed_bwd(tm, interpret, res, dy):
    xs, weights, w1c, w3c, w2c, d, y_rows, masters = res
    rows = xs.shape[0]
    with jax.named_scope("moe_combine"):
        # dy in the dtype the products run in, as the rows it multiplies
        info = jnp.finfo(xs.dtype)
        dy = jax.lax.reduce_precision(dy.astype(jnp.float32), info.nexp,
                                      info.nmant)
        # rows of inactive tiles were never written: only rows an
        # assignment points at are read back
        dy_rows, row_dot = combine_rows_bwd(
            dy, y_rows, weights, d.row_assign, d.active_tiles, tm=tm,
            dtype=xs.dtype, interpret=interpret)
        d_weights = jnp.where(
            d.dest < rows, row_dot[jnp.minimum(d.dest, rows - 1)], 0.0)
    with jax.named_scope("moe_experts"):
        dxs, dw1, dw3, dw2 = grouped_swiglu_bwd(
            xs, dy_rows, w1c, w3c, w2c, d.tile_expert, d.active_tiles,
            tm=tm, interpret=interpret)
        chosen = (d.counts > 0)[:, None, None]
        dw1, dw3, dw2 = (
            jnp.where(chosen, dw, 0.0).astype(like.dtype)
            for dw, like in zip((dw1, dw3, dw2), masters))
    with jax.named_scope("moe_dispatch"):
        # the dispatch's transpose: a token's k rows, summed
        dx = _combined(dxs, d, weights, tm, interpret,
                       ones=True).astype(xs.dtype)
    no_grad = jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, jax.dtypes.float0), d)
    return dx, d_weights.astype(weights.dtype), dw1, dw3, dw2, no_grad


_routed.defvjp(_routed_fwd, _routed_bwd)


def moe_layer(x: jax.Array, w_router: jax.Array, w1: jax.Array,
              w3: jax.Array, w2: jax.Array, *, top_k: int,
              held: Tuple[int, int], valid: Optional[jax.Array] = None,
              normalize: bool = True,
              scores: Callable[[jax.Array], jax.Array] = softmax_scores,
              interpret: Optional[bool] = None,
              bias: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The routed part of an expert layer for the experts held here.

    x [T, D]; w_router [D, num_experts]; w1, w3 [E_held, D, F], w2
    [E_held, F, D] (the held experts' matrices only).  Returns (y [T, D]
    float32 — the sum over this share's chosen experts of routing weight
    x SwiGLU_e(x), unscaled; the caller applies the model's factor and
    adds what every share computes alike; `bias` is `route`'s — and the
    counters of
    `TRAIN_COUNTERS` as int32 scalars).  The chosen experts `ids` [T, k]
    ride along under "ids" for a caller that compares routings."""
    t = x.shape[0]
    num_experts = w_router.shape[-1]
    if valid is None:
        valid = jnp.ones((t,), bool)
    ids, weights = route(x, w_router, top_k, normalize, scores, bias)
    tm = row_tile(t, top_k, num_experts)
    d = dispatch(ids, valid, held, tm)
    y = _routed(x, weights, w1, w3, w2, d, tm, interpret)
    counters = {"assignments": jnp.sum(d.counts),
                "expert_calls": jnp.sum(d.counts > 0).astype(jnp.int32),
                "max_load": jnp.max(d.counts),
                "row_tiles_active": d.active_tiles,
                "row_tiles": jnp.int32(d.tile_expert.shape[0]),
                "ids": ids}
    return y, counters
