"""ops/moe.py: an expert layer that holds a share of the experts, at a
small size on the CPU (the Pallas kernels run in the interpreter)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe

D, F, E, K = 32, 16, 8, 2


def _weights(seed=0, experts=E):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (D, experts), jnp.float32) * D ** -0.5,
            jax.random.normal(ks[1], (experts, D, F), jnp.float32) * D ** -0.5,
            jax.random.normal(ks[2], (experts, D, F), jnp.float32) * D ** -0.5,
            jax.random.normal(ks[3], (experts, F, D), jnp.float32) * F ** -0.5)


def _naive(x, w_router, w1, w3, w2, held, top_k=K, valid=None):
    """Every held expert on every token, times its routing weight or 0."""
    ids, weights = moe.route(x, w_router, top_k)
    out = jnp.zeros(x.shape, jnp.float32)
    for local, e in enumerate(range(*held)):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        if valid is not None:
            w = jnp.where(valid, w, 0.0)
        y = (jax.nn.silu(x @ w1[local]) * (x @ w3[local])) @ w2[local]
        out = out + w[:, None] * y
    return out


def _layer(x, wr, w1, w3, w2, held, **kw):
    lo, hi = held
    return moe.moe_layer(x, wr, w1[lo:hi], w3[lo:hi], w2[lo:hi], top_k=K,
                         held=held, **kw)


@pytest.mark.parametrize("tokens", [1, 7, 40], ids=lambda t: f"{t}tok")
def test_layer_is_every_expert_on_every_token_weighted(tokens):
    wr, w1, w3, w2 = _weights()
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, D), jnp.float32)
    y, counters = _layer(x, wr, w1, w3, w2, (0, E))
    np.testing.assert_allclose(y, _naive(x, wr, w1, w3, w2, (0, E)),
                               rtol=1e-5, atol=1e-5)
    assert int(counters["assignments"]) == tokens * K   # none dropped


def test_every_token_on_one_expert_and_experts_nobody_chose():
    """A router that sends every token to experts 3 and 5: those two
    hold every assignment (40 each, three row tiles of 16), the other
    six are never touched."""
    _wr, w1, w3, w2 = _weights()
    wr = jnp.zeros((D, E)).at[:, 3].set(1.0).at[:, 5].set(0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (40, D))) + 0.1
    ids, _w = moe.route(x, wr, K)
    assert set(np.asarray(ids).ravel()) == {3, 5}
    y, counters = _layer(x, wr, w1, w3, w2, (0, E))
    np.testing.assert_allclose(y, _naive(x, wr, w1, w3, w2, (0, E)),
                               rtol=1e-5, atol=1e-5)
    assert int(counters["expert_calls"]) == 2
    assert int(counters["max_load"]) == 40
    d = moe.dispatch(ids, jnp.ones((40,), bool), (0, E), 16)
    assert int(d.active_tiles) == 6
    assert list(np.asarray(d.tile_expert[:6])) == [3, 3, 3, 5, 5, 5]
    # a share that holds neither computes nothing and says so
    y0, c0 = _layer(x, wr, w1, w3, w2, (6, 8))
    assert float(jnp.abs(y0).max()) == 0.0
    assert int(c0["assignments"]) == int(c0["expert_calls"]) == 0


def test_padding_tokens_are_routed_nowhere():
    wr, w1, w3, w2 = _weights()
    x = jax.random.normal(jax.random.PRNGKey(3), (12, D), jnp.float32)
    valid = jnp.arange(12) < 5
    y, counters = _layer(x, wr, w1, w3, w2, (0, E), valid=valid)
    want = _naive(x, wr, w1, w3, w2, (0, E), valid=valid)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(y[5:]).max()) == 0.0
    assert int(counters["assignments"]) == 5 * K


@pytest.mark.parametrize("shares", [2, 4], ids=lambda n: f"{n}shares")
def test_the_shares_add_up_to_the_uncut_reference_layer(shares):
    """ISSUE 28 point 5: the routed parts every share's `ops/moe.py`
    gives, plus the shared expert counted once, equal the uncut plain
    reference's expert layer for the same tokens."""
    from benchmarks import reference_laguna as ref

    wr, w1, w3, w2 = _weights(seed=5)
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    shared = {"w1": {"kernel": jax.random.normal(ks[0], (D, F)) * D ** -0.5},
              "w3": {"kernel": jax.random.normal(ks[1], (D, F)) * D ** -0.5},
              "w2": {"kernel": jax.random.normal(ks[2], (F, D)) * F ** -0.5}}
    x = jax.random.normal(ks[3], (24, D), jnp.float32)
    per = E // shares
    parts = [_layer(x, wr, w1, w3, w2, (s * per, (s + 1) * per))
             for s in range(shares)]
    assert sum(int(c["assignments"]) for _y, c in parts) == 24 * K
    shared_y = ref._swiglu(x, *(shared[n]["kernel"]
                                for n in ("w1", "w3", "w2")))
    ours = ref.combine_shared(shared_y, sum(y for y, _c in parts), 2.5)
    with jax.default_matmul_precision("highest"):
        routed, _margin = ref._routed(
            x, {"moe_router": wr, "moe_experts_w1": w1,
                "moe_experts_w3": w3, "moe_experts_w2": w2},
            top_k=K, normalize=True, lo=0)
        uncut = ref.combine_shared(shared_y, routed, 2.5)
    np.testing.assert_allclose(ours, uncut, rtol=1e-4, atol=1e-5)


def _two_experts(tokens, k):
    """ids [T, k]: every token on experts 3 and 5 (k = 2) or 3 (k = 1)."""
    return jnp.tile(jnp.asarray([[3, 5][:k]], jnp.int32), (tokens, 1))


def _drawn(tokens, k, seed):
    """ids [T, k]: k distinct experts of E a token."""
    scores = jax.random.uniform(jax.random.PRNGKey(seed), (tokens, E))
    return jax.lax.top_k(scores, k)[1].astype(jnp.int32)


# name: (tokens, k, held, row tile, rows' dtype, ids, valid tokens)
_ROW_CASES = {
    "a decode batch": (16, 2, (0, E), 16, jnp.bfloat16, None, 16),
    "a prefill pass": (512, 2, (0, 4), 32, jnp.float32, None, 512),
    "a training shape": (96, 2, (2, 6), 16, jnp.bfloat16, None, 96),
    "valid masks a tail": (40, 2, (0, E), 16, jnp.float32, None, 29),
    "no assignment held": (24, 2, (6, 8), 16, jnp.float32, _two_experts, 24),
    "one expert holds every assignment, the buffer full":
        (48, 1, (3, 4), 16, jnp.bfloat16, _two_experts, 48),
}


@pytest.mark.parametrize("case", list(_ROW_CASES), ids=lambda c: c.replace(
    " ", "_").replace(",", ""))
def test_row_kernels_are_the_plain_forms_they_replace(case):
    """`moe_dispatch_rows`, `moe_combine_rows` and `moe_combine_rows_bwd`
    against the gathers over the whole buffer they replaced, kept here
    as the reference: `xpad[row_token]`, and `y_rows[dest]` with the
    select and the weighted sum.  The rows of INACTIVE tiles are NaN on
    the way in: nothing reads them.  Dispatch and the backward's rows
    are exact; the sums are float32 in another ORDER (the kernel adds a
    token's rows as they lie, by expert; the plain form over its k
    choices), so they agree to float32's last places."""
    tokens, k, held, tm, dtype, ids_of, n_valid = _ROW_CASES[case]
    ids = ids_of(tokens, k) if ids_of else _drawn(tokens, k, seed=tokens)
    valid = jnp.arange(tokens) < n_valid
    d = moe.dispatch(ids, valid, held, tm)
    rows = d.row_assign.shape[0]
    row_token = d.row_assign // k              # `tokens` on a padding row
    live = int(d.active_tiles) * tm
    if case == "no assignment held":
        assert live == 0
    if case.startswith("one expert"):
        assert int(jnp.sum(d.counts)) == tokens * k and live == tokens * k
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (tokens, D), jnp.float32).astype(dtype)
    weights = jax.random.uniform(ks[1], (tokens, k), jnp.float32)
    dead = (jnp.arange(rows) >= live)[:, None]
    y_rows = jnp.where(dead, jnp.nan,
                       jax.random.normal(ks[2], (rows, D), jnp.float32))
    dxs = jnp.where(dead, jnp.nan, jax.random.normal(
        ks[3], (rows, D), jnp.float32)).astype(dtype)
    dy = jax.random.normal(ks[4], (tokens, D), jnp.float32)
    dy = dy.astype(dtype).astype(jnp.float32)   # as _routed_bwd rounds it

    def pad(a):
        return jnp.concatenate([a, jnp.zeros((1, D), a.dtype)])

    def plain_combine(rows_, w):
        here = d.dest < rows
        picked = rows_[jnp.minimum(d.dest, rows - 1)]
        return jnp.sum(jnp.where(here[..., None], picked, 0.0)
                       * jnp.where(here, w, 0.0)[..., None], axis=1)

    # dispatch: the row is the row
    xs = moe.dispatch_rows(x, d.row_assign, d.active_tiles, top_k=k, tm=tm,
                           interpret=True)
    assert xs.dtype == dtype and xs.shape == (rows, D)
    np.testing.assert_array_equal(
        np.asarray(xs[:live], np.float32),
        np.asarray(pad(x)[row_token][:live], np.float32))
    # combine: float32 sums, the poisoned rows never read
    y = moe.combine_rows(y_rows, d.row_assign, d.active_tiles, tokens,
                         top_k=k, tm=tm, weights=weights, interpret=True)
    assert y.dtype == jnp.float32
    np.testing.assert_allclose(y, plain_combine(y_rows, weights),
                               rtol=2e-6, atol=2e-6)
    # the dispatch's transpose: weights of one
    dx = moe.combine_rows(dxs, d.row_assign, d.active_tiles, tokens,
                          top_k=k, tm=tm, interpret=True)
    np.testing.assert_allclose(
        dx, plain_combine(dxs.astype(jnp.float32), jnp.ones_like(weights)),
        rtol=2e-6, atol=2e-6)
    # the combine's transpose: dy's rows, their weights, their dots
    dy_rows, row_dot = moe.combine_rows_bwd(
        dy, y_rows, weights, d.row_assign, d.active_tiles, tm=tm,
        dtype=dtype, interpret=True)
    dy_tok = pad(dy)[row_token][:live]
    # a row belongs to one assignment: its weight (0 on a padding row)
    row_w = jnp.zeros((rows + 1,), jnp.float32).at[d.dest].set(
        jnp.where(d.dest < rows, weights, 0.0))[:rows]
    assert dy_rows.dtype == dtype and row_dot.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(dy_rows[:live], np.float32),
        np.asarray((dy_tok * row_w[:live, None]).astype(dtype), np.float32))
    np.testing.assert_allclose(
        row_dot[:live], jnp.sum(dy_tok * y_rows[:live], axis=-1),
        rtol=1e-5, atol=1e-5)
    if n_valid < tokens:
        assert float(jnp.abs(y[n_valid:]).max()) == 0.0


def test_row_tile_follows_the_mean_group():
    assert moe.row_tile(32, 10, 256) == 16      # a decode batch
    assert moe.row_tile(512, 10, 256) == 32     # a prefill pass
    assert moe.row_tile(8192, 10, 256) == 128   # never over the MXU's side



def test_an_expert_too_wide_for_one_block_is_walked_in_slices(monkeypatch):
    """`hidden_tile`, `out_tile`: an expert whose blocks do not fit the
    budget is taken a slice of its columns a grid step — gate and up
    over the hidden width, down over its output — the same layer (an
    expert nobody chose and padding included), and the sizes the cells
    run keep one slice or get the slices the chip's VMEM allows."""
    assert moe.hidden_tile(3072, 1024, 2) == 1024      # Laguna's expert
    assert moe.hidden_tile(2304, 896, 2) == 896
    assert moe.hidden_tile(2048, 768, 2) == 768
    assert moe.hidden_tile(7680, 2048, 2) == 512
    assert moe.hidden_tile(6144, 2048, 2) == 1024     # two slices
    assert moe.out_tile(3072, 1024, 2) == 3072
    assert moe.out_tile(2304, 896, 2) == 2304
    assert moe.out_tile(2048, 768, 2) == 2048
    assert moe.out_tile(7680, 2048, 2) == 3840
    assert moe.out_tile(6144, 2048, 2) == 6144
    d, f = 256, 512
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    wr = jax.random.normal(ks[0], (d, 4), jnp.float32) * d ** -0.5
    w1, w3 = (jax.random.normal(k, (4, d, f), jnp.float32) * d ** -0.5
              for k in ks[1:3])
    w2 = jax.random.normal(ks[3], (4, f, d), jnp.float32) * f ** -0.5
    x = jax.random.normal(ks[4], (23, d), jnp.float32)
    valid = jnp.arange(23) % 5 != 0
    whole, _ = moe.moe_layer(x, wr, w1, w3, w2, top_k=2, held=(0, 4),
                             valid=valid)
    monkeypatch.setattr(moe, "_EXPERT_BLOCK_BYTES", 2 * 2 * d * 128 * 4)
    assert moe.hidden_tile(d, f, 4) == 128
    assert moe.out_tile(d, f, 4) == 128
    jax.clear_caches()
    sliced, counters = moe.moe_layer(x, wr, w1, w3, w2, top_k=2,
                                     held=(0, 4), valid=valid)
    jax.clear_caches()
    np.testing.assert_allclose(sliced, whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        sliced, _naive_wide(x, wr, w1, w3, w2, valid), rtol=1e-5, atol=1e-5)
    assert int(counters["assignments"]) == 2 * int(valid.sum())


# Row tiles of each of four experts, the tiles the buffer has, and the
# rows of the last active tile that hold a token (the others are the
# zeros the dispatch writes)
_TILE_LAYOUTS = {
    "0_1_3_5_tiles": ((0, 1, 3, 5), 12, 8),
    "last_tile_partial": ((2, 0, 0, 3), 7, 3),
    "first_expert_only": ((4, 0, 0, 0), 6, 8),
    "one_tile_each": ((1, 1, 1, 1), 8, 8),
    "all_tiles_dead": ((0, 0, 0, 0), 5, 0),
    "all_tiles_active": ((2, 1, 1, 3), 7, 8),
    "one_row": ((0, 0, 1, 0), 5, 1),
}
# (d, f) and its (gate/up, down) slices under a budget of 1 MiB in
# float32: the 7680 x 2048 expert cut
# to test size (gate and up in four slices, down in two) and
# the 6144 x 2048 one (two slices, down whole)
_SLICED_SIZES = {"up4_down2": ((512, 512), (4, 2)),
                 "up2_down1": ((256, 512), (2, 1))}
_TEST_BUDGET = 1024 * 1024
_TM = 8


def _layout(name):
    tiles, n_tiles, last_rows = _TILE_LAYOUTS[name]
    tile_expert = [e for e, n in enumerate(tiles) for _ in range(n)]
    active = len(tile_expert)
    # past the groups the dispatch names the last expert
    tile_expert += [len(tiles) - 1] * (n_tiles - active)
    return np.asarray(tile_expert, np.int32), active, n_tiles, last_rows


@pytest.mark.parametrize("size", list(_SLICED_SIZES))
@pytest.mark.parametrize("layout", list(_TILE_LAYOUTS))
def test_sliced_forward_is_the_plain_swiglu_of_every_active_row(
        monkeypatch, layout, size):
    """`grouped_swiglu` over hand-built tile layouts, its experts cut in
    slices, against each row's expert's plain SwiGLU in float32."""
    (d, f), slices = _SLICED_SIZES[size]
    monkeypatch.setattr(moe, "_EXPERT_BLOCK_BYTES", _TEST_BUDGET)
    assert (f // moe.hidden_tile(d, f, 4),
            d // moe.out_tile(d, f, 4)) == slices
    tile_expert, active, n_tiles, last_rows = _layout(layout)
    rng = np.random.RandomState(len(layout))
    w1, w3 = (rng.randn(4, d, f).astype(np.float32) * d ** -0.5
              for _ in range(2))
    w2 = rng.randn(4, f, d).astype(np.float32) * f ** -0.5
    xs = rng.randn(n_tiles * _TM, d).astype(np.float32)
    if active:
        xs[(active - 1) * _TM + last_rows:active * _TM] = 0.0
    got = np.asarray(moe.grouped_swiglu(
        jnp.asarray(xs), jnp.asarray(w1), jnp.asarray(w3), jnp.asarray(w2),
        jnp.asarray(tile_expert), jnp.int32(active), tm=_TM))
    assert got.shape == (n_tiles * _TM, d) and got.dtype == np.float32
    for t in range(active):
        e, rows = tile_expert[t], xs[t * _TM:(t + 1) * _TM]
        gate = rows @ w1[e]
        plain = (gate / (1.0 + np.exp(-gate)) * (rows @ w3[e])) @ w2[e]
        np.testing.assert_allclose(got[t * _TM:(t + 1) * _TM], plain,
                                   rtol=2e-5, atol=2e-5)
    if active and last_rows < _TM:
        assert not got[(active - 1) * _TM + last_rows:active * _TM].any()


def _blocks_in_grid_order(grid, index_map, tile_expert, active):
    """The block every grid step stands on, the last grid index moving
    fastest (the order a TPU walks a grid in)."""
    te = jnp.asarray(tile_expert)
    na = jnp.asarray([active], jnp.int32)
    return [tuple(int(v) for v in index_map(*step, te, na))
            for step in np.ndindex(*grid)]


def _returned_to(blocks):
    """The blocks a walk leaves and stands on again later: each costs a
    second fetch (the pipeline skips a fetch only where a step's block
    is the step before's)."""
    runs = [b for n, b in enumerate(blocks) if n == 0 or blocks[n - 1] != b]
    return sorted({b for b in runs if runs.count(b) > 1})


@pytest.mark.parametrize("slices", [4, 2])
@pytest.mark.parametrize("layout", list(_TILE_LAYOUTS))
def test_no_weight_block_is_returned_to_after_it_is_left(layout, slices):
    """The property the grid's order is for: every (expert, slice) block
    of a sliced expert is one run of consecutive steps, so each touched
    expert's bytes are fetched once a call however many tiles it fills;
    every active tile meets every slice of its expert, and a block of
    the result is stood on once."""
    tile_expert, active, n_tiles, _rows = _layout(layout)
    grid, at = moe._walk(n_tiles, slices)
    assert grid == (slices, n_tiles)
    weights = _blocks_in_grid_order(grid, at(moe._expert_cut), tile_expert,
                                    active)
    assert _returned_to(weights) == []
    touched = {int(tile_expert[t]) for t in range(active)}
    if active:
        assert set(weights) == {(e, 0, j) for e in touched
                                for j in range(slices)}
    else:       # the one block the first step cannot avoid
        assert set(weights) == {(int(tile_expert[0]), 0, 0)}
    out = _blocks_in_grid_order(grid, at(moe._rows_cut), tile_expert, active)
    assert _returned_to(out) == []
    live = [out[j * n_tiles + t] for j in range(slices)
            for t in range(active)]
    assert live == [(t, j) for j in range(slices) for t in range(active)]
    rows = _blocks_in_grid_order(grid, at(moe._rows_whole), tile_expert,
                                 active)
    assert rows == [(t, 0) for t, _j in out]


def test_slices_inside_the_row_tiles_return_to_a_block():
    """What the grid did before: slices walked INSIDE the row tiles come
    back to an expert's first slice at every further tile of it — the
    check above refuses that order."""
    tile_expert, active, n_tiles, _rows = _layout("0_1_3_5_tiles")
    slices = 4

    def inside(i, j, te, na):
        return moe._expert_cut(moe._tile(i, na), j, te)

    blocks = _blocks_in_grid_order((n_tiles, slices), inside, tile_expert,
                                   active)
    assert _returned_to(blocks) == [(e, 0, j) for e in (2, 3)
                                    for j in range(slices)]


@pytest.mark.parametrize("layout", ["0_1_3_5_tiles", "all_tiles_dead"])
def test_one_slice_keeps_the_grid_of_row_tiles_alone(layout):
    tile_expert, active, n_tiles, _rows = _layout(layout)
    grid, at = moe._walk(n_tiles, 1)
    assert grid == (n_tiles,)
    weights = _blocks_in_grid_order(grid, at(moe._expert_cut), tile_expert,
                                    active)
    last = max(active - 1, 0)
    assert weights == [(int(tile_expert[min(t, last)]), 0, 0)
                       for t in range(n_tiles)]
    assert _returned_to(weights) == []


def test_the_row_tiles_a_layer_filled_are_counted_for_the_engine():
    """`row_tiles_active` on a hand-built routing: 40 tokens choose
    expert 1 (three tiles of 16), the first 10 expert 2 beside it (one
    tile) and the others expert 5, which the share (0, 4) does not hold.
    The engine's vector ends in the count; a training step's keeps its
    five names in their order."""
    assert moe.COUNTERS == ("assignments", "expert_calls", "max_load",
                            "row_tiles_active")
    assert moe.TRAIN_COUNTERS == ("assignments", "expert_calls", "max_load",
                                  "row_tiles_active", "row_tiles")
    _wr, w1, w3, w2 = _weights()
    wr = jnp.zeros((D, E)).at[0, 1].set(2.0).at[1, 2].set(1.0).at[2, 5].set(
        1.0)
    x = jnp.zeros((40, D)).at[:, 0].set(1.0).at[:10, 1].set(1.0).at[
        10:, 2].set(1.0)
    assert moe.row_tile(40, K, E) == 16
    _y, counters = _layer(x, wr, w1, w3, w2, (0, 4))
    assert int(counters["assignments"]) == 50
    assert int(counters["expert_calls"]) == 2
    assert int(counters["max_load"]) == 40
    assert int(counters["row_tiles_active"]) == 3 + 1


def _naive_wide(x, wr, w1, w3, w2, valid):
    ids, weights = moe.route(x, wr, 2)
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w1.shape[0]):
        w = jnp.where(valid, jnp.sum(jnp.where(ids == e, weights, 0.0),
                                     axis=-1), 0.0)
        out = out + w[:, None] * (
            (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
    return out
