"""ops/moe.py's backward: jax.grad through the expert layer's Pallas
kernels and the hand-written transposes of dispatch and combine, at a
small size on the CPU (the kernels run in the interpreter)."""

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


def _dense_masked(x, wr, w1, w3, w2, held, valid, top_k=K):
    """The layer as plain jax that autodiff differentiates: every held
    expert on every token, times its routing weight or zero."""
    logits = jnp.dot(x, wr, precision="highest")
    weights, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    out = jnp.zeros(x.shape, jnp.float32)
    for local, e in enumerate(range(*held)):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1) * valid
        y = (jax.nn.silu(x @ w1[local]) * (x @ w3[local])) @ w2[local]
        out = out + w[:, None] * y
    return out


@pytest.mark.parametrize("tokens,held", [(24, (0, 8)), (80, (2, 6)),
                                         (136, (4, 8))])
def test_gradients_are_autodiffs_of_the_dense_masked_form(tokens, held):
    """jax.grad through the Pallas kernels and the hand-written
    transposes of dispatch and combine: x, the router and the three
    expert tensors, with padding tokens and an expert nobody chose."""
    wr, w1, w3, w2 = _weights(3)
    # expert held[0] + 1 is chosen by no token: its logit is far below
    wr = wr.at[:, held[0] + 1].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, D))
    x = x.at[:, 0].set(1.0)
    wr = wr.at[0, held[0] + 1].set(-50.0)
    valid = jnp.arange(tokens) < tokens - 3
    cot = jax.random.normal(jax.random.PRNGKey(6), (tokens, D))
    lo, hi = held
    args = (x, wr, w1[lo:hi], w3[lo:hi], w2[lo:hi])

    def kernel(x, wr, w1, w3, w2):
        y, counters = moe.moe_layer(x, wr, w1, w3, w2, top_k=K, held=held,
                                    valid=valid, interpret=True)
        return jnp.sum(y * cot), counters

    def dense(x, wr, w1, w3, w2):
        return jnp.sum(_dense_masked(x, wr, w1, w3, w2, held, valid) * cot)

    with jax.default_matmul_precision("highest"):
        (value, counters), got = jax.value_and_grad(
            kernel, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        want_value, want = jax.value_and_grad(
            dense, argnums=(0, 1, 2, 3, 4))(*args)
    np.testing.assert_allclose(value, want_value, rtol=1e-5)
    for g, r, name in zip(got, want, ("x", "router", "w1", "w3", "w2")):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(g, r, atol=2e-5, rtol=2e-5,
                                   err_msg=name)
    # the expert nobody chose: a written zero, not an unwritten block
    assert int(counters["expert_calls"]) < hi - lo
    for g in got[2:]:
        assert float(jnp.max(jnp.abs(g[1]))) == 0.0
    # padding tokens get no gradient
    assert float(jnp.max(jnp.abs(got[0][tokens - 3:]))) == 0.0
    assert int(counters["row_tiles_active"]) <= int(counters["row_tiles"])


def _poison_inactive_rows(monkeypatch):
    """The expert kernels' rows of INACTIVE tiles — unwritten on the chip
    — come back NaN, forward and backward: what reads one shows it."""
    def poisoned(fn, tile_args):
        def run(xs, *args, tm, **kw):
            active = args[tile_args]
            dead = (jnp.arange(xs.shape[0]) >= active * tm)[:, None]
            out = fn(xs, *args, tm=tm, **kw)
            if isinstance(out, tuple):
                return (jnp.where(dead, jnp.nan, out[0]),) + out[1:]
            return jnp.where(dead, jnp.nan, out)
        return run

    monkeypatch.setattr(moe, "grouped_swiglu",
                        poisoned(moe.grouped_swiglu, 4))
    monkeypatch.setattr(moe, "grouped_swiglu_bwd",
                        poisoned(moe.grouped_swiglu_bwd, 5))


# name: (tokens, held, valid tokens, the two experts every token is sent
# to or None for the drawn router)
_EDGE_CASES = {
    "a decode batch": (16, (0, 8), 16, None),
    "valid masks a tail": (40, (0, 8), 29, None),
    "no assignment held": (24, (6, 8), 24, (3, 5)),
    "two experts hold every assignment": (48, (0, 8), 48, (3, 5)),
    "one expert of the share holds its every assignment": (32, (3, 4), 32,
                                                           (3, 5)),
}


@pytest.mark.parametrize("case", list(_EDGE_CASES),
                         ids=lambda c: c.replace(" ", "_"))
def test_gradients_where_the_row_kernels_meet_their_edges(case, monkeypatch):
    """`jax.grad` through `moe_layer` — `moe_dispatch_rows`, the expert
    kernels, `moe_combine_rows` and, backward, `moe_combine_rows_bwd`
    and `moe_combine_rows` again — against autodiff of the plain form,
    for x, the router and the three expert tensors, with every row of an
    inactive tile poisoned: a buffer nobody fills, one filled to its last
    assignment, tiles of a single row."""
    tokens, held, n_valid, both = _EDGE_CASES[case]
    _poison_inactive_rows(monkeypatch)
    wr, w1, w3, w2 = _weights(11)
    x = jax.random.normal(jax.random.PRNGKey(12), (tokens, D))
    if both:
        # every token chooses `both`, by a margin no gradient step moves
        x = jnp.abs(x) + 0.1
        wr = 0.01 * wr
        wr = wr.at[:, both[0]].add(1.0).at[:, both[1]].add(0.5)
    valid = jnp.arange(tokens) < n_valid
    cot = jax.random.normal(jax.random.PRNGKey(13), (tokens, D))
    lo, hi = held
    args = (x, wr, w1[lo:hi], w3[lo:hi], w2[lo:hi])

    def kernel(x, wr, w1, w3, w2):
        y, counters = moe.moe_layer(x, wr, w1, w3, w2, top_k=K, held=held,
                                    valid=valid, interpret=True)
        return jnp.sum(y * cot), counters

    def dense(x, wr, w1, w3, w2):
        return jnp.sum(_dense_masked(x, wr, w1, w3, w2, held, valid) * cot)

    with jax.default_matmul_precision("highest"):
        (value, counters), got = jax.value_and_grad(
            kernel, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        want_value, want = jax.value_and_grad(
            dense, argnums=(0, 1, 2, 3, 4))(*args)
    np.testing.assert_allclose(value, want_value, rtol=1e-5, atol=1e-6)
    for g, r, name in zip(got, want, ("x", "router", "w1", "w3", "w2")):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(g, r, atol=2e-5, rtol=2e-5,
                                   err_msg=name)
    if both:
        held_here = sum(lo <= e < hi for e in both)
        assert int(counters["assignments"]) == held_here * n_valid
        assert int(counters["expert_calls"]) == held_here
    if case == "no assignment held":
        assert int(counters["row_tiles_active"]) == 0
        assert all(float(jnp.abs(g).max()) == 0.0 for g in got)


def test_float32_masters_get_float32_gradients_of_bfloat16_products():
    """A trainer hands the layer float32 matrices and bfloat16 rows: the
    products run in bfloat16, the gradients come back float32 and close
    to the float32 layer's."""
    wr, w1, w3, w2 = _weights(4)
    x = jax.random.normal(jax.random.PRNGKey(7), (32, D))

    def loss(x, w1, w3, w2):
        y, _ = moe.moe_layer(x, wr, w1, w3, w2, top_k=K, held=(0, E),
                             interpret=True)
        return jnp.sum(jnp.square(y))

    low = jax.grad(loss, argnums=(0, 1, 2, 3))(
        x.astype(jnp.bfloat16), w1, w3, w2)
    full = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w1, w3, w2)
    assert low[0].dtype == jnp.bfloat16
    for g, r in zip(low[1:], full[1:]):
        assert g.dtype == jnp.float32
        err = jnp.linalg.norm(g - r) / jnp.linalg.norm(r)
        assert float(err) < 0.05


def test_backward_kernels_are_named_for_the_trace():
    wr, w1, w3, w2 = _weights(1)
    x = jax.random.normal(jax.random.PRNGKey(2), (32, D))
    text = jax.jit(jax.grad(lambda x, w1: jnp.sum(moe.moe_layer(
        x, wr, w1, w3, w2, top_k=K, held=(0, E),
        interpret=True)[0]), (0, 1))).lower(x, w1).as_text(debug_info=True)
    for name in ("moe_experts_bwd_dx", "moe_experts_bwd_dw", "moe_router",
                 "moe_dispatch", "moe_combine", "moe_dispatch_rows",
                 "moe_combine_rows", "moe_combine_rows_bwd"):
        assert name in text, name
