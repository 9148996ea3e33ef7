"""The gated delta rule's two forms (ops/delta_rule.py) against the
recurrence itself, token by token: the chunk kernel (interpreted) and
its XLA twin over one chunk, several, a ragged last chunk, a lane that
starts beside one that carries, a dead lane, beta near 2 and alpha near
0 and 1; the decode kernel (interpreted) in place over the paired pool.
Toy heads of 24 x 48: neither width the other's tile nor the chip's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import delta_rule as dr

H, DK, DV = 4, 24, 48


@pytest.fixture(autouse=True)
def exact_products():
    """The recurrence and the XLA twin at float32's own precision — for
    this module's tests alone: set while a module is imported, the
    option would reach every test a worker collects."""
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(lanes, s, seed=0, beta="mixed", alpha="mixed"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (lanes, s, H, DK))
    k = jax.random.normal(ks[1], (lanes, s, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(DK)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (lanes, s, H, DV))
    lo, hi = {"mixed": (-7.0, 1.5), "one": (-12.0, -9.0),
              "zero": (2.0, 3.0)}[alpha]
    g = -jnp.exp(jax.random.uniform(ks[3], (lanes, s, H), minval=lo,
                                    maxval=hi))
    shift = {"mixed": 0.0, "two": 7.0, "zero": -7.0}[beta]
    beta = 2 * jax.nn.sigmoid(
        jax.random.normal(ks[4], (lanes, s, H)) * (3.0 if not shift else 0.3)
        + shift)
    s0 = jax.random.normal(ks[5], (lanes, H, DK, DV))
    return q, k, v, g, beta, s0


def _close(a, b, tol=2e-5):
    scale = max(1.0, float(jnp.abs(b).max()))
    assert float(jnp.abs(a - b).max()) <= tol * scale


def test_pairing_is_a_layout_and_nothing_else():
    s = jax.random.normal(jax.random.PRNGKey(1), (3, H, DK, DV))
    p = dr.pair_state(s)
    assert p.shape == (3, H // 2, DK, 2 * DV)
    np.testing.assert_array_equal(dr.unpair_state(p), s)
    # head 2p on the first dv lanes, head 2p + 1 on the rest
    np.testing.assert_array_equal(p[:, 1, :, :DV], s[:, 2])
    np.testing.assert_array_equal(p[:, 1, :, DV:], s[:, 3])


@pytest.mark.parametrize("tokens,chunk", [(16, 16), (64, 16), (64, 64),
                                          (128, 64)],
                         ids=["one-chunk", "four-chunks", "chunk-64",
                              "two-of-64"])
@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_the_chunk_form_is_the_recurrence(form, tokens, chunk):
    q, k, v, g, beta, s0 = _inputs(2, tokens, seed=tokens + chunk)
    want_o, want_s = dr.gated_delta_recurrent(q, k, v, g, beta, s0)
    if form == "xla":
        o, s1 = dr.gated_delta_chunk_xla(q, k, v, g, beta, s0, chunk=chunk)
    else:
        o, s1 = dr.gated_delta_chunk(q, k, v, g, beta, dr.pair_state(s0),
                                     chunk=chunk)
        s1 = dr.unpair_state(s1)
    _close(o, want_o)
    _close(s1, want_s)


@pytest.mark.parametrize("beta,alpha", [
    ("two", "mixed"), ("two", "one"), ("zero", "mixed"), ("mixed", "zero"),
    ("mixed", "one")],
    ids=["beta-near-2", "beta-near-2-no-decay", "beta-near-0",
         "alpha-near-0", "alpha-near-1"])
def test_the_chunk_kernel_at_the_gates_ends(beta, alpha):
    """beta near 2 is a reflection (eigenvalue -1 along k) and alpha
    near 1 keeps everything: the inverse of the chunk's triangle then
    has entries of size 1 down every column, which forward substitution
    (the kernel's doubling) carries and a Neumann product would not."""
    q, k, v, g, b, s0 = _inputs(2, 64, seed=7, beta=beta, alpha=alpha)
    want_o, want_s = dr.gated_delta_recurrent(q, k, v, g, b, s0)
    o, s1 = dr.gated_delta_chunk(q, k, v, g, b, dr.pair_state(s0),
                                 chunk=32)
    _close(o, want_o, 5e-5)
    _close(dr.unpair_state(s1), want_s, 5e-5)


def test_repeated_keys_at_beta_2_stay_finite_and_right():
    """The worst case for the triangle's inverse: one key all chunk
    long, beta = 2, no decay."""
    q, k, v, g, b, s0 = _inputs(1, 32, seed=3)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, b = jnp.zeros_like(g), jnp.full_like(b, 2.0)
    want_o, want_s = dr.gated_delta_recurrent(q, k, v, g, b, s0)
    o, s1 = dr.gated_delta_chunk(q, k, v, g, b, dr.pair_state(s0),
                                 chunk=32)
    _close(o, want_o, 1e-4)
    _close(dr.unpair_state(s1), want_s, 1e-4)


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_padding_leaves_the_state_and_a_dead_lane_its_slot(form):
    """Lane 0 has 40 valid tokens of 64 (a ragged last chunk of 16s:
    two whole, one of 8), lane 1 none at all, lane 2 starts its
    sequence (a zero state) beside them: beta = 0 and g = 0 at the
    padding, as the model masks them."""
    q, k, v, g, beta, s0 = _inputs(3, 64, seed=11)
    s0 = s0.at[2].set(0.0)
    lens = jnp.asarray([40, 0, 64])
    valid = (jnp.arange(64)[None, :] < lens[:, None])[..., None]
    g, beta = g * valid, beta * valid
    if form == "xla":
        o, s1 = dr.gated_delta_chunk_xla(q, k, v, g, beta, s0, chunk=16)
    else:
        o, s1 = dr.gated_delta_chunk(q, k, v, g, beta, dr.pair_state(s0),
                                     chunk=16)
        s1 = dr.unpair_state(s1)
    want_o, want_s = dr.gated_delta_recurrent(
        q[:1, :40], k[:1, :40], v[:1, :40], g[:1, :40], beta[:1, :40],
        s0[:1])
    _close(o[:1, :40], want_o)
    _close(s1[:1], want_s)
    np.testing.assert_allclose(s1[1], s0[1], rtol=0, atol=1e-6)
    full_o, full_s = dr.gated_delta_recurrent(
        q[2:], k[2:], v[2:], g[2:], beta[2:], s0[2:])
    _close(o[2:], full_o)
    _close(s1[2:], full_s)
    assert bool(jnp.isfinite(o).all())


@pytest.mark.parametrize("slots", [[3, 0, 1], [0, 0, 0], [0, 2, 0],
                                   [4, 3, 2]],
                         ids=["a-dead-lane", "all-dead", "dead-first-last",
                              "all-live"])
def test_the_decode_kernel_updates_the_pool_in_place_by_slot(slots):
    q, k, v, g, beta, s0 = _inputs(3, 1, seed=5, beta="two")
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    filler = jax.random.normal(jax.random.PRNGKey(9), (5, H, DK, DV))
    pool = dr.pair_state(filler)
    slots = jnp.asarray(slots, jnp.int32)
    want_o, want_pool = dr.gated_delta_update_xla(pool, slots, q, k, v, g,
                                                  beta)
    o, got = dr.gated_delta_update(jnp.array(pool), slots, q, k, v, g, beta)
    _close(o, want_o)
    _close(got, want_pool)
    # ... which is the recurrence's step on the live lanes' slots, every
    # other slot (the garbage slot among them) as it was
    live = np.flatnonzero(np.asarray(slots))
    step_o, step_s = dr.gated_delta_step(q, k, v, g, beta,
                                         filler[np.asarray(slots)])
    for lane in live:
        _close(o[lane], step_o[lane])
        _close(dr.unpair_state(got)[int(slots[lane])], step_s[lane])
    untouched = sorted(set(range(5)) - {int(slots[i]) for i in live})
    np.testing.assert_array_equal(got[np.asarray(untouched)],
                                  pool[np.asarray(untouched)])
    dead = np.flatnonzero(np.asarray(slots) == 0)
    assert not np.asarray(o)[dead].any()


def test_decode_steps_behind_a_chunk_are_the_recurrence():
    """A prefill chunk then eight decode steps through the pool, as the
    engine runs them, against 72 tokens of the recurrence."""
    q, k, v, g, beta, s0 = _inputs(2, 72, seed=13)
    want_o, want_s = dr.gated_delta_recurrent(q, k, v, g, beta, s0 * 0)
    head = tuple(x[:, :64] for x in (q, k, v, g, beta))
    o, s1 = dr.gated_delta_chunk(*head, dr.pair_state(s0 * 0))
    pool = jnp.zeros((4, H // 2, DK, 2 * DV)).at[jnp.asarray([2, 3])].set(s1)
    outs = [o]
    for t in range(64, 72):
        o_t, pool = dr.gated_delta_update(
            pool, jnp.asarray([2, 3], jnp.int32), q[:, t], k[:, t], v[:, t],
            g[:, t], beta[:, t])
        outs.append(o_t[:, None])
    _close(jnp.concatenate(outs, axis=1), want_o)
    _close(dr.unpair_state(pool[jnp.asarray([2, 3])]), want_s)
