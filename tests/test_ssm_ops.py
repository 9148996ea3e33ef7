"""ops/ssm.py: the one-chunk form of the state-space recurrence against
the recurrence itself, token by token; the convolution's carried inputs;
the decode kernel (interpreted) against XLA's gather -> update ->
scatter.  Small sizes, seeded inputs, CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm

L, S, H, P, N = 3, 16, 4, 8, 16


@pytest.fixture(scope="module")
def inputs():
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        x=f(L, 3 * S, H, P),
        dt=jnp.asarray(rng.uniform(0.01, 0.5, (L, 3 * S, H)), jnp.float32),
        a=-jnp.asarray(rng.uniform(1, 8, (H,)), jnp.float32),
        b=f(L, 3 * S, N), c=f(L, 3 * S, N), d=f(H))


def _by_token(v, lo, hi, h):
    ys = []
    for t in range(lo, hi):
        y, h = ssm.ssm_step(v["x"][:, t], v["dt"][:, t], v["a"],
                            v["b"][:, t], v["c"][:, t], v["d"], h)
        ys.append(y)
    return jnp.stack(ys, 1), h


def _chunk(v, lo, hi, h, dt=None):
    return ssm.ssm_chunk(v["x"][:, lo:hi],
                         v["dt"][:, lo:hi] if dt is None else dt, v["a"],
                         v["b"][:, lo:hi], v["c"][:, lo:hi], v["d"], h)


def test_chunks_are_the_recurrence_across_their_boundaries(inputs):
    """Three chunks, each from the state the last one left, give every
    token's y and the final state of the token-by-token recurrence."""
    h0 = jnp.zeros((L, H, P, N))
    want_y, want_h = _by_token(inputs, 0, 3 * S, h0)
    ys, h = [], h0
    for lo in range(0, 3 * S, S):
        y, h = _chunk(inputs, lo, lo + S, h)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), want_y, atol=2e-5)
    np.testing.assert_allclose(h, want_h, atol=2e-6)


@pytest.mark.parametrize("lens", [(16, 5, 0), (1, 16, 9), (0, 0, 0)])
def test_ragged_lanes_end_with_the_state_their_tokens_made(inputs, lens):
    """dt masked by the lane's valid length: the valid positions' y and
    the state are the recurrence's over those tokens alone; a lane with
    no token, and the padded positions of any, leave the state
    BIT-identical."""
    rng = np.random.default_rng(1)
    h0 = jnp.asarray(rng.normal(size=(L, H, P, N)), jnp.float32)
    valid = np.arange(S)[None, :] < np.asarray(lens)[:, None]
    y, h = _chunk(inputs, 0, S, h0,
                  dt=inputs["dt"][:, :S] * valid[..., None])
    for lane, n in enumerate(lens):
        one = {k: v[lane:lane + 1] if v.ndim > 1 else v
               for k, v in inputs.items()}
        want_y, want_h = _by_token(one, 0, n, h0[lane:lane + 1]) if n \
            else (None, h0[lane:lane + 1])
        if n:
            np.testing.assert_allclose(y[lane, :n], want_y[0], atol=2e-5)
            np.testing.assert_allclose(h[lane], want_h[0], atol=2e-6)
        else:
            np.testing.assert_array_equal(h[lane], h0[lane])
    # the same lanes with their padding cut off end in the same state
    full = int(max(lens))
    if full:
        _y, h_cut = _chunk({k: v[:, :full] if v.ndim > 2 else v
                            for k, v in inputs.items()}, 0, full, h0,
                           dt=(inputs["dt"][:, :S] * valid[..., None]
                               )[:, :full])
        np.testing.assert_array_equal(h, h_cut)


def test_conv_chunks_carry_the_last_valid_inputs():
    """Two ragged chunks through the carried inputs are one causal
    convolution over the valid tokens; a lane with no token keeps its
    carried inputs as they were."""
    rng = np.random.default_rng(2)
    taps, ch = 4, 6
    u = jnp.asarray(rng.normal(size=(L, 2 * S, ch)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, ch)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(ch,)), jnp.float32)
    state0 = jnp.zeros((L, taps - 1, ch))
    lens1 = np.asarray([S, 5, 0])
    out1, state1 = ssm.conv_chunk(u[:, :S], state0, w, bias,
                                  jnp.asarray(lens1))
    np.testing.assert_array_equal(state1[2], state0[2])
    # the second chunk's tokens follow the first's VALID ones
    out2, state2 = ssm.conv_chunk(u[:, S:], state1, w, bias,
                                  jnp.asarray([S, S, 2]))
    for lane, n1 in enumerate(lens1):
        seq = np.concatenate([np.asarray(u[lane, :n1]),
                              np.asarray(u[lane, S:])])
        padded = np.concatenate([np.zeros((taps - 1, ch)), seq])
        want = sum(padded[k:k + len(seq)] * np.asarray(w[k])
                   for k in range(taps)) + np.asarray(bias)
        np.testing.assert_allclose(out1[lane, :n1], want[:n1], atol=1e-5)
        np.testing.assert_allclose(out2[lane], want[n1:], atol=1e-5)
    np.testing.assert_array_equal(state2[0], u[0, 2 * S - 3:])
    # lane 2: no token, then two: one carried input left, then the two
    np.testing.assert_array_equal(
        state2[2], jnp.concatenate([state1[2][2:], u[2, S:S + 2]]))


@pytest.mark.parametrize("slots", [(3, 0, 1), (0, 0, 0), (0, 2, 0),
                                   (0, 0, 4), (1, 2, 3)])
def test_kernel_is_the_xla_update_and_skips_dead_lanes(inputs, slots):
    """The interpreted kernel = gather -> `ssm_step` -> scatter for the
    live lanes, wherever the dead ones lie; a dead lane (slot 0) and
    every slot no lane names stay BIT-identical; a dead lane's y is
    finite (D x: what its pass writes to the garbage slots)."""
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.normal(size=(5, H, P, N)), jnp.float32)
    slots = jnp.asarray(slots, jnp.int32)
    args = (slots, inputs["x"][:, 0], inputs["dt"][:, 0], inputs["a"],
            inputs["b"][:, 0], inputs["c"][:, 0], inputs["d"])
    want_y, want_pool = ssm.ssm_state_update_xla(pool, *args)
    y, got = ssm.ssm_state_update(pool + 0.0, *args)
    np.testing.assert_allclose(y, want_y, atol=2e-6)
    np.testing.assert_allclose(got, want_pool, atol=1e-6)
    untouched = sorted(set(range(5)) - {int(s) for s in slots if s})
    np.testing.assert_array_equal(got[jnp.asarray(untouched)],
                                  pool[jnp.asarray(untouched)])
    assert bool(jnp.isfinite(y).all())


def test_kernel_walks_head_blocks(inputs, monkeypatch):
    """Several head blocks a lane: the same numbers as one block."""
    rng = np.random.default_rng(4)
    pool = jnp.asarray(rng.normal(size=(4, H, P, N)), jnp.float32)
    args = (jnp.asarray([2, 0, 3], jnp.int32), inputs["x"][:, 0],
            inputs["dt"][:, 0], inputs["a"], inputs["b"][:, 0],
            inputs["c"][:, 0], inputs["d"])
    want_y, want_pool = ssm.ssm_state_update(pool + 0.0, *args)
    monkeypatch.setattr(ssm, "HEAD_BLOCK", 2)
    ssm._update_call.clear_cache()
    y, got = ssm.ssm_state_update(pool + 0.0, *args)
    ssm._update_call.clear_cache()
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(got, want_pool)
