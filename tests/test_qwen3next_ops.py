"""The paged kernels over a FLAT KV row (models/cache.py, `FlatKVCache`:
few wide heads side by side in one vector) against the same numbers by
heads and against plain attention, interpreted on the CPU; and THE SHARE
TEST of the hybrid expert family's expert layer: two shares' routed parts
and the shared expert counted once are the uncut layer, which is the
plain reference's (benchmarks/reference_qwen3next.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_qwen3next as ref
from ray_tpu.models.laguna import ExpertLayer
from ray_tpu.models.llama import dense_attention
from ray_tpu.models.qwen3_next import Qwen3NextConfig
from ray_tpu.ops.paged_attention import paged_attention
from ray_tpu.ops.paged_prefill import paged_prefill_attention

CFG = Qwen3NextConfig.tiny()
PAGE = 16


def _flat_case(lanes, ctx_lens, hkv=2, d=32, group=4, seed=0):
    """Pools of `hkv` heads `d` wide in both forms, lanes whose pages are
    dealt from the pool's far end first."""
    rng = np.random.RandomState(seed)
    pages = sum(-(-n // PAGE) for n in ctx_lens) + 1
    slots = pages * PAGE
    k = rng.standard_normal((slots, hkv, d)).astype(np.float32)
    v = rng.standard_normal((slots, hkv, d)).astype(np.float32)
    width = max(-(-n // PAGE) for n in ctx_lens)
    table = np.zeros((lanes, width), np.int32)
    free = list(range(pages - 1, 0, -1))
    for b, n in enumerate(ctx_lens):
        for j in range(-(-n // PAGE)):
            table[b, j] = free.pop(0)
    return k, v, table


@pytest.mark.parametrize("hkv", [1, 2])
def test_the_decode_kernel_reads_a_flat_row_as_it_reads_heads(hkv):
    """`paged_attention` over `[T, Hkv x D]` pools is what it is over
    `[T, Hkv, D]` pools of the same numbers, and both are plain attention
    over the lane's rows."""
    ctx_lens, d, group = (37, 5, 64, 0), 32, 4
    k, v, table = _flat_case(4, ctx_lens, hkv=hkv, d=d)
    q = np.random.RandomState(1).standard_normal(
        (4, 1, hkv * group, d)).astype(np.float32)
    lens = jnp.asarray(ctx_lens, jnp.int32)
    by_head = paged_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              jnp.asarray(table), lens, page_size=PAGE)
    flat = paged_attention(
        jnp.asarray(q), jnp.asarray(k.reshape(-1, hkv * d)),
        jnp.asarray(v.reshape(-1, hkv * d)), jnp.asarray(table), lens,
        page_size=PAGE)
    np.testing.assert_allclose(flat, by_head, atol=1e-6)
    for b, n in enumerate(ctx_lens):
        if not n:
            assert not np.asarray(flat[b]).any()
            continue
        rows = (table[b, :, None] * PAGE + np.arange(PAGE)).reshape(-1)[:n]
        want = dense_attention(
            jnp.asarray(q[b:b + 1]), jnp.asarray(k[rows][None]),
            jnp.asarray(v[rows][None]), causal=False)
        np.testing.assert_allclose(flat[b:b + 1], want, atol=2e-5)


@pytest.mark.parametrize("hkv", [1, 2])
def test_the_prefill_kernel_reads_a_flat_row_as_it_reads_heads(hkv):
    """`paged_prefill_attention` of a chunk behind a context, over flat
    pools: plain causal attention over the lane's rows."""
    d, group, chunk = 32, 4, 16
    ctx_lens = (48, 16, 0)            # rows held, the chunk's included
    k, v, table = _flat_case(3, ctx_lens, hkv=hkv, d=d, seed=2)
    width = table.shape[1] * PAGE
    ctx = (table[:, :, None] * PAGE + np.arange(PAGE)).reshape(3, width)
    mask = np.arange(width)[None, :] < np.asarray(ctx_lens)[:, None]
    q_pos = np.stack([np.arange(n - chunk, n) if n else np.zeros(chunk)
                      for n in ctx_lens]).astype(np.int32)
    q = np.random.RandomState(3).standard_normal(
        (3, chunk, hkv * group, d)).astype(np.float32)
    args = (jnp.asarray(ctx), jnp.asarray(mask), jnp.asarray(q_pos))
    by_head = paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), *args,
        page_size=PAGE)
    flat = paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(k.reshape(-1, hkv * d)),
        jnp.asarray(v.reshape(-1, hkv * d)), *args, page_size=PAGE,
        kv_heads=hkv)
    np.testing.assert_allclose(flat, by_head, atol=1e-6)
    for b, n in enumerate(ctx_lens):
        if not n:
            assert not np.asarray(flat[b]).any()
            continue
        rows = ctx[b, :n]
        whole = dense_attention(
            jnp.pad(jnp.asarray(q[b:b + 1]),
                    ((0, 0), (n - chunk, 0), (0, 0), (0, 0))),
            jnp.asarray(k[rows][None]), jnp.asarray(v[rows][None]))
        np.testing.assert_allclose(flat[b:b + 1], whole[:, n - chunk:],
                                   atol=2e-5)


def test_two_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TEST: the routed parts of [0, 4) and [4, 8), each with
    the shared expert, add up to the uncut layer with the shared expert
    counted once — and the uncut layer is the reference's."""
    whole = dataclasses.replace(CFG, experts_held=(0, 8))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, CFG.hidden_size))
    valid = jnp.ones(x.shape[:2], bool)
    layer = lambda cfg: ExpertLayer(cfg, shared_gate=True)   # noqa: E731
    p = layer(whole).init(jax.random.PRNGKey(4), x, valid)["params"]
    # the router off its small seeded draw, so that the weights differ
    p = {**p, "moe_router": p["moe_router"] * 8.0}
    full, counters = layer(whole).apply({"params": p}, x, valid)

    def share(lo, hi):
        cut = {**p, **{f"moe_experts_{n}": p[f"moe_experts_{n}"][lo:hi]
                       for n in ("w1", "w3", "w2")}}
        return layer(dataclasses.replace(CFG, experts_held=(lo, hi))).apply(
            {"params": cut}, x, valid)

    (a, ca), (b, cb) = share(0, 4), share(4, 8)
    none = {**p, **{f"moe_experts_{n}": jnp.zeros_like(
        p[f"moe_experts_{n}"]) for n in ("w1", "w3", "w2")}}
    shared_once, _ = layer(whole).apply({"params": none}, x, valid)
    np.testing.assert_allclose(a + b - shared_once, full, atol=2e-5)
    # every assignment lands on exactly one share
    assert int(ca["assignments"] + cb["assignments"]) \
        == int(counters["assignments"]) == 2 * 24 * 2
    assert 0 < int(ca["assignments"]) < 96
    with jax.default_matmul_precision("highest"):
        want, _margin = ref._experts(
            x.reshape(-1, CFG.hidden_size), p, top_k=2, normalize=True,
            lo=0, reading=None)
    np.testing.assert_allclose(full.reshape(want.shape), want, atol=2e-5)
    # and a share of the reference is a share of the program
    with jax.default_matmul_precision("highest"):
        cut = {**p, **{f"moe_experts_{n}": p[f"moe_experts_{n}"][4:]
                       for n in ("w1", "w3", "w2")}}
        want_b, _ = ref._experts(x.reshape(-1, CFG.hidden_size), cut,
                                 top_k=2, normalize=True, lo=4, reading=None)
    np.testing.assert_allclose(b.reshape(want_b.shape), want_b, atol=2e-5)
