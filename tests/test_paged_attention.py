"""Paged-attention kernel tests (ops/paged_attention.py).

The kernel runs in Pallas interpret mode on CPU — same numerics as the
TPU compilation — so these tests pin the decode kernel against the
dense gather-then-softmax reference (models/llama.py cached_attention)
across batch, context length, GQA grouping, and page size, including
ragged lengths, all-garbage lanes, and non-contiguous / shuffled
physical page assignment.  The engine-level tests at the bottom hold
the engine's decode, which goes through the kernel, to the module's
no-cache forward.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from ray_tpu.models import cache as kv_cache
from ray_tpu.models.llama import LlamaConfig, cached_attention
from ray_tpu.ops.paged_attention import paged_attention, pages_per_step

ROW = 1024   # 8 KV heads x 128: a cache row that bounds no step's keys


def _rand_paged_case(rng, batch, ctx_lens, n_heads, n_kv_heads, head_dim,
                     page_size, num_pages):
    """Random pools + a shuffled (non-contiguous) page assignment per
    lane; returns everything both the paged kernel and the dense
    reference need.  Page 0 is the garbage page, never assigned."""
    used = [-(-c // page_size) for c in ctx_lens]
    num_pages = max(num_pages, sum(used) + 2)
    t = num_pages * page_size
    pool_k = jnp.asarray(rng.normal(size=(t, n_kv_heads, head_dim)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(t, n_kv_heads, head_dim)),
                         jnp.float32)
    q = jnp.asarray(rng.normal(size=(batch, 1, n_heads, head_dim)),
                    jnp.float32)
    width = max(max(used), 1)
    pages = list(rng.permutation(np.arange(1, num_pages)))
    bt = np.zeros((batch, width), np.int32)
    for b in range(batch):
        for p in range(used[b]):
            bt[b, p] = pages.pop()
    return q, pool_k, pool_v, bt, np.asarray(ctx_lens, np.int32)


def _dense_reference(q, pool_k, pool_v, bt, ctx_lens, page_size,
                     window=None):
    """cached_attention over ctx/ctx_pos/ctx_mask arrays derived from
    the same block tables — the form a prefill pass's group carries."""
    batch = q.shape[0]
    length = bt.shape[1] * page_size
    ctx = np.zeros((batch, length), np.int32)
    ctx_pos = np.zeros((batch, length), np.int32)
    ctx_mask = np.zeros((batch, length), bool)
    for b in range(batch):
        for pos in range(int(ctx_lens[b])):
            ctx[b, pos] = bt[b, pos // page_size] * page_size \
                + pos % page_size
            ctx_pos[b, pos] = pos
            ctx_mask[b, pos] = True
    q_pos = np.maximum(ctx_lens.astype(np.int32) - 1, 0)[:, None]
    return cached_attention(q, pool_k, pool_v, jnp.asarray(ctx),
                            jnp.asarray(ctx_pos), jnp.asarray(ctx_mask),
                            jnp.asarray(q_pos), window=window)


def _window_tables(bt, ctx_lens, page_size, window):
    """What the engine's window group hands a decode pass
    (`cache_groups.WindowPages`): each lane's pages from the one the window's
    first position lies on, and the position that page starts at."""
    first = np.maximum(0, ctx_lens - window) // page_size
    tables = np.zeros((len(bt), -(-window // page_size) + 1), np.int32)
    for b, n in enumerate(ctx_lens):
        live = bt[b, first[b]:-(-int(n) // page_size)]
        tables[b, :len(live)] = live
    return tables, (first * page_size).astype(np.int32)


# what a grid of K = pages_per_step(width, 16) pages a step can get wrong
# (width 33: K 8, five blocks, the last of one page; 64: K 16; 4: K 4;
# 20, a window of 300's table: K 8, three blocks)
_BLOCK_CASES = [
    # a table no multiple of K; lanes that end in the middle of a block
    # (12 pages), on a block's edge (16), after one page, at the table's
    # whole width (33), and lanes of context 0 between held ones
    pytest.param(6, [181, 0, 256, 7, 0, 525], 8, 2, 16, 33, None, None,
                 id="w33-k8-group4"),
    pytest.param(6, [181, 0, 256, 7, 0, 525], 18, 2, 16, 33, None, None,
                 id="w33-k8-group9"),
    # narrower than the least K: one block of the table's width
    pytest.param(4, [64, 0, 1, 33], 8, 2, 16, 4, None, None,
                 id="w4-k4"),
    # one page beside a lane of W pages; an edge (16 pages) and one past it
    pytest.param(4, [3, 1024, 256, 257], 12, 2, 16, 64, None, None,
                 id="w64-k16-group6"),
    # a window whose table starts elsewhere in every lane and whose lower
    # bound lies inside the first block; a lane shorter than the window,
    # one of a single page, one not held
    pytest.param(6, [500, 301, 40, 0, 777, 9], 18, 2, 16, None, 300, None,
                 id="window300-w20-k8-group9"),
    # two lanes whose first ten pages are the same physical pages: a
    # whole block and a part of the next
    pytest.param(3, [200, 0, 170], 12, 2, 16, 16, None, (0, 2, 10),
                 id="w16-k8-shared-pages"),
]


@pytest.mark.parametrize(
    "batch,ctx_lens,heads,kv_heads,page_size,width,window,share", [
        (1, [1], 4, 2, 8, None, None, None),   # single token, single lane
        (2, [5, 16], 4, 4, 8, None, None, None),   # MHA, page-exact length
        (3, [13, 1, 9], 4, 2, 4, None, None, None),   # GQA group=2, ragged
        (4, [31, 8, 17, 2], 8, 2, 8, None, None, None),   # group=4, ragged
        (2, [7, 23], 4, 2, 16, None, None, None),   # pages > one context
    ] + _BLOCK_CASES)
def test_kernel_matches_dense_reference(batch, ctx_lens, heads, kv_heads,
                                        page_size, width, window, share):
    """`width`: the table's (the longest lane's pages without it), the
    columns past a lane's pages holding the garbage page 0; `window`: a
    window layer's call, its tables cut as the engine cuts them;
    `share` = (lane, lane, pages): the second lane's first pages are the
    first lane's."""
    rng = np.random.default_rng(hash((batch, heads, page_size)) % 2**32)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, batch, ctx_lens, heads, kv_heads, head_dim=16,
        page_size=page_size, num_pages=24)
    if share:
        src, dst, n = share
        bt[dst, :n] = bt[src, :n]
    ref = _dense_reference(q, pk, pv, bt, cl, page_size, window=window)
    tables, starts = bt, None
    if window:
        tables, starts = _window_tables(bt, cl, page_size, window)
    elif width:
        tables = np.zeros((batch, width), np.int32)
        tables[:, :bt.shape[1]] = bt
    if width or window:
        steps = -(-tables.shape[1]
                  // pages_per_step(tables.shape[1], page_size, ROW))
        assert steps > 1 or tables.shape[1] <= 8
    out = paged_attention(q, pk, pv, jnp.asarray(tables), jnp.asarray(cl),
                          page_size=page_size, window=window,
                          starts=None if starts is None
                          else jnp.asarray(starts))
    # (a lane of context 0 gives zeros; the dense softmax over nothing
    # gives a mean)
    np.testing.assert_allclose(np.asarray(out)[cl > 0],
                               np.asarray(ref)[cl > 0],
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(out)[cl == 0] == 0)


# every table width the two serving configurations can ask for, at page
# 16: the chat engine's buckets (contexts to 4096), Laguna's full kind
# (to 8192) and its window kind (`min(bucket, 33)`)
@pytest.mark.parametrize("width,pages", [
    (4, 4), (16, 8), (64, 16), (256, 32), (512, 32), (33, 8)])
def test_pages_per_step_at_the_engines_widths(width, pages):
    from ray_tpu.serve.llm import _pow4_widths

    asked = set(_pow4_widths(4, 256)) | set(_pow4_widths(4, 512))
    asked |= {min(w, 512 // 16 + 1) for w in asked}
    assert width in asked and asked == {4, 16, 64, 256, 512, 33}
    assert pages_per_step(width, 16, ROW) == pages
    assert -(-width // pages) <= 16      # a lane is at most 16 steps
    # 128 keys a step or the whole table, never more than 512 keys
    assert pages == width or 128 <= pages * 16 <= 512


def test_pages_per_step_follows_the_page_size():
    """K is set in keys: a smaller page means more pages a step, and a
    table is never split below its width."""
    assert [pages_per_step(64, p, ROW) for p in (4, 8, 16, 32, 128, 256)] \
        == [32, 16, 16, 16, 4, 2]
    assert all(1 <= pages_per_step(w, p, ROW) <= w
               for w in range(1, 70) for p in (1, 4, 16, 128, 1024))


def test_ragged_with_garbage_lanes():
    """Inactive lanes (context length 0, table pointing at the garbage
    page) must produce finite zeros — never NaNs from an all-masked
    softmax — while live lanes stay exact."""
    rng = np.random.default_rng(7)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, 4, [11, 0, 3, 0], 4, 2, head_dim=8, page_size=4,
        num_pages=16)
    out = np.asarray(paged_attention(q, pk, pv, jnp.asarray(bt),
                                     jnp.asarray(cl), page_size=4))
    assert np.all(np.isfinite(out))
    assert np.all(out[1] == 0) and np.all(out[3] == 0)
    ref = np.asarray(_dense_reference(q, pk, pv, bt, cl, 4))
    np.testing.assert_allclose(out[0], ref[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[2], ref[2], rtol=1e-5, atol=1e-5)


def test_all_garbage_batch_is_zero():
    rng = np.random.default_rng(11)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, 3, [0, 0, 0], 4, 2, head_dim=8, page_size=8, num_pages=8)
    out = np.asarray(paged_attention(q, pk, pv, jnp.asarray(bt),
                                     jnp.asarray(cl), page_size=8))
    assert np.all(out == 0) and np.all(np.isfinite(out))


def test_kernel_under_jit_and_wide_table():
    """The engine calls the kernel inside jit with a bucketed table
    width that can exceed any lane's used pages — trailing table
    entries must not perturb the result."""
    rng = np.random.default_rng(3)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, 2, [9, 4], 4, 2, head_dim=16, page_size=4, num_pages=16)
    ref = paged_attention(q, pk, pv, jnp.asarray(bt), jnp.asarray(cl),
                          page_size=4)
    wide = np.zeros((2, 8), np.int32)           # width 3 -> 8
    wide[:, :bt.shape[1]] = bt
    fn = jax.jit(lambda *a: paged_attention(*a, page_size=4))
    out = fn(q, pk, pv, jnp.asarray(wide), jnp.asarray(cl))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_shared_pages_between_lanes():
    """Prefix sharing: two lanes whose tables alias the SAME physical
    pages must each read the shared KV — the kernel only ever addresses
    pages through the table, so aliasing is invisible to it."""
    rng = np.random.default_rng(5)
    q, pk, pv, bt, cl = _rand_paged_case(
        rng, 2, [12, 12], 4, 2, head_dim=8, page_size=4, num_pages=16)
    bt[1] = bt[0]                                # full alias
    out = paged_attention(q, pk, pv, jnp.asarray(bt), jnp.asarray(cl),
                          page_size=4)
    ref = _dense_reference(q, pk, pv, bt, cl, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------ slot-pool round trips


def test_gather_scatter_copy_round_trip():
    """Property test over the KV slot-pool plumbing the paged cache
    rides on: scatter(gather(x)) is identity on the touched slots, a
    gather after shipping through numpy equals the original rows, and
    copy_kv_slots makes dst rows literally equal src rows (the CoW
    split primitive)."""
    cfg = LlamaConfig(vocab_size=16, dim=16, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=16, max_seq_len=32,
                      dtype=jnp.float32)
    spec = cfg.cache_spec()
    kinds = [layer.kind for layer in spec]

    def make_kv_pools(cfg, num_slots):
        return kv_cache.make_pools(spec, {"full": num_slots}, cfg.dtype)

    def gather_kv_slots(pools, slots):
        return kv_cache.gather_slots(pools, kinds, {"full": slots})

    def scatter_kv_slots(pools, slots, rows):
        return kv_cache.scatter_slots(pools, kinds, {"full": slots}, rows)

    def copy_kv_slots(pools, src, dst):
        return kv_cache.copy_slots(pools, kinds, "full", src, dst)

    rng = np.random.default_rng(13)
    for trial in range(5):
        num_slots = 40
        pools = make_kv_pools(cfg, num_slots)
        pools = {"k": [jnp.asarray(rng.normal(size=p.shape), p.dtype)
                       for p in pools["k"]],
                 "v": [jnp.asarray(rng.normal(size=p.shape), p.dtype)
                       for p in pools["v"]]}
        n = int(rng.integers(1, 12))
        slots = rng.choice(np.arange(1, num_slots), size=n, replace=False)
        rows = gather_kv_slots(pools, slots)
        # round trip into a fresh zeroed pool set
        fresh = make_kv_pools(cfg, num_slots)
        fresh = scatter_kv_slots(fresh, slots, rows)
        back = gather_kv_slots(fresh, slots)
        for side in ("k", "v"):
            for a, b in zip(rows[side], back[side]):
                np.testing.assert_array_equal(a, b)
        # copy: dst slots must equal src slots afterwards
        free = [s for s in range(1, num_slots) if s not in set(slots)]
        dst = np.asarray(free[:n], np.int32)
        copied = copy_kv_slots(pools, slots, dst)
        after_src = gather_kv_slots(copied, slots)
        after_dst = gather_kv_slots(copied, dst)
        for side in ("k", "v"):
            for a, b in zip(after_src[side], after_dst[side]):
                np.testing.assert_array_equal(a, b)


# ------------------------------------------------ the engine's decode


def _engine_cfg(max_seq_len=64):
    return LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=64, max_seq_len=max_seq_len,
                       dtype=jnp.float32)


@pytest.mark.parametrize("page,ctx,long_prompt,widths", [
    (8, 64, 28, [4, 8]),         # 28 + 8 tokens cross 4 pages of 8
    (4, 128, 60, [4, 16, 32]),   # 60 + 8 cross 16 pages of 4; 13 + 6, 4
])
def test_engine_decode_is_the_no_cache_forwards_argmax(page, ctx,
                                                       long_prompt, widths):
    """Every token the engine decodes through block tables is the argmax
    of the module's no-cache forward over the prompt and the tokens so
    far (float32 keeps the argmax bit-stable): three short prompts, and
    one whose decode crosses a block-table width bucket while the others
    share its pass."""
    from ray_tpu.models.llama import LlamaModel
    from ray_tpu.serve.llm import LLMEngine

    cfg = _engine_cfg(ctx)
    eng = LLMEngine(cfg, page_size=page, max_batch=4, prefill_chunk=8,
                    max_queue=8)
    assert eng._paged_width_buckets() == widths
    reqs = [{"tokens": [5, 9, 3], "max_new_tokens": 6},
            {"tokens": [7, 11, 2, 4, 8, 1, 9, 10, 3, 2],
             "max_new_tokens": 6},
            {"tokens": [3] * 13, "max_new_tokens": 6},
            {"tokens": [1 + (7 * i) % 60 for i in range(long_prompt)],
             "max_new_tokens": 8}]
    out = eng.generate_batch([dict(r) for r in reqs])
    full = jax.jit(LlamaModel(cfg).apply)
    for req, gen in zip(reqs, out):
        assert len(gen) == req["max_new_tokens"]
        # one causal forward: row len(prompt) - 1 + j saw prompt + gen[:j]
        toks = req["tokens"] + gen
        padded = np.zeros((1, ctx), np.int32)
        padded[0, :len(toks)] = toks
        logits = np.asarray(full({"params": eng._params}, padded))[0]
        want = logits[len(req["tokens"]) - 1:len(toks) - 1].argmax(-1)
        assert gen == want.tolist(), (req["tokens"][:4], gen, want)


def test_the_engine_has_one_decode_path():
    """No selector, one report field kept for the benchmark's files, and
    the form is the cache group's: a group with block tables goes through
    the kernel, a group without takes the gathered form, in both
    families' modules."""
    from ray_tpu.models import laguna, llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = _engine_cfg()
    with pytest.raises(TypeError, match="attention_impl"):
        LLMEngine(cfg, attention_impl="dense")
    eng = LLMEngine(cfg, page_size=8, max_batch=2, prefill_chunk=8)
    assert eng.device_report()["attention_impl"] == "paged"
    assert "attention_impl" not in eng.stats()
    for family, fcfg in ((llama, cfg), (laguna, laguna.LagunaConfig.tiny())):
        model = family.build(fcfg, 8)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))["params"]
        spec = fcfg.cache_spec()
        pools = jax.eval_shape(lambda: kv_cache.make_pools(
            spec, {kind: 64 for kind in kv_cache.kinds_of(spec)},
            fcfg.dtype))
        one = jnp.zeros((2, 1), jnp.int32)
        lens = jnp.ones((2,), jnp.int32)
        tables = {"block_tables": jnp.zeros((2, 4), jnp.int32),
                  "context_lens": lens}
        gathered = {"ctx": jnp.zeros((2, 16), jnp.int32),
                    "ctx_pos": jnp.zeros((2, 16), jnp.int32),
                    "ctx_mask": jnp.ones((2, 16), bool)}
        for through_kernel, full, window in (
                (True, tables, {**tables, "starts": lens}),
                (False, gathered, gathered)):
            groups = {kind: {"slots": one,
                             **(full if kind == "full" else window)}
                      for kind in kv_cache.kinds_of(spec)}
            text = str(jax.make_jaxpr(
                lambda p, k, v, groups=groups: model.apply(
                    {"params": p}, one,
                    {"k": k, "v": v, "q_pos": one, "groups": groups}))(
                params, pools["k"], pools["v"]))
            assert ("paged_attention_decode" in text) == through_kernel, \
                family.__name__
