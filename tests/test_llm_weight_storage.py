"""What a serving engine stores (ISSUE 29, ROADMAP A1): the serving
module of the Llama family declares its matrices in `cfg.dtype`, drawn
as a float32 module's and rounded once, and `LLMEngine` holds its tree
in the dtypes its module declares whatever tree it was given.  The
forward is the float32-stored module's on the same numbers; a trainer's
trees stay float32; a family that already stored what it multiplies by
(Laguna) is left as it was.
"""

import dataclasses
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import cache as kv_cache
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig, LlamaModel
from ray_tpu.serve.llm import LLMEngine

SEED, PAGE, LANES = 5, 8, 4
TOKENS = np.zeros((1, 8), np.int32)


def _leaves(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_leaves_with_path(tree)}


def _same(a, b):
    return a.dtype == b.dtype and np.array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32))


def _upcast(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _engine(cfg=None, **kw):
    return LLMEngine(cfg, model="tiny", page_size=PAGE, max_batch=LANES,
                     seed=SEED, **kw)


@pytest.fixture(scope="module")
def eng():
    return _engine()


@pytest.fixture(scope="module")
def masters():
    """The float32 draw of the seed: what a trainer's module, and the
    engine of every earlier commit, holds."""
    return LlamaModel(LlamaConfig.tiny()).init(
        jax.random.PRNGKey(SEED), TOKENS)["params"]


def test_a_seeds_matrices_are_the_float32_draw_rounded_once(eng, masters):
    """(a) Every matrix is bfloat16 and equals the float32 module's draw
    of that seed `.astype(bfloat16)` (a bfloat16 draw is another model);
    the norm scales are float32 and equal.  `param_bytes` is pinned:
    106,496 matrix entries x 2 B + 320 scales x 4 B (427,264 before)."""
    held, drawn = _leaves(eng._params), _leaves(masters)
    assert held.keys() == drawn.keys() and len(held) == 21
    for path, leaf in held.items():
        if path.endswith("['scale']"):
            assert _same(leaf, drawn[path]) and leaf.dtype == jnp.float32
        else:
            assert _same(leaf, drawn[path].astype(jnp.bfloat16)), path
    assert eng.device_report()["param_bytes"] == 214272 == \
        106496 * 2 + 320 * 4


def _prefill_cache(pools, n):
    """A prompt of n tokens written to slots PAGE.. in one pass."""
    at = np.arange(n, dtype=np.int32)[None]
    return {"k": pools["k"], "v": pools["v"], "q_pos": at,
            "groups": {"full": {"slots": PAGE + at, "ctx": PAGE + at,
                                "ctx_pos": at,
                                "ctx_mask": np.ones((1, n), bool)}}}


def _decode_cache(pools, n):
    """The token after it, through the paged kernel."""
    table = np.zeros((1, 4), np.int32)
    table[0, :3] = (1, 2, 3)
    return {"k": pools["k"], "v": pools["v"],
            "q_pos": np.full((1, 1), n, np.int32),
            "groups": {"full": {
                "slots": np.full((1, 1), PAGE + n, np.int32),
                "block_tables": table,
                "context_lens": np.full((1,), n + 1, np.int32)}}}


def test_the_forward_is_the_float32_stored_modules(eng):
    """(b) Prefill and decode logits are bit-equal to the float32-stored
    module's (the forward of every earlier commit: flax rounds kernel
    and table to `cfg.dtype` before the product) on the engine's tree
    upcast to float32, same tokens, same cache."""
    stored_f32 = LlamaModel(eng.cfg, page_size=PAGE)
    assert eng._model.cfg.param_dtype == jnp.bfloat16
    assert stored_f32.cfg.param_dtype == jnp.float32
    up = _upcast(eng._params)
    pools = kv_cache.make_pools(eng.cfg.cache_spec(), {"full": 5 * PAGE},
                                eng.cfg.dtype)
    prompt = np.array([[3, 17, 250, 9, 41, 7, 7, 100, 63, 2, 19, 200]])
    n = prompt.shape[1]
    got, got_pools = eng._model.apply({"params": eng._params}, prompt,
                                      _prefill_cache(pools, n))
    want, want_pools = stored_f32.apply({"params": up}, prompt,
                                        _prefill_cache(pools, n))
    assert got.dtype == want.dtype == jnp.bfloat16 and _same(got, want)
    for a, b in zip(jax.tree.leaves(got_pools), jax.tree.leaves(want_pools)):
        assert _same(a, b)
    nxt = np.asarray(jnp.argmax(got[:, -1], axis=-1))[:, None]
    got, _ = eng._model.apply({"params": eng._params}, nxt,
                              _decode_cache(got_pools, n))
    want, _ = stored_f32.apply({"params": up}, nxt,
                               _decode_cache(want_pools, n))
    assert got.shape == (1, 1, 256) and _same(got, want)


def test_greedy_tokens_are_the_float32_stored_engines(eng):
    """(b) 32 greedy tokens of three prompts, chunked prefill and paged
    decode in one batch, against an engine that runs the float32-stored
    module on the upcast tree, with the two largest logits behind every
    token: the same tokens and the same logits."""
    ours = _engine(params=eng._params, prefill_chunk=8, logit_trace=True)
    theirs = _engine(params=eng._params, prefill_chunk=8, logit_trace=True)
    theirs._model = LlamaModel(theirs.cfg, page_size=PAGE)
    theirs._params = _upcast(eng._params)
    reqs = [{"tokens": [5, 9, 3], "max_new_tokens": 32, "request_id": "a"},
            {"tokens": list(range(1, 20)), "max_new_tokens": 32,
             "request_id": "b"},
            {"tokens": [7] * 11, "max_new_tokens": 32, "request_id": "c"}]
    out = ours.generate_batch([dict(r) for r in reqs])
    assert out == theirs.generate_batch([dict(r) for r in reqs])
    assert [len(o) for o in out] == [32, 32, 32]
    assert ours.device_report()["logit_trace"] == \
        theirs.device_report()["logit_trace"]


def test_a_float32_tree_passed_in_is_stored_rounded(eng, masters):
    """(c) A trainer's checkpoint: brought to the declared dtypes leaf by
    leaf (the seed's engine exactly), the caller's tree left alone, the
    float32 scales taken as they are."""
    given = _engine(params=masters)
    for path, leaf in _leaves(given._params).items():
        assert _same(leaf, _leaves(eng._params)[path]), path
        if path.endswith("['scale']"):
            assert leaf is _leaves(masters)[path]
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(masters))
    assert given.device_report()["param_bytes"] == 214272


def test_a_tree_as_declared_is_taken_without_a_copy(eng):
    """(c) A leaf already in its declared dtype is the caller's array."""
    given = _engine(params=eng._params)
    for ours, theirs in zip(jax.tree.leaves(given._params),
                            jax.tree.leaves(eng._params)):
        assert ours is theirs


def test_float32_activations_store_float32(masters):
    """(d) `cfg.dtype` float32 (what most engine tests run): the serving
    module declares float32 and the tree is the float32 draw itself, the
    engine of every earlier commit."""
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    held = _engine(cfg)
    assert held._model.cfg.param_dtype == jnp.float32
    for path, leaf in _leaves(held._params).items():
        assert _same(leaf, _leaves(masters)[path]), path
    assert held.device_report()["param_bytes"] == 427264
    assert all(a is b for a, b in zip(
        jax.tree.leaves(_engine(cfg, params=masters)._params),
        jax.tree.leaves(masters)))


def test_a_laguna_engine_holds_what_it_held():
    """(e) The constructor tests no family's name: a Laguna module
    declares `param_dtype` (bfloat16) for everything but its float32
    scales and draws it so, so its tree is `init`'s own, leaf dtypes and
    bytes (518,144 at the parent commit 8d027c8, read there with this
    jax)."""
    from ray_tpu.models import laguna

    held = LLMEngine(laguna.LagunaConfig.tiny(), page_size=PAGE,
                     max_batch=LANES, seed=SEED)
    declared = jax.eval_shape(held._model.init, jax.random.PRNGKey(SEED),
                              TOKENS)["params"]
    for path, leaf in _leaves(held._params).items():
        assert leaf.dtype == _leaves(declared)[path].dtype == (
            jnp.float32 if path.endswith("['scale']") else jnp.bfloat16), path
    assert held.device_report()["param_bytes"] == 518144


@pytest.mark.parametrize("init, default", [
    (llama._kernel_init, nn.linear.default_kernel_init),
    (llama._embed_init, nn.linear.default_embed_init)])
def test_a_float32_modules_draw_is_flaxs_own(init, default):
    """(f) The initializers of `models/llama.py` at float32 are flax's
    defaults bit for bit (a trainer's tree comes out as it did), and at
    bfloat16 those numbers rounded."""
    key, shape = jax.random.PRNGKey(11), (48, 96)
    assert _same(init(key, shape, jnp.float32),
                 default(key, shape, jnp.float32))
    assert _same(init(key, shape, jnp.bfloat16),
                 default(key, shape, jnp.float32).astype(jnp.bfloat16))
    assert not _same(init(key, shape, jnp.bfloat16),
                     default(key, shape, jnp.bfloat16))


def _mesh():
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(dp=2), devices=jax.devices()[:2])


def _all_float32_and_mirrored(params, opt_state):
    shapes = sorted(x.shape for x in jax.tree.leaves(params))
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(params))
    adam = opt_state[0]
    for moments in (adam.mu, adam.nu):
        assert jax.tree.structure(moments) == jax.tree.structure(params)
        assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(moments))
        assert sorted(x.shape for x in jax.tree.leaves(moments)) == shapes


def test_a_train_state_is_float32_leaf_for_leaf(masters):
    """(f) `build_llama_train_state`: float32 masters, the draw of the
    seed as `LlamaModel(cfg).init` gives it, adamw moments alike."""
    from ray_tpu.train.gspmd import build_llama_train_state

    params, opt_state, _step, model = build_llama_train_state(
        LlamaConfig.tiny(), _mesh(), batch_size=2, seq_len=8, rng_seed=SEED)
    assert model.cfg.param_dtype == jnp.float32
    _all_float32_and_mirrored(params, opt_state)
    for path, leaf in _leaves(params).items():
        assert _same(leaf, _leaves(masters)[path]), path


def test_a_stage_state_is_float32_leaf_for_leaf():
    """(f) `build_llama_stage_state` (`LlamaStage`), first and last."""
    from ray_tpu.train.gspmd import build_llama_stage_state

    for layers, first, last in (((0, 1), True, False), ((1, 2), False, True)):
        state = build_llama_stage_state(
            LlamaConfig.tiny(), _mesh(), layers, first=first, last=last,
            microbatch_size=2, seq_len=8, num_microbatches=2)
        _all_float32_and_mirrored(state["params"], state["opt_state"])
        assert ("embed" in state["params"]) == first
        assert ("lm_head" in state["params"]) == last


def _decode_text(engine):
    return engine._lower_decode(4).as_text()


def _matrix_converts(text, params):
    """`convert`s of the lowered text that take a float32 array of a
    matrix's shape: a whole stored matrix rounded inside the pass."""
    shapes = {"x".join(map(str, x.shape))
              for x in jax.tree.leaves(params) if x.ndim > 1}
    taken = re.findall(r"stablehlo\.convert[^\n]*\(tensor<([0-9x]+)xf32>\)",
                       text)
    return [shape for shape in taken if shape in shapes]


def test_the_decode_program_rounds_no_stored_matrix(eng):
    """(g) The per-pass converts cannot come back unnoticed: the lowered
    decode step takes no float32 array of a matrix's shape into a
    `convert`.  The float32-stored module's step, as a control, rounds
    all sixteen matrices."""
    assert _matrix_converts(_decode_text(eng), eng._params) == []
    before = _engine(params=eng._params)
    before._model = LlamaModel(before.cfg, page_size=PAGE)
    before._params = _upcast(eng._params)
    assert len(_matrix_converts(_decode_text(before), eng._params)) == 16
