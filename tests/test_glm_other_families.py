"""The four families the repo had are what they were before the `index`
part of a row and the router's selection bias (PR 42): no index pool in
their pool trees, no bias among their parameters, no indexer's operation
in their programs, their counter vectors at the length they had."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import cache as kv_cache, resolve
from test_granite_other_families import PAGE, _laguna, _llama, _pangu


def _granite():
    from ray_tpu.models.granite import GraniteConfig

    return GraniteConfig.tiny()


def _leaves(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _leaves(v, path + (k,))
    else:
        yield "/".join(path)


@pytest.mark.parametrize("model", [_llama, _laguna, _pangu, _granite])
def test_a_family_without_an_indexer_is_what_it_was(model):
    family, cfg = resolve(model())
    spec = cfg.cache_spec()
    assert not any(isinstance(layer, kv_cache.IndexedLatentCache)
                   for layer in spec)
    pools = kv_cache.make_pools(
        spec, {k: 2 * PAGE for k in kv_cache.kinds_of(spec)}, jnp.bfloat16)
    assert "index" not in pools
    assert set(pools) in ({"k", "v"}, {"latent"}, {"k", "v", "conv", "ssm"})

    model = family.build(cfg, PAGE)
    names = list(_leaves(jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"]))
    assert names and not any("indexer" in n or "moe_router_bias" in n
                             for n in names)
    counters = getattr(model, "counters", ())
    assert not any(c.startswith("sparse_") for c in counters)
    assert len(counters) in (0, 6)


def test_the_latent_family_without_index_keys_takes_the_kernels_as_before():
    """Pangu's prefill pass hands the chunk kernel no selection, and its
    traced kernel has the operands it had: table, lengths, queries,
    positions, the pool."""
    from ray_tpu.ops import latent_attention as la

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 8, 2, 128), jnp.float32)
    pool = jnp.asarray(rng.randn(4 * PAGE, 128), jnp.float32)
    ctx = jnp.asarray(np.arange(PAGE, 3 * PAGE)[None], jnp.int32)
    mask = jnp.ones((1, 2 * PAGE), bool)
    q_pos = jnp.asarray(np.arange(24, 32)[None], jnp.int32)

    def call(select):
        return jax.make_jaxpr(lambda: la.latent_chunk_attention(
            q, pool, ctx, None, mask, q_pos, page_size=PAGE, value_width=32,
            scale=0.1, interpret=False, select=select))()

    def kernel_operands(jaxpr):
        found = []

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append(len(eqn.invars))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found

    assert kernel_operands(call(None)) == [5]
    marks = jnp.zeros((1, 8, 2 * PAGE), jnp.float32)
    picked = (marks, jnp.zeros((1, 8)), jnp.zeros((1, 8), jnp.int32))
    assert kernel_operands(call(picked)) == [8]


_DECODE_PASS = """
import re, sys
sys.path[:0] = [{tests!r}, {root!r}]
import jax, jax.numpy as jnp, numpy as np
from test_granite_other_families import PAGE, _pangu
from ray_tpu.models import cache as kv_cache, resolve

family, cfg = resolve(_pangu())
model = family.build(cfg, PAGE)
tokens = np.zeros((2, 1), np.int32)
params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
pools = kv_cache.make_pools(cfg.cache_spec(), {{"full": 9 * PAGE}},
                            jnp.bfloat16)
# (4 pages of 16 rows: a table an indexer of 32 rows would select from)
cache = {{**pools, "q_pos": np.full((2, 1), 40, np.int32),
         "groups": {{"full": {{
             "slots": np.asarray([[PAGE + 40], [5 * PAGE + 40]], np.int32),
             "block_tables": 1 + np.arange(8, dtype=np.int32).reshape(2, 4),
             "context_lens": np.full((2,), 41, np.int32)}}}}}}
text = str(jax.make_jaxpr(
    lambda p, c: model.apply(p, tokens, c))(params, cache))
print("KERNELS", sorted(set(re.findall(r"name=(latent_attention_\\w+|"
                                       r"sparse_\\w+|select_\\w+)", text))))
print("LOADED", [m for m in sys.modules if m.endswith("sparse_decode")])
"""


def test_the_latent_family_without_an_indexer_decodes_as_before():
    """Pangu's decode pass over a table wider than a GLM selection names
    the dense decode kernel and nothing of the sparse setting, and its
    process never loads `ops.sparse_decode` (PR 56)."""
    tests = os.path.dirname(os.path.abspath(__file__))
    done = subprocess.run(
        [sys.executable, "-c", _DECODE_PASS.format(
            tests=tests, root=os.path.dirname(tests))],
        capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-3000:]
    kernels, loaded = done.stdout.splitlines()[-2:]
    assert kernels == "KERNELS ['latent_attention_decode']"
    assert loaded == "LOADED []"


def test_a_router_without_a_bias_routes_as_before():
    from ray_tpu.ops import moe

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(12, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 8), jnp.float32)
    ids, weights = moe.route(x, w, 2, True, jax.nn.sigmoid)
    same_ids, same = moe.route(x, w, 2, True, jax.nn.sigmoid,
                               jnp.zeros((8,)))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(same_ids))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(same))
    # a bias moves who is chosen and leaves a chosen expert's weight
    bias = jnp.asarray([5.0] + [0.0] * 7)
    moved, by = moe.route(x, w, 2, False, jax.nn.sigmoid, bias)
    assert (np.asarray(moved)[:, 0] == 0).all()
    scores = np.asarray(jax.nn.sigmoid(
        np.asarray(x, np.float64) @ np.asarray(w, np.float64)))
    np.testing.assert_allclose(
        np.asarray(by), np.take_along_axis(scores, np.asarray(moved), -1),
        rtol=1e-5)
