"""The families the engine served before the hybrid expert one are what
they were (PR 55): none imports `models/qwen3_next.py`; the two modules it
borrows keep their parameter trees and, where its switches are off, their
traced programs (`laguna.ExpertLayer` without `shared_gate`,
`olmo_hybrid.GatedDeltaNet` with as many key heads as value heads); and
the paged kernels over `[T, Hkv, D]` pools trace the head-major transpose
they always did, the flat row's slices only over `[T, Hkv x D]`.  (The
builder's comparison of 33 traced programs of six families against the
parent commit, kernel bodies included: PERF.md section 6, PR 55; the
kernels' compiles at the cells' widths: tests/test_tpu_compile.py.)"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.paged_attention import paged_attention, pages_per_step
from ray_tpu.ops.paged_prefill import paged_prefill_attention

PAGE = 16

_FRESH = """
import sys
sys.path[:0] = [{tests!r}, {root!r}]
import jax, numpy as np
import test_olmo_other_families as here
from ray_tpu.models import FAMILIES, resolve
from ray_tpu.models.olmo_hybrid import OlmoHybridConfig

models = [getattr(here, n)() for n in ("_llama", "_laguna", "_sdar",
                                       "_granite", "_pangu", "_glm")]
for model in models + [OlmoHybridConfig.tiny()]:
    family, cfg = resolve(model)
    jax.eval_shape(family.build(cfg, 16).init, jax.random.PRNGKey(0),
                   np.zeros((1, 8), np.int32))
assert FAMILIES["qwen3_next"] == "qwen3_next"
print("LOADED", [m for m in sys.modules if m.endswith("qwen3_next")])
"""


def test_no_other_family_imports_the_hybrid_expert_family():
    tests = os.path.dirname(os.path.abspath(__file__))
    done = subprocess.run(
        [sys.executable, "-c", _FRESH.format(
            tests=tests, root=os.path.dirname(tests))],
        capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-3000:]
    assert done.stdout.splitlines()[-1] == "LOADED []"


def _tree(module, *args):
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), *args)
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                shapes["params"])[0]}


def test_the_borrowed_modules_keep_their_trees_and_programs():
    from ray_tpu.models.laguna import ExpertLayer, LagunaConfig
    from ray_tpu.models.olmo_hybrid import GatedDeltaNet, OlmoHybridConfig

    cfg = LagunaConfig.tiny()
    x, valid = jnp.zeros((2, 8, 64), cfg.dtype), jnp.ones((2, 8), bool)
    plain = _tree(ExpertLayer(cfg), x, valid)
    assert sorted(plain) == [
        "moe_experts_w1", "moe_experts_w2", "moe_experts_w3", "moe_router",
        "moe_shared/w1/kernel", "moe_shared/w2/kernel",
        "moe_shared/w3/kernel"]
    gated = _tree(ExpertLayer(cfg, shared_gate=True), x, valid)
    assert set(gated) - set(plain) == {"moe_shared_gate"}
    assert gated["moe_shared_gate"] == (64, 1)

    def sigmoids(layer):
        params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x, valid)
        return str(jax.make_jaxpr(lambda p: layer.apply(p, x, valid))(
            params)).count("logistic")

    # the one sigmoid more is the shared gate's (a silu holds one too)
    assert sigmoids(ExpertLayer(cfg, shared_gate=True)) \
        == sigmoids(ExpertLayer(cfg)) + 1
    ocfg = OlmoHybridConfig.tiny()
    mixer = _tree(GatedDeltaNet(ocfg), jnp.zeros((1, 64, 128), ocfg.dtype))
    assert mixer["ab_proj/kernel"] == (128, 2 * ocfg.linear_num_value_heads)
    assert mixer["a_log"] == (ocfg.linear_num_value_heads,)
    assert sorted(mixer) == ["a_log", "ab_proj/kernel", "conv_w", "dt_bias",
                             "gate_proj/kernel", "norm_w",
                             "out_proj/kernel", "qkv_proj/kernel"]
    # the other hybrid family still refuses what its module did not write
    with pytest.raises(ValueError, match="key heads"):
        OlmoHybridConfig.from_dict({**{
            f.name: getattr(ocfg, f.name)
            for f in dataclasses.fields(ocfg) if "dtype" not in f.name},
            "linear_num_key_heads": 2})


def _decode(pool_shape, heads, d):
    spec = jax.ShapeDtypeStruct
    return str(jax.make_jaxpr(lambda q, k, v, bt, cl: paged_attention(
        q, k, v, bt, cl, page_size=PAGE, interpret=False))(
        spec((4, 1, heads, d), jnp.bfloat16),
        spec(pool_shape, jnp.bfloat16), spec(pool_shape, jnp.bfloat16),
        spec((4, 16), jnp.int32), spec((4,), jnp.int32)))


def _prefill(pool_shape, heads, kv, d):
    spec = jax.ShapeDtypeStruct
    return str(jax.make_jaxpr(
        lambda q, k, v, ctx, mask, pos: paged_prefill_attention(
            q, k, v, ctx, mask, pos, page_size=PAGE, kv_heads=kv,
            interpret=False))(
        spec((2, 64, heads, d), jnp.bfloat16),
        spec(pool_shape, jnp.bfloat16), spec(pool_shape, jnp.bfloat16),
        spec((2, 256), jnp.int32), spec((2, 256), jnp.bool_),
        spec((2, 64), jnp.int32)))


def test_a_pool_by_heads_is_walked_as_it_was_and_a_flat_one_by_slices():
    by_head = _decode((64 * PAGE, 8, 128), 32, 128)
    flat = _decode((64 * PAGE, 512), 16, 256)
    assert "transpose" in by_head and "concatenate" not in by_head
    assert "bf16[2,8,16,8,128]" in by_head and "bf16[2,8,16,512]" in flat
    assert "concatenate" in flat      # the stack of the heads' slices
    assert "transpose" in _prefill((64 * PAGE, 32, 128), 30, 30, 128)
    flat = _prefill((64 * PAGE, 512), 16, 2, 256)
    assert "bf16[2,16,16,512]" in flat and "bf16[2,256,256]" in flat
    for width in (1, 4, 16, 64, 256, 1024):
        assert pages_per_step(width, PAGE, 512) \
            == min(width, max(8, min(32, width // 4)))
