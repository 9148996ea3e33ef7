"""The three families that keep rows a position are what they were
before the `state` kind (models/cache.py, PR 40): no state part in their
specifications and pools, no state slot in their engines, no `state`
group in their passes."""

import dataclasses

import jax.numpy as jnp
import pytest

from ray_tpu.models import cache as kv_cache, resolve
from ray_tpu.serve.llm import LLMEngine

PAGE = 16


def _published(cfg, **more):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if "dtype" not in f.name and f.name != "gated"} | more


def _llama():
    return "tiny"


def _laguna():
    from ray_tpu.models.laguna import LagunaConfig

    return {"model_type": "laguna",
            **_published(LagunaConfig.tiny(), gating="per-head")}


def _pangu():
    from ray_tpu.models.pangu import PanguConfig

    return {"model_type": "pangu_ultra_moe",
            **_published(PanguConfig.tiny())}


@pytest.mark.parametrize("model", [_llama, _laguna, _pangu])
def test_a_family_without_state_layers_is_what_it_was(model):
    _family, cfg = resolve(model())
    spec = cfg.cache_spec()
    assert all(isinstance(layer, kv_cache.LayerCache)
               and layer.dtypes() == {} for layer in spec)
    assert "state" not in kv_cache.kinds_of(spec)
    pools = kv_cache.make_pools(
        spec, {k: 2 * PAGE for k in kv_cache.kinds_of(spec)}, jnp.bfloat16)
    assert not {"conv", "ssm"} & set(pools)
    assert all(p.dtype == jnp.bfloat16 for part in pools.values()
               for p in part if p is not None)

    eng = LLMEngine(model=cfg, seed=0, page_size=PAGE, max_batch=2)
    groups, step = [], eng._step_fn

    def spy(*args, **kw):
        groups.append(args[6])
        return step(*args, **kw)

    eng._step_fn = spy
    eng.generate_batch([{"tokens": [1, 2, 3], "max_new_tokens": 3}])
    assert groups and not any("state" in g for g in groups)
    assert not any(k.startswith("state_") for k in eng.stats())
    assert "state" not in eng._groups
    assert eng.device_report()["state_pool_bytes"] == 0
