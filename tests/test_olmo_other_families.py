"""The families the engine served before the hybrid linear-attention
one are what they were (PR 50): no counter of the delta rule in their
`stats()`, no model counter they did not have, a cache row of at most
2,048 numbers — so the decode kernel's pages a grid step, the one thing
`ops/paged_attention.py` and `serve/cache_groups.py` now derive from
the row, are what they were at every table width (the kernels' compiles
at the cells' widths: tests/test_tpu_compile.py).  Nor is a program of
theirs moved by the block-table prefill kernel the hybrid family got in
PR 54 (`ops/paged_prefill.py`): they never import it."""

import dataclasses
import os
import subprocess
import sys

import pytest

from ray_tpu.models import resolve
from ray_tpu.ops.paged_attention import pages_per_step
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


def _sdar():
    from ray_tpu.models.laguna import LagunaConfig

    return LagunaConfig.tiny_blocks()


def _granite():
    from ray_tpu.models.granite import GraniteConfig

    return GraniteConfig.tiny()


def _pangu():
    from ray_tpu.models.pangu import PanguConfig

    return {"model_type": "pangu_ultra_moe",
            **_published(PanguConfig.tiny())}


def _glm():
    from ray_tpu.models.pangu import PanguConfig

    return PanguConfig.tiny_sparse()


_FRESH = """
import sys
sys.path[:0] = [{tests!r}, {root!r}]
import test_olmo_other_families as here
from ray_tpu.models import resolve
from ray_tpu.serve.llm import LLMEngine

family, cfg = resolve(getattr(here, {model!r})())
eng = LLMEngine(model=cfg, seed=0, page_size=here.PAGE, max_batch=2)
out = eng.generate_batch([{{"tokens": list(range(1, 41)),
                           "max_new_tokens": 3}}])
st = eng.stats()
assert len(out[0]) >= 3 and st["prefill_steps"] and st["decode_steps"], st
loaded = [m for m in ("ray_tpu.ops.paged_prefill",
                      "ray_tpu.models.olmo_hybrid") if m in sys.modules]
print("FAMILY", family.__name__, "LOADED", loaded)
"""


@pytest.mark.parametrize("model", ["_llama", "_laguna", "_sdar", "_granite",
                                   "_pangu", "_glm"])
def test_a_family_never_imports_the_hybrid_familys_prefill_kernel(model):
    """In a fresh interpreter (another test of this worker may have
    imported the hybrid family): resolving and building the family's
    model and running its prefill and decode passes leaves
    `ops/paged_prefill.py` and `models/olmo_hybrid.py` — the two files
    PR 54 touched — out of `sys.modules`.  What a family never imports
    cannot change its program."""
    tests = os.path.dirname(os.path.abspath(__file__))
    done = subprocess.run(
        [sys.executable, "-c", _FRESH.format(
            tests=tests, root=os.path.dirname(tests), model=model)],
        capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-3000:]
    said = [ln for ln in done.stdout.splitlines() if ln.startswith("FAMILY")]
    assert said and said[-1].endswith("LOADED []"), said


@pytest.mark.parametrize("model", [_llama, _laguna, _sdar, _granite, _pangu])
def test_a_family_without_delta_layers_is_what_it_was(model):
    family, cfg = resolve(model())
    assert family.__name__ != "ray_tpu.models.olmo_hybrid"
    counters = getattr(family.build(cfg, PAGE), "counters", ())
    assert not any(name.startswith("delta_") for name in counters)
    eng = LLMEngine(model=cfg, seed=0, page_size=PAGE, max_batch=2)
    full = eng._groups["full"]
    assert full.row <= 2048
    for width in (1, 4, 16, 64, 256, 1024):
        assert pages_per_step(width, PAGE, full.row) \
            == min(width, max(8, min(32, width // 4)))
    eng.generate_batch([{"tokens": [1, 2, 3, 4, 5], "max_new_tokens": 3}])
    st = eng.stats()
    assert not any(k.startswith("delta_") for k in st)
    assert st["paged_grid_steps_total"] >= st["paged_grid_steps_live_total"]


def test_the_published_rows_of_the_benchmarks_other_models():
    """KV heads x head width of every serving configuration the
    benchmark had: 1,024 numbers at most."""
    import json
    import os

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs")
    rows = {}
    for name in ("mistral-7b-v0.3-serve", "laguna-s-2.1-serve",
                 "granite-4.0-h-micro-serve", "sdar-30b-a3b-chat-serve"):
        with open(os.path.join(here, name + ".json")) as f:
            m = json.load(f)
        head = m.get("head_dim") or m["hidden_size"] // m[
            "num_attention_heads"]
        rows[name] = m["num_key_value_heads"] * head
    assert max(rows.values()) <= 1024, rows
