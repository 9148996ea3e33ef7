"""The six settings that generate token by token are what they were
before the block setting (PR 46): the programs of their decode pass and
of their prefill pass cost what they cost at the parent commit (flops,
bytes accessed, transcendentals, read there with this jax — the decode
pass's by the compiler's analysis of the compiled program, the prefill
pass's of the lowered one, which is a tenth of the time; the lowered
texts of both were the same, character for character), their parameter
trees have no norm on q and k, their pools
the bytes they had, and their engines none of the block's counters."""

import dataclasses

import jax
import numpy as np
import pytest

from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMEngine

DROPPED = ("gated", "qk_norm", "block_length", "denoising_steps",
           "confidence_threshold", "mask_token_id")


def _published(cfg, **more):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if "dtype" not in f.name and f.name not in DROPPED} | more


def _llama():
    return "tiny"


def _laguna():
    from ray_tpu.models.laguna import LagunaConfig

    return {"model_type": "laguna",
            **_published(LagunaConfig.tiny(), gating="per-head")}


def _mellum():
    from ray_tpu.models.laguna import LagunaConfig

    return LagunaConfig.tiny_ungated()


def _pangu():
    from ray_tpu.models.pangu import PanguConfig

    return {"model_type": "pangu_ultra_moe",
            **_published(PanguConfig.tiny())}


def _glm():
    from ray_tpu.models.pangu import PanguConfig

    return PanguConfig.tiny_sparse()


def _granite():
    from ray_tpu.models.granite import GraniteConfig

    return GraniteConfig.tiny()


# model -> (decode pass at table width 4, prefill pass at its narrowest
# context, parameters' leaves, pool bytes, parameter bytes) at commit
# ec57b8b, page 16, 2 lanes; the four families with experts since
# `row_tiles_active` rides their counter vector (one more int32 sum an
# expert layer: 1 to 7 operations and 20 to 112 bytes a pass)
PARENT = {
    _llama: ((833136.0, 2567250.0, 1178.0),
             (33225146.0, 21751354.0, 172704.0), 21, 69632, 214272),
    _laguna: ((3109943.0, 8841512.0, 4356.0),
              (75008000.0, 75460272.0, 545408.0), 69, 239616, 518144),
    _mellum: ((4962367.0, 11891046.0, 8162.0),
              (129570784.0, 151449728.0, 1165440.0), 83, 172032, 1528064),
    _pangu: ((2988864.0, 9711498.0, 2354.0),
             (94743312.0, 55280612.0, 234624.0), 53, 405504, 354496),
    # (the sparse decode pass at 4 pages is PR 56's own change: no more
    # pages than `index_topk` 32 rows, so the lane's pages are walked
    # under the selection's mask — the threshold search and the masked
    # kernel's interpreter program where the sort, the gather and the
    # decode kernel's read (2954870.0, 9682180.0, 2060.0); its prefill
    # pass, leaves and bytes are what they were)
    _glm: ((3962594.0, 9842455.0, 2444.0),
           (103482016.0, 97393240.0, 240384.0), 64, 456192, 369536),
    _granite: ((13674598.0, 32869884.0, 9004.0),
               (699572736.0, 182604736.0, 1087120.0), 46, 594624, 4562432),
}


def _cost(program):
    cost = program.cost_analysis()
    return (cost["flops"], cost["bytes accessed"], cost["transcendentals"])


@pytest.mark.parametrize("model", list(PARENT), ids=lambda f: f.__name__[1:])
def test_a_token_by_token_family_is_what_it_was(model):
    decode, prefill, leaves, pool_bytes, param_bytes = PARENT[model]
    eng = LLMEngine(model=model(), seed=0, page_size=16, max_batch=2)
    assert not eng._block and eng._lane_out == 1
    paths = jax.tree_util.tree_flatten_with_path(eng._params)[0]
    assert len(paths) == leaves
    if model in (_laguna, _mellum):   # the family the norm was added to
        assert not any("q_norm" in str(p) or "k_norm" in str(p)
                       for p, _ in paths)
    rep = eng.device_report()
    assert (rep["kv_pool_bytes"], rep["param_bytes"]) == (pool_bytes,
                                                          param_bytes)
    lowered = eng._lower_decode(4)
    text = lowered.as_text()
    assert "paged_attention_block" not in text and "diffusion" not in text
    assert _cost(lowered.compile()) == decode
    tokens, q_pos, last_idx, groups = eng._prefill_inputs(
        [], eng.prefill_lanes, eng.prefill_chunk, eng._prefill_widths[0])
    assert _cost(llm._jitted_forward(0.0, 0, False).lower(
        eng._model, eng._params, eng._pools, tokens, q_pos, last_idx,
        np.zeros((2,), "uint32"), groups, None)) == prefill
    assert not any(k.startswith("block") for k in eng.stats())
