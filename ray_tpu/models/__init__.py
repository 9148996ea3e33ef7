"""Model zoo for the TPU-native stack, and what a model is to the rest
of it.

A model FAMILY is a module of this package that defines

    config(model)          the value of `LLMEngine(model=...)` — a config
                           instance, a dictionary of sizes, a preset's
                           name — as the family's frozen config dataclass
                           (`max_seq_len`, `dtype`, `cache_spec()`)
    build(cfg, page_size)  the serving module: `init(rng, tokens)` and
                           `apply(params, tokens, cache)` -> (logits,
                           pools[, counters]); `counters` names the
                           entries of the counter vector, if it has one.
                           It declares every parameter in the dtype its
                           forward reads it in (no master weights), and
                           the engine holds its tree in those dtypes

and whose config states its cache by layer (models/cache.py): rows a
position in pages (`full`, `window`), or — kind `state` — ONE
fixed-size row a sequence, a recurrent layer's carry (a state-space
or a gated-delta-rule mixer's), for which the
engine keeps a slot a sequence and hands a pass `groups["state"]` (the
lanes' slots, valid lengths, and whether the chunk starts its
sequence).  A row's part states its dtype where it is not the model's
(a state's float32 carry in a bfloat16 model).  The plain
reference of a family lives with the benchmark (`benchmarks/reference*`)
and reads nothing but the parameter tree.

The TRAIN side (`train/gspmd.build_train_state(model=...)`) is three
more names of the same module:

    train_build(cfg, kernel)  the training module: `init(rng, tokens)`
                           and `apply(params, tokens)` over a whole
                           sequence, no cache -> logits, or (logits,
                           counters, aux) where `train_counters` names
                           the entries of the int32 counter vector that
                           the step sums on the device and hands out
                           beside the loss.  `kernel` is the mesh's
                           attention (None: `default_attention`).  Unlike
                           `build` it keeps float32 masters where the
                           config's `param_dtype` says so, rounds them to
                           `dtype` for each product, and recomputes each
                           block in the backward
    train_loss(logits, tokens)  the scalar the step differentiates
    param_rules()          PartitionSpecs by parameter-path substring for
                           `parallel.mesh.init_sharded`

`resolve(model)` picks the family: a dictionary's `model_type`, or the
family whose config class the instance is; a dictionary without
`model_type`, and a preset's name, are Llama's.
"""

from importlib import import_module
from typing import Any, Tuple

from ray_tpu.models.llama import LlamaConfig, LlamaModel, llama_param_rules

__all__ = ["LlamaConfig", "LlamaModel", "llama_param_rules", "resolve",
           "FAMILIES"]

# `model_type` -> the module of this package that implements it
FAMILIES = {"llama": "llama", "mistral": "llama", "laguna": "laguna",
            "mellum": "laguna", "pangu_ultra_moe": "pangu",
            "glm_moe_dsa": "pangu", "granitemoehybrid": "granite",
            "sdar_moe": "laguna", "olmo_hybrid": "olmo_hybrid",
            "qwen3_next": "qwen3_next"}
# keys that a `model_type`'s published config class defaults, so that a
# dictionary of that type may leave them out (a family's `from_dict`
# takes an absent key for an absent mechanism).  `benchmarks/kinds/
# serve_laguna.py` hands the engine its keys without `gating`; once it
# lists the key this entry can go.
# `sdar_moe`: the published class derives from the Qwen3-MoE code, which
# norms q and k whatever the config says (it has no key for it).
# (`qwen3_next` needs no entry: every mechanism of its published class
# has a key in its config or is written into models/qwen3_next.py.)
CLASS_DEFAULTS = {"laguna": {"gating": "per-head"},
                  "sdar_moe": {"qk_norm": True}}


def resolve(model: Any) -> Tuple[Any, Any]:
    """(the family's module, the config) of an engine's `model=`."""
    if isinstance(model, dict):
        model = dict(model)
        model_type = model.pop("model_type", "llama")
        if model_type not in FAMILIES:
            raise ValueError(f"no model family for model_type "
                             f"{model_type!r} (have {sorted(FAMILIES)})")
        name = FAMILIES[model_type]
        model = {**CLASS_DEFAULTS.get(model_type, {}), **model}
    elif isinstance(model, str):
        name = "llama"
    else:
        # a config instance: its class lives in its family's module
        name = type(model).__module__.rsplit(".", 1)[-1]
        if name not in FAMILIES.values():
            raise ValueError(f"no model family takes {model!r}")
    family = import_module(f"ray_tpu.models.{name}")
    return family, family.config(model)
