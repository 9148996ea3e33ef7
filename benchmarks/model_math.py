"""Operations and bytes a model's step needs, from its shapes alone.

Every function takes the model's sizes under the keys of its published
`config.json` (the configuration file's `model` group).  Kept with the
benchmark so that no PR that claims a gain can change the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict

# LlamaConfig field for each published key (the program's model file
# names its sizes differently)
LLAMA_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "hidden_dim",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


def llama_kwargs(model: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes as `LlamaConfig` keyword arguments.  Refuses
    what `LlamaConfig` cannot express instead of dropping it."""
    if model["head_dim"] * model["num_attention_heads"] \
            != model["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim as hidden_size / "
                         "heads; this configuration's differs")
    if model.get("sliding_window") is not None:
        raise ValueError("models/llama.py has no sliding window")
    if model.get("tie_word_embeddings"):
        raise ValueError("models/llama.py does not tie embeddings")
    return {field: model[key] for key, field in LLAMA_FIELDS.items()}


def layer_matmul_params(m: Dict[str, Any]) -> int:
    """Weights of one block that a token is multiplied by: q, k, v, o
    and the three SwiGLU matrices (norm scales multiply elementwise)."""
    d, hd = m["hidden_size"], m["head_dim"]
    attn = d * hd * (2 * m["num_attention_heads"]
                     + 2 * m["num_key_value_heads"])
    return attn + 3 * d * m["intermediate_size"]


def layer_params(m: Dict[str, Any]) -> int:
    return layer_matmul_params(m) + 2 * m["hidden_size"]


def total_params(m: Dict[str, Any]) -> int:
    """All parameters: blocks, final norm, embedding table, untied head."""
    return (m["num_hidden_layers"] * layer_params(m) + m["hidden_size"]
            + 2 * m["vocab_size"] * m["hidden_size"])


def matmul_params(m: Dict[str, Any]) -> int:
    """Parameters that do a multiply-add for every token: the blocks and
    the head.  The embedding table is a lookup and does none."""
    return (m["num_hidden_layers"] * layer_matmul_params(m)
            + m["vocab_size"] * m["hidden_size"])


def train_flops_per_token(m: Dict[str, Any], seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE for one token
    of a causal sequence of `seq_len`: 6 for each matmul parameter (2
    forward, 4 backward), and causal attention's score and value
    products, 2 * 2 * heads * head_dim a token pair forward and twice
    that backward, over the seq_len / 2 earlier tokens a position sees
    on average.  What rematerialisation recomputes is not counted."""
    attn = (12 * m["num_hidden_layers"] * m["num_attention_heads"]
            * m["head_dim"] * (seq_len / 2.0))
    return 6.0 * matmul_params(m) + attn


def kv_bytes_per_token(m: Dict[str, Any], kv_itemsize: int) -> int:
    """Bytes of keys and values one token holds in the cache, all layers."""
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * kv_itemsize)


def decode_step_bytes(m: Dict[str, Any], weight_itemsize: int,
                      kv_itemsize: int, context_tokens: float) -> float:
    """Bytes a decode step has to read: every block's weights and the
    head once (whatever the batch), one embedding row a lane (left out:
    kilobytes), and the keys and values of `context_tokens` live tokens
    summed over the batch.  Stored float32 weights count as float32."""
    weights = (m["num_hidden_layers"] * layer_params(m) + m["hidden_size"]
               + m["vocab_size"] * m["hidden_size"]) * weight_itemsize
    return weights + context_tokens * kv_bytes_per_token(m, kv_itemsize)
