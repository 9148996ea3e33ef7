"""Operations and bytes a step of the SDAR configuration needs, from its
shapes and from what was routed and read.

Every function takes the configuration file's keys
(`configs/sdar-30b-a3b-chat-serve.json`): the published ones, every
expert and the whole vocabulary held.  Kept with the benchmark so that
no PR that claims a gain can change the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict


def attention_params(m: Dict[str, Any]) -> int:
    """q, k, v and o of one layer, and the two norms' weights."""
    d, hd = m["hidden_size"], m["head_dim"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * hd * (2 * heads + 2 * kv) + 2 * hd


def expert_params(m: Dict[str, Any]) -> int:
    """The three SwiGLU matrices of one routed expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params_outside_experts(m: Dict[str, Any]) -> int:
    """Attention, the two layer norms and the router."""
    d = m["hidden_size"]
    return attention_params(m) + 2 * d + d * m["num_experts"]


def params_outside_experts(m: Dict[str, Any]) -> int:
    """Everything a block pass multiplies by whatever was routed: the
    layers without their experts, the final norm and the head.  (The
    embedding is a lookup: four rows a lane.)"""
    return (m["num_hidden_layers"] * layer_params_outside_experts(m)
            + m["hidden_size"] + m["vocab_size"] * m["hidden_size"])


def total_params(m: Dict[str, Any]) -> int:
    """All parameters held here: the above, the embedding table and
    every expert of every layer."""
    return (params_outside_experts(m) + m["vocab_size"] * m["hidden_size"]
            + m["num_hidden_layers"] * m["num_experts"] * expert_params(m))


def kv_bytes_per_row(m: Dict[str, Any], kv_itemsize: int) -> int:
    """A cached position's key and value in ONE layer."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * kv_itemsize


def block_pass_bytes(m: Dict[str, Any], weight_itemsize: float,
                     kv_itemsize: int, rows_read: float,
                     experts_touched: float) -> float:
    """Bytes a block pass has to read: the weights outside the experts
    once, the matrices of the `experts_touched` experts some position
    chose (summed over the layers), and the `rows_read` cached rows its
    lanes' blocks see (a lane's context, a layer: the engine's
    `block_rows_read_total`)."""
    return ((params_outside_experts(m)
             + experts_touched * expert_params(m)) * weight_itemsize
            + rows_read * kv_bytes_per_row(m, kv_itemsize))


def block_kernel_cost(m: Dict[str, Any], rows_read: float,
                      kv_itemsize: int) -> Dict[str, float]:
    """Operations and bytes the block kernel REQUIRES for `rows_read`
    cached rows (a lane's context, a layer): each row's key and value
    read once; each of the block's B queries in each of the H heads
    takes a dot product with the key and adds the value, 2 x 2 x hd
    operations a query a head a row.  (The queries and the output are
    B x H x hd numbers a lane whatever it reads: not counted.)"""
    b = m["generation"]["block_length"]
    return {"bytes": rows_read * kv_bytes_per_row(m, kv_itemsize),
            "flops": rows_read * 4.0 * b * m["num_attention_heads"]
            * m["head_dim"]}
