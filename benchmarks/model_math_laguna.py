"""Operations and bytes a Laguna-family step needs, from its shapes and
from what was routed.

Every function takes the configuration file's keys (`configs/laguna-*`):
the published ones, with `num_experts` = the experts HELD here and
`vocab_size` = the rows held here.  Kept with the benchmark so that no
PR that claims a gain can change the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

SLIDING = "sliding_attention"


def attention_params(m: Dict[str, Any], layer: int) -> int:
    """q, k, v, o and the head-wise gate of one layer."""
    d, hd = m["hidden_size"], m["head_dim"]
    heads = m["num_attention_heads_per_layer"][layer]
    return d * hd * (2 * heads + 2 * m["num_key_value_heads"]) + d * heads


def expert_params(m: Dict[str, Any]) -> int:
    """The three SwiGLU matrices of one routed expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params_outside_experts(m: Dict[str, Any], layer: int) -> int:
    d = m["hidden_size"]
    n = attention_params(m, layer) + 2 * d
    if m["mlp_layer_types"][layer] == "dense":
        return n + 3 * d * m["intermediate_size"]
    return (n + d * m["num_experts_routed_over"]
            + 3 * d * m["shared_expert_intermediate_size"])


def sparse_layers(m: Dict[str, Any]) -> int:
    return sum(t == "sparse" for t in m["mlp_layer_types"])


def params_outside_experts(m: Dict[str, Any]) -> int:
    """Everything a decode step multiplies by whatever was routed: the
    layers without their routed experts, the final norm and the head.
    (The embedding is a lookup: one row a lane.)"""
    return (sum(layer_params_outside_experts(m, i)
                for i in range(m["num_hidden_layers"]))
            + m["hidden_size"] + m["vocab_size"] * m["hidden_size"])


def total_params(m: Dict[str, Any]) -> int:
    """All parameters held here: the above, the embedding table and the
    held experts of every sparse layer."""
    return (params_outside_experts(m)
            + m["vocab_size"] * m["hidden_size"]
            + sparse_layers(m) * m["num_experts"] * expert_params(m))


def kv_bytes_per_token_layer(m: Dict[str, Any], kv_itemsize: int) -> int:
    return 2 * m["num_key_value_heads"] * m["head_dim"] * kv_itemsize


def decode_kv_bytes(m: Dict[str, Any], kv_itemsize: int,
                    contexts: Sequence[float]) -> float:
    """Keys and values a decode step reads for lanes with these context
    lengths: a full layer every position, a sliding layer the last
    `sliding_window` at most."""
    row = kv_bytes_per_token_layer(m, kv_itemsize)
    total = 0.0
    for kind in m["layer_types"]:
        for n in contexts:
            total += row * (min(n, m["sliding_window"])
                            if kind == SLIDING else n)
    return total


def decode_step_bytes(m: Dict[str, Any], weight_itemsize: float,
                      kv_itemsize: int, contexts: Sequence[float],
                      experts_touched: float) -> float:
    """Bytes a decode step has to read: the weights outside the routed
    experts once, the matrices of the `experts_touched` experts that a
    token chose (summed over the sparse layers), and the live keys and
    values with the window bound."""
    return ((params_outside_experts(m)
             + experts_touched * expert_params(m)) * weight_itemsize
            + decode_kv_bytes(m, kv_itemsize, contexts))


def expert_matmul_cost(m: Dict[str, Any], assignments: float,
                       experts_touched: float, weight_itemsize: float = 2,
                       act_itemsize: float = 2) -> Dict[str, float]:
    """Operations and bytes the routed experts' matmuls REQUIRE for
    `assignments` (token, expert) pairs over `experts_touched` (expert,
    layer pass) pairs: 2 x 3 x D x F multiply-adds a pair; each touched
    expert's three matrices read once a pass; a pair's input row read,
    its hidden row written and read, its output row written (float32).
    Rows a kernel pads its tiles with are not required and not
    counted."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return {"flops": 2.0 * assignments * expert_params(m),
            "bytes": (experts_touched * expert_params(m) * weight_itemsize
                      + assignments * (d * act_itemsize
                                       + 2 * f * act_itemsize + 4 * d))}
