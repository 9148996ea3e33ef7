"""Operations and bytes a step of the latent-attention family
(`model_type: pangu_ultra_moe`) needs, from its shapes and from what was
routed and read.

Every function takes the configuration file's keys
(`configs/openpangu-*`): the published ones, with `n_routed_experts` =
the experts HELD here, `num_experts_routed_over` the router's width and
`vocab_size` = the rows held here.  Kept with the benchmark so that no
PR that claims a gain can change the yardstick.  (The routed experts'
matmuls are `model_math_laguna.expert_matmul_cost`'s: it reads
`hidden_size` and `moe_intermediate_size`, which this family's file has
under the same names.)
"""

from __future__ import annotations

from typing import Any, Dict, Sequence


def latent_row_numbers(m: Dict[str, Any]) -> int:
    """What a token keeps in a layer's cache: (c, k_rope)."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_params(m: Dict[str, Any]) -> int:
    """One layer's five projections and its two inner norms."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    rq, r = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    return (d * rq + rq + rq * h * (dn + dr) + d * (r + dr) + r
            + r * h * (dn + dv) + h * dv * d)


def expert_params(m: Dict[str, Any]) -> int:
    """The three SwiGLU matrices of one routed expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def is_sparse(m: Dict[str, Any], layer: int) -> bool:
    return layer >= m["first_k_dense_replace"]


def sparse_layers(m: Dict[str, Any]) -> int:
    return sum(is_sparse(m, i) for i in range(m["num_hidden_layers"]))


def layer_params_outside_experts(m: Dict[str, Any], layer: int) -> int:
    """Attention, the layer's norms (four with sandwich norms), and the
    dense MLP or the router and the shared expert."""
    d = m["hidden_size"]
    n = attention_params(m) + (4 if m.get("sandwich_norm") else 2) * d
    if not is_sparse(m, layer):
        return n + 3 * d * m["intermediate_size"]
    return (n + d * m["num_experts_routed_over"]
            + m["n_shared_experts"] * expert_params(m))


def params_outside_experts(m: Dict[str, Any]) -> int:
    """Everything a decode step multiplies by whatever was routed: the
    layers without their routed experts, the final norm and the head.
    (The embedding is a lookup: one row a lane.)"""
    return (sum(layer_params_outside_experts(m, i)
                for i in range(m["num_hidden_layers"]))
            + m["hidden_size"] + m["vocab_size"] * m["hidden_size"])


def total_params(m: Dict[str, Any]) -> int:
    """All parameters held here: the above, the embedding table and the
    held experts of every expert layer."""
    return (params_outside_experts(m)
            + m["vocab_size"] * m["hidden_size"]
            + sparse_layers(m) * m["n_routed_experts"] * expert_params(m))


def latent_attention_cost(m: Dict[str, Any], rows: float,
                          itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes the absorbed attention REQUIRES to read
    `rows` (token, layer) cache rows with one query a lane: every head's
    score over the whole row and its weighted sum of the row's
    compressed part, 2 H (row + kv_lora_rank) operations a row; the
    row's numbers read once (the pool stores a row 640 wide; the 64
    zeros behind it are the layout's, not required, and not counted)."""
    row = latent_row_numbers(m)
    return {"flops": 2.0 * rows * m["num_attention_heads"]
            * (row + m["kv_lora_rank"]),
            "bytes": float(rows) * row * itemsize}


def decode_step_bytes(m: Dict[str, Any], weight_itemsize: float,
                      kv_itemsize: int, contexts: Sequence[float],
                      experts_touched: float) -> float:
    """Bytes a decode step has to read: the weights outside the routed
    experts once, the matrices of the `experts_touched` experts that a
    token chose (summed over the expert layers), and every layer's
    latent rows of the live contexts."""
    rows = m["num_hidden_layers"] * float(sum(contexts))
    return ((params_outside_experts(m)
             + experts_touched * expert_params(m)) * weight_itemsize
            + latent_attention_cost(m, rows, kv_itemsize)["bytes"])
