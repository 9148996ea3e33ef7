"""Operations and bytes a step of the latent-attention family's SPARSE
setting (`model_type: glm_moe_dsa`) needs, from its shapes and from what
was routed, scored, selected and read.

Every function takes the configuration file's keys (`configs/glm-5-*`):
the published ones, with `n_routed_experts` = the experts HELD here,
`num_experts_routed_over` the router's width and `vocab_size` = the rows
held here.  Kept with the benchmark so that no PR that claims a gain can
change the yardstick.  (The routed experts' matmuls are
`model_math_laguna.expert_matmul_cost`'s: it reads `hidden_size` and
`moe_intermediate_size`, which this family's file has under the same
names.)
"""

from __future__ import annotations

from typing import Any, Dict

STORED_LATENT_ROW = 640    # a 576-number row as the pool stores it


def latent_row_numbers(m: Dict[str, Any]) -> int:
    """The latent part of what a token keeps in a layer: (c, k_rope)."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def cache_row_bytes(m: Dict[str, Any], itemsize: int = 2) -> int:
    """What a token takes in a layer's pools: the latent row as stored
    (640 wide) and the indexer's key."""
    return (STORED_LATENT_ROW + m["index_head_dim"]) * itemsize


def attention_params(m: Dict[str, Any]) -> int:
    """One layer's five projections and its two inner norms."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    rq, r = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    return (d * rq + rq + rq * h * (dn + dr) + d * (r + dr) + r
            + r * h * (dn + dv) + h * dv * d)


def indexer_params(m: Dict[str, Any]) -> int:
    """One layer's indexer: the index queries' and the index key's
    projections, the LayerNorm's scale and bias, the head weights'."""
    d, j, di = m["hidden_size"], m["index_n_heads"], m["index_head_dim"]
    return m["q_lora_rank"] * j * di + d * di + 2 * di + d * j


def expert_params(m: Dict[str, Any]) -> int:
    """The three SwiGLU matrices of one routed expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def is_sparse(m: Dict[str, Any], layer: int) -> bool:
    return layer >= m["first_k_dense_replace"]


def sparse_layers(m: Dict[str, Any]) -> int:
    return sum(is_sparse(m, i) for i in range(m["num_hidden_layers"]))


def layer_params_outside_experts(m: Dict[str, Any], layer: int) -> int:
    """Attention, indexer, the layer's two norms, and the dense MLP or
    the router, its bias and the shared expert."""
    d = m["hidden_size"]
    n = attention_params(m) + indexer_params(m) + 2 * d
    if not is_sparse(m, layer):
        return n + 3 * d * m["intermediate_size"]
    return (n + (d + 1) * m["num_experts_routed_over"]
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


def index_score_cost(m: Dict[str, Any], pairs: float, keys_read: float,
                     itemsize: int = 2) -> Dict[str, float]:
    """What scoring `pairs` (query, visible key) pairs REQUIRES: every
    index head's product over the key, 2 J dI operations a pair (8,192
    at 32 x 128; the ReLU, the weights and the sum over heads are a
    hundredth of that and not counted), and each of `keys_read` index
    keys read once (256 B)."""
    return {"flops": 2.0 * pairs * m["index_n_heads"] * m["index_head_dim"],
            "bytes": float(keys_read) * m["index_head_dim"] * itemsize}


def selected_attention_flops(m: Dict[str, Any], pairs: float) -> float:
    """What the absorbed attention REQUIRES for `pairs` (query, SELECTED
    row) pairs: every head's score over the whole latent row and its
    weighted sum of the row's compressed part, 2 H (row + kv_lora_rank)
    operations a pair (139,264 at 64 heads, 576 + 512)."""
    return 2.0 * pairs * m["num_attention_heads"] * (
        latent_row_numbers(m) + m["kv_lora_rank"])


def decode_step_bytes(m: Dict[str, Any], weight_itemsize: float,
                      kv_itemsize: int, index_rows: float,
                      latent_rows: float, experts_touched: float) -> float:
    """Bytes a decode step has to read: the weights outside the routed
    experts once, the matrices of the `experts_touched` experts that a
    token chose (summed over the expert layers), the `index_rows` index
    keys its lanes' indexers scan (256 B each) and the `latent_rows`
    latent rows their attention then reads (576 numbers each: the 64
    zeros behind a stored row are the layout's, not required) — both
    summed over lanes and layers."""
    return ((params_outside_experts(m)
             + experts_touched * expert_params(m)) * weight_itemsize
            + index_rows * m["index_head_dim"] * kv_itemsize
            + latent_rows * latent_row_numbers(m) * kv_itemsize)
