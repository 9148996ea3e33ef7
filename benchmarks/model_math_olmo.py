"""Operations and bytes a step of the hybrid linear-attention family
(`model_type: olmo_hybrid`) needs, from its shapes and from what the
engine counted.

Every function takes the configuration file's keys (`configs/olmo-*`:
the published ones; `num_hidden_layers` and `layer_types` are the
layers THIS chip holds).  What is counted is what the algorithm
REQUIRES, whatever implements it: a state row at its shape's bytes
whatever the stored layout pads, a key and a value of the model's 30
heads whatever the cache row holds, the chunk form's products and not
how the inverse is built.  Kept with the benchmark so that no PR that
claims a gain can change the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

STATE_ITEMSIZE = 4    # the recurrence's carry is float32 (`assumed`)
CHUNK = 64            # tokens a chunk of the chunk form


def key_dim(m: Dict[str, Any]) -> int:
    return m["linear_num_key_heads"] * m["linear_key_head_dim"]


def value_dim(m: Dict[str, Any]) -> int:
    return m["linear_num_value_heads"] * m["linear_value_head_dim"]


def conv_dim(m: Dict[str, Any]) -> int:
    """The convolutions' channels: q, k and v."""
    return 2 * key_dim(m) + value_dim(m)


def mlp_params(m: Dict[str, Any]) -> int:
    """gate, up hidden -> width each; down width -> hidden."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def linear_layer_params(m: Dict[str, Any]) -> int:
    """q, k, v and the output gate, a and b, the three depthwise
    convolutions, A_log and dt_bias a head, the gated norm's weight,
    out; the MLP and the layer's two norms."""
    d, heads = m["hidden_size"], m["linear_num_value_heads"]
    mixer = (d * conv_dim(m) + d * value_dim(m) + 2 * d * heads
             + m["linear_conv_kernel_dim"] * conv_dim(m) + 2 * heads
             + m["linear_value_head_dim"] + value_dim(m) * d)
    return mixer + mlp_params(m) + 2 * d


def full_layer_params(m: Dict[str, Any]) -> int:
    """q, o hidden x hidden; k, v hidden x KV width; the q and k norms
    over their whole widths; the MLP and the layer's two norms."""
    d = m["hidden_size"]
    kv = m["num_key_value_heads"] * (d // m["num_attention_heads"])
    return 2 * d * d + 2 * d * kv + d + kv + mlp_params(m) + 2 * d


def layers(m: Dict[str, Any], kind: str) -> int:
    return sum(t == kind for t in m["layer_types"])


def embedding_params(m: Dict[str, Any]) -> int:
    return m["vocab_size"] * m["hidden_size"]


def total_params(m: Dict[str, Any]) -> int:
    """The file's layers, the embedding, the untied head, the final
    norm."""
    return (layers(m, "linear_attention") * linear_layer_params(m)
            + layers(m, "full_attention") * full_layer_params(m)
            + 2 * embedding_params(m) + m["hidden_size"])


def state_row_numbers(m: Dict[str, Any]) -> int:
    """S of one (sequence, layer): heads x key width x value width."""
    return (m["linear_num_value_heads"] * m["linear_key_head_dim"]
            * m["linear_value_head_dim"])


def state_bytes_per_sequence(m: Dict[str, Any], itemsize: int = 2) -> int:
    """What ONE sequence keeps over all the linear layers, whatever its
    length: S in float32 and the convolutions' last inputs in the
    model's dtype."""
    conv = (m["linear_conv_kernel_dim"] - 1) * conv_dim(m) * itemsize
    return layers(m, "linear_attention") * (
        state_row_numbers(m) * STATE_ITEMSIZE + conv)


def kv_bytes_per_token(m: Dict[str, Any], itemsize: int = 2) -> int:
    """A key and a value of every KV head of the MODEL, every full
    layer (the cache row holds two heads of zeros more: `assumed`)."""
    head = m["hidden_size"] // m["num_attention_heads"]
    return (layers(m, "full_attention") * 2 * m["num_key_value_heads"]
            * head * itemsize)


def chunk_cost(m: Dict[str, Any], tokens: float, lane_passes: float,
               itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes the CHUNK form requires for `tokens` (token,
    linear layer) pairs in `lane_passes` (lane, pass, linear layer)
    visits of a state, at chunks of 64.  A token a head: K K^T and
    Q K^T (2 C dk each), T applied to (K | V) (2 C (dk + dv)), W S_0,
    Q S_0 and K^T V' (2 dk dv each), the masked scores on V' (2 C dv) —
    196,608 operations at 96 x 192, 5.9 M over 30 heads.  Bytes: q, k,
    v and o of a token in the model's dtype, its two gates in float32;
    the state read once and written once a visit."""
    heads = m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    per_token = heads * (2 * 2 * CHUNK * dk + 2 * CHUNK * (dk + dv)
                         + 3 * 2 * dk * dv + 2 * CHUNK * dv)
    token_bytes = (2 * key_dim(m) + 2 * value_dim(m)) * itemsize \
        + 2 * heads * 4
    return {"flops": float(per_token) * tokens,
            "bytes": token_bytes * tokens + 2.0 * lane_passes
            * state_row_numbers(m) * STATE_ITEMSIZE}


def state_update_cost(m: Dict[str, Any], rows: float) -> Dict[str, float]:
    """Operations and bytes the decode recurrence REQUIRES for `rows`
    (live lane, linear layer) rows: S read once and written once in
    float32 at its shape's bytes; an element of S a multiply by the
    decay, a multiply-add into S^T k, a multiply-add of the rank-1
    update and a multiply-add into S^T q: 7 operations."""
    numbers = state_row_numbers(m)
    return {"flops": 7.0 * rows * numbers,
            "bytes": 2.0 * rows * numbers * STATE_ITEMSIZE}


def decode_step_bytes(m: Dict[str, Any], weight_itemsize: float,
                      kv_itemsize: int, contexts: Sequence[float],
                      state_rows: float) -> float:
    """Bytes a decode step has to move: every weight a pass reads (all
    but the embedding table, of which it reads a row a lane), the live
    contexts' keys and values, and each live (lane, linear layer) state
    row read AND written."""
    return ((total_params(m) - embedding_params(m)) * weight_itemsize
            + kv_bytes_per_token(m, kv_itemsize) * float(sum(contexts))
            + state_update_cost(m, state_rows)["bytes"])
