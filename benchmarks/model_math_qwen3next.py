"""Operations and bytes a step of the hybrid expert family (`model_type:
qwen3_next`) needs, from its shapes and from what the engine counted.

Every function takes the configuration file's keys
(`configs/qwen3-next-*`: the published ones, with `num_hidden_layers`
the layers THIS chip holds, `num_experts` the experts HELD here of
`num_experts_routed_over`, and `vocab_size` the rows held here).  What is
counted is what the algorithm REQUIRES, whatever implements it: a state
row at its shape's bytes, a key and a value of 2 heads x 256, the chunk
form's products with the key heads' `K K^T` and `Q K^T` counted ONCE a
key head (a pair of value heads shares its key head exactly; that the
program repeats q and k to the value heads' number is not required
work), an expert's matrices once a pass that touches it.  Kept with the
benchmark so that no PR that claims a gain can change the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

STATE_ITEMSIZE = 4    # the recurrence's carry is float32 (`assumed`)
CHUNK = 64            # tokens a chunk of the chunk form
LINEAR, FULL = "linear_attention", "full_attention"


def layer_types(m: Dict[str, Any]) -> List[str]:
    """`layer_types`, or every `full_attention_interval`-th layer full."""
    if m.get("layer_types"):
        return list(m["layer_types"])
    every = int(m.get("full_attention_interval", 4))
    return [FULL if i % every == every - 1 else LINEAR
            for i in range(int(m["num_hidden_layers"]))]


def layers(m: Dict[str, Any], kind: str) -> int:
    return sum(t == kind for t in layer_types(m))


def key_dim(m: Dict[str, Any]) -> int:
    return m["linear_num_key_heads"] * m["linear_key_head_dim"]


def value_dim(m: Dict[str, Any]) -> int:
    return m["linear_num_value_heads"] * m["linear_value_head_dim"]


def conv_dim(m: Dict[str, Any]) -> int:
    """The convolution's channels: q, k and v."""
    return 2 * key_dim(m) + value_dim(m)


def linear_mixer_params(m: Dict[str, Any]) -> int:
    """q, k, v and z in one map's count, b and a, the depthwise
    convolution, A_log and dt_bias a value head, the gated norm's
    weight, out."""
    d, heads = m["hidden_size"], m["linear_num_value_heads"]
    return (d * (conv_dim(m) + value_dim(m)) + 2 * d * heads
            + m["linear_conv_kernel_dim"] * conv_dim(m) + 2 * heads
            + m["linear_value_head_dim"] + value_dim(m) * d)


def full_mixer_params(m: Dict[str, Any]) -> int:
    """q with its gate, k, v, o, the q and k norms a head's width."""
    d, hd = m["hidden_size"], m["head_dim"]
    heads, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * heads * 2 * hd + 2 * d * hkv * hd + heads * hd * d + 2 * hd


def expert_params(m: Dict[str, Any]) -> int:
    """The three SwiGLU matrices of one routed expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_layer_params_outside_experts(m: Dict[str, Any]) -> int:
    """The router over ALL experts, the shared expert and its gate."""
    d = m["hidden_size"]
    return (d * m["num_experts_routed_over"]
            + 3 * d * m["shared_expert_intermediate_size"] + d)


def layer_params(m: Dict[str, Any], kind: str) -> int:
    """A whole layer as held: its mixer, the expert layer with the
    experts held here, the two norms."""
    mixer = linear_mixer_params(m) if kind == LINEAR else full_mixer_params(m)
    return (mixer + expert_layer_params_outside_experts(m)
            + m["num_experts"] * expert_params(m) + 2 * m["hidden_size"])


def embedding_params(m: Dict[str, Any]) -> int:
    return m["vocab_size"] * m["hidden_size"]


def total_params(m: Dict[str, Any]) -> int:
    """The file's layers, the embedding, the untied head, the final
    norm."""
    return (sum(layer_params(m, kind) for kind in layer_types(m))
            + 2 * embedding_params(m) + m["hidden_size"])


def params_outside_experts(m: Dict[str, Any]) -> int:
    """Everything a decode step multiplies by whatever was routed: all
    but the routed experts and the embedding table (a lookup)."""
    return (total_params(m) - embedding_params(m)
            - len(layer_types(m)) * m["num_experts"] * expert_params(m))


def state_row_numbers(m: Dict[str, Any]) -> int:
    """S of one (sequence, layer): value heads x key width x value
    width."""
    return (m["linear_num_value_heads"] * m["linear_key_head_dim"]
            * m["linear_value_head_dim"])


def state_bytes_per_sequence(m: Dict[str, Any], itemsize: int = 2) -> int:
    """What ONE sequence keeps over all the linear layers, whatever its
    length: S in float32 and the convolution's last inputs in the model's
    dtype."""
    conv = (m["linear_conv_kernel_dim"] - 1) * conv_dim(m) * itemsize
    return layers(m, LINEAR) * (state_row_numbers(m) * STATE_ITEMSIZE + conv)


def kv_bytes_per_token(m: Dict[str, Any], itemsize: int = 2) -> int:
    """A key and a value of every KV head, every full layer."""
    return (layers(m, FULL) * 2 * m["num_key_value_heads"] * m["head_dim"]
            * itemsize)


def chunk_cost(m: Dict[str, Any], tokens: float, lane_passes: float,
               itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes the CHUNK form requires for `tokens` (token,
    linear layer) pairs in `lane_passes` (lane, pass, linear layer)
    visits of a state, at chunks of 64.  A token: K K^T and Q K^T a KEY
    head (2 C dk each); a VALUE head, T applied to (K | V) (2 C (dk +
    dv)), W S_0, Q S_0 and K^T V' (2 dk dv each) and the masked scores on
    V' (2 C dv).  Bytes: q, k of the key heads, v and o of a token in the
    model's dtype, its two gates in float32; the state read once and
    written once a visit."""
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    per_token = (hk * 2 * 2 * CHUNK * dk
                 + hv * (2 * CHUNK * (dk + dv) + 3 * 2 * dk * dv
                         + 2 * CHUNK * dv))
    token_bytes = (2 * key_dim(m) + 2 * value_dim(m)) * itemsize + 2 * hv * 4
    return {"flops": float(per_token) * tokens,
            "bytes": token_bytes * tokens + 2.0 * lane_passes
            * state_row_numbers(m) * STATE_ITEMSIZE}


def state_update_cost(m: Dict[str, Any], rows: float) -> Dict[str, float]:
    """Operations and bytes the decode recurrence REQUIRES for `rows`
    (live lane, linear layer) rows: S read once and written once in
    float32 at its shape's bytes; 7 operations an element of S."""
    numbers = state_row_numbers(m)
    return {"flops": 7.0 * rows * numbers,
            "bytes": 2.0 * rows * numbers * STATE_ITEMSIZE}


def decode_step_bytes(m: Dict[str, Any], weight_itemsize: float,
                      kv_itemsize: int, contexts: Sequence[float],
                      experts_touched: float, state_rows: float) -> float:
    """Bytes a decode step has to move: the weights outside the routed
    experts once (the mixers, routers, shared experts, norms and this
    share's half of the head), the matrices of the `experts_touched`
    (expert, layer) pairs a token chose, the live contexts' keys and
    values, and each live (lane, linear layer) state row read AND
    written."""
    return ((params_outside_experts(m)
             + experts_touched * expert_params(m)) * weight_itemsize
            + kv_bytes_per_token(m, kv_itemsize) * float(sum(contexts))
            + state_update_cost(m, state_rows)["bytes"])
