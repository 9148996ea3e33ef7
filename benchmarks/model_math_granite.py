"""Operations and bytes a step of the hybrid state-space family
(`model_type: granitemoehybrid`) needs, from its shapes and from what
the engine counted.

Every function takes the configuration file's keys
(`configs/granite-*`: the published ones).  Kept with the benchmark so
that no PR that claims a gain can change the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

STATE_ITEMSIZE = 4    # the recurrence's carry is float32 (`assumed`)


def d_inner(m: Dict[str, Any]) -> int:
    return m["mamba_n_heads"] * m["mamba_d_head"]


def conv_dim(m: Dict[str, Any]) -> int:
    """The convolution's channels: x, B and C side by side."""
    return d_inner(m) + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def mlp_params(m: Dict[str, Any]) -> int:
    """W_in hidden -> 2 x width, W_out width -> hidden."""
    return 3 * m["hidden_size"] * m["shared_intermediate_size"]


def mamba_layer_params(m: Dict[str, Any]) -> int:
    """in_proj (z | xBC | dt), the convolution's weight and bias, A_log,
    dt_bias and D a head, the gated norm's scale, out_proj; the MLP and
    the layer's two norms."""
    d, di, heads = m["hidden_size"], d_inner(m), m["mamba_n_heads"]
    conv = conv_dim(m)
    mixer = (d * (di + conv + heads)
             + conv * m["mamba_d_conv"] + (conv if m.get("mamba_conv_bias")
                                           else 0)
             + 3 * heads + di + di * d)
    return mixer + mlp_params(m) + 2 * d


def attention_layer_params(m: Dict[str, Any]) -> int:
    d = m["hidden_size"]
    head = d // m["num_attention_heads"]
    kv = m["num_key_value_heads"] * head
    return 2 * d * d + 2 * d * kv + mlp_params(m) + 2 * d


def layers(m: Dict[str, Any], kind: str) -> int:
    return sum(t == kind for t in m["layer_types"])


def embedding_params(m: Dict[str, Any]) -> int:
    """The table, which is the head too (tied)."""
    return m["vocab_size"] * m["hidden_size"]


def total_params(m: Dict[str, Any]) -> int:
    return (layers(m, "mamba") * mamba_layer_params(m)
            + layers(m, "attention") * attention_layer_params(m)
            + embedding_params(m) + m["hidden_size"])


def state_row_numbers(m: Dict[str, Any]) -> int:
    """H of one (sequence, layer): heads x head width x state size."""
    return m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"]


def state_bytes_per_sequence(m: Dict[str, Any], itemsize: int = 2) -> int:
    """What ONE sequence keeps over all the state layers, whatever its
    length: H in float32 and the convolution's last inputs in the
    model's dtype."""
    conv = (m["mamba_d_conv"] - 1) * conv_dim(m) * itemsize
    return layers(m, "mamba") * (
        state_row_numbers(m) * STATE_ITEMSIZE + conv)


def kv_bytes_per_token(m: Dict[str, Any], itemsize: int = 2) -> int:
    """A key and a value of every KV head, every attention layer."""
    head = m["hidden_size"] // m["num_attention_heads"]
    return (layers(m, "attention") * 2 * m["num_key_value_heads"] * head
            * itemsize)


def state_update_cost(m: Dict[str, Any], rows: float) -> Dict[str, float]:
    """Operations and bytes the decode recurrence REQUIRES for `rows`
    (live lane, state layer) rows: H read once and written once in
    float32; an element of H a multiply by the decay, a multiply-add of
    the input's outer product, and a multiply-add into y: 5 operations."""
    numbers = state_row_numbers(m)
    return {"flops": 5.0 * rows * numbers,
            "bytes": 2.0 * rows * numbers * STATE_ITEMSIZE}


def decode_step_bytes(m: Dict[str, Any], weight_itemsize: float,
                      kv_itemsize: int, contexts: Sequence[float],
                      state_rows: float) -> float:
    """Bytes a decode step has to move: every weight once (the embedding
    table as the head), the live contexts' keys and values, and each
    live (lane, state layer) state row read AND written."""
    return (total_params(m) * weight_itemsize
            + kv_bytes_per_token(m, kv_itemsize) * float(sum(contexts))
            + state_update_cost(m, state_rows)["bytes"])
