"""Parameters, operations and bytes of a Mellum-family TRAINING step, for
the share of the model one chip holds, from its shapes and from what was
routed.

Every function takes the configuration file's keys (`configs/mellum2-*`):
the published ones, with `num_attention_heads`, `num_key_value_heads`,
`num_experts` and `vocab_size` = what is HELD here and
`num_experts_routed_over` = the router's width.  Kept with the benchmark
so that no PR that claims a gain can change the yardstick.

REQUIRED operations are what the mathematics asks of this share: a
multiply-add for each held weight a token meets (the experts a token's
routing lands here, not all 16), attention's products over the positions
a layer lets a query see (the band of a sliding layer), and twice that
for the backward.  What rematerialisation recomputes, what a kernel's
padding rows or predicated-off tiles cost, and the probabilities a
blockwise backward recomputes are NOT required and not counted.
"""

from __future__ import annotations

from typing import Any, Dict

SLIDING = "sliding_attention"


# ---------------------------------------------------------------- parameters


def attention_params(m: Dict[str, Any]) -> int:
    """q, k, v and o of one layer, for the heads held."""
    return m["hidden_size"] * m["head_dim"] * (
        2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])


def router_params(m: Dict[str, Any]) -> int:
    return m["hidden_size"] * m["num_experts_routed_over"]


def expert_params(m: Dict[str, Any]) -> int:
    """The three SwiGLU matrices of one routed expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params(m: Dict[str, Any]) -> int:
    return (attention_params(m) + router_params(m) + 2 * m["hidden_size"]
            + m["num_experts"] * expert_params(m))


def total_params(m: Dict[str, Any]) -> int:
    """All parameters held here: the layers, the final norm, the
    embedding rows and the head columns held."""
    return (m["num_hidden_layers"] * layer_params(m) + m["hidden_size"]
            + 2 * m["vocab_size"] * m["hidden_size"])


def whole_model_params(m: Dict[str, Any]) -> int:
    """The published model's, from `published` over the held counts."""
    whole = {**m, **m["published"],
             "num_experts_routed_over": m["published"]["num_experts"]}
    return total_params(whole)


def state_bytes(m: Dict[str, Any]) -> int:
    """float32 parameters and two adamw moments: 12 B a parameter."""
    return 12 * total_params(m)


# ---------------------------------------------------------------- attention


def mean_context(seq_len: int, window: int) -> float:
    """Positions a query sees, mean over a causal sequence of `seq_len`:
    min(i + 1, window) for query i (window 0: no window)."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2.0
    return (window * (window + 1) / 2.0
            + (seq_len - window) * window) / seq_len


def layer_window(m: Dict[str, Any], layer: int) -> int:
    return int(m["sliding_window"]) \
        if m["layer_types"][layer] == SLIDING else 0


def attention_pairs(m: Dict[str, Any], seq_len: int, which: str = "all"
                    ) -> float:
    """(query, key) pairs a sequence's attention covers, summed over the
    layers of a kind: "window", "full" or "all"."""
    total = 0.0
    for i in range(m["num_hidden_layers"]):
        window = layer_window(m, i)
        if which == "all" or (which == "window") == bool(window):
            total += seq_len * mean_context(seq_len, window)
    return total


def attention_flops_per_pair(m: Dict[str, Any], backward: bool) -> float:
    """q k^T and p v forward (2 products of 2 * heads * head_dim
    operations a pair); dV, dP, dQ and dK backward (4)."""
    return (8.0 if backward else 4.0) \
        * m["num_attention_heads"] * m["head_dim"]


def flash_cost(m: Dict[str, Any], seq_len: int, sequences: float, *,
               which: str, backward: bool, itemsize: int = 2
               ) -> Dict[str, float]:
    """Operations and bytes the flash kernels of one direction REQUIRE
    for `sequences` sequences in the layers of a kind.  Bytes: q, k, v
    and the output (and, backward, dO and the three gradients) once; the
    k and v blocks a kernel streams again for every query block are not
    required."""
    pairs = sequences * attention_pairs(m, seq_len, which)
    layers = sum((which == "all") or ((which == "window")
                                      == bool(layer_window(m, i)))
                 for i in range(m["num_hidden_layers"]))
    q = seq_len * m["num_attention_heads"] * m["head_dim"] * itemsize
    kv = seq_len * m["num_key_value_heads"] * m["head_dim"] * itemsize
    tensors = (3 * q + 4 * kv) if backward else (2 * q + 2 * kv)
    return {"flops": pairs * attention_flops_per_pair(m, backward),
            "bytes": float(sequences * layers * tensors)}


# ------------------------------------------------------------------ experts


def expected_held_assignments_per_token(m: Dict[str, Any]) -> float:
    """Of a token's `num_experts_per_tok` assignments, those that land on
    an expert held here if the router spreads them evenly."""
    return (m["num_experts_per_tok"] * m["num_experts"]
            / m["num_experts_routed_over"])


def expert_train_cost(m: Dict[str, Any], assignments: float,
                      expert_calls: float, forward_passes: float = 1.0,
                      act_itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes the expert kernels REQUIRE for `assignments`
    (token, expert) pairs over `expert_calls` (expert, layer pass) pairs
    of a step: `forward_passes` forwards (2 where every block is
    rematerialised: the recomputation is a pass the kernels are really
    asked for) and one backward.

    A forward: 2 * 3 * D * F operations a pair; each touched expert's
    three matrices read once (bfloat16); a pair's input row read, its
    hidden row written and read, its output row written (float32).  The
    backward: twice the operations (dX and dW); the matrices read once
    more and their float32 gradient written; a pair's input and
    cotangent rows read, its hidden row and the two hidden cotangents
    written and read, its dx row written."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    a = act_itemsize
    fwd = {"flops": 2.0 * assignments * expert_params(m),
           "bytes": (expert_calls * expert_params(m) * a
                     + assignments * (d * a + 2 * f * a + 4 * d))}
    bwd = {"flops": 4.0 * assignments * expert_params(m),
           "bytes": (expert_calls * expert_params(m) * (a + 4)
                     + assignments * (2 * d * a + 6 * f * a + d * a))}
    return {k: forward_passes * fwd[k] + bwd[k] for k in fwd}


# ---------------------------------------------------------------- the step


def forward_flops_per_token(m: Dict[str, Any], seq_len: int,
                            held_assignments_per_token: float
                            ) -> Dict[str, float]:
    """Required forward operations of one token of a causal sequence of
    `seq_len`, by part (no embedding lookup: it multiplies nothing)."""
    layers = m["num_hidden_layers"]
    return {
        "projections": 2.0 * layers * attention_params(m),
        "attention": attention_pairs(m, seq_len) / seq_len
        * attention_flops_per_pair(m, backward=False),
        "experts": 2.0 * layers * held_assignments_per_token
        * expert_params(m),
        "router": 2.0 * layers * router_params(m),
        "head": 2.0 * m["vocab_size"] * m["hidden_size"]}


def train_flops_per_token(m: Dict[str, Any], seq_len: int,
                          held_assignments_per_token: float) -> float:
    """Forward and backward: three times the forward."""
    return 3.0 * sum(forward_flops_per_token(
        m, seq_len, held_assignments_per_token).values())
