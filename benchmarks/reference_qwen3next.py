"""Plain reference of the hybrid expert decoder (`model_type:
qwen3_next`) for the share of it one chip holds: gated-delta-rule mixers
of fewer key heads than value heads, a gated full-attention layer of few
wide KV heads with partial rotary positions, and in EVERY layer routed
experts beside a gated shared expert; as many layers as the parameter
tree holds.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`, with no cache, no kernel and no
chunk form: the recurrence runs TOKEN BY TOKEN exactly as it is written
below (`lax.scan` over the sequence, the state its carry), the
convolution is four shifted sums, the attention a causal softmax a block
of queries at a time, the expert layer a LOOP over the experts held, each
applied to every token and multiplied by that token's routing weight for
it, or zero.  It shares no code with `ray_tpu/`: it reads the engine's
parameter tree (flax names of `ray_tpu/models/qwen3_next.py`)

    embed/embedding [V, D]; lm_head/kernel [D, V]; final_norm/w
    layer_i/{norm, mlp_norm}/w
    layer_i/mixer/{qkv_proj [D, 2 Hk dk + Hv dv], gate_proj [D, Hv dv],
                   ab_proj [D, 2 Hv], out_proj [Hv dv, D]}/kernel,
                   conv_w [K, channels], a_log, dt_bias [Hv], norm_w [dv]
    layer_i/attn/{wq_gate [D, H, 2 d], wk, wv [D, Hkv, d],
                  wo [H, d, D]}/kernel, {q_norm, k_norm}/w [d]
    layer_i/moe/moe_router [D, E]; moe_experts_{w1, w3} [E_held, D, F],
                  moe_experts_w2 [E_held, F, D];
                  moe_shared/{w1, w3, w2}/kernel; moe_shared_gate [D, 1]

and the model's published sizes (`sizes`, the configuration file's
keys).  For layer l:

    h = x + Mixer(Norm(x));  y = h + Experts(Norm(h))
    Norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)
    full layer: [q | gate] = W_q x a head; k, v = W_k x, W_v x; q, k <-
        Norm a head; rotary over the first `partial_rotary_factor` of the
        head (rotate-half within them, theta `rope_theta`); causal
        softmax(q k^T / sqrt(d)) v, a KV head serving H / Hkv query
        heads; W_o (attn * sigmoid(gate))
    linear layer, value head h under key head h // (Hv / Hk):
        q~, k~, v~ = silu(conv(W_qkv x)), causal depthwise, no bias
        q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(dk); k = k~ / sqrt(|k~|^2 + 1e-6)
        beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias)
        S_t = e^g S_{t-1} + beta k_t (v_t - e^g S_{t-1}^T k_t)^T
        o_t = S_t^T q_t
        out = W_o concat_h(RMSNorm(o_t; norm_w) * silu(W_z x))
    experts: p = softmax(W_r x) over all E; the k largest, divided by
        their sum; routed = sum over the chosen experts e in
        [lo, lo + E_held) of p_e down_e(silu(gate_e x) * up_e x);
        y = routed + sigmoid(w_g . x) down_s(silu(gate_s x) * up_s x)
    logits = lm_head(Norm(x))

What experts of the other share would add is left out, as the program
leaves it out.  Computed in blocks so that it fits beside an engine that
fills the chip: a layer at a time, one expert's float32 copy alive at a
time (`lax.fori_loop`), the attention's queries in blocks of 128, the
head's vocabulary in slices whose largest logit, its id and the picked
token's logit are kept and nothing else.

Besides what `reference.teacher_forced` returns, each position gets its
router MARGIN (as `reference_laguna`'s): the smallest, over the layers,
of log p(k-th) - log p((k+1)-th) of the router's probabilities.
`carried_states` gives each linear layer's state behind a sequence's
last token, for the comparison of the CARRY that
`replica_qwen3next.bench_carry` makes.

**Other readings** (`reading=`), each the same computation with ONE thing
changed, for what the comparison of `kinds/serve_qwen3next.py` says of a
program with that fault (the reading is compared with the reference
proper, as a faulty program would be):

    "float8_e4m3fn"    every stored matrix rounded to that dtype (the
                       nearest precision below the stated bfloat16)
    "beta_doubled"     beta = 2 sigmoid(b): the other hybrid family's
    "keys_tiled"       key heads repeated by TILING (value head h under
                       key head h mod Hk) instead of pairwise
    "alpha_one"        g = 0: the state never decays
    "bfloat16_state"   the recurrence's carry rounded to bfloat16 a token
    "stale_slot"       the state does not start at zero but at what
                       ANOTHER sequence of the prompt's length left (the
                       prompt's own inputs, newest first, run from zero)
    "no_attn_gate"     the full layer's output is not gated
    "rotary_all"       every dimension of a head is rotated
    "no_qk_norm"       the full layer's q and k are not normed
    "w_not_one_plus_w" every zero-centred norm multiplies by w
    "no_shared_gate"   the shared expert is added ungated
    "topk_not_normalised"  the chosen experts keep their softmax weights
    "other_share_added"    a share that is not a share: an expert of the
                       other share's range is computed with the held
                       expert of the same index there and added in
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# (of the benchmark's own files, plain arithmetic: which layers are full,
# and the distance between two lists of states)
from benchmarks.model_math_qwen3next import layer_types
from benchmarks.reference_olmo import carry_distance  # noqa: F401

F32 = jnp.float32
Q_BLOCK = 128        # queries a block of the attention
LENGTHS = (256, 1024, 4096, 9728, 16384)   # a sequence is padded to
VOCAB_SLICE = 9496   # columns a slice of the head (75968 / 8)
L2_EPS = 1e-6
READINGS = ("float8_e4m3fn", "beta_doubled", "keys_tiled", "alpha_one",
            "bfloat16_state", "stale_slot", "no_attn_gate", "rotary_all",
            "no_qk_norm", "w_not_one_plus_w", "no_shared_gate",
            "topk_not_normalised", "other_share_added")


def _matrix(w, reading: Optional[str]):
    """A stored matrix as float32, through float8 under that reading."""
    if reading == "float8_e4m3fn":
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(F32)


def _norm(x, w, eps, reading=None):
    """The zero-centred norm: assumed (1), `1 + w`."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    w = w.astype(F32)
    scale = w if reading == "w_not_one_plus_w" else 1.0 + w
    return x * jax.lax.rsqrt(var + eps) * scale


def _swiglu(h, w1, w3, w2, reading):
    return (jax.nn.silu(h @ _matrix(w1, reading)) * (h @ _matrix(w3, reading))
            ) @ _matrix(w2, reading)


def _rotary(x, dim: int, theta: float):
    """x [S, H, d]: the first `dim` dimensions rotated by position
    (rotate-half inside them), the rest pass."""
    inv = np.asarray([theta ** (-2.0 * i / dim) for i in range(dim // 2)],
                     np.float32)
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv   # [S, dim/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dim:]], axis=-1)


def _attention(x, p, *, rotary: float, theta: float, eps: float, reading):
    """The gated full-attention layer on one sequence x [S, D], S a
    multiple of Q_BLOCK."""
    s = x.shape[0]
    qg = jnp.einsum("sd,dhk->shk", x, _matrix(p["wq_gate"]["kernel"],
                                               reading))
    d = qg.shape[-1] // 2
    q, gate = qg[..., :d], qg[..., d:]
    k = jnp.einsum("sd,dhk->shk", x, _matrix(p["wk"]["kernel"], reading))
    v = jnp.einsum("sd,dhk->shk", x, _matrix(p["wv"]["kernel"], reading))
    if reading != "no_qk_norm":
        q = _norm(q, p["q_norm"]["w"], eps, reading)
        k = _norm(k, p["k_norm"]["w"], eps, reading)
    dim = d if reading == "rotary_all" else int(d * rotary)
    q, k = _rotary(q, dim, theta), _rotary(k, dim, theta)
    heads, hkv = q.shape[1], k.shape[1]
    k = jnp.repeat(k, heads // hkv, axis=1)
    v = jnp.repeat(v, heads // hkv, axis=1)
    scale = d ** -0.5

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        scores = jnp.einsum("shk,thk->hst", qi, k) * scale
        seen = (jnp.arange(s)[None, :]
                <= (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None])
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hst,thk->shk", probs, v)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK)).reshape(s, heads, d)
    if reading != "no_attn_gate":
        out = out * jax.nn.sigmoid(gate)
    return jnp.einsum("shk,hkd->sd", out, _matrix(p["wo"]["kernel"],
                                                   reading))


def _delta(x, p, *, key_heads: int, heads: int, dk: int, dv: int,
           eps: float, n_real, reading):
    """One gated-delta-rule mixer over x [S, D], the recurrence token by
    token -> (its output, the state [Hv, dk, dv] behind the last real
    token).  `n_real`: the tokens before the padding (traced)."""
    s = x.shape[0]
    kd = key_heads * dk
    gate = x @ _matrix(p["gate_proj"]["kernel"], reading)
    ab = x @ _matrix(p["ab_proj"]["kernel"], reading)
    taps = p["conv_w"].shape[0]
    at = jnp.arange(s)

    def part(lo, hi):
        """silu(conv(W x)) of the channels [lo, hi)."""
        u = x @ _matrix(p["qkv_proj"]["kernel"][:, lo:hi], reading)
        w = p["conv_w"][:, lo:hi].astype(F32)            # [K, channels]
        padded = jnp.concatenate([jnp.zeros((taps - 1, hi - lo), F32), u])
        conv = jnp.zeros_like(u)
        for j in range(taps):
            conv = conv + padded[j:j + s] * w[j]
        return jax.nn.silu(conv)

    q = part(0, kd).reshape(s, key_heads, dk)
    k = part(kd, 2 * kd).reshape(s, key_heads, dk)
    v = part(2 * kd, 2 * kd + heads * dv).reshape(s, heads, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) / np.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    # key head j serves value heads 2j and 2j + 1: assumed (3)
    each = heads // key_heads
    if reading == "keys_tiled":
        q, k = jnp.tile(q, (1, each, 1)), jnp.tile(k, (1, each, 1))
    else:
        q, k = jnp.repeat(q, each, axis=1), jnp.repeat(k, each, axis=1)
    a = jnp.exp(p["a_log"].astype(F32))                  # [Hv]
    g = -a * jax.nn.softplus(ab[:, :heads] + p["dt_bias"].astype(F32))
    if reading == "alpha_one":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(ab[:, heads:])
    if reading == "beta_doubled":
        beta = 2.0 * beta
    # the padding behind the sequence leaves the state as it was
    real = (at < n_real["row"])[:, None]
    g, beta = g * real, beta * real

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        state = jnp.exp(g_t)[:, None, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, k_t)      # S^T k
        state = state + k_t[:, :, None] \
            * (b_t[:, None] * (v_t - seen))[:, None, :]
        if reading == "bfloat16_state":
            # not `astype` there and back: XLA may keep a convert pair's
            # excess precision, and the reading then changes nothing
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    state = jnp.zeros((heads, dk, dv), F32)
    if reading == "stale_slot":
        own = (at < n_real["prompt"])[::-1, None]
        state, _ = jax.lax.scan(
            step, state, (q[::-1], k[::-1], v[::-1], g[::-1] * own,
                          beta[::-1] * own))
    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    # the norm a head with its plain weight, THEN the gate: assumed (2)
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = (o * jax.lax.rsqrt(var + eps) * p["norm_w"].astype(F32)
         ).reshape(s, heads * dv)
    return (o * jax.nn.silu(gate)) @ _matrix(p["out_proj"]["kernel"],
                                             reading), state


def _experts(h, moe, *, top_k: int, normalize: bool, lo: int, reading):
    """h [T, D] -> (this share's routed sum plus the gated shared expert
    [T, D], the router's margin [T])."""
    logits = h @ _matrix(moe["moe_router"], reading)              # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)       # before the top-k: (5)
    top, ids = jax.lax.top_k(probs, top_k + 1)
    margin = jnp.log(top[:, top_k - 1]) - jnp.log(top[:, top_k])
    top, ids = top[:, :top_k], ids[:, :top_k]
    if normalize and reading != "topk_not_normalised":
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    w1, w3, w2 = (moe[f"moe_experts_{n}"] for n in ("w1", "w3", "w2"))
    held = w1.shape[0]

    def one(e, acc):
        mine = ids == lo + e
        if reading == "other_share_added":
            mine = (ids - lo) % held == e
        weight = jnp.sum(jnp.where(mine, top, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(h, w1[e], w3[e], w2[e],
                                               reading)

    routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(h))
    shared = _swiglu(h, *(moe["moe_shared"][n]["kernel"]
                          for n in ("w1", "w3", "w2")), reading)
    if reading != "no_shared_gate":
        shared = jax.nn.sigmoid(h @ _matrix(moe["moe_shared_gate"],
                                            reading)) * shared
    return routed + shared, margin


@partial(jax.jit, static_argnames=("kind", "sizes", "reading"))
def _layer(p, x, n_real, *, kind: str, sizes, reading):
    """One layer on x [S, D] -> (its output, the router's margin [S],
    the recurrence's state behind the sequence's last token or None);
    `sizes`: a tuple of (key, value) pairs."""
    m = dict(sizes)
    eps = m["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        state = None
        h = _norm(x, p["norm"]["w"], eps, reading)
        if kind == "linear_attention":
            y, state = _delta(
                h, p["mixer"], key_heads=m["linear_num_key_heads"],
                heads=m["linear_num_value_heads"],
                dk=m["linear_key_head_dim"], dv=m["linear_value_head_dim"],
                eps=eps, n_real=n_real, reading=reading)
        else:
            y = _attention(h, p["attn"], rotary=m["partial_rotary_factor"],
                           theta=m["rope_theta"], eps=eps, reading=reading)
        x = x + y
        h = _norm(x, p["mlp_norm"]["w"], eps, reading)
        y, margin = _experts(h, p["moe"], top_k=m["num_experts_per_tok"],
                             normalize=m["norm_topk_prob"], lo=m["lo"],
                             reading=reading)
        return x + y, margin, state


@partial(jax.jit, static_argnames=("eps", "reading"))
def _head(head, norm_w, x, picks, *, eps, reading):
    """(largest logit, its id, the logit of `picks`) of x [K, D], the
    vocabulary a slice at a time.  head: [D, V]."""
    with jax.default_matmul_precision("highest"):
        h = _norm(x, norm_w, eps, reading)
        vocab = head.shape[1]
        top = jnp.full((x.shape[0],), -jnp.inf, F32)
        top_id = jnp.zeros((x.shape[0],), jnp.int32)
        picked = jnp.zeros((x.shape[0],), F32)
        for lo in range(0, vocab, VOCAB_SLICE):
            cols = _matrix(head[:, lo:lo + VOCAB_SLICE], reading)
            lg = h @ cols
            best = jnp.argmax(lg, axis=-1)
            best_val = jnp.max(lg, axis=-1)
            top_id = jnp.where(best_val > top, lo + best, top_id
                               ).astype(jnp.int32)
            top = jnp.maximum(top, best_val)
            here = (picks >= lo) & (picks < lo + cols.shape[1])
            mine = jnp.take_along_axis(
                lg, jnp.clip(picks - lo, 0, cols.shape[1] - 1)[:, None],
                axis=-1)[:, 0]
            picked = jnp.where(here, mine, picked)
        return top, top_id, picked


def _static(sizes: Dict[str, Any]):
    keys = ("rms_norm_eps", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "partial_rotary_factor", "rope_theta", "num_experts_per_tok",
            "norm_topk_prob")
    return tuple((k, sizes[k]) for k in keys) \
        + (("lo", int(sizes.get("experts_held", (0, 0))[0])),)


def hidden(params: Dict[str, Any], tokens, sizes: Dict[str, Any],
           prompt_len: Optional[int] = None, reading: Optional[str] = None,
           states: Optional[List[Any]] = None):
    """(the last layer's output [S_padded, D], the router's margin
    [S_padded]) of one sequence `tokens` (padded behind its end to a
    length of LENGTHS: what follows a causal sequence changes nothing
    before it).  `states`: a list that takes each linear layer's state
    [Hv, dk, dv] behind the last token."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = next(length for length in LENGTHS if length >= n)
    tokens = jnp.asarray(np.pad(tokens, (0, padded - n)))
    n_real = {"prompt": jnp.asarray(prompt_len or n, jnp.int32),
              "row": jnp.asarray(n, jnp.int32)}
    x = _matrix(params["embed"]["embedding"][tokens], reading)
    margin = jnp.full((padded,), jnp.inf, F32)
    static = _static(sizes)
    for i, kind in enumerate(layer_types(sizes)):
        x, m, state = _layer(params[f"layer_{i}"], x, n_real, kind=kind,
                             sizes=static, reading=reading)
        margin = jnp.minimum(margin, m)
        if states is not None and state is not None:
            states.append(state)
    return x, margin


def logits(params: Dict[str, Any], tokens, sizes: Dict[str, Any],
           reading: Optional[str] = None):
    """Float32 logits [S, V] of one short sequence (tests: the whole
    vocabulary at once)."""
    x = hidden(params, tokens, sizes, reading=reading)[0][:len(tokens)]
    with jax.default_matmul_precision("highest"):
        h = _norm(x, params["final_norm"]["w"],
                  float(sizes["rms_norm_eps"]), reading)
        return h @ _matrix(params["lm_head"]["kernel"], reading)


def teacher_forced(params: Dict[str, Any], prompts, answers,
                   sizes: Dict[str, Any], picks=None,
                   reading: Optional[str] = None) -> List[Dict[str, Any]]:
    """One prompt at a time, teacher-forced with the engine's answer:
    for each of the answer's K tokens {"top", "top_id", "picked"} — the
    reference's largest logit at the position that predicts it, that
    logit's id, and the reference's logit of the engine's token (of
    `picks[b][j]` with `picks`, in the context the ANSWER makes) — and
    "margin", the router margin of that position."""
    out = []
    for b, (prompt, answer) in enumerate(zip(prompts, answers)):
        row = list(prompt) + list(answer[:-1])
        at = len(prompt) - 1 + np.arange(len(answer))
        x, margin = hidden(params, row, sizes, prompt_len=len(prompt),
                           reading=reading)
        chosen = answer if picks is None else picks[b]
        top, top_id, picked = _head(
            params["lm_head"]["kernel"], params["final_norm"]["w"], x[at],
            jnp.asarray(chosen, jnp.int32),
            eps=float(sizes["rms_norm_eps"]), reading=reading)
        out.append({"top": [float(v) for v in top],
                    "top_id": [int(v) for v in top_id],
                    "picked": [float(v) for v in picked],
                    "margin": [float(v) for v in margin[at]]})
    return out


def carried_states(params: Dict[str, Any], prompt, answer,
                   sizes: Dict[str, Any], reading: Optional[str] = None
                   ) -> List[Any]:
    """Each linear layer's state [Hv, dk, dv], float32, behind a prompt
    and all of its answer but the last token (which nothing has read):
    what a sequence that ended with that answer left in its slot."""
    states: List[Any] = []
    hidden(params, list(prompt) + list(answer[:-1]), sizes,
           prompt_len=len(prompt), reading=reading, states=states)
    return states
