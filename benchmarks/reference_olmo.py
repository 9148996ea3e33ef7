"""Plain reference of the hybrid linear-attention decoder (`model_type:
olmo_hybrid`): gated-delta-rule mixers and full-attention layers
without positions, as many layers as the parameter tree holds.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`, with no cache, no kernel and no
chunk form: the recurrence runs TOKEN BY TOKEN exactly as it is written
below (`lax.scan` over the sequence, the state its carry), the
convolution is four shifted sums, the attention a causal softmax a
block of queries at a time.  It shares no code with `ray_tpu/`: it
reads the engine's parameter tree (flax names of
`ray_tpu/models/olmo_hybrid.py`)

    embed/embedding [V, D]; lm_head/kernel [D, V]; final_norm/scale
    layer_i/{norm, mlp_norm}/scale
    layer_i/mixer/{qkv_proj [D, 2 Hk dk + Hv dv], gate_proj [D, Hv dv],
                   ab_proj [D, 2 H], out_proj [Hv dv, D]}/kernel,
                   conv_w [K, channels], a_log, dt_bias [H], norm_w [dv]
    layer_i/attn/{wq [D, H d], wk [D, Hkv d], wv [D, Hkv, d],
                  wo [H, d, D]}/kernel, {q_norm, k_norm}/scale
    layer_i/mlp/{w_in [D, 2 F], w_out [F, D]}/kernel

and the model's published sizes (`sizes`, the configuration file's
keys).  For layer l (the norm on each sublayer's OUTPUT):

    h = x + Norm(mixer(x));  x = h + Norm(W_out (silu(gate) * up)),
        (gate, up) = W_in h
    full attention: q, k = Norm(W_q x), Norm(W_k x) over the whole
        width, v = W_v x; softmax(q k^T / sqrt(d)) v, causal, no rotary
    linear attention, head h:
        q~, k~, v~ = silu(conv(W_qkv x)), causal depthwise, no bias
        q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(dk); k = k~ / sqrt(|k~|^2 + 1e-6)
        beta = 2 sigmoid(b), g = -exp(A_log) softplus(a + dt_bias)
        S_t = e^g S_{t-1} + beta k_t (v_t - e^g S_{t-1}^T k_t)^T
        o_t = S_t^T q_t
        out = W_o concat_h(RMSNorm(o_t; norm_w) * silu(W_g x))
    logits = lm_head(Norm(x))

Computed in blocks so that it fits beside an engine that fills the chip:
a layer at a time (one layer's float32 matrices alive), the attention's
queries in blocks of 128, the MLP's width in slices, the head's
vocabulary in slices whose largest logit, its id and the picked token's
logit are kept and nothing else.

`carried_states` gives each linear layer's state behind a sequence's last
token (the padding behind it leaves the state as it was), for the
comparison of the CARRY that `replica_olmo.bench_carry` makes.

**Other readings** (`reading=`), each the same computation with ONE
thing changed, for what the comparison of `kinds/serve_olmo.py` says of
a program with that fault (the reading is compared with the reference
proper, as a faulty program would be):

    "float8_e4m3fn"    every stored matrix rounded to that dtype (the
                       nearest precision below the stated bfloat16)
    "beta_not_doubled" beta = sigmoid(b): no negative eigenvalue
    "qk_not_normalised" q~ and k~ enter the recurrence at their own
                       lengths (q still by 1 / sqrt(dk))
    "alpha_one"        g = 0: the state never decays
    "bfloat16_state"   the recurrence's carry rounded to bfloat16 a token
    "updating_pad"     a prefill chunk's padded positions are not
                       masked: behind a prompt that does not end on a
                       chunk of 64 the state decays over the padding as
                       a layer fed zeros decays it (g = -exp(A_log)
                       softplus(dt_bias); a zero key adds nothing)
    "stale_slot"       the state does not start at zero but at what
                       ANOTHER sequence of the prompt's length left (the
                       prompt's own inputs, newest first, run from zero)
    "conv_edge_dropped" the convolution's last inputs are not carried
                       over a prefill chunk's edge: the first three
                       positions of every chunk of 64 of the prompt read
                       zeros before the chunk
    "no_qk_norm"       the full layers' q and k are not normed
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 128        # queries a block of the attention
LENGTHS = (256, 1024, 4096, 8192, 16384)   # a sequence is padded to
MLP_SLICES = 4       # slices of the MLP's width
VOCAB_SLICE = 12544  # columns a slice of the head (100352 / 8)
CHUNK = 64           # the engine's prefill chunk (two of the readings)
L2_EPS = 1e-6
READINGS = ("float8_e4m3fn", "beta_not_doubled", "qk_not_normalised",
            "alpha_one", "bfloat16_state", "updating_pad", "stale_slot",
            "conv_edge_dropped", "no_qk_norm")


def _matrix(w, reading: Optional[str]):
    """A stored matrix as float32, through float8 under that reading."""
    if reading == "float8_e4m3fn":
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(F32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _mlp(h, w_in, w_out, reading):
    f = w_out.shape[0]
    step = -(-f // MLP_SLICES)
    out = jnp.zeros(h.shape[:-1] + (w_out.shape[-1],), F32)
    for lo in range(0, f, step):
        cols = slice(lo, min(lo + step, f))
        gate = h @ _matrix(w_in[:, :f][:, cols], reading)
        up = h @ _matrix(w_in[:, f:][:, cols], reading)
        out = out + (jax.nn.silu(gate) * up) @ _matrix(w_out[cols], reading)
    return out


def _attention(x, p, *, heads: int, eps: float, reading):
    """Causal softmax attention of one sequence x [S, D], S a multiple
    of Q_BLOCK; every query head reads its group's key head."""
    wq, wk = _matrix(p["wq"]["kernel"], reading), \
        _matrix(p["wk"]["kernel"], reading)
    wv, wo = _matrix(p["wv"]["kernel"], reading), \
        _matrix(p["wo"]["kernel"], reading)
    s = x.shape[0]
    q, k = x @ wq, x @ wk
    if reading != "no_qk_norm":
        q = _rms_norm(q, p["q_norm"]["scale"], eps)
        k = _rms_norm(k, p["k_norm"]["scale"], eps)
    hkv = wv.shape[1]
    q = q.reshape(s, heads, -1)
    k = jnp.repeat(k.reshape(s, hkv, -1), heads // hkv, axis=1)
    v = jnp.repeat(jnp.einsum("sd,dhk->shk", x, wv), heads // hkv, axis=1)
    scale = q.shape[-1] ** -0.5

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        scores = jnp.einsum("shk,thk->hst", qi, k) * scale
        seen = (jnp.arange(s)[None, :]
                <= (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None])
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hst,thk->shk", probs, v)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))
    return jnp.einsum("shk,hkd->sd", out.reshape(s, heads, -1), wo)


def _delta(x, p, *, heads: int, dk: int, dv: int, eps: float, doubled: bool,
           n_real, reading):
    """One gated-delta-rule mixer over x [S, D], the recurrence token by
    token.  `n_real`: the tokens before the padding (traced), for the
    readings that depend on where a sequence starts and ends."""
    s = x.shape[0]
    kd = heads * dk
    gate = x @ _matrix(p["gate_proj"]["kernel"], reading)
    ab = x @ _matrix(p["ab_proj"]["kernel"], reading)
    taps = p["conv_w"].shape[0]
    at = jnp.arange(s)

    def part(lo, hi):
        """silu(conv(W x)) of the channels [lo, hi): a part at a time,
        so that no [S, all channels] float32 array is alive."""
        u = x @ _matrix(p["qkv_proj"]["kernel"][:, lo:hi], reading)
        w = p["conv_w"][:, lo:hi].astype(F32)            # [K, channels]
        padded = jnp.concatenate([jnp.zeros((taps - 1, hi - lo), F32), u])
        conv = jnp.zeros_like(u)
        for j in range(taps):
            term = padded[j:j + s] * w[j]
            if reading == "conv_edge_dropped":
                # tap j reads position t - (taps - 1 - j): before the
                # chunk of a prompt position it reads zero
                back = taps - 1 - j
                kept = (at % CHUNK >= back) | (at >= n_real["prompt"])
                term = jnp.where(kept[:, None], term, 0.0)
            conv = conv + term
        return jax.nn.silu(conv)

    q = part(0, kd).reshape(s, heads, dk)
    k = part(kd, 2 * kd).reshape(s, heads, dk)
    v = part(2 * kd, 2 * kd + heads * dv).reshape(s, heads, dv)
    if reading != "qk_not_normalised":
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    q = q / np.sqrt(dk)
    a = jnp.exp(p["a_log"].astype(F32))                  # [H]
    dt_bias = p["dt_bias"].astype(F32)
    g = -a * jax.nn.softplus(ab[:, :heads] + dt_bias)    # [S, H]
    if reading == "alpha_one":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(ab[:, heads:])
    if doubled and reading != "beta_not_doubled":
        beta = 2.0 * beta
    # the padding behind the sequence leaves the state as it was (alpha
    # 1, beta 0: nothing causal reads what it computes), so the scan's
    # last carry is the state behind the sequence's last token
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
        # what another sequence of the prompt's length left in the slot:
        # this one's own prompt inputs, newest first, run from zero
        own = (at < n_real["prompt"])[::-1, None]
        state, _ = jax.lax.scan(
            step, state, (q[::-1], k[::-1], v[::-1], g[::-1] * own,
                          beta[::-1] * own))
    if reading == "updating_pad":
        # the padding behind the prompt's last chunk decays the state
        # once, where the prompt ends
        pad = (-n_real["prompt"]) % CHUNK
        decay = jnp.exp(-pad * a * jax.nn.softplus(dt_bias))
        last = at == n_real["prompt"] - 1

        def step_pad(state, inputs):
            *inputs, end = inputs
            state, o_t = step(state, inputs)
            return jnp.where(end, decay[:, None, None] * state, state), o_t

        state, o = jax.lax.scan(step_pad, state, (q, k, v, g, beta, last))
    else:
        state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    o = _rms_norm(o, p["norm_w"], eps).reshape(s, heads * dv)
    return (o * jax.nn.silu(gate)) @ _matrix(p["out_proj"]["kernel"],
                                             reading), state


@partial(jax.jit, static_argnames=("kind", "sizes", "reading"))
def _layer(p, x, n_real, *, kind: str, sizes, reading):
    """One layer on x [S, D] -> (its output, the recurrence's state
    [H, dk, dv] behind the sequence's last token, or None for a full
    layer); `sizes`: a tuple of (key, value) pairs."""
    m = dict(sizes)
    eps = m["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        state = None
        if kind == "linear_attention":
            y, state = _delta(
                x, p["mixer"], heads=m["linear_num_value_heads"],
                dk=m["linear_key_head_dim"], dv=m["linear_value_head_dim"],
                eps=eps, doubled=bool(m["linear_allow_neg_eigval"]),
                n_real=n_real, reading=reading)
        else:
            y = _attention(x, p["attn"], heads=m["num_attention_heads"],
                           eps=eps, reading=reading)
        h = x + _rms_norm(y, p["norm"]["scale"], eps)
        y = _mlp(h, p["mlp"]["w_in"]["kernel"], p["mlp"]["w_out"]["kernel"],
                 reading)
        return h + _rms_norm(y, p["mlp_norm"]["scale"], eps), state


@partial(jax.jit, static_argnames=("eps", "reading"))
def _head(head, norm_scale, x, picks, *, eps, reading):
    """(largest logit, its id, the logit of `picks`) of x [K, D], the
    vocabulary a slice at a time.  head: [D, V]."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, norm_scale, eps)
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
    keys = ("rms_norm_eps", "num_attention_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_allow_neg_eigval")
    return tuple((k, sizes.get(k, True)) for k in keys)


def hidden(params: Dict[str, Any], tokens, sizes: Dict[str, Any],
           prompt_len: Optional[int] = None, reading: Optional[str] = None,
           states: Optional[List[Any]] = None):
    """The last layer's output [S_padded, D] of one sequence `tokens`
    (padded behind its end to a length of LENGTHS: what follows a
    causal sequence changes nothing before it).  `states`: a list that
    takes each linear layer's state [H, dk, dv] behind the last token."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = next(length for length in LENGTHS if length >= n)
    tokens = jnp.asarray(np.pad(tokens, (0, padded - n)))
    n_real = {"prompt": jnp.asarray(prompt_len or n, jnp.int32),
              "row": jnp.asarray(n, jnp.int32)}
    x = _matrix(params["embed"]["embedding"][tokens], reading)
    static = _static(sizes)
    for i, kind in enumerate(sizes["layer_types"]):
        x, state = _layer(params[f"layer_{i}"], x, n_real, kind=kind,
                          sizes=static, reading=reading)
        if states is not None and state is not None:
            states.append(state)
    return x


def logits(params: Dict[str, Any], tokens, sizes: Dict[str, Any],
           reading: Optional[str] = None):
    """Float32 logits [S, V] of one short sequence (tests: the whole
    vocabulary at once)."""
    x = hidden(params, tokens, sizes, reading=reading)[:len(tokens)]
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, params["final_norm"]["scale"],
                      float(sizes["rms_norm_eps"]))
        return h @ _matrix(params["lm_head"]["kernel"], reading)


def teacher_forced(params: Dict[str, Any], prompts, answers,
                   sizes: Dict[str, Any], picks=None,
                   reading: Optional[str] = None) -> List[Dict[str, Any]]:
    """One prompt at a time, teacher-forced with the engine's answer:
    for each of the answer's K tokens {"top", "top_id", "picked"} — the
    reference's largest logit at the position that predicts it, that
    logit's id, and the reference's logit of the engine's token (of
    `picks[b][j]` with `picks`, in the context the ANSWER makes).
    "margin" is 1 everywhere: this model routes nothing, so no position
    is set aside (`kinds/serve_laguna.check_canaries` reads it)."""
    out = []
    for b, (prompt, answer) in enumerate(zip(prompts, answers)):
        row = list(prompt) + list(answer[:-1])
        at = len(prompt) - 1 + np.arange(len(answer))
        x = hidden(params, row, sizes, prompt_len=len(prompt),
                   reading=reading)[at]
        chosen = answer if picks is None else picks[b]
        top, top_id, picked = _head(
            params["lm_head"]["kernel"], params["final_norm"]["scale"], x,
            jnp.asarray(chosen, jnp.int32),
            eps=float(sizes["rms_norm_eps"]), reading=reading)
        out.append({"top": [float(v) for v in top],
                    "top_id": [int(v) for v in top_id],
                    "picked": [float(v) for v in picked],
                    "margin": [1.0] * len(answer)})
    return out


def carried_states(params: Dict[str, Any], prompt, answer,
                   sizes: Dict[str, Any], reading: Optional[str] = None
                   ) -> List[Any]:
    """Each linear layer's state [H, dk, dv], float32, behind a prompt
    and all of its answer but the last token (which nothing has read):
    what a sequence that ended with that answer left in its slot."""
    states: List[Any] = []
    hidden(params, list(prompt) + list(answer[:-1]), sizes,
           prompt_len=len(prompt), reading=reading, states=states)
    return states


def carry_distance(got: List[Any], want: List[Any]) -> Dict[str, Any]:
    """How far the states `got` lie from `want`, layer by layer: the
    norm of the difference over the norm of `want`, of each layer whole
    (`layers`) and of each head alone (`heads`: a fault that grows with
    a head's memory shows in its slowest head first)."""
    layers, heads = [], []
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        layers.append(float(np.linalg.norm(a - b) / np.linalg.norm(b)))
        heads.append([float(np.linalg.norm(x - y) / np.linalg.norm(y))
                      for x, y in zip(a, b)])
    return {"layers": layers, "heads": heads}
