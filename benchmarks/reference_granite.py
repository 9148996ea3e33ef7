"""Plain reference of the hybrid state-space decoder (`model_type:
granitemoehybrid`): Mamba-2 mixers and a few attention layers without
positions, the whole model.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`, with no cache, no kernel and no
chunked form: the recurrence runs TOKEN BY TOKEN (`lax.scan` over the
sequence, the state its carry), the convolution is four shifted sums,
the attention a causal softmax a block of queries at a time.  It shares
no code with `ray_tpu/`: it reads the engine's parameter tree (flax
names of `ray_tpu/models/granite.py`)

    embed/embedding [V, D] (the head too); final_norm/scale
    layer_i/{norm, mlp_norm}/scale
    layer_i/mixer/{in_proj [D, d_inner + conv + Hm], out_proj [d_inner,
                   D]}/kernel, conv_w [K, conv], conv_b [conv], a_log,
                   dt_bias, d [Hm], norm_w [d_inner]   (a mamba layer)
    layer_i/attn/{wq [D, H, d], wk, wv [D, Hkv, d], wo [H, d, D]}/kernel
    layer_i/mlp/{w_in [D, 2 F], w_out [F, D]}/kernel

and the model's published sizes (`sizes`, the configuration file's
keys).  For layer l:

    x = embed(tokens) * embedding_multiplier
    x += residual_multiplier * mixer(RMSNorm(x))
    x += residual_multiplier * W_out (silu(gate) * up), (gate, up) =
         W_in RMSNorm(x)
    attention: softmax(q k^T * attention_multiplier) v, causal, no rotary
    mamba: (z, xBC, dt) = W_in h; xBC = silu(conv(xBC) + b);
           dt = softplus(dt + dt_bias); A = -exp(a_log)
           H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t;
           y_t = H_t C_t + D x_t
           out = W_out (RMSNorm(y * silu(z)) * norm_w)
    logits = RMSNorm(x) @ E^T / logits_scaling

Computed in blocks so that it fits beside an engine that fills the chip:
a layer at a time (one layer's float32 matrices alive), the attention's
queries in blocks of 256, the MLP's width in slices, the head's
vocabulary in slices whose largest logit, its id and the picked token's
logit are kept and nothing else.

**Other readings** (`reading=`), each the same computation with ONE
thing changed, for what the comparison of `kinds/serve_granite.py` says
of a program with that fault (its text has the method; the reading is
compared with the reference proper, as a faulty program would be):

    "float8_e4m3fn"  every stored matrix rounded to that dtype (the
                     nearest precision below the stated bfloat16)
    "bfloat16_state" the recurrence's carry rounded to bfloat16 a token
    "scale_1_8"      attention scores by 1/8 (1/sqrt(64)), not 1/64
    "decaying_pad"   a prefill chunk's padded positions are not masked:
                     behind a prompt that does not end on a chunk of 64
                     the state decays over the padding (dt = softplus(
                     dt_bias), no input)
    "stale_slot"     the state does not start at zero but at what
                     ANOTHER sequence of the prompt's length left (the
                     prompt's own inputs, newest first, run from zero)
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256        # queries a block of the attention
LENGTHS = (256, 512, 1024, 2048, 3072, 4096)   # a sequence is padded to
MLP_SLICES = 4       # slices of the MLP's width
VOCAB_SLICE = 12544  # columns a slice of the head (100352 / 8)
CHUNK = 64           # the engine's prefill chunk ("decaying_pad")
READINGS = ("float8_e4m3fn", "bfloat16_state", "scale_1_8", "decaying_pad",
            "stale_slot")


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
    step = f // MLP_SLICES
    out = jnp.zeros(h.shape[:-1] + (w_out.shape[-1],), F32)
    for i in range(MLP_SLICES):
        cols = slice(i * step, (i + 1) * step)
        gate = h @ _matrix(w_in[:, :f][:, cols], reading)
        up = h @ _matrix(w_in[:, f:][:, cols], reading)
        out = out + (jax.nn.silu(gate) * up) @ _matrix(w_out[cols], reading)
    return out


def _attention(h, p, scale: float, reading):
    """Causal softmax attention of one sequence h [S, D], S a multiple
    of Q_BLOCK; every query head reads its group's key head."""
    wq, wk = _matrix(p["wq"]["kernel"], reading), \
        _matrix(p["wk"]["kernel"], reading)
    wv, wo = _matrix(p["wv"]["kernel"], reading), \
        _matrix(p["wo"]["kernel"], reading)
    s = h.shape[0]
    heads, hkv = wq.shape[1], wk.shape[1]
    q = jnp.einsum("sd,dhk->shk", h, wq)
    k = jnp.repeat(jnp.einsum("sd,dhk->shk", h, wk), heads // hkv, axis=1)
    v = jnp.repeat(jnp.einsum("sd,dhk->shk", h, wv), heads // hkv, axis=1)

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


def _mamba(h, p, *, heads: int, d_head: int, d_state: int, eps: float,
           n_real, reading):
    """One Mamba-2 mixer over h [S, D], the recurrence token by token.
    `n_real`: the tokens before the padding (traced), for the readings
    that depend on where a sequence starts and ends."""
    s = h.shape[0]
    di = heads * d_head
    conv_dim = di + 2 * d_state
    zxbcdt = h @ _matrix(p["in_proj"]["kernel"], reading)
    z, u, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + conv_dim],
                zxbcdt[:, di + conv_dim:])
    w = p["conv_w"].astype(F32)                          # [K, conv]
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim), F32), u])
    conv = sum(padded[k:k + s] * w[k] for k in range(taps))
    if "conv_b" in p:
        conv = conv + p["conv_b"].astype(F32)
    u = jax.nn.silu(conv)
    x = u[:, :di].reshape(s, heads, d_head)
    b, c = u[:, di:di + d_state], u[:, di + d_state:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))     # [S, Hm]
    a = -jnp.exp(p["a_log"].astype(F32))                    # [Hm]
    d_skip = p["d"].astype(F32)

    def step(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        if reading == "bfloat16_state":
            # not `astype` there and back: XLA may keep a convert pair's
            # excess precision, and the reading then changes nothing
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        y_t = jnp.einsum("hpn,n->hp", state, c_t) + d_skip[:, None] * x_t
        return state, y_t

    state = jnp.zeros((heads, d_head, d_state), F32)
    if reading == "stale_slot":
        # what another sequence of the prompt's length left in the slot:
        # this one's own prompt inputs, newest first, run from zero
        own = (jnp.arange(s) < n_real["prompt"])[::-1]
        state, _ = jax.lax.scan(
            step, state, (x[::-1], b[::-1], c[::-1],
                          dt[::-1] * own[:, None]))
    if reading == "decaying_pad":
        # the padding behind the prompt's last chunk decays the state
        # once, where the prompt ends: split the scan there
        pad = (-n_real["prompt"]) % CHUNK
        decay = jnp.exp(pad * jax.nn.softplus(p["dt_bias"].astype(F32)) * a)
        at_end = jnp.arange(s) == n_real["prompt"] - 1

        def step_pad(state, inputs):
            *inputs, last = inputs
            state, y_t = step(state, inputs)
            return jnp.where(last, decay[:, None, None] * state, state), y_t

        _, y = jax.lax.scan(step_pad, state, (x, b, c, dt, at_end))
    else:
        _, y = jax.lax.scan(step, state, (x, b, c, dt))
    y = y.reshape(s, di) * jax.nn.silu(z)
    y = _rms_norm(y, p["norm_w"], eps)
    return y @ _matrix(p["out_proj"]["kernel"], reading)


@partial(jax.jit, static_argnames=("kind", "sizes", "reading"))
def _layer(p, x, n_real, *, kind: str, sizes, reading):
    """One layer on x [S, D]; `sizes`: a tuple of (key, value) pairs."""
    m = dict(sizes)
    eps, res = m["rms_norm_eps"], m["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, p["norm"]["scale"], eps)
        if kind == "mamba":
            y = _mamba(h, p["mixer"], heads=m["mamba_n_heads"],
                       d_head=m["mamba_d_head"], d_state=m["mamba_d_state"],
                       eps=eps, n_real=n_real, reading=reading)
        else:
            scale = 0.125 if reading == "scale_1_8" \
                else m["attention_multiplier"]
            y = _attention(h, p["attn"], scale, reading)
        x = x + res * y
        h = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        return x + res * _mlp(h, p["mlp"]["w_in"]["kernel"],
                              p["mlp"]["w_out"]["kernel"], reading)


@partial(jax.jit, static_argnames=("eps", "scaling", "reading"))
def _head(embedding, norm_scale, x, picks, *, eps, scaling, reading):
    """(largest logit, its id, the logit of `picks`) of x [K, D], the
    vocabulary a slice at a time."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, norm_scale, eps)
        vocab = embedding.shape[0]
        top = jnp.full((x.shape[0],), -jnp.inf, F32)
        top_id = jnp.zeros((x.shape[0],), jnp.int32)
        picked = jnp.zeros((x.shape[0],), F32)
        for lo in range(0, vocab, VOCAB_SLICE):
            rows = _matrix(embedding[lo:lo + VOCAB_SLICE], reading)
            lg = h @ rows.T / scaling
            best = jnp.argmax(lg, axis=-1)
            best_val = jnp.max(lg, axis=-1)
            top_id = jnp.where(best_val > top, lo + best, top_id
                               ).astype(jnp.int32)
            top = jnp.maximum(top, best_val)
            here = (picks >= lo) & (picks < lo + rows.shape[0])
            mine = jnp.take_along_axis(
                lg, jnp.clip(picks - lo, 0, rows.shape[0] - 1)[:, None],
                axis=-1)[:, 0]
            picked = jnp.where(here, mine, picked)
        return top, top_id, picked


def _static(sizes: Dict[str, Any]):
    keys = ("rms_norm_eps", "residual_multiplier", "attention_multiplier",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state")
    defaults = {"residual_multiplier": 1.0}
    return tuple((k, sizes.get(k, defaults.get(k))) for k in keys)


def hidden(params: Dict[str, Any], tokens, sizes: Dict[str, Any],
           prompt_len: Optional[int] = None, reading: Optional[str] = None):
    """The last layer's output [S_padded, D] of one sequence `tokens`
    (padded behind its end to a length of LENGTHS: what follows a
    causal sequence changes nothing before it)."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = next(length for length in LENGTHS if length >= n)
    tokens = jnp.asarray(np.pad(tokens, (0, padded - n)))
    n_real = {"prompt": jnp.asarray(prompt_len or n, jnp.int32)}
    x = _matrix(params["embed"]["embedding"][tokens], reading) \
        * float(sizes.get("embedding_multiplier", 1.0))
    static = _static(sizes)
    for i, kind in enumerate(sizes["layer_types"]):
        x = _layer(params[f"layer_{i}"], x, n_real, kind=kind, sizes=static,
                   reading=reading)
    return x


def logits(params: Dict[str, Any], tokens, sizes: Dict[str, Any],
           reading: Optional[str] = None):
    """Float32 logits [S, V] of one short sequence (tests: the whole
    vocabulary at once)."""
    x = hidden(params, tokens, sizes, reading=reading)[:len(tokens)]
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, params["final_norm"]["scale"],
                      float(sizes["rms_norm_eps"]))
        return h @ _matrix(params["embed"]["embedding"], reading).T \
            / float(sizes.get("logits_scaling", 1.0))


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
            params["embed"]["embedding"], params["final_norm"]["scale"], x,
            jnp.asarray(chosen, jnp.int32),
            eps=float(sizes["rms_norm_eps"]),
            scaling=float(sizes.get("logits_scaling", 1.0)),
            reading=reading)
        out.append({"top": [float(v) for v in top],
                    "top_id": [int(v) for v in top_id],
                    "picked": [float(v) for v in picked],
                    "margin": [1.0] * len(answer)})
    return out
