"""Plain reference of the published block: pre-norm RMSNorm, rotary
embeddings (half-split, as the Hugging Face Mistral/Llama code rotates),
grouped-query causal attention, SwiGLU, no biases, untied head.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")` (on a TPU a float32 matmul
otherwise runs in bfloat16 passes), no kernels, no cache, no batching
tricks.  It shares no code with `ray_tpu/models/llama.py`; it only reads
that model's parameter tree, whose layout is (flax names):

    embed/embedding [V, D]; layer_i/{attn_norm,mlp_norm}/scale [D];
    layer_i/attn/{wq [D, H, hd], wk, wv [D, Hkv, hd], wo [H, hd, D]}/kernel;
    layer_i/mlp/{w1 (gate), w3 (up) [D, F], w2 (down) [F, D]}/kernel;
    final_norm/scale [D]; lm_head/kernel [D, V]

Departures from the published model: none in the mathematics.  One block
is jitted with its weights as arguments and called once a layer, so a
12-layer reference compiles one block, and the weights are the engine's
own arrays, never copies.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rotary(x, positions, theta):
    """x: [B, S, H, hd]; positions: [B, S]."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    angles = positions[..., None].astype(F32) * inv_freq   # [B, S, hd/2]
    emb = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


@partial(jax.jit, static_argnames=("theta", "eps"))
def block(layer: Dict[str, Any], x, positions, *, theta: float, eps: float):
    """One decoder block on x [B, S, D] float32."""
    attn = layer["attn"]
    wq, wk, wv, wo = (attn[n]["kernel"].astype(F32)
                      for n in ("wq", "wk", "wv", "wo"))
    h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
    q = _rotary(jnp.einsum("bsd,dhk->bshk", h, wq), positions, theta)
    k = _rotary(jnp.einsum("bsd,dhk->bshk", h, wk), positions, theta)
    v = jnp.einsum("bsd,dhk->bshk", h, wv)
    n_heads, n_kv = q.shape[2], k.shape[2]
    k = jnp.repeat(k, n_heads // n_kv, axis=2)   # each kv head serves a
    v = jnp.repeat(v, n_heads // n_kv, axis=2)   # group of query heads
    scores = jnp.einsum("bshk,bthk->bhst", q, k) / jnp.sqrt(F32(q.shape[-1]))
    s = x.shape[1]
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthk->bshk", probs, v)
    x = x + jnp.einsum("bshk,hkd->bsd", out, wo)
    mlp = layer["mlp"]
    h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
    gate = h @ mlp["w1"]["kernel"].astype(F32)
    up = h @ mlp["w3"]["kernel"].astype(F32)
    return x + (jax.nn.silu(gate) * up) @ mlp["w2"]["kernel"].astype(F32)


@jax.jit
def _embed(table, tokens):
    return table.astype(F32)[tokens]


@partial(jax.jit, static_argnames=("eps",))
def _head(scale, kernel, x, *, eps: float):
    return _rms_norm(x, scale, eps) @ kernel.astype(F32)


def logits(params: Dict[str, Any], tokens, *, n_layers: int, theta: float,
           eps: float, at=None):
    """float32 logits [B, S, V] of `tokens` [B, S] under `params`; with
    `at` [B, K], the logits [B, K, V] of those positions of each row."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["embedding"], tokens)
        for i in range(n_layers):
            x = block(params[f"layer_{i}"], x, positions,
                      theta=float(theta), eps=float(eps))
        if at is not None:
            x = jnp.take_along_axis(
                x, jnp.asarray(at, jnp.int32)[..., None], axis=1)
        return _head(params["final_norm"]["scale"],
                     params["lm_head"]["kernel"], x, eps=float(eps))


def next_token_loss(params: Dict[str, Any], tokens, **sizes) -> float:
    """Mean next-token cross entropy of `tokens` [B, S], one sequence at
    a time (what the reference can hold beside a training state)."""
    total = 0.0
    for row in jnp.asarray(tokens, jnp.int32):
        lg = logits(params, row[None], **sizes)[0, :-1]
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
        total += float(jnp.mean(lse - picked))
    return total / len(tokens)


def teacher_forced(params: Dict[str, Any], prompts, answers, **sizes):
    """What the reference says of every token of each `answer`, given
    its prompt and the answer's OWN earlier tokens (so one differing pick
    does not condemn the tokens after it).  All answers have one length
    K.  For each prompt: {"top": the K largest logits, "top_id": their
    ids, "picked": the logits of the answer's tokens}.

    One batch: row b is prompt + answer[:-1], padded on the right to the
    longest row — under a causal mask a position sees nothing to its
    right — and position len(prompt) - 1 + j predicts answer token j."""
    rows = [list(p) + list(a[:-1]) for p, a in zip(prompts, answers)]
    width = max(len(r) for r in rows)
    tokens = jnp.asarray([r + [0] * (width - len(r)) for r in rows],
                         jnp.int32)
    k = len(answers[0])
    at = jnp.asarray([[len(p) - 1 + j for j in range(k)] for p in prompts])
    lg = logits(params, tokens, at=at, **sizes)              # [B, K, V]
    picked = jnp.take_along_axis(
        lg, jnp.asarray(answers, jnp.int32)[..., None], axis=-1)[..., 0]
    top, top_id = jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1)
    return [{"top": [float(x) for x in top[b]],
             "top_id": [int(x) for x in top_id[b]],
             "picked": [float(x) for x in picked[b]]}
            for b in range(len(rows))]
