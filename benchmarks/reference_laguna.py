"""Plain reference of the Laguna block (`model_type: laguna`), for the
share of it one chip holds.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`, no kernels, no cache, no batching
tricks.  It shares no code with `ray_tpu/`: it reads the engine's
parameter tree (flax names of `ray_tpu/models/laguna.py`)

    embed/embedding [V, D]; final_norm/scale; lm_head/kernel [D, V]
    layer_i/{attn_norm,mlp_norm}/scale [D]
    layer_i/attn/{wq [D, H_i, hd], wk, wv [D, Hkv, hd], wo [H_i, hd, D],
                  attn_gate [D, H_i]}/kernel
    layer_i/mlp/{w1, w3 [D, F], w2 [F, D]}/kernel          (a dense layer)
    layer_i/moe/moe_router [D, E]                          (a sparse layer)
    layer_i/moe/{moe_experts_w1, _w3 [E_held, D, Fe], _w2 [E_held, Fe, D]}
    layer_i/moe/moe_shared/{w1, w3, w2}/kernel

and the model's published sizes (`sizes`, the configuration file's keys:
`layer_types`, `mlp_layer_types`, `sliding_window`, `rope_parameters`,
`num_experts_per_tok`, `norm_topk_prob`, `moe_routed_scaling_factor`,
`rms_norm_eps`, `head_dim`, `experts_held`).

For layer l, h = RMSNorm(x): q, k, v projections; rotary by the layer's
kind (sliding: the whole head at its theta; full: the first
`partial_rotary_factor` of the head with YaRN's frequencies as
`transformers`' `_compute_yarn_parameters` computes them, cos and sin
times `attention_factor`); causal scores / sqrt(hd), a sliding layer
sees i - W < j <= i; softmax; the head-wise gate; W_o.  Then h' =
RMSNorm(x) and a dense SwiGLU, or the shared expert plus
`moe_routed_scaling_factor` times the routed sum.

The expert layer is a LOOP over the experts held: each is applied to
every token and multiplied by that token's routing weight for it, or
zero — nothing is grouped, gathered or skipped — with one expert's
float32 copy alive at a time (`lax.fori_loop`).  The router scores ALL
`num_experts`; what the experts of other shares would add is left out,
as the program leaves it out.

What the published config does not say is one function each, as in the
model file (the configuration lists them under `assumed`):
`gate_activation`, `router_scores`, `combine_shared`, `qk_normalize`.

**The second reading.**  `matrices=<dtype name>` rounds every stored
matrix (the embedding, every projection, every expert, the head) to that
dtype before it is used, and changes nothing else: `float8_e4m3fn` is
the nearest precision below the bfloat16 the configuration states, and
what this reference then picks has to come out as NOT correct by the
comparison of `kinds/serve_laguna.py` (PERF.md has both readings).

Besides what `reference.teacher_forced` returns, each position gets its
router MARGIN: the smallest, over the sparse layers, of log p(10th) -
log p(11th) of the router's probabilities (k-th and (k+1)-th in
general) — which, the scores being a softmax, is the gap between those
two router logits.  A position whose margin is small may route one
expert differently in a correct bfloat16 program; the benchmark's kind
says what it does about those (`kinds/serve_laguna.py`).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ------------------------------------------------- the assumed conventions


def gate_activation(z):
    return jax.nn.sigmoid(z)


def router_scores(logits):
    return jax.nn.softmax(logits, axis=-1)


def combine_shared(shared, routed, factor):
    return shared + factor * routed


def qk_normalize(q, k):
    return q, k


# ------------------------------------------------------------------ pieces


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _f32(w, matrices=None):
    """A stored matrix as float32, through `matrices` if given."""
    return (w if matrices is None else w.astype(matrices)).astype(F32)


def _swiglu(h, w1, w3, w2, matrices=None):
    return (jax.nn.silu(h @ _f32(w1, matrices)) * (h @ _f32(w3, matrices))) \
        @ _f32(w2, matrices)


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """`_compute_yarn_parameters` of `transformers`, over `dim` rotated
    dimensions: for pair i, frequency f_i = theta^(-2i/dim);
    interpolated f_i / factor, extrapolated f_i, blended by a ramp that
    is 0 up to the correction dimension of beta_fast and 1 from that of
    beta_slow (dimension of `r` rotations over the original length:
    dim ln(original / (2 pi r)) / (2 ln theta), floored and ceiled)."""
    def corr(r):
        return dim * math.log(original / (r * 2 * math.pi)) \
            / (2 * math.log(theta))

    low, high = max(math.floor(corr(beta_fast)), 0), \
        min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append((f / factor) * ramp + f * (1.0 - ramp))
    return np.asarray(out, np.float32)


def _rotary(x, positions, rope: Dict[str, Any]):
    """x [B, S, H, hd]; the first `partial_rotary_factor` x hd
    dimensions are rotated (half-split inside them), the rest pass."""
    hd = x.shape[-1]
    dim = int(hd * float(rope.get("partial_rotary_factor", 1)))
    theta = float(rope["rope_theta"])
    if rope.get("rope_type", "default") == "yarn":
        inv = yarn_inv_freq(dim, theta, float(rope["factor"]),
                            int(rope["original_max_position_embeddings"]),
                            float(rope["beta_fast"]),
                            float(rope["beta_slow"]))
        factor = float(rope["attention_factor"])
    else:
        inv = np.asarray([theta ** (-2.0 * i / dim)
                          for i in range(dim // 2)], np.float32)
        factor = 1.0
    angles = positions[..., None].astype(F32) * inv            # [B,S,dim/2]
    emb = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    rot, rest = x[..., :dim], x[..., dim:]
    half = dim // 2
    rotated = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    out = rot * (jnp.cos(emb) * factor) + rotated * (jnp.sin(emb) * factor)
    return jnp.concatenate([out, rest], axis=-1)


def _routed(h, moe, *, top_k, normalize, lo, matrices=None):
    """h [T, D] -> (the held experts' part of the routed sum [T, D], the
    margin [T]).  The loop the module's text describes."""
    logits = h @ _f32(moe["moe_router"], matrices)                # [T, E]
    probs = router_scores(logits)
    top, ids = jax.lax.top_k(probs, top_k + 1)
    margin = jnp.log(top[:, top_k - 1]) - jnp.log(top[:, top_k])
    top, ids = top[:, :top_k], ids[:, :top_k]
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    w1, w3, w2 = (moe[f"moe_experts_{n}"] for n in ("w1", "w3", "w2"))

    def one(e, acc):
        weight = jnp.sum(jnp.where(ids == lo + e, top, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(h, w1[e], w3[e], w2[e],
                                               matrices)

    return jax.lax.fori_loop(0, w1.shape[0], one, jnp.zeros_like(h)), margin


@partial(jax.jit, static_argnames=("window", "rope", "sparse", "top_k",
                                   "normalize", "factor", "lo", "eps",
                                   "matrices"))
def block(layer: Dict[str, Any], x, positions, *, window: int, rope,
          sparse: bool, top_k: int, normalize: bool, factor: float,
          lo: int, eps: float, matrices=None):
    """One decoder layer on x [B, S, D] float32 -> (x, margin [B, S])."""
    attn = layer["attn"]
    wq, wk, wv, wo, wg = (_f32(attn[n]["kernel"], matrices)
                          for n in ("wq", "wk", "wv", "wo", "attn_gate"))
    h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, wq)
    k = jnp.einsum("bsd,dhk->bshk", h, wk)
    v = jnp.einsum("bsd,dhk->bshk", h, wv)
    q, k = qk_normalize(q, k)
    q = _rotary(q, positions, dict(rope))
    k = _rotary(k, positions, dict(rope))
    n_heads, n_kv = q.shape[2], k.shape[2]
    k = jnp.repeat(k, n_heads // n_kv, axis=2)
    v = jnp.repeat(v, n_heads // n_kv, axis=2)
    scores = jnp.einsum("bshk,bthk->bhst", q, k) / jnp.sqrt(F32(q.shape[-1]))
    s = x.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window:
        seen = seen & (j > i - window)
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhst,bthk->bshk", jax.nn.softmax(scores, axis=-1), v)
    gate = gate_activation(h @ wg)                               # [B,S,H]
    x = x + jnp.einsum("bshk,hkd->bsd", out * gate[..., None], wo)
    h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
    if not sparse:
        mlp = layer["mlp"]
        y = _swiglu(h, *(mlp[n]["kernel"] for n in ("w1", "w3", "w2")),
                    matrices)
        return x + y, jnp.full(x.shape[:2], jnp.inf, F32)
    moe = layer["moe"]
    b, d = x.shape[0], x.shape[-1]
    routed, margin = _routed(h.reshape(b * s, d), moe, top_k=top_k,
                             normalize=normalize, lo=lo, matrices=matrices)
    shared = _swiglu(h, *(moe["moe_shared"][n]["kernel"]
                          for n in ("w1", "w3", "w2")), matrices)
    y = combine_shared(shared, routed.reshape(b, s, d), factor)
    return x + y, margin.reshape(b, s)


@partial(jax.jit, static_argnames=("matrices",))
def _embed(table, tokens, *, matrices=None):
    return _f32(table[tokens], matrices)


@partial(jax.jit, static_argnames=("eps", "matrices"))
def _head(scale, kernel, x, *, eps: float, matrices=None):
    return _rms_norm(x, scale, eps) @ _f32(kernel, matrices)


def _hashable(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _hashable(v)) for k, v in value.items()))
    return value


def logits(params: Dict[str, Any], tokens, sizes: Dict[str, Any], at=None,
           matrices=None):
    """(float32 logits [B, S, V], margins [B, S]) of `tokens` [B, S];
    with `at` [B, K], both at those positions of each row."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["embedding"], tokens, matrices=matrices)
        for i, kind in enumerate(sizes["layer_types"]):
            sliding = kind == "sliding_attention"
            x, m = block(
                params[f"layer_{i}"], x, positions,
                window=int(sizes["sliding_window"]) if sliding else 0,
                rope=_hashable(sizes["rope_parameters"][kind]),
                sparse=sizes["mlp_layer_types"][i] == "sparse",
                top_k=int(sizes["num_experts_per_tok"]),
                normalize=bool(sizes["norm_topk_prob"]),
                factor=float(sizes["moe_routed_scaling_factor"]),
                lo=int(sizes["experts_held"][0]),
                eps=float(sizes["rms_norm_eps"]), matrices=matrices)
            margin = jnp.minimum(margin, m)
        if at is not None:
            at = jnp.asarray(at, jnp.int32)
            x = jnp.take_along_axis(x, at[..., None], axis=1)
            margin = jnp.take_along_axis(margin, at, axis=1)
        return _head(params["final_norm"]["scale"],
                     params["lm_head"]["kernel"], x,
                     eps=float(sizes["rms_norm_eps"]),
                     matrices=matrices), margin


def teacher_forced(params: Dict[str, Any], prompts, answers,
                   sizes: Dict[str, Any], picks=None, matrices=None
                   ) -> List[Dict[str, Any]]:
    """`reference.teacher_forced` for this block, one prompt at a time
    (a sliding layer's mask has no padding to reason about, and one row
    of scores is what fits beside an engine): for each prompt {"top",
    "top_id", "picked"} of the answer's K tokens, and "margin", the
    router margin of the position that predicts each.  With `picks`,
    "picked" is the logit of `picks[b][j]` in the context the ANSWER
    makes (what the reference says of another program's choice there);
    `matrices`: the module's text, "The second reading"."""
    out = []
    for b, (prompt, answer) in enumerate(zip(prompts, answers)):
        row = list(prompt) + list(answer[:-1])
        at = [[len(prompt) - 1 + j for j in range(len(answer))]]
        lg, margin = logits(params, [row], sizes, at=at, matrices=matrices)
        lg, margin = lg[0], margin[0]
        chosen = answer if picks is None else picks[b]
        picked = jnp.take_along_axis(
            lg, jnp.asarray(chosen, jnp.int32)[:, None], axis=-1)[:, 0]
        out.append({"top": [float(x) for x in jnp.max(lg, axis=-1)],
                    "top_id": [int(x) for x in jnp.argmax(lg, axis=-1)],
                    "picked": [float(x) for x in picked],
                    "margin": [float(x) for x in margin]})
    return out
