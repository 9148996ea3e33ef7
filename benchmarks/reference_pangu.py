"""Plain reference of the latent-attention block (`model_type:
pangu_ultra_moe`), for the share of it one chip holds.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: the PLAIN form of the attention —
every token's row is expanded to its 128 keys and values and a causal
softmax is taken over them — with no cache, no kernel and no absorbed
product.  It shares no code with `ray_tpu/`: it reads the engine's
parameter tree (flax names of `ray_tpu/models/pangu.py`)

    embed/embedding [V, D]; final_norm/scale; lm_head/kernel [D, V]
    layer_i/{attn_norm, post_attn_norm, mlp_norm, post_mlp_norm}/scale
    layer_i/attn/{wq_a [D, rq], wq_b [rq, H, dn + dr], wkv_a [D, r + dr],
                  wo [H, dv, D]}/kernel, {q_norm, kv_norm}/scale,
                  wkv_b [r, H, dn + dv]
    layer_i/mlp/{w1, w3 [D, F], w2 [F, D]}/kernel          (a dense layer)
    layer_i/moe/moe_router [D, E]                         (an expert layer)
    layer_i/moe/{moe_experts_w1, _w3 [E_held, D, Fe], _w2 [E_held, Fe, D]}
    layer_i/moe/moe_shared/{w1, w3, w2}/kernel

and the model's published sizes (`sizes`, the configuration file's keys:
`num_hidden_layers`, `first_k_dense_replace`, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `rope_theta`,
`num_experts_per_tok`, `norm_topk_prob`, `routed_scaling_factor`,
`rms_norm_eps`, `experts_held`).  For layer l, h = RMSNorm_in(x):

    cq = RMSNorm_q(h Wqa); (q_nope, q_rope) = cq Wqb
    (c, k_rope) = h Wkva; c = RMSNorm_kv(c); (k_nope, v) = c Wkvb
    q_rope, k_rope rotated (plain rotary at rope_theta, half-split
      pairs; k_rope is one head, every head's)
    score_ij = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) / sqrt(dn + dr)
    a = causal softmax(score) v; x += RMSNorm_post_attn(concat(a) Wo)
    h' = RMSNorm_pre_mlp(x); x += RMSNorm_post_mlp(mlp(h'))
    mlp: SwiGLU (l < first_k_dense_replace), or the shared expert plus
      routed_scaling_factor x the routed sum: s = sigmoid(h' Wr), the
      top-k of s divided by their sum

The attention is taken a block of queries at a time (`lax.map` over
blocks of 256 rows, the sequence padded behind its end to whole blocks)
and the dense MLP a slice of its width at a time: the same sums; a
4,200-token canary's [128, S, S] scores are 9 GB and do not fit beside
an engine.  The expert layer is a LOOP over the experts held, each
applied to every token and multiplied by that token's weight for it, or
zero (one expert's float32 copy alive at a time).  The router scores
ALL `n_routed_experts`; what other shares' experts would add is left
out, as the program leaves it out.

What the published config does not say is one function each, as in the
model file (the configuration lists them under `assumed`):
`router_scores`, `combine_shared`, `softmax_scale`.

**The second reading.**  `matrices=<dtype name>` rounds every stored
matrix to that dtype before it is used and changes nothing else
(`reference_laguna.py` has the method).

Each position also gets its router MARGIN: the smallest, over the
expert layers, gap between the k-th and (k+1)-th largest router LOGIT
(the sigmoid keeps their order).  A position whose margin is small may
route one expert differently in a correct bfloat16 program
(`kinds/serve_pangu.py`).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256       # queries a block of the attention
# the lengths a sequence is padded to, whole blocks each
LENGTHS = (256, 512) + tuple(range(1024, 8192 + 1, 1024))
MLP_SLICES = 4      # slices of the dense MLP's width


# ------------------------------------------------- the assumed conventions


def router_scores(logits):
    return jax.nn.sigmoid(logits)


def combine_shared(shared, routed, factor):
    return shared + factor * routed


def softmax_scale(dn: int, dr: int) -> float:
    return float(dn + dr) ** -0.5


# ------------------------------------------------------------------ pieces


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _f32(w, matrices=None):
    """A stored matrix as float32, through `matrices` if given."""
    return (w if matrices is None else w.astype(matrices)).astype(F32)


def _swiglu(h, w1, w3, w2, matrices=None, slices: int = 1):
    """SwiGLU, `slices` slices of its width at a time (a sum of the
    slices' outputs: the hidden units do not mix)."""
    step = w1.shape[-1] // slices
    out = jnp.zeros(h.shape[:-1] + (w2.shape[-1],), F32)
    for i in range(slices):
        cols = slice(i * step, (i + 1) * step)
        out = out + (jax.nn.silu(h @ _f32(w1[:, cols], matrices))
                     * (h @ _f32(w3[:, cols], matrices))) \
            @ _f32(w2[cols], matrices)
    return out


def _rotary(x, positions, theta: float):
    """x [S, H, dr]: plain rotary over the whole of the last dimension,
    pairs (i, i + dr/2)."""
    dr = x.shape[-1]
    inv = np.asarray([theta ** (-2.0 * i / dr) for i in range(dr // 2)],
                     np.float32)
    angles = positions[:, None].astype(F32) * inv             # [S, dr/2]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _routed(h, moe, *, top_k, normalize, lo, matrices=None):
    """h [T, D] -> (the held experts' part of the routed sum [T, D], the
    margin [T])."""
    logits = h @ _f32(moe["moe_router"], matrices)                # [T, E]
    order, _ = jax.lax.top_k(logits, top_k + 1)
    margin = order[:, top_k - 1] - order[:, top_k]
    top, ids = jax.lax.top_k(router_scores(logits), top_k)
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    w1, w3, w2 = (moe[f"moe_experts_{n}"] for n in ("w1", "w3", "w2"))

    def one(e, acc):
        weight = jnp.sum(jnp.where(ids == lo + e, top, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(h, w1[e], w3[e], w2[e],
                                               matrices)

    return jax.lax.fori_loop(0, w1.shape[0], one, jnp.zeros_like(h)), margin


def _attention(attn, h, positions, *, r, dn, dr, theta, eps, matrices):
    """The plain form on h [S, D] -> [S, D] (before the output norm)."""
    cq = _rms_norm(h @ _f32(attn["wq_a"]["kernel"], matrices),
                   attn["q_norm"]["scale"], eps)
    q = jnp.einsum("sr,rhk->shk", cq, _f32(attn["wq_b"]["kernel"], matrices))
    ckv = h @ _f32(attn["wkv_a"]["kernel"], matrices)
    c = _rms_norm(ckv[:, :r], attn["kv_norm"]["scale"], eps)
    kv = jnp.einsum("sr,rhk->shk", c, _f32(attn["wkv_b"], matrices))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_nope, q_rope = q[..., :dn], _rotary(q[..., dn:], positions, theta)
    k_rope = _rotary(ckv[:, None, r:], positions, theta)[:, 0]    # [S, dr]
    s = h.shape[0]
    scale = softmax_scale(dn, dr)

    def rows_of(start):
        """The queries `start .. start + Q_BLOCK` against every key."""
        take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, start, Q_BLOCK, axis=0)
        scores = (jnp.einsum("shn,thn->hst", take(q_nope), k_nope)
                  + jnp.einsum("shr,tr->hst", take(q_rope), k_rope)) * scale
        seen = jnp.arange(s)[None, :] <= start + jnp.arange(Q_BLOCK)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hst,thv->shv", probs, v)

    out = jax.lax.map(rows_of, jnp.arange(0, s, Q_BLOCK))
    out = out.reshape(s, *out.shape[2:])
    return jnp.einsum("shv,hvd->sd", out, _f32(attn["wo"]["kernel"],
                                               matrices))


@partial(jax.jit, static_argnames=("sparse", "sandwich", "r", "dn", "dr",
                                   "theta", "top_k", "normalize", "factor",
                                   "lo", "eps", "matrices"))
def block(layer: Dict[str, Any], x, positions, *, sparse: bool,
          sandwich: bool, r: int, dn: int, dr: int, theta: float,
          top_k: int, normalize: bool, factor: float, lo: int, eps: float,
          matrices=None):
    """One decoder layer on x [S, D] float32 -> (x, margin [S])."""
    def after(name, y):
        return _rms_norm(y, layer[name]["scale"], eps) if sandwich else y

    h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
    x = x + after("post_attn_norm", _attention(
        layer["attn"], h, positions, r=r, dn=dn, dr=dr, theta=theta,
        eps=eps, matrices=matrices))
    h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
    if not sparse:
        mlp = layer["mlp"]
        y = _swiglu(h, *(mlp[n]["kernel"] for n in ("w1", "w3", "w2")),
                    matrices, slices=MLP_SLICES)
        return x + after("post_mlp_norm", y), \
            jnp.full(x.shape[:1], jnp.inf, F32)
    moe = layer["moe"]
    routed, margin = _routed(h, moe, top_k=top_k, normalize=normalize,
                             lo=lo, matrices=matrices)
    y = routed * factor
    if "moe_shared" in moe:
        shared = _swiglu(h, *(moe["moe_shared"][n]["kernel"]
                              for n in ("w1", "w3", "w2")), matrices)
        y = combine_shared(shared, routed, factor)
    return x + after("post_mlp_norm", y), margin


@partial(jax.jit, static_argnames=("matrices",))
def _embed(table, tokens, *, matrices=None):
    return _f32(table[tokens], matrices)


@partial(jax.jit, static_argnames=("eps", "matrices"))
def _head(scale, kernel, x, *, eps: float, matrices=None):
    return _rms_norm(x, scale, eps) @ _f32(kernel, matrices)


def logits(params: Dict[str, Any], tokens, sizes: Dict[str, Any], at=None,
           matrices=None):
    """(float32 logits [S, V], margins [S]) of one sequence `tokens`
    [S]; with `at` [K], both at those positions."""
    tokens = np.asarray(tokens, np.int32)
    # to a length of LENGTHS: tokens behind a causal sequence change
    # nothing before them, the attention's blocks are whole, and the
    # canaries' sixteen lengths are six programs to compile, not sixteen
    n_tokens = len(tokens)
    padded = next(n for n in LENGTHS if n >= n_tokens)
    tokens = jnp.asarray(np.pad(tokens, (0, padded - n_tokens)))
    positions = jnp.arange(padded)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["embedding"], tokens, matrices=matrices)
        for i in range(int(sizes["num_hidden_layers"])):
            x, m = block(
                params[f"layer_{i}"], x, positions,
                sparse=i >= int(sizes.get("first_k_dense_replace", 0)),
                sandwich=bool(sizes.get("sandwich_norm", False)),
                r=int(sizes["kv_lora_rank"]),
                dn=int(sizes["qk_nope_head_dim"]),
                dr=int(sizes["qk_rope_head_dim"]),
                theta=float(sizes["rope_theta"]),
                top_k=int(sizes["num_experts_per_tok"]),
                normalize=bool(sizes["norm_topk_prob"]),
                factor=float(sizes.get("routed_scaling_factor", 1.0)),
                lo=int(sizes["experts_held"][0]),
                eps=float(sizes["rms_norm_eps"]), matrices=matrices)
            margin = jnp.minimum(margin, m)
        at = jnp.arange(n_tokens) if at is None \
            else jnp.asarray(at, jnp.int32)
        x, margin = x[at], margin[at]
        return _head(params["final_norm"]["scale"],
                     params["lm_head"]["kernel"], x,
                     eps=float(sizes["rms_norm_eps"]),
                     matrices=matrices), margin


def teacher_forced(params: Dict[str, Any], prompts, answers,
                   sizes: Dict[str, Any], picks=None, matrices=None
                   ) -> List[Dict[str, Any]]:
    """What `reference_laguna.teacher_forced` returns, for this block:
    one prompt at a time, for each {"top", "top_id", "picked"} of the
    answer's K tokens and "margin", the router margin of the position
    that predicts each.  With `picks`, "picked" is the logit of
    `picks[b][j]` in the context the ANSWER makes; `matrices`: the
    module's text, "The second reading"."""
    out = []
    for b, (prompt, answer) in enumerate(zip(prompts, answers)):
        row = list(prompt) + list(answer[:-1])
        at = [len(prompt) - 1 + j for j in range(len(answer))]
        lg, margin = logits(params, row, sizes, at=at, matrices=matrices)
        chosen = answer if picks is None else picks[b]
        picked = jnp.take_along_axis(
            lg, jnp.asarray(chosen, jnp.int32)[:, None], axis=-1)[:, 0]
        out.append({"top": [float(x) for x in jnp.max(lg, axis=-1)],
                    "top_id": [int(x) for x in jnp.argmax(lg, axis=-1)],
                    "picked": [float(x) for x in picked],
                    "margin": [float(x) for x in margin]})
    return out
