"""Plain reference of the latent-attention block with LEARNED SPARSE
ATTENTION (`model_type: glm_moe_dsa`), for the share of it one chip
holds.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: the PLAIN form of the attention —
every token's row is expanded to each head's key and value and a softmax
is taken over the rows the query's indexer selected — with no cache, no
kernel, no absorbed product and no threshold: the selection is `top_k`
of the index scores.  It shares no code with `ray_tpu/`: it reads the
engine's parameter tree (flax names of `ray_tpu/models/pangu.py`)

    embed/embedding [V, D]; final_norm/scale; lm_head/kernel [D, V]
    layer_i/{attn_norm, mlp_norm}/scale
    layer_i/attn/{wq_a [D, rq], wq_b [rq, H, dn + dr], wkv_a [D, r + dr],
                  wo [H, dv, D]}/kernel, {q_norm, kv_norm}/scale,
                  wkv_b [r, H, dn + dv]
    layer_i/attn/indexer/{wq_b [rq, J, dI], wk [D, dI]}/kernel,
                  k_norm/{scale, bias} [dI], weights [D, J]
    layer_i/mlp/{w1, w3 [D, F], w2 [F, D]}/kernel          (a dense layer)
    layer_i/moe/{moe_router [D, E], moe_router_bias [E]}  (an expert layer)
    layer_i/moe/{moe_experts_w1, _w3 [E_held, D, Fe], _w2 [E_held, Fe, D]}
    layer_i/moe/moe_shared/{w1, w3, w2}/kernel

and the model's published sizes (`sizes`, the configuration file's
keys).  For layer l, h = RMSNorm(x), t a query position, s <= t:

    cq = RMSNorm(h Wqa); (q_nope, q_rope)_i = cq Wqb_i     i of H heads
    (c, k_rope) = h Wkva; c = RMSNorm(c); (k_nope, v)_i = c Wkvb_i
    q_rope, k_rope rotated at rope_theta over PAIRS (2j, 2j + 1)
    indexer: qI_j = cq WqI_j (j of J); kI = LayerNorm(h WkI); the first
      dr numbers of both rotated the same way; w = h Ww / sqrt(J dI)
      I_ts = sum_j w_tj ReLU(qI_tj . kI_s)
    S_t = the min(index_topk, t + 1) positions s <= t of largest I_ts
      (a tie to the lower s)
    a_tis = softmax over s in S_t of (q_nope . k_nope + q_rope . k_rope)
      / sqrt(dn + dr);  x += concat_i(sum_s a v) Wo
    h' = RMSNorm(x); x += mlp(h')
    mlp: SwiGLU (l < first_k_dense_replace), or: sigma = sigmoid(h' Wr);
      the 8 of largest sigma + b CHOSEN, weighted sigma / sum of the
      chosen sigma x routed_scaling_factor, summed over the chosen
      experts HELD HERE, plus the shared expert

Memory.  A 30,000-token canary is judged beside an engine that holds 12
GB: nothing of size [heads, S, S] or [S, heads, ...] is ever made.  A
layer walks its queries a block at a time (`lax.map`); a block scores
the index an index head at a time, takes its selection, and then goes
head by head: the head's keys and values are expanded from `c` ([S, dn]
and [S, dv]), its softmax taken over the selected rows and its output
multiplied into the head's slice of Wo.  The dense MLP goes a slice of
its width at a time, the expert layer an expert at a time.

**The second reading.**  `matrices=<dtype name>` rounds every stored
matrix to that dtype before it is used and changes nothing else
(`reference_laguna.py` has the method).  `variant=` is a reference with
ONE mechanism wrong, for the comparison to refuse (`VARIANTS`).

Each position also gets two MARGINS, the smallest over the layers: the
router's (the gap of the 8th and 9th largest sigma + b) and the
SELECTION's (the gap of the index_topk-th and the next index score of a
query that sees more rows than that; infinite where it does not).  A
position where either is small may choose differently in a correct
bfloat16 program (`kinds/serve_glm.py`).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# the lengths a sequence is padded to (tokens behind a causal sequence
# change nothing before them), and the queries a block of the walk
LENGTHS = (256, 1024, 2048, 3072, 5120, 9216, 17408, 32768)
MLP_SLICES = 4      # slices of the dense MLP's width
LN_EPS = 1e-6       # the indexer's LayerNorm
# a reference with one mechanism wrong: what the comparison must refuse
VARIANTS = ("dense", "topk_half", "topk_double", "no_relu", "no_weights",
            "unrotated_keys", "no_bias")


def q_block(length: int) -> int:
    return 256 if length <= 2048 else 1024


# ------------------------------------------------------------------ pieces


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(F32) \
        + bias.astype(F32)


def _f32(w, matrices=None):
    """A stored matrix as float32, through `matrices` if given."""
    return (w if matrices is None else w.astype(matrices)).astype(F32)


def _swiglu(h, w1, w3, w2, matrices=None, slices: int = 1):
    step = w1.shape[-1] // slices
    out = jnp.zeros(h.shape[:-1] + (w2.shape[-1],), F32)
    for i in range(slices):
        cols = slice(i * step, (i + 1) * step)
        out = out + (jax.nn.silu(h @ _f32(w1[:, cols], matrices))
                     * (h @ _f32(w3[:, cols], matrices))) \
            @ _f32(w2[cols], matrices)
    return out


def _rotate_pairs(x, positions, dim: int, theta: float):
    """x [S, ..., n]: the first `dim` numbers rotated, pair (2j, 2j + 1)
    by the angle position x theta^(-2j / dim); the rest as they are."""
    inv = np.asarray([theta ** (-2.0 * j / dim) for j in range(dim // 2)],
                     np.float32)
    angles = positions.astype(F32)[:, None] * inv               # [S, dim/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    even, odd = x[..., 0:dim:2], x[..., 1:dim:2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1).reshape(x.shape[:-1] + (dim,))
    return jnp.concatenate([turned, x[..., dim:]], axis=-1)


def _routed(h, moe, *, top_k, normalize, lo, matrices=None, bias=True):
    """h [T, D] -> (the held experts' part of the routed sum [T, D], the
    router margin [T])."""
    sigma = jax.nn.sigmoid(h @ _f32(moe["moe_router"], matrices))  # [T, E]
    chosen_by = sigma + moe["moe_router_bias"].astype(F32) if bias \
        else sigma
    order, ids = jax.lax.top_k(chosen_by, top_k + 1)
    margin = order[:, top_k - 1] - order[:, top_k]
    ids = ids[:, :top_k]
    top = jnp.take_along_axis(sigma, ids, axis=-1)
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    w1, w3, w2 = (moe[f"moe_experts_{n}"] for n in ("w1", "w3", "w2"))

    def one(e, acc):
        weight = jnp.sum(jnp.where(ids == lo + e, top, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(h, w1[e], w3[e], w2[e],
                                               matrices)

    return jax.lax.fori_loop(0, w1.shape[0], one, jnp.zeros_like(h)), margin


def _attention(attn, h, positions, *, r, dn, dr, theta, eps, index_topk,
               matrices, variant):
    """h [S, D] -> (the sublayer's output [S, D], selection margin [S])."""
    s = h.shape[0]
    qb = q_block(s)
    ix = attn["indexer"]
    heads = attn["wq_b"]["kernel"].shape[1]
    index_heads, index_dim = ix["wq_b"]["kernel"].shape[1:]
    top_k = {"topk_half": index_topk // 2,
             "topk_double": index_topk * 2}.get(variant, index_topk)
    cq = _rms_norm(h @ _f32(attn["wq_a"]["kernel"], matrices),
                   attn["q_norm"]["scale"], eps)
    ckv = h @ _f32(attn["wkv_a"]["kernel"], matrices)
    c = _rms_norm(ckv[:, :r], attn["kv_norm"]["scale"], eps)
    k_rope = _rotate_pairs(ckv[:, r:], positions, dr, theta)      # [S, dr]
    k_index = _layer_norm(h @ _f32(ix["wk"]["kernel"], matrices),
                          ix["k_norm"]["scale"], ix["k_norm"]["bias"],
                          LN_EPS)                                 # [S, dI]
    if variant != "unrotated_keys":
        k_index = _rotate_pairs(k_index, positions, dr, theta)
    w_index = (h @ _f32(ix["weights"], matrices)) \
        * float(index_heads * index_dim) ** -0.5                  # [S, J]
    scale = float(dn + dr) ** -0.5
    wq_b, wkv_b, wo = attn["wq_b"]["kernel"], attn["wkv_b"], \
        attn["wo"]["kernel"]

    def head_of(w, i, axis):
        return _f32(jax.lax.dynamic_index_in_dim(w, i, axis, False),
                    matrices)

    def rows_of(start):
        """The queries `start .. start + qb` against every row."""
        take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, start, qb, axis=0)
        cq_b, pos_b = take(cq), take(positions)
        seen = positions[None, :] <= pos_b[:, None]               # [qb, S]
        if variant == "dense" or s <= top_k:
            chosen, margin = seen, jnp.full((qb,), jnp.inf, F32)
        else:
            q_index = _rotate_pairs(
                jnp.einsum("sr,rjk->sjk", cq_b,
                           _f32(ix["wq_b"]["kernel"], matrices)),
                pos_b, dr, theta)                                 # [qb, J, dI]
            w_b = take(w_index)

            def index_head(j, acc):
                dots = q_index[:, j] @ k_index.T                  # [qb, S]
                if variant != "no_relu":
                    dots = jnp.maximum(dots, 0.0)
                if variant != "no_weights":
                    dots = dots * jax.lax.dynamic_slice_in_dim(
                        w_b, j, 1, axis=1)
                return acc + dots

            marks = jax.lax.fori_loop(0, index_heads, index_head,
                                      jnp.zeros((qb, s), F32))
            marks = jnp.where(seen, marks, -jnp.inf)
            # `top_k` puts equal scores lower position first
            best, at = jax.lax.top_k(marks, top_k + 1)
            chosen = jnp.zeros((qb, s), bool).at[
                jnp.arange(qb)[:, None], at[:, :top_k]].set(True) & seen
            margin = jnp.where(pos_b + 1 > top_k,
                               best[:, top_k - 1] - best[:, top_k], jnp.inf)

        def head(i, acc):
            q = cq_b @ head_of(wq_b, i, 1)                        # [qb, dn+dr]
            q_rope = _rotate_pairs(q[:, dn:], pos_b, dr, theta)
            kv = c @ head_of(wkv_b, i, 1)                         # [S, dn+dv]
            scores = (q[:, :dn] @ kv[:, :dn].T + q_rope @ k_rope.T) * scale
            probs = jax.nn.softmax(jnp.where(chosen, scores, -jnp.inf),
                                   axis=-1)
            return acc + (probs @ kv[:, dn:]) @ head_of(wo, i, 0)

        out = jax.lax.fori_loop(0, heads, head,
                                jnp.zeros((qb, wo.shape[-1]), F32))
        return out, margin

    out, margin = jax.lax.map(rows_of, jnp.arange(0, s, qb))
    return out.reshape(s, -1), margin.reshape(s)


@partial(jax.jit, static_argnames=(
    "sparse", "r", "dn", "dr", "theta", "index_topk", "top_k", "normalize",
    "factor", "lo", "eps", "matrices", "variant"))
def block(layer: Dict[str, Any], x, positions, *, sparse: bool, r: int,
          dn: int, dr: int, theta: float, index_topk: int, top_k: int,
          normalize: bool, factor: float, lo: int, eps: float,
          matrices=None, variant=None):
    """One decoder layer on x [S, D] float32 -> (x, router margin [S],
    selection margin [S])."""
    h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
    a, picked = _attention(
        layer["attn"], h, positions, r=r, dn=dn, dr=dr, theta=theta,
        eps=eps, index_topk=index_topk, matrices=matrices, variant=variant)
    x = x + a
    h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
    if not sparse:
        mlp = layer["mlp"]
        y = _swiglu(h, *(mlp[n]["kernel"] for n in ("w1", "w3", "w2")),
                    matrices, slices=MLP_SLICES)
        return x + y, jnp.full(x.shape[:1], jnp.inf, F32), picked
    moe = layer["moe"]
    routed, margin = _routed(h, moe, top_k=top_k, normalize=normalize,
                             lo=lo, matrices=matrices,
                             bias=variant != "no_bias")
    shared = _swiglu(h, *(moe["moe_shared"][n]["kernel"]
                          for n in ("w1", "w3", "w2")), matrices)
    return x + shared + factor * routed, margin, picked


@partial(jax.jit, static_argnames=("matrices",))
def _embed(table, tokens, *, matrices=None):
    return _f32(table[tokens], matrices)


@partial(jax.jit, static_argnames=("eps", "matrices"))
def _head(scale, kernel, x, *, eps: float, matrices=None):
    return _rms_norm(x, scale, eps) @ _f32(kernel, matrices)


def rope_theta(sizes: Dict[str, Any]) -> float:
    return float((sizes.get("rope_parameters") or sizes)["rope_theta"])


def logits(params: Dict[str, Any], tokens, sizes: Dict[str, Any], at=None,
           matrices=None, variant=None):
    """(float32 logits [S, V], router margins [S], selection margins
    [S]) of one sequence `tokens` [S]; with `at` [K], all at those
    positions."""
    assert variant is None or variant in VARIANTS, variant
    tokens = np.asarray(tokens, np.int32)
    n_tokens = len(tokens)
    padded = next(n for n in LENGTHS if n >= n_tokens)
    tokens = jnp.asarray(np.pad(tokens, (0, padded - n_tokens)))
    positions = jnp.arange(padded)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    picked = margin
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["embedding"], tokens, matrices=matrices)
        for i in range(int(sizes["num_hidden_layers"])):
            x, m, p = block(
                params[f"layer_{i}"], x, positions,
                sparse=i >= int(sizes.get("first_k_dense_replace", 0)),
                r=int(sizes["kv_lora_rank"]),
                dn=int(sizes["qk_nope_head_dim"]),
                dr=int(sizes["qk_rope_head_dim"]),
                theta=rope_theta(sizes),
                index_topk=int(sizes["index_topk"]),
                top_k=int(sizes["num_experts_per_tok"]),
                normalize=bool(sizes["norm_topk_prob"]),
                factor=float(sizes.get("routed_scaling_factor", 1.0)),
                lo=int(sizes["experts_held"][0]),
                eps=float(sizes["rms_norm_eps"]), matrices=matrices,
                variant=variant)
            margin, picked = jnp.minimum(margin, m), jnp.minimum(picked, p)
        at = jnp.arange(n_tokens) if at is None \
            else jnp.asarray(at, jnp.int32)
        return _head(params["final_norm"]["scale"],
                     params["lm_head"]["kernel"], x[at],
                     eps=float(sizes["rms_norm_eps"]),
                     matrices=matrices), margin[at], picked[at]


def teacher_forced(params: Dict[str, Any], prompts, answers,
                   sizes: Dict[str, Any], picks=None, matrices=None,
                   variant=None) -> List[Dict[str, Any]]:
    """What `reference_pangu.teacher_forced` returns, for this block:
    one prompt at a time, for each {"top", "top_id", "picked"} of the
    answer's K tokens, "margin" — the router margin of the position that
    predicts each — and "select_margin", its selection margin.  With
    `picks`, "picked" is the logit of `picks[b][j]` in the context the
    ANSWER makes; `matrices`, `variant`: the module's text."""
    out = []
    for b, (prompt, answer) in enumerate(zip(prompts, answers)):
        row = list(prompt) + list(answer[:-1])
        at = [len(prompt) - 1 + j for j in range(len(answer))]
        lg, margin, picked_m = logits(params, row, sizes, at=at,
                                      matrices=matrices, variant=variant)
        chosen = answer if picks is None else picks[b]
        picked = jnp.take_along_axis(
            lg, jnp.asarray(chosen, jnp.int32)[:, None], axis=-1)[:, 0]
        out.append({"top": [float(x) for x in jnp.max(lg, axis=-1)],
                    "top_id": [int(x) for x in jnp.argmax(lg, axis=-1)],
                    "picked": [float(x) for x in picked],
                    "margin": [float(x) for x in margin],
                    "select_margin": [float(x) for x in picked_m]})
    return out
