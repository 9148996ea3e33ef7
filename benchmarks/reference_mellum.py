"""Plain reference of the Mellum block (`model_type: mellum`) for the
share of it one chip holds: the forward, the next-token loss AND the
gradient of every parameter.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`, no kernels, no code of
`ray_tpu/`: it reads the program's parameter tree (flax names)

    embed/embedding [V, D]; final_norm/scale; lm_head/kernel [D, V]
    layer_i/{attn_norm,mlp_norm}/scale [D]
    layer_i/attn/{wq [D, H, hd], wk, wv [D, Hkv, hd], wo [H, hd, D]}/kernel
    layer_i/moe/moe_router [D, E]
    layer_i/moe/{moe_experts_w1, _w3 [E_held, D, F], _w2 [E_held, F, D]}

and the configuration file's keys (`sizes`: `layer_types`,
`sliding_window`, `rope_parameters`, `num_experts_per_tok`,
`norm_topk_prob`, `rms_norm_eps`, `experts_held`).

For layer l, h = RMSNorm(x): q, k, v projections of the heads HELD
(H of the model's heads, Hkv of its KV heads: what the absent heads
would add to `wo`'s sum is another chip's, and is left out here as in
the program); rotary by the layer's kind over the whole head (sliding:
plain at its theta; full: YaRN's frequencies as `transformers`'
`_compute_yarn_parameters` computes them, cos and sin times
`attention_factor`); causal scores / sqrt(hd), a sliding layer sees
i - W < j <= i; softmax; W_o.  Then h' = RMSNorm(x) and the routed sum:
float32 logits h' W_r over ALL experts, softmax, the top k divided by
their sum, and for each expert HELD e: weight_e(t) x W2_e (silu(W1_e h')
* W3_e h') — applied to every token and multiplied by that token's
weight for it, or zero; nothing grouped, gathered or skipped.  No gate,
no shared expert, no factor.  The loss is the mean next-token cross
entropy of float32 logits.

What the published config does not say is one function each, as in
`reference_laguna.py` (the configuration lists them under `assumed`):
`router_scores` (softmax before the top-k) and `qk_normalize` (none) are
that file's; the loss alone (no auxiliary term) and no extra prediction
head are `next_token_loss` here.

**Given routing.**  Which k experts a token takes is discontinuous: a
bfloat16 program picks another expert where the k-th and (k+1)-th
probabilities lie close (PERF.md section 6, PR 28), and a swapped expert
moves gradients by far more than rounding does.  `routing` = the
program's chosen expert ids, one [T, k] array a layer, makes this
reference take those experts with ITS OWN probabilities for them; it
reports the share of tokens whose own top-k set differs
(`routing_differs`).  Without `routing` it takes its own top-k.

**Memory.**  The gradients come layer by layer (`grads_by_part`), last
layer first, so that a caller compares a part and drops it; attention
runs in blocks of `Q_BLOCK` queries and the experts one at a time (a
`lax.scan` over the held experts), each under `jax.checkpoint`, so a
layer's backward holds one block's scores and one expert's hidden rows
at a time, beside a training state.

**Second readings.**  `mutant` computes one part in the nearest lower
precision and nothing else: "logits_bfloat16" (the logits, the loss
arithmetic and the loss itself, rounded once more at the end), "router_bfloat16" (the router's product and softmax),
"experts_float8_e4m3fn" (the operands of every expert product),
"head_float8_e4m3fn" (the operands of the head's product).  Each has to
come out as NOT correct by the comparison of `kinds/train_mellum.py`, or
PERF.md says which cannot be refused.

**The first update.**  `first_adamw_step` is what adamw's first step
makes of a parameter tree given its gradients, in plain arithmetic:
`kinds/train_mellum.py` puts THIS reference's gradients through it and
holds the state the timed step leaves behind to the result
(`update_errors`; a state left unchanged reads 1).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference_laguna import (_rms_norm, _rotary, qk_normalize,
                                         router_scores)

F32 = jnp.float32
SLIDING = "sliding_attention"
Q_BLOCK = 1024
MUTANTS = ("logits_bfloat16", "router_bfloat16", "experts_float8_e4m3fn",
           "head_float8_e4m3fn")


def _through(x, dtype):
    """x rounded to `dtype` and back."""
    return x.astype(dtype).astype(F32)


# ------------------------------------------------------------------ pieces


@partial(jax.checkpoint, static_argnums=(4, 5))
def _attend_block(q, k, v, start, window, scale):
    """q [B, Q, H, hd] at positions start.. against all of k, v [B, S,
    H, hd]: masked softmax over the whole row."""
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k) * scale
    i = start + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window:
        seen = seen & (j > i - window)
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(scores, axis=-1), v)


def _attention(layer, h, positions, *, window: int, rope) -> jax.Array:
    attn = layer["attn"]
    wq, wk, wv, wo = (attn[n]["kernel"].astype(F32)
                      for n in ("wq", "wk", "wv", "wo"))
    q = jnp.einsum("bsd,dhk->bshk", h, wq)
    k = jnp.einsum("bsd,dhk->bshk", h, wk)
    v = jnp.einsum("bsd,dhk->bshk", h, wv)
    q, k = qk_normalize(q, k)
    q = _rotary(q, positions, dict(rope))
    k = _rotary(k, positions, dict(rope))
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)    # each KV head serves its group
    v = jnp.repeat(v, group, axis=2)
    s = h.shape[1]
    step = min(Q_BLOCK, s)
    out = jnp.concatenate([
        _attend_block(q[:, a:a + step], k, v, a, window,
                      float(q.shape[-1]) ** -0.5)
        for a in range(0, s, step)], axis=1)
    return jnp.einsum("bshk,hkd->bsd", out, wo)


def _expert(h, weight, w1, w3, w2, mutant):
    """weight [T] x SwiGLU_e(h [T, D]) for one expert, every token."""
    w1, w3, w2 = (w.astype(F32) for w in (w1, w3, w2))
    if mutant == "experts_float8_e4m3fn":
        low = jnp.float8_e4m3fn
        h, w1, w3, w2 = (_through(a, low) for a in (h, w1, w3, w2))
        hidden = _through(jax.nn.silu(h @ w1) * (h @ w3), low)
    else:
        hidden = jax.nn.silu(h @ w1) * (h @ w3)
    return weight[:, None] * (hidden @ w2)


def _routed(moe, h, given, *, top_k: int, normalize: bool, lo: int,
            mutant: Optional[str]):
    """h [T, D] -> (the held experts' part of the routed sum [T, D], the
    ids taken [T, k], the share of tokens whose own top-k set is not the
    given one)."""
    w_r = moe["moe_router"].astype(F32)
    if mutant == "router_bfloat16":
        low = jnp.bfloat16
        logits = jnp.dot(h.astype(low), w_r.astype(low)).astype(F32)
        probs = router_scores(logits.astype(low)).astype(F32)
    else:
        probs = router_scores(h @ w_r)
    top, own = jax.lax.top_k(probs, top_k)
    differs = jnp.zeros((), F32)
    ids = own
    if given is not None:
        ids = given
        top = jnp.take_along_axis(probs, ids, axis=-1)
        differs = jnp.mean(jnp.any(
            jnp.sort(own, axis=-1) != jnp.sort(ids, axis=-1), axis=-1))
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    w1, w3, w2 = (moe[f"moe_experts_{n}"] for n in ("w1", "w3", "w2"))

    @jax.checkpoint
    def one(y, expert):
        e, w1_e, w3_e, w2_e = expert
        weight = jnp.sum(jnp.where(ids == lo + e, top, 0.0), axis=-1)
        return y + _expert(h, weight, w1_e, w3_e, w2_e, mutant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(w1.shape[0]), w1, w3, w2))
    return y, ids, differs


def _block(layer, x, positions, given, *, window, rope, top_k, normalize,
           lo, eps, mutant):
    """One decoder layer on x [B, S, D] float32 -> (x, (ids, differs))."""
    h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
    x = x + _attention(layer, h, positions, window=window, rope=rope)
    h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
    b, s, d = h.shape
    y, ids, differs = _routed(layer["moe"], h.reshape(b * s, d), given,
                              top_k=top_k, normalize=normalize, lo=lo,
                              mutant=mutant)
    return x + y.reshape(b, s, d), (ids, differs)


_STATIC = ("window", "rope", "top_k", "normalize", "lo", "eps", "mutant")
block = jax.jit(_block, static_argnames=_STATIC)


@partial(jax.jit, static_argnames=_STATIC)
def block_grads(layer, x, positions, given, dy, **static):
    """(d layer, d x) of `block` for the cotangent dy of its output."""
    _, vjp, _ = jax.vjp(
        lambda p, x_: _block(p, x_, positions, given, **static),
        layer, x, has_aux=True)
    return vjp(dy)


def next_token_loss(final_norm, lm_head, x, tokens, *, eps: float,
                    mutant: Optional[str] = None):
    """Mean next-token cross entropy of float32 logits; no auxiliary
    term and no further prediction head (the configuration's
    `assumed`)."""
    h, w = _rms_norm(x, final_norm["scale"], eps), lm_head["kernel"]
    if mutant == "head_float8_e4m3fn":
        h, w = (_through(a, jnp.float8_e4m3fn) for a in (h, w))
    logits = h @ w.astype(F32)
    logits, targets = logits[:, :-1], tokens[:, 1:]
    if mutant == "logits_bfloat16":
        logits = logits.astype(jnp.bfloat16)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked).astype(F32)    # the mutant's: bfloat16


@partial(jax.jit, static_argnames=("eps", "mutant"))
def head_grads(final_norm, lm_head, x, tokens, *, eps, mutant):
    return jax.value_and_grad(next_token_loss, argnums=(0, 1, 2))(
        final_norm, lm_head, x, tokens, eps=eps, mutant=mutant)


@jax.jit
def _embed(table, tokens):
    return table.astype(F32)[tokens]


@jax.jit
def _embed_grads(table, tokens, dx):
    return jnp.zeros(table.shape, F32).at[tokens].add(dx)


# ------------------------------------------------------------ whole model


def _layer_static(sizes: Dict[str, Any], layer: int, mutant) -> Dict[str, Any]:
    kind = sizes["layer_types"][layer]
    rope = sizes["rope_parameters"][kind]
    return {"window": int(sizes["sliding_window"]) if kind == SLIDING else 0,
            "rope": tuple(sorted(rope.items())),
            "top_k": int(sizes["num_experts_per_tok"]),
            "normalize": bool(sizes["norm_topk_prob"]),
            "lo": int(sizes["experts_held"][0]),
            "eps": float(sizes["rms_norm_eps"]), "mutant": mutant}


def grads_by_part(params: Dict[str, Any], tokens, sizes: Dict[str, Any],
                  routing: Optional[Sequence[Any]] = None,
                  mutant: Optional[str] = None
                  ) -> Iterator[Tuple[str, Any]]:
    """The reference's loss and gradients, a part at a time:

        ("loss", {"loss": float, "ids": [own or given ids a layer],
                  "routing_differs": [share a layer]})
        ("head", {"final_norm": ..., "lm_head": ...})
        ("layer_<n-1>", that layer's gradient tree) ... ("layer_0", ...)
        ("embed", {"embedding": ...})

    each shaped as the same part of `params`.  `routing`: the program's
    chosen ids, one [T, k] array a layer, or None."""
    assert mutant is None or mutant in MUTANTS, mutant
    tokens = jnp.asarray(tokens, jnp.int32)
    n = len(sizes["layer_types"])
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    static = [_layer_static(sizes, i, mutant) for i in range(n)]
    given = [None if routing is None else jnp.asarray(routing[i], jnp.int32)
             for i in range(n)]
    with jax.default_matmul_precision("highest"):
        inputs, ids, differs = [], [], []
        x = _embed(params["embed"]["embedding"], tokens)
        for i in range(n):
            inputs.append(x)
            x, (taken, share) = block(params[f"layer_{i}"], x, positions,
                                      given[i], **static[i])
            ids.append(taken)
            differs.append(float(share))
        loss, (g_norm, g_head, dx) = head_grads(
            params["final_norm"], params["lm_head"], x, tokens,
            eps=static[0]["eps"], mutant=mutant)
        yield "loss", {"loss": float(loss), "ids": ids,
                       "routing_differs": differs}
        yield "head", {"final_norm": g_norm, "lm_head": g_head}
        for i in reversed(range(n)):
            g_layer, dx = block_grads(params[f"layer_{i}"], inputs.pop(),
                                      positions, given[i], dx, **static[i])
            yield f"layer_{i}", g_layer
        yield "embed", {"embedding": _embed_grads(
            params["embed"]["embedding"], tokens, dx)}


def loss_and_grads(params, tokens, sizes, routing=None, mutant=None):
    """`grads_by_part` gathered: (the "loss" record, the whole gradient
    tree shaped as `params`).  For sizes that fit whole."""
    parts = dict(grads_by_part(params, tokens, sizes, routing, mutant))
    said = parts.pop("loss")
    head = parts.pop("head")
    return said, {**parts, **head}


def group_errors(part: str, got: Any, want: Any) -> Dict[str, List[float]]:
    """||got - want|| / ||want|| and ||want|| of each parameter group of
    one part: "embed"; "head" (the head's matrix with the final norm);
    and of a layer "attention" (q, k, v, o and the norm before them),
    "router" (with the norm before the MLP), "w1", "w3", "w2"."""
    def rel(g, w) -> List[float]:
        g, w = jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(w)
        diff = sum(float(jnp.sum(jnp.square(a.astype(F32) - b)))
                   for a, b in zip(g, w)) ** 0.5
        norm = sum(float(jnp.sum(jnp.square(b))) for b in w) ** 0.5
        return [diff / norm if norm else float("inf"), norm]

    if not part.startswith("layer_"):
        return {part: rel(got, want)}
    pick = lambda t: {  # noqa: E731
        "attention": (t["attn"], t["attn_norm"]),
        "router": (t["moe"]["moe_router"], t["mlp_norm"]),
        **{w: t["moe"][f"moe_experts_{w}"] for w in ("w1", "w3", "w2")}}
    got, want = pick(got), pick(want)
    return {f"{part}.{name}": rel(got[name], want[name]) for name in want}


# ------------------------------------------------------- the first update


@partial(jax.jit, static_argnames=("lr", "eps", "weight_decay"))
def first_adamw_step(params, grads, *, lr: float, eps: float,
                     weight_decay: float):
    """(what adamw's FIRST step makes of `params` given `grads`, the
    squared norm of each leaf's change), both shaped as `params`.  Both
    moments start at zero, so after the bias correction they are g and
    g * g whatever the decay rates, and the step is
    p - lr (g / (|g| + eps) + weight_decay p): each element moves by lr
    against its gradient's SIGN (|g| is far above eps), which is why a
    gradient that is right to a hundredth leaves a change that is not:
    an element near zero whose sign the program's rounding flips is off
    by 2 lr."""
    def one(p, g):
        p = p.astype(F32)
        new = p - lr * (g / (jnp.abs(g) + eps) + weight_decay * p)
        return new, jnp.sum(jnp.square(new - p))

    both = jax.tree_util.tree_map(one, params, grads)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda _, b: b[i], params, both)
    return pick(0), pick(1)


@jax.jit
def _squared_distances(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sum(jnp.square(x.astype(F32) - y)), a, b)


def update_errors(after, expected, moved) -> Dict[str, List[float]]:
    """By leaf, ||after - expected|| / ||expected - before|| and the
    denominator: `after` the parameters a step left, `expected` and
    `moved` (the squared denominators) from `first_adamw_step`.  A leaf
    the step left as it was reads 1, whatever the reference's gradient."""
    off = jax.tree_util.tree_flatten_with_path(
        jax.device_get(_squared_distances(after, expected)))[0]
    moved = jax.tree_util.tree_leaves(jax.device_get(moved))
    out = {}
    for (path, diff), norm in zip(off, moved):
        name = ".".join(str(getattr(k, "key", k)) for k in path)
        norm = float(norm) ** 0.5
        out[name] = [float(diff) ** 0.5 / norm if norm else float("inf"),
                     norm]
    return out

