"""Plain reference of SDAR-30B-A3B-Chat (`model_type: sdar_moe`): the
Qwen3-MoE block its config's keys spell, under a BLOCK-causal mask, and
the published sampler's loop that generates by diffusion over blocks.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`, no kernels, NO CACHE: every pass
of the loop is a full forward of the whole sequence under the block
mask (attention in blocks of queries, so that the scores fit beside an
engine).  It shares no code with `ray_tpu/`: it reads the engine's
parameter tree (flax names of `ray_tpu/models/laguna.py`)

    embed/embedding [V, D]; final_norm/scale; lm_head/kernel [D, V]
    layer_i/{attn_norm,mlp_norm}/scale [D]
    layer_i/attn/{wq [D, H, hd], wk, wv [D, Hkv, hd], wo [H, hd, D]}/kernel
    layer_i/attn/{q_norm,k_norm}/scale [hd]
    layer_i/moe/moe_router [D, E]
    layer_i/moe/{moe_experts_w1, _w3 [E, D, F], _w2 [E, F, D]}

and the configuration file's keys (`sizes`): `num_hidden_layers`,
`head_dim`, `rope_theta`, `rms_norm_eps`, `num_experts_per_tok`,
`norm_topk_prob`, and the `generation` group (`block_length` B,
`denoising_steps` T, `confidence_threshold`, `mask_token_id` M).

The layer (h = RMSNorm(x)): q, k, v projections without biases; a
weighted RMSNorm over each head's numbers of q and of k; rotary over the
whole head, half-split pairs, theta as published; scores / sqrt(hd);
position t sees s iff s // B <= t // B; softmax; W_o.  Then h' =
RMSNorm(x), router probabilities by softmax over ALL experts in
float32, the top-k renormalised, and the sum of the chosen experts'
SwiGLU — a LOOP over the experts, each applied to every token and
multiplied by that token's weight for it or zero (nothing grouped,
gathered or skipped).  Final RMSNorm, untied head.  The logits at
position t predict the token AT t: no shift.

The loop (`generate`): the block that holds the prompt's end opens with
the prompt's tail, then masks; a DENOISING pass takes z = argmax and c =
softmax(logits)[z] at the block's positions (the mask's own id is no
candidate), and unmasks, among the masked positions, those with c >
threshold, or where they are fewer than the schedule's count for the
pass (B / T a pass, the remainder to the first passes) that many of
largest c, a tie to the lower position; once no mask is left the block
is committed — here: it simply stays in the sequence — and the next
opens.  The answer is what the blocks hold behind the prompt, cut at
`max_new`; the last block gets no commit.

`teacher_forced` takes a program's own record of its passes and says
what this reference makes of each (its text).  `matrices=<dtype name>`
rounds every stored matrix to that dtype first (`float8_e4m3fn`: the
nearest precision below the configuration's bfloat16); `mutant=` leaves
a mechanism out or does it wrong (`MUTANTS`): what a comparison built
on this reference has to refuse (`kinds/serve_sdar.py`).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
PAD = 256          # a sequence is padded to a multiple (later blocks: unseen)
QUERY_BLOCK = 256  # queries whose scores are alive at once

# what can be done wrong, by name: a causal mask inside the block; no
# norm on q and k; the logits at t - 1 sampled for position t; every
# earlier block left as its LAST DENOISING pass read it (no commit: its
# rows never rewritten once it was whole); the block's own keys and
# values left as the pass before wrote them (stale open rows); the
# prompt's tail masked and generated, not given
MUTANTS = ("causal_in_block", "no_qk_norm", "shift_by_one", "no_commit",
           "stale_open_rows", "tail_generated")


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _f32(w, matrices=None):
    """A stored matrix as float32, through `matrices` if given."""
    return (w if matrices is None else w.astype(matrices)).astype(F32)


def _rotary(x, positions, theta: float):
    """x [S, H, hd], the whole head rotated, pairs (i, i + hd / 2)."""
    hd = x.shape[-1]
    inv = np.asarray([theta ** (-2.0 * i / hd) for i in range(hd // 2)],
                     np.float32)
    angles = positions[:, None].astype(F32) * inv               # [S, hd/2]
    emb = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    half = hd // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _routed(h, moe, *, top_k: int, normalize: bool, matrices=None):
    """h [S, D] -> (the routed sum [S, D], the router margin [S]: log
    p(k-th) - log p((k+1)-th), the gap of those two router logits)."""
    probs = jax.nn.softmax(h @ _f32(moe["moe_router"], matrices), axis=-1)
    top, ids = jax.lax.top_k(probs, top_k + 1)
    margin = jnp.log(top[:, top_k - 1]) - jnp.log(top[:, top_k])
    top, ids = top[:, :top_k], ids[:, :top_k]
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    w1, w3, w2 = (moe[f"moe_experts_{n}"] for n in ("w1", "w3", "w2"))

    def one(e, acc):
        weight = jnp.sum(jnp.where(ids == e, top, 0.0), axis=-1)
        y = (jax.nn.silu(h @ _f32(w1[e], matrices))
             * (h @ _f32(w3[e], matrices))) @ _f32(w2[e], matrices)
        return acc + weight[:, None] * y

    return jax.lax.fori_loop(0, w1.shape[0], one, jnp.zeros_like(h)), margin


@partial(jax.jit, static_argnames=("block", "theta", "top_k", "normalize",
                                   "eps", "matrices", "causal", "qk_norm"))
def layer_forward(layer: Dict[str, Any], x, kv_x, *, block: int,
                  theta: float, top_k: int, normalize: bool, eps: float,
                  matrices=None, causal: bool = False, qk_norm: bool = True):
    """One decoder layer on x [S, D] float32 -> (x, margin [S]).  Keys
    and values are made of `kv_x` (x itself, but for the mutant whose
    open rows are stale)."""
    attn = layer["attn"]
    wq, wk, wv, wo = (_f32(attn[n]["kernel"], matrices)
                      for n in ("wq", "wk", "wv", "wo"))
    s = x.shape[0]
    positions = jnp.arange(s)
    h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
    h_kv = _rms_norm(kv_x, layer["attn_norm"]["scale"], eps)
    q = jnp.einsum("sd,dhk->shk", h, wq)
    k = jnp.einsum("sd,dhk->shk", h_kv, wk)
    v = jnp.einsum("sd,dhk->shk", h_kv, wv)
    if qk_norm:
        q = _rms_norm(q, attn["q_norm"]["scale"], eps)
        k = _rms_norm(k, attn["k_norm"]["scale"], eps)
    q = _rotary(q, positions, theta)
    k = _rotary(k, positions, theta)
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scale = 1.0 / jnp.sqrt(F32(q.shape[-1]))

    def attend(args):
        qb, at = args                                  # [Q, H, hd], [Q]
        scores = jnp.einsum("qhk,thk->hqt", qb, k) * scale
        last = at if causal else (at // block + 1) * block - 1
        seen = positions[None, :] <= last[:, None]               # [Q, S]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, axis=-1), v)

    nq = s // QUERY_BLOCK
    out = jax.lax.map(attend, (q.reshape(nq, QUERY_BLOCK, *q.shape[1:]),
                               positions.reshape(nq, QUERY_BLOCK)))
    x = x + jnp.einsum("shk,hkd->sd", out.reshape(q.shape), wo)
    h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
    routed, margin = _routed(h, layer["moe"], top_k=top_k,
                             normalize=normalize, matrices=matrices)
    return x + routed, margin


@partial(jax.jit, static_argnames=("matrices",))
def _embed(table, tokens, *, matrices=None):
    return _f32(table[tokens], matrices)


@partial(jax.jit, static_argnames=("eps", "matrices"))
def _head(scale, kernel, x, *, eps: float, matrices=None):
    return _rms_norm(x, scale, eps) @ _f32(kernel, matrices)


def logits(params: Dict[str, Any], tokens: Sequence[int],
           sizes: Dict[str, Any], at: Sequence[int], matrices=None,
           mutant: Optional[str] = None,
           stale: Optional[Sequence[int]] = None):
    """(float32 logits [K, V], router margins [K]) of ONE sequence at the
    positions `at`: a full forward under the block mask.  `stale` (the
    mutant `stale_open_rows`): the sequence as the pass BEFORE read it,
    whose keys and values this pass's queries then see in place of its
    own."""
    gen = sizes["generation"]
    n = len(tokens)
    padded = -(-n // PAD) * PAD
    row = np.zeros((padded,), np.int32)
    row[:n] = tokens
    kw = dict(block=int(gen["block_length"]),
              theta=float(sizes["rope_theta"]),
              top_k=int(sizes["num_experts_per_tok"]),
              normalize=bool(sizes["norm_topk_prob"]),
              eps=float(sizes["rms_norm_eps"]), matrices=matrices,
              causal=mutant == "causal_in_block",
              qk_norm=mutant != "no_qk_norm")
    margin = jnp.full((padded,), jnp.inf, F32)
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["embedding"]
        x = _embed(table, jnp.asarray(row), matrices=matrices)
        old = None
        if stale is not None:
            row_old = row.copy()
            row_old[:n] = stale
            old = _embed(table, jnp.asarray(row_old), matrices=matrices)
        for i in range(int(sizes["num_hidden_layers"])):
            layer = params[f"layer_{i}"]
            x, m = layer_forward(layer, x, x if old is None else old, **kw)
            if old is not None:
                old, _ = layer_forward(layer, old, old, **kw)
            margin = jnp.minimum(margin, m)
        at = jnp.asarray(at, jnp.int32)
        return _head(params["final_norm"]["scale"],
                     params["lm_head"]["kernel"], x[at],
                     eps=float(sizes["rms_norm_eps"]),
                     matrices=matrices), margin[at]


# ----------------------------------------------------------- the sampler


def schedule(block: int, steps: int) -> List[int]:
    """Positions a pass has to unmask at least: B / T a pass, the
    remainder to the first passes."""
    return [block // steps + (k < block % steps) for k in range(steps)]


def confidences(lg, mask_id: int):
    """(z [K], log c [K], the candidates' logits [K, V]) of logits [K,
    V]: the mask's id is no candidate; c = softmax(.)[z], as its log."""
    lg = np.array(lg, np.float32)
    lg[:, mask_id] = -np.inf
    top = lg.max(axis=-1)
    log_c = -np.log(np.exp(lg - top[:, None]).sum(axis=-1))
    return lg.argmax(axis=-1), log_c, lg


def transfers(masked: List[bool], log_c, need: int, threshold: float
              ) -> List[int]:
    """The positions of a block a denoising pass unmasks."""
    c = np.exp(log_c)
    over = [t for t, m in enumerate(masked) if m and c[t] > threshold]
    if len(over) >= need:
        return over
    order = sorted((t for t, m in enumerate(masked) if m),
                   key=lambda t: (-log_c[t], t))
    return sorted(order[:need])


def generate(params: Dict[str, Any], prompt: Sequence[int], max_new: int,
             sizes: Dict[str, Any], steps: Optional[int] = None,
             threshold: Optional[float] = None, matrices=None,
             mutant: Optional[str] = None) -> Dict[str, Any]:
    """The loop of the module's text, cache-free -> {"tokens": the
    answer, "passes": [[the block's first position, the block as the
    pass read it, as it left it], ...]}, the commits among them (a pass
    that read no mask and changed nothing)."""
    gen = sizes["generation"]
    b, mask = int(gen["block_length"]), int(gen["mask_token_id"])
    steps = int(steps or gen["denoising_steps"])
    threshold = float(gen["confidence_threshold"]
                      if threshold is None else threshold)
    seq = list(prompt)
    p0 = len(seq) // b * b
    if mutant == "tail_generated":
        seq = seq[:p0]
    total = len(prompt) + max_new
    passes: List[list] = []
    context = seq[:p0]     # what later blocks read of the earlier ones
    while p0 < total:
        cur = seq[p0:] + [mask] * (b - len(seq[p0:]))
        before = list(cur)
        for k, need in enumerate(schedule(b, steps)):
            if mask not in cur:
                break
            at = list(range(p0, p0 + b))
            if mutant == "shift_by_one":
                at = [max(t - 1, 0) for t in at]
            lg, _ = logits(params, context + cur, sizes, at, matrices,
                           mutant, stale=context + before
                           if mutant == "stale_open_rows" else None)
            z, log_c, _ = confidences(lg, mask)
            before = list(cur)
            for t in transfers([t == mask for t in cur], log_c, need,
                               threshold):
                cur[t] = int(z[t])
            passes.append([p0, before, list(cur)])
        seq = seq[:p0] + cur
        # the mutant's later blocks read this one as its last denoising
        # pass did; the program proper rewrites its rows once more
        context = context + (before if mutant == "no_commit" else cur)
        p0 += b
        if p0 < total:
            passes.append([p0 - b, list(cur), list(cur)])   # the commit
    return {"tokens": seq[len(prompt):total], "passes": passes}


def teacher_forced(params: Dict[str, Any], prompts, trajectories,
                   sizes: Dict[str, Any], matrices=None,
                   mutant: Optional[str] = None) -> List[List[Dict]]:
    """What this reference makes of a program's own record: for each
    prompt, for each DENOISING pass of `trajectories[b]` = {"tokens",
    "passes"} (a commit's logits are nobody's), in the context the
    program's own blocks make (its answer, teacher-forced), a dictionary

        "p0", "moved": the positions (0 .. B-1) the program unmasked
        "top", "top_id", "picked": a moved position's largest candidate
            logit, its id, and the logit of the token the program put
        "margin": a moved position's router margin (the smallest over
            the layers)
        "conf_margin": a moved position's log c less the largest log c
            among the masked positions the program did NOT move (+inf
            where none is left) — below zero: the reference would have
            moved another
        "over": the masked positions whose c exceeds the threshold here
    """
    gen = sizes["generation"]
    mask = int(gen["mask_token_id"])
    threshold = float(gen["confidence_threshold"])
    out = []
    for prompt, traj in zip(prompts, trajectories):
        full = list(prompt) + list(traj["tokens"])
        rows = []
        for p0, before, after in traj["passes"]:
            moved = [t for t, (x, y) in enumerate(zip(before, after))
                     if x == mask and y != mask]
            if mask not in before:
                continue
            b = len(before)
            lg, margin = logits(params, full[:p0] + list(before), sizes,
                                list(range(p0, p0 + b)), matrices, mutant)
            z, log_c, lg = confidences(lg, mask)
            left = [log_c[t] for t in range(b)
                    if before[t] == mask and t not in moved]
            rows.append({
                "p0": p0, "moved": moved,
                "top": [float(lg[t].max()) for t in moved],
                "top_id": [int(z[t]) for t in moved],
                "picked": [float(lg[t, after[t]]) for t in moved],
                "margin": [float(margin[t]) for t in moved],
                "conf_margin": [float(log_c[t] - max(left)) if left
                                else float("inf") for t in moved],
                "over": [t for t in range(b) if before[t] == mask
                         and np.exp(log_c[t]) > threshold]})
        out.append(rows)
    return out
