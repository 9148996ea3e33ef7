"""The expert-layer decoder family: attention whose kind (full or
sliding window) and head count change by layer, rotary tables by layer
kind, and MLPs that are dense or routed experts by layer.  Three
published models are settings of it, told apart by the keys their
`config.json` has and never by name:

    `model_type: laguna`  a head-wise attention gate (`gating`; `gated`
        here), a dense leading layer, a shared expert beside the routed ones
        (`shared_expert_intermediate_size`), a factor on the routed sum
        (`moe_routed_scaling_factor`), 48 or 72 heads by layer
        (`num_attention_heads_per_layer`) — served through LLMEngine
    `model_type: mellum`  none of those keys: no gate, no
        shared expert, every layer sparse, one head count
        (`num_attention_heads`) — trained through train/gspmd.py
    `model_type: sdar_moe`  none of those keys either, and no
        `layer_types` (every layer full attention), `rope_theta` where
        the others have `rope_parameters`; a weighted RMSNorm on each
        head's q and k (`qk_norm`: the published class's code, which
        the config has no key for — `models.CLASS_DEFAULTS`); and a
        `generation` group: the model GENERATES BY DIFFUSION OVER
        BLOCKS of `block_length` positions (below) — served through
        LLMEngine, a decode pass carrying a whole block a lane

A mechanism whose key is absent is not there (`LagunaConfig.from_dict`).
Written from the published keys: `layer_types`, `mlp_layer_types`,
`sliding_window`, `rope_parameters` by layer kind, `num_experts`,
`num_experts_per_tok`, `norm_topk_prob`.  For layer l with input x
[S, D], H_l heads and h = RMSNorm(x):

    q = h Wq [S, H_l, hd]; k = h Wk, v = h Wv [S, Hkv, hd]
    rotary by kind: a sliding layer rotates the whole head at its theta;
      a full layer rotates the first `partial_rotary_factor` of the head
      with YaRN's frequencies, cos and sin times `attention_factor`
    causal scores / sqrt(hd), a sliding layer sees i - W < j <= i
    gated: g = sigmoid(h Wg) [S, H_l]; x += (g_h * a_h concatenated) Wo
    ungated: x += (a_h concatenated) Wo
    h' = RMSNorm(x); a dense layer: x += SwiGLU(h')
    a sparse layer: x += [SwiGLU_shared(h') +] factor * routed(h')

What the config does not say is ONE function each, so that a reader with
the model's own code corrects it in one place (the configuration file
lists them under `assumed`): `gate_activation`, `router_scores`,
`combine_shared` and `qk_normalize`; the block setting's are
`block_candidates` (the mask id is never a candidate), `block_confidence`
and `block_transfer` (the sampler's `low_confidence_dynamic` rule), and
the logits at position t predict the token AT t (no shift: the engine
samples a block's positions from their own logits).

Generation by diffusion over blocks (`block_length` B > 0): attention is
BLOCK-causal — position t sees s iff s // B <= t // B, its whole block
and every earlier one — in the whole-sequence route, in a prefill chunk
(`cached_attention(block=B)`; a chunk ends on a block's end) and in a
block pass, where a lane's B queries see the committed rows and the
block's own B rows, which the pass writes first
(`paged_attention_block`).  The engine's sampler (`block_sample`, run on
the device behind the model) takes the block's tokens, masked where they
equal `mask_token_id`, and the logits: z = argmax over the candidates,
c = softmax(logits)[z] in float32; among the masked positions those
with c > `confidence_threshold` are unmasked, and where they are fewer
than the pass's scheduled count the `need` of largest c instead.

This chip may hold a share of a layer: `experts_held` = (lo, hi) of the
router's `num_experts` (ops/moe.py computes that share's part of the
routed sum), `vocab_size` rows of the vocabulary, and the heads its
config counts (`num_attention_heads_per_layer`, `num_key_value_heads`:
a share of the heads is a model with fewer heads whose `wo` adds a
partial result).  The matrices are stored in `param_dtype`: bfloat16
for the serving module (`build`), float32 masters for the training one
(`train_build`).

Without a cache the module runs a whole sequence through
`llama.default_attention` — the one route, flash kernels with a window
for long sequences — and, in training mode, returns the expert layers'
counters and chosen experts beside the logits.

The cache is by layer (`cache_spec`): a full layer keeps every position,
a sliding layer the last `sliding_window`; the engine hands each kind
its own slots, context and block tables under `cache["groups"][kind]`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields
from functools import partial
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.cache import LayerCache
from ray_tpu.models.llama import (RMSNorm, cached_attention,
                                  causal_lm_loss, default_attention)
from ray_tpu.ops import moe

FULL, SLIDING = "full_attention", "sliding_attention"


def _frozen(value):
    """Lists and dicts of a config.json as hashable tuples."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352           # the rows held here
    hidden_size: int = 3072
    intermediate_size: int = 12288     # the dense layers' width
    num_hidden_layers: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-6
    num_experts: int = 256             # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    sliding_window: int = 512
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    rope_parameters: Tuple = ()        # `_frozen` of the published group
    experts_held: Tuple[int, int] = (0, 256)
    gated: bool = True                 # the head-wise attention gate
    qk_norm: bool = False              # RMSNorm, weighted, on q and k heads
    # generation by diffusion over blocks (the `generation` group): 0 is
    # token by token
    block_length: int = 0
    denoising_steps: int = 0           # T <= B passes a block at most
    confidence_threshold: float = 0.0
    mask_token_id: int = 0
    dtype: Any = jnp.bfloat16          # activations and the KV cache
    param_dtype: Any = jnp.bfloat16    # the stored matrices

    @classmethod
    def from_dict(cls, model: Dict[str, Any]) -> "LagunaConfig":
        """The published keys (and `experts_held`) as a config.  A
        mechanism whose key the dictionary lacks is not there: no
        `gating`, no attention gate; no
        `shared_expert_intermediate_size`, no shared expert; no
        `moe_routed_scaling_factor`, factor 1; no
        `num_attention_heads_per_layer`, `num_attention_heads` on every
        layer; no `mlp_layer_types`, every layer sparse; no
        `experts_held`, all of `num_experts`; no `layer_types`, every
        layer full attention; no `rope_parameters`, the default rotary of
        the whole head at `rope_theta`; no `generation`, token by token.
        A key whose value is null is absent.  (The constructor's own
        defaults are the gated model's; `models.resolve` gives a
        dictionary the keys its `model_type`'s config class defaults.)
        Keys that say nothing of the shape (`model_type`, ...) are read
        by nobody."""
        names = {f.name for f in fields(cls)}
        layers = int(model.get("num_hidden_layers", cls.num_hidden_layers))
        absent = {
            "gated": "gating" in model,
            "shared_expert_intermediate_size": 0,
            "moe_routed_scaling_factor": 1.0,
            "mlp_layer_types": ("sparse",) * layers,
            "experts_held": (0, int(model.get("num_experts",
                                              cls.num_experts)))}
        model = {k: v for k, v in model.items() if v is not None}
        if "num_attention_heads" in model:
            absent["num_attention_heads_per_layer"] = (
                int(model["num_attention_heads"]),) * layers
        if "layer_types" not in model:
            absent["layer_types"] = (FULL,) * layers
        if "rope_parameters" not in model and "rope_theta" in model:
            absent["rope_parameters"] = _frozen({FULL: {
                "rope_type": "default", "rope_theta": model["rope_theta"]}})
        given = {k: _frozen(v) for k, v in model.items() if k in names}
        # the `generation` group's numbers are fields here; a key that is
        # not (the sampler's rule by name: `block_transfer` is the one
        # this program has) is read by nobody
        generation = {k: v for k, v in model.get("generation", {}).items()
                      if k in names}
        return cls(**{**absent, **given, **generation})

    @classmethod
    def tiny(cls) -> "LagunaConfig":
        """Test size: the five leading layer kinds, 8 experts, 4 held."""
        return cls.from_dict(dict(
            gating="per-head", moe_routed_scaling_factor=2.5,
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=256, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, sliding_window=32,
            layer_types=[FULL] + [SLIDING] * 3 + [FULL],
            mlp_layer_types=["dense"] + ["sparse"] * 4,
            num_attention_heads_per_layer=[4, 6, 6, 6, 4],
            rope_parameters={
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 500000,
                    "factor": 128, "original_max_position_embeddings": 64,
                    "beta_fast": 32, "beta_slow": 1,
                    "attention_factor": 1.4852030263919618,
                    "partial_rotary_factor": 0.5},
                "sliding_attention": {
                    "rope_type": "default", "rope_theta": 10000,
                    "partial_rotary_factor": 1}},
            experts_held=[0, 4]))

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    def rope(self, layer_type: str) -> Dict[str, Any]:
        return dict(dict(self.rope_parameters)[layer_type])

    def cache_spec(self) -> Tuple[LayerCache, ...]:
        return tuple(
            LayerCache("window" if t == SLIDING else "full",
                       self.sliding_window if t == SLIDING else 0,
                       self.num_key_value_heads, self.head_dim)
            for t in self.layer_types)

    def share(self) -> Dict[str, Any]:
        """What of each layer this chip holds (`device_report`)."""
        return {"experts_held": list(self.experts_held),
                "num_experts": self.num_experts,
                "vocab_rows": self.vocab_size}

    @classmethod
    def tiny_blocks(cls) -> "LagunaConfig":
        """Test size of the block-diffusion setting: 3 full layers, q/k
        norms, every one of 8 experts held, blocks of 4; float32, so
        that a test's comparison is of the mechanism."""
        return cls.from_dict(dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=8, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=256, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            rope_theta=1000000, qk_norm=True, sliding_window=None,
            generation={"block_length": 4, "denoising_steps": 4,
                        "confidence_threshold": 0.9, "mask_token_id": 255},
            dtype=jnp.float32, param_dtype=jnp.float32))

    @classmethod
    def tiny_ungated(cls) -> "LagunaConfig":
        """Test size of the other setting: two periods of sliding x3 +
        full, no gate, no shared expert, one head count, 4 of 8 experts
        held, float32 masters."""
        return cls.from_dict(dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=8,
            num_attention_heads=8, num_key_value_heads=1, head_dim=16,
            max_position_embeddings=256, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            sliding_window=32,
            layer_types=([SLIDING] * 3 + [FULL]) * 2,
            rope_parameters={
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 500000,
                    "factor": 16, "original_max_position_embeddings": 64,
                    "beta_fast": 32, "beta_slow": 1,
                    "attention_factor": 1.2772588722239782},
                "sliding_attention": {
                    "rope_type": "default", "rope_theta": 500000}},
            experts_held=[0, 4], param_dtype=jnp.float32))


# ------------------------------------------------- the assumed conventions


def gate_activation(z: jax.Array) -> jax.Array:
    """assumed (1): the head-wise gate is the logistic sigmoid."""
    return jax.nn.sigmoid(z)


def router_scores(logits: jax.Array) -> jax.Array:
    """assumed (2): softmax over all experts, before the top-k."""
    return jax.nn.softmax(logits, axis=-1)


def combine_shared(shared: jax.Array, routed: jax.Array,
                   factor: float) -> jax.Array:
    """assumed (3): the shared expert is added ungated and unscaled;
    the factor multiplies the routed sum only."""
    return shared + factor * routed


def qk_normalize(q: jax.Array, k: jax.Array, norm_q=None, norm_k=None):
    """assumed (4): no normalisation of q or k — but in the setting whose
    published class has one (`qk_norm`): a weighted RMSNorm over each
    head's numbers, one weight vector a layer for q and one for k,
    BEFORE the rotation."""
    if norm_q is None:
        return q, k
    with jax.named_scope("qk_norm"):
        return norm_q(q), norm_k(k)


def block_candidates(logits: jax.Array, mask_id: int) -> jax.Array:
    """assumed (5): the mask id is never a candidate — a position
    unmasked to the mask's own id would read as masked for ever."""
    return jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                     logits.astype(jnp.float32))


def block_confidence(logits: jax.Array):
    """assumed (6): (z, c) = the argmax of the candidates and its softmax
    probability, in float32."""
    top = jnp.max(logits, axis=-1)
    c = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), c


def block_transfer(masked: jax.Array, c: jax.Array, need: jax.Array,
                   threshold: float):
    """assumed (7): the sampler's `low_confidence_dynamic` rule over a
    block [..., B] -> (the positions unmasked, those among them that the
    threshold alone allowed): among the masked positions those with c >
    threshold, and where they are fewer than `need` [...] the `need` of
    largest c instead, a tie to the lower position."""
    over = masked & (c > threshold)
    conf = jnp.where(masked, c, -jnp.inf)
    at = jnp.arange(c.shape[-1])
    ahead = (conf[..., None, :] > conf[..., :, None]) | (
        (conf[..., None, :] == conf[..., :, None])
        & (at[None, :] < at[:, None]))
    forced = masked & (jnp.sum(ahead, axis=-1) < need[..., None])
    enough = jnp.sum(over, axis=-1, keepdims=True) >= need[..., None]
    transfer = jnp.where(enough, over, forced)
    return transfer, transfer & over


def block_sample(cfg: "LagunaConfig", tokens: jax.Array, logits: jax.Array,
                 need: jax.Array):
    """One pass of the block sampler, on the device behind the model:
    `tokens` [L, B] the blocks as the pass read them, `logits` [L, B, V]
    what it made of them, `need` [L] the schedule's count for the pass.
    -> (the blocks' next state [L, B], then a lane: positions masked at
    entry, unmasked by the pass, unmasked by the threshold alone).  A
    block with no mask left comes back as it is: its pass was the
    commit, which wrote its rows for good."""
    with jax.named_scope("diffusion_sample"):
        z, c = block_confidence(block_candidates(logits, cfg.mask_token_id))
    with jax.named_scope("diffusion_transfer"):
        masked = tokens == cfg.mask_token_id
        transfer, over = block_transfer(masked, c, need,
                                        cfg.confidence_threshold)
        counts = (jnp.sum(m, axis=-1).astype(jnp.int32)
                  for m in (masked, transfer, over))
        return (jnp.where(transfer, z, tokens).astype(jnp.int32), *counts)


# ------------------------------------------------------------------ rotary


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies over `dim` rotated dimensions, as
    `transformers`' `_compute_yarn_parameters`: interpolated by 1 /
    factor, extrapolated, blended by the linear ramp between the
    correction dimensions of beta_fast and beta_slow."""

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    return ((1.0 / (factor * pos_freqs)) * (1.0 - extrapolation)
            + (1.0 / pos_freqs) * extrapolation).astype(np.float32)


def rope_tables(rope: Dict[str, Any], head_dim: int):
    """(rotated dimensions, inverse frequencies, cos/sin factor) of one
    layer kind's `rope_parameters` entry."""
    dim = int(head_dim * float(rope.get("partial_rotary_factor", 1)))
    theta = float(rope["rope_theta"])
    if rope.get("rope_type", "default") == "yarn":
        inv = yarn_inv_freq(dim, theta, float(rope["factor"]),
                            int(rope["original_max_position_embeddings"]),
                            float(rope["beta_fast"]),
                            float(rope["beta_slow"]))
        return dim, inv, float(rope["attention_factor"])
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return dim, inv.astype(np.float32), 1.0


def _rotary(x: jax.Array, positions: jax.Array, dim: int,
            inv_freq: np.ndarray, factor: float) -> jax.Array:
    """Half-split rotation of the first `dim` dimensions of x [B, S, H,
    hd]; the rest pass through."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * factor)[:, :, None, :]
    sin = (jnp.sin(angles) * factor)[:, :, None, :]
    half = dim // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:dim].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)
    if dim == x.shape[-1]:
        return out
    return jnp.concatenate([out, x[..., dim:]], axis=-1)


# ----------------------------------------------------------------- modules


class SwiGLU(nn.Module):
    cfg: LagunaConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name=name)
        gate = dense(self.width, "w1")(x)
        up = dense(self.width, "w3")(x)
        return dense(cfg.hidden_size, "w2")(nn.silu(gate) * up)


class GatedAttention(nn.Module):
    """Attention of one layer, with the head-wise gate where the config
    has one."""
    cfg: LagunaConfig
    layer: int
    page_size: int = 0
    kernel: Any = None     # a mesh's attention (train/gspmd.py)

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        kind = cfg.layer_types[self.layer]
        n_heads = cfg.num_attention_heads_per_layer[self.layer]
        window = cfg.sliding_window if kind == SLIDING else 0
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            features=feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        q = dense((n_heads, cfg.head_dim), "wq")(x)
        k = dense((cfg.num_key_value_heads, cfg.head_dim), "wk")(x)
        v = dense((cfg.num_key_value_heads, cfg.head_dim), "wv")(x)
        norms = (RMSNorm(cfg.rms_norm_eps, name="q_norm"),
                 RMSNorm(cfg.rms_norm_eps, name="k_norm")) \
            if cfg.qk_norm else ()
        q, k = qk_normalize(q, k, *norms)
        rot = rope_tables(cfg.rope(kind), cfg.head_dim)
        q = _rotary(q, positions, *rot)
        k = _rotary(k, positions, *rot)
        if cfg.gated:
            with jax.named_scope("attn_gate"):
                gate = gate_activation(
                    dense(n_heads, "attn_gate")(x).astype(jnp.float32))
        wo = nn.DenseGeneral(
            features=cfg.hidden_size, axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="wo")
        pools = None
        if cache is None:
            mask = {"block": cfg.block_length} if cfg.block_length else {}
            out = (self.kernel or default_attention)(
                q, k, v, causal=True, window=window, **mask)
        else:
            b, s = k.shape[0], k.shape[1]
            flat = cache["slots"].reshape(-1)
            pool_k = cache["k"].at[flat].set(k.reshape(b * s, *k.shape[2:]))
            pool_v = cache["v"].at[flat].set(v.reshape(b * s, *v.shape[2:]))
            pools = (pool_k, pool_v)
            if cache.get("block_tables") is not None:
                # the form a decode pass's group carries (llama.py)
                from ray_tpu.ops.paged_attention import paged_attention

                out = paged_attention(
                    q, pool_k, pool_v, cache["block_tables"],
                    cache["context_lens"], page_size=self.page_size,
                    window=window or None, starts=cache.get("starts"))
            else:
                out = cached_attention(
                    q, pool_k, pool_v, cache["ctx"], cache["ctx_pos"],
                    cache["ctx_mask"], positions, window=window or None,
                    block=cfg.block_length)
        if cfg.gated:
            with jax.named_scope("attn_gate"):
                out = (out.astype(jnp.float32) * gate[..., None]).astype(
                    cfg.dtype)
        return wo(out), pools


class ExpertLayer(nn.Module):
    """This share's part of the routed sum, plus the shared expert where
    the config has one.  `scores` is the family's score function
    (another family's expert layer is this one with its own); with
    `selection_bias` the router has a bias an expert, `moe_router_bias`,
    that moves who is chosen and not what a chosen expert weighs
    (`ops/moe.py`, `route`); with `shared_gate` the shared expert's
    output is multiplied by sigmoid(x . w), one number a token
    (`moe_shared_gate` [D, 1]: models/qwen3_next.py)."""
    cfg: Any
    scores: Any = router_scores
    selection_bias: bool = False
    shared_gate: bool = False

    @nn.compact
    def __call__(self, x, valid):
        cfg = self.cfg
        b, s, d = x.shape
        lo, hi = cfg.experts_held
        e, f = hi - lo, cfg.moe_intermediate_size
        flat = x.reshape(b * s, d)
        w_router = self.param(
            "moe_router", nn.initializers.lecun_normal(),
            (d, cfg.num_experts), cfg.param_dtype)

        def experts(shape, fan_in):
            return nn.initializers.normal(fan_in ** -0.5), shape, \
                cfg.param_dtype

        w1 = self.param("moe_experts_w1", *experts((e, d, f), d))
        w3 = self.param("moe_experts_w3", *experts((e, d, f), d))
        w2 = self.param("moe_experts_w2", *experts((e, f, d), f))
        # drawn small and not zero: zeros would hide a dropped bias
        bias = self.param("moe_router_bias", nn.initializers.normal(0.05),
                          (cfg.num_experts,), jnp.float32) \
            if self.selection_bias else None
        routed, counters = moe.moe_layer(
            flat, w_router, w1, w3, w2, top_k=cfg.num_experts_per_tok,
            held=(lo, hi), valid=valid.reshape(b * s),
            normalize=cfg.norm_topk_prob, scores=self.scores, bias=bias)
        routed = routed.reshape(b, s, d)
        if cfg.shared_expert_intermediate_size:
            shared = SwiGLU(cfg, cfg.shared_expert_intermediate_size,
                            name="moe_shared")(x).astype(jnp.float32)
            if self.shared_gate:
                with jax.named_scope("moe_shared_gate"):
                    w_gate = self.param(
                        "moe_shared_gate", nn.initializers.lecun_normal(),
                        (d, 1), cfg.param_dtype)
                    shared = shared * jax.nn.sigmoid(jnp.dot(
                        x.astype(jnp.float32), w_gate.astype(jnp.float32)))
            y = combine_shared(shared, routed,
                               cfg.moe_routed_scaling_factor)
        else:
            y = cfg.moe_routed_scaling_factor * routed
        return y.astype(cfg.dtype), counters


class LagunaBlock(nn.Module):
    cfg: LagunaConfig
    layer: int
    page_size: int = 0
    kernel: Any = None

    @nn.compact
    def __call__(self, x, positions, valid, cache=None):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x)
        a, pools = GatedAttention(cfg, self.layer, self.page_size,
                                  self.kernel, name="attn")(
            h, positions, cache)
        x = x + a
        h = RMSNorm(cfg.rms_norm_eps, name="mlp_norm")(x)
        counters = None
        if cfg.mlp_layer_types[self.layer] == "dense":
            x = x + SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
        else:
            y, counters = ExpertLayer(cfg, name="moe")(h, valid)
            x = x + y
        return x, pools, counters


class LagunaModel(nn.Module):
    """`forward(tokens, cache)`: with a cache, (logits, pools, counters
    [len(moe.COUNTERS) + 1] int32: `moe.COUNTERS` summed over the sparse
    layers, then the sparse layers passed); without, the logits of the
    whole sequence — and, where `train`, (logits, the vector named by
    `train_counters`, the chosen experts [T, k] of each sparse layer)."""
    cfg: LagunaConfig
    page_size: int = 0
    kernel: Any = None
    train: bool = False

    # names of the counter vector's entries, for the engine's stats()
    counters = tuple(f"moe_{n}_total" for n in moe.COUNTERS) \
        + ("moe_layer_passes_total", "moe_expert_slots_total")
    # and of a training step's (train/gspmd.py hands them out)
    train_counters = tuple(f"train_moe_{n}_total"
                           for n in moe.TRAIN_COUNTERS) \
        + ("train_moe_layer_passes_total",)

    @nn.compact
    def __call__(self, tokens, cache=None):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        if cache is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
            valid = jnp.ones(tokens.shape, bool)
        else:
            positions = cache["q_pos"]
            # slot 0 is the engine's garbage slot: a token written there
            # is padding and is routed to no expert
            valid = cache["groups"]["full"]["slots"] != 0
        new_k, new_v, totals, passes, routing = [], [], None, 0, []
        counted = moe.TRAIN_COUNTERS if self.train else moe.COUNTERS
        block_cls = LagunaBlock
        if self.train:
            # keep only block boundaries; a block's internals (the row
            # buffers of its expert layer among them) are recomputed
            block_cls = nn.remat(LagunaBlock, prevent_cse=True)
        for i, spec in enumerate(cfg.cache_spec()):
            layer_cache = None
            if cache is not None:
                layer_cache = {"k": cache["k"][i], "v": cache["v"][i],
                               **cache["groups"][spec.kind]}
            x, pools, counters = block_cls(
                cfg, i, self.page_size, self.kernel, name=f"layer_{i}")(
                x, positions, valid, layer_cache)
            if pools is not None:
                new_k.append(pools[0])
                new_v.append(pools[1])
            if counters is not None:
                passes += 1
                routing.append(counters["ids"])
                vec = jnp.stack([counters[n] for n in counted])
                totals = vec if totals is None else totals + vec
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        head = {}
        if self.train:
            # the loss reads float32 logits: the product of the rounded
            # operands, accumulated and left in float32
            head["dot_general"] = partial(
                jax.lax.dot_general, preferred_element_type=jnp.float32)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name="lm_head",
                          **head)(x)
        if totals is None:
            totals = jnp.zeros((len(counted),), jnp.int32)
        if cache is None:
            if not self.train:
                return logits
            return logits, jnp.concatenate([
                totals.astype(jnp.int32),
                jnp.asarray([passes], jnp.int32)]), routing
        held = cfg.experts_held[1] - cfg.experts_held[0]
        vec = jnp.concatenate([
            totals.astype(jnp.int32),
            jnp.asarray([passes, passes * held], jnp.int32)])
        return logits, {"k": new_k, "v": new_v}, vec


def build(cfg: LagunaConfig, page_size: int) -> LagunaModel:
    return LagunaModel(cfg, page_size=page_size)


# the train side of the family interface (models/__init__.py)
train_loss = causal_lm_loss


def train_build(cfg: LagunaConfig, kernel=None) -> LagunaModel:
    return LagunaModel(cfg, kernel=kernel, train=True)


def param_rules() -> Dict[str, Any]:
    """What `parallel.mesh.param_shardings` would not do by itself
    (largest dimension over fsdp): norm scales stay whole."""
    from jax.sharding import PartitionSpec as P

    return {"norm": P(None)}


def config(model: Any) -> LagunaConfig:
    """`LLMEngine(model=...)`'s value as a config: a config, the
    published keys as a dictionary, or a preset's name."""
    if isinstance(model, LagunaConfig):
        return model
    if isinstance(model, dict):
        return LagunaConfig.from_dict(model)
    return getattr(LagunaConfig, str(model))()
