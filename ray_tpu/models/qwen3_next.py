"""The hybrid family whose EVERY layer has both a cache row and an expert
layer (`model_type: qwen3_next`): three layers in four are
gated-delta-rule mixers (one fixed-size state a sequence), the fourth a
gated full-attention layer of few wide KV heads with partial rotary
positions, and every layer's feed-forward part is routed experts beside
a gated shared expert.  Written from the published keys:
`full_attention_interval` (or `layer_types`), `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `partial_rotary_factor`, `rope_theta`,
`linear_num_key_heads`, `linear_num_value_heads`, `linear_key_head_dim`,
`linear_value_head_dim`, `linear_conv_kernel_dim`, `num_experts`,
`num_experts_per_tok`, `moe_intermediate_size`,
`shared_expert_intermediate_size`, `norm_topk_prob`, `rms_norm_eps`.

    x = embed(tokens)
    layer l:  h = x + Mixer_l(Norm(x));   x = h + Experts(Norm(h))
    logits = lm_head(Norm(x))                              (untied)
    Norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)          float32
    full layer (16 heads, 2 KV heads of 256):
      [q | gate] = W_q x a head; k, v = W_k x, W_v x
      q, k <- Norm_256 a head (1 + w); rotary over the FIRST 64 of the
      256 dimensions (half-split inside them, theta 1e7), 192 pass
      causal softmax(q k^T / sqrt(256)) v, 8 query heads a KV head
      y = W_o (attn * sigmoid(gate)), the gate elementwise
    linear layer (Hk key heads under Hv value heads, key head j serves
    value heads 2j and 2j + 1): `olmo_hybrid.GatedDeltaNet`'s text with
      beta = sigmoid(W_b x) in (0, 1) and q, k repeated to Hv heads
      y = W_o concat_h(RMSNorm_dv(o; w) * silu(W_z x))  plain w, then gate
    experts (every layer): p = softmax(W_r x) over ALL experts in
      float32, the k largest, divided by their sum;
      routed = sum over the chosen experts HELD HERE of p_e SwiGLU_e(x)
      y = routed + sigmoid(w_g . x) SwiGLU_shared(x)

What the family's config could say and this module does not write is
refused by its key (`from_dict`): a `rope_scaling`, `mlp_only_layers`
that is not empty, `decoder_sparse_step` other than 1, a sliding window,
biases, a tied head, another activation.  `intermediate_size` (the dense
MLP no layer has) is read by nobody.

This chip may hold a share of a layer (models/laguna.py's text):
`experts_held` = (lo, hi) of the router's width — given as
`num_experts_routed_over` where `num_experts` counts the experts HELD —
and `vocab_size` rows of the vocabulary.  Nothing stands in for the
other share.

The cache by layer (`cache_spec`): a linear layer is of kind `state` in
the paired layout of `ops/delta_rule.py` — (Hv / 2, dk, 2 dv) = (16, 128,
256) as published, whole tiles — a full layer of kind `full` with a FLAT
row (`cache.FlatKVCache`: a key and a value of 2 x 256 = 512 numbers
each, where a `[slots, 2, 256]` pool is stored 8 x its shape).  The
passes are the hybrid family's: `gated_delta_chunk` / `gated_delta_update`
over the state pool, `paged_prefill_attention` / `paged_attention` over
the pages.

**What the config does not say** is decided HERE and in the two modules
this one imports, and listed under `assumed` in the configuration file:
`ZeroCentredNorm` (the published class's `1 + w`), the gates' functions
(`laguna.gate_activation`, the shared expert's sigmoid in
`laguna.ExpertLayer`), `laguna.router_scores` (softmax before the
top-k), the order norm-then-gate and the interleaved repeat of key heads
(`olmo_hybrid.GatedDeltaNet`), the float32 state, and the seeded draw:
`A_log`, `dt_bias` and the embedding as the hybrid family draws them,
norm weights `w` ~ N(0, 0.1) so that `w` read for `1 + w` shows, the q
and k norms' `w` ~ N(1, 0.1) so that the attention looks somewhere
(`_qk_norm_init`).

The model counts on the device (`counters`): the expert layers' as
Laguna's (`moe_*_total`), then the delta rule's as the hybrid family's
(`delta_prefill_*_total`).
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.cache import FlatKVCache, StateCache
from ray_tpu.models.laguna import (ExpertLayer, LagunaModel, _rotary,
                                   gate_activation, rope_tables)
from ray_tpu.models.llama import (_drawn_in_float32, _kernel_init,
                                  dense_attention)
from ray_tpu.models.olmo_hybrid import (FULL, LINEAR, GatedDeltaNet,
                                        OlmoHybridModel, _embed_init)
from ray_tpu.ops import moe, paged_prefill

_norm_init = _drawn_in_float32(nn.initializers.normal(0.1))


def _qk_norm_init(key, shape, dtype=jnp.float32):
    """w ~ N(1, 0.1) for the q and k norms of a head: q and k are then
    about TWICE unit size and the scores spread by ~4, so that seeded
    weights give an attention that looks somewhere — at the usual draw the
    scores spread by ~1, the softmax is near uniform, and a full layer
    without its norms, with every dimension rotated or without its gate
    moves no logit by a bfloat16 spacing (PERF.md section 6, PR 55: the
    reference's other readings; granite's `wq`, `wk` at 8 x the usual
    variance are the same cure)."""
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936           # the rows held here
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512             # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    experts_held: Tuple[int, int] = (0, 512)
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16          # activations, keys, values, conv
    param_dtype: Any = jnp.bfloat16    # the stored matrices

    # what `olmo_hybrid.GatedDeltaNet` and `laguna.ExpertLayer` read of a
    # config and this family's has no key for
    linear_allow_neg_eigval = False    # beta = sigmoid, in (0, 1)
    moe_routed_scaling_factor = 1.0

    @classmethod
    def from_dict(cls, model: Dict[str, Any]) -> "Qwen3NextConfig":
        """The published keys (and the share's) as a config.  No
        `layer_types`: every `full_attention_interval`-th layer is full,
        the others linear.  `num_experts_routed_over`: the router's
        width where `num_experts` counts the experts held."""
        unwritten = {
            "rope_scaling": lambda v: v is None,
            "mlp_only_layers": lambda v: not v,
            "decoder_sparse_step": lambda v: v == 1,
            "use_sliding_window": lambda v: not v,
            "sliding_window": lambda v: v is None,
            "attention_bias": lambda v: not v,
            "tie_word_embeddings": lambda v: not v,
            "hidden_act": lambda v: v == "silu"}
        for key, served in unwritten.items():
            if key in model and not served(model[key]):
                raise ValueError(
                    f"{key}: {model[key]!r} is a part of the qwen3_next "
                    f"family that models/qwen3_next.py does not write")
        names = {f.name for f in fields(cls)}
        layers = int(model.get("num_hidden_layers", cls.num_hidden_layers))
        every = int(model.get("full_attention_interval", 4))
        width = int(model.get("num_experts_routed_over",
                              model.get("num_experts", cls.num_experts)))
        absent = {"layer_types": tuple(
            FULL if i % every == every - 1 else LINEAR
            for i in range(layers)), "experts_held": (0, width)}
        given = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in model.items() if k in names}
        cfg = cls(**{**absent, **given, "num_experts": width})
        lo, hi = cfg.experts_held
        if "num_experts_routed_over" in model \
                and hi - lo != int(model["num_experts"]):
            raise ValueError(f"experts_held {cfg.experts_held} is not the "
                             f"{model['num_experts']} experts held")
        if not 0 <= lo < hi <= width:
            raise ValueError(f"experts_held {cfg.experts_held} of {width}")
        if len(cfg.layer_types) != cfg.num_hidden_layers \
                or set(cfg.layer_types) - {LINEAR, FULL}:
            raise ValueError(f"layer_types {cfg.layer_types} for "
                             f"{cfg.num_hidden_layers} layers of "
                             f"{LINEAR!r} / {FULL!r}")
        if cfg.linear_num_value_heads % cfg.linear_num_key_heads \
                or cfg.linear_num_value_heads % 2:
            raise ValueError(
                f"{cfg.linear_num_key_heads} key heads under "
                f"{cfg.linear_num_value_heads} value heads: a key head "
                f"serves whole value heads, kept in pairs")
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError(f"{cfg.num_attention_heads} heads over "
                             f"{cfg.num_key_value_heads}")
        return cfg

    @classmethod
    def tiny(cls) -> "Qwen3NextConfig":
        """Test size: one period, 2 key heads under 4 value heads, 2 KV
        heads of 32 with 8 rotated, 4 of 8 experts held; float32, so
        that a test's comparison is of the mechanism and not of which
        expert a rounding picks."""
        return cls.from_dict(dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=8, num_key_value_heads=2, head_dim=32,
            partial_rotary_factor=0.25, rope_theta=10000.0,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            num_experts=4, num_experts_routed_over=8, experts_held=[0, 4],
            num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=32,
            max_position_embeddings=512, dtype=jnp.float32,
            param_dtype=jnp.float32))

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """The convolution's channels: q, k and v side by side."""
        return 2 * self.key_dim + self.value_dim

    def cache_spec(self) -> Tuple[Any, ...]:
        state = StateCache(
            "state", 0, (self.linear_conv_kernel_dim - 1, self.conv_dim),
            (self.linear_num_value_heads // 2, self.linear_key_head_dim,
             2 * self.linear_value_head_dim))
        full = FlatKVCache("full", 0, self.num_key_value_heads,
                           self.head_dim)
        return tuple(state if t == LINEAR else full
                     for t in self.layer_types)

    def share(self) -> Dict[str, Any]:
        """What of each layer this chip holds (`device_report`)."""
        return {"experts_held": list(self.experts_held),
                "num_experts": self.num_experts,
                "vocab_rows": self.vocab_size}


# ----------------------------------------------------------------- modules


class ZeroCentredNorm(nn.Module):
    """x / sqrt(mean(x^2) + eps) * (1 + w) in float32: assumed (1), the
    published class's weight, stored about zero."""
    eps: float = 1e-6
    init: Any = _norm_init

    @nn.compact
    def __call__(self, x):
        w = self.param("w", self.init, (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + self.eps) * (1.0 + w)
                ).astype(x.dtype)


class GatedFullAttention(nn.Module):
    cfg: Qwen3NextConfig
    page_size: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        heads, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        dense = lambda feats, name, **kw: nn.DenseGeneral(  # noqa: E731
            features=feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=_kernel_init,
            name=name, **kw)
        # a head's query and its gate side by side, one map
        qg = dense((heads, 2 * d), "wq_gate")(x)
        q, gate = qg[..., :d], qg[..., d:]
        k = dense((hkv, d), "wk")(x)
        v = dense((hkv, d), "wv")(x)
        with jax.named_scope("qk_norm"):
            q = ZeroCentredNorm(cfg.rms_norm_eps, _qk_norm_init,
                                name="q_norm")(q)
            k = ZeroCentredNorm(cfg.rms_norm_eps, _qk_norm_init,
                                name="k_norm")(k)
        rot = rope_tables({"rope_theta": cfg.rope_theta,
                           "partial_rotary_factor":
                               cfg.partial_rotary_factor}, d)
        q = _rotary(q, positions, *rot)
        k = _rotary(k, positions, *rot)
        wo = dense(cfg.hidden_size, "wo", axis=(-2, -1))
        pool_k = pool_v = None
        if cache is None:
            out = dense_attention(q, k, v)
        else:
            # the flat row: a position's heads side by side
            b, s = x.shape[0], x.shape[1]
            flat = cache["slots"].reshape(-1)
            pool_k = cache["k"].at[flat].set(k.reshape(b * s, hkv * d))
            pool_v = cache["v"].at[flat].set(v.reshape(b * s, hkv * d))
            if cache.get("block_tables") is not None:
                from ray_tpu.ops.paged_attention import paged_attention

                out = paged_attention(q, pool_k, pool_v,
                                      cache["block_tables"],
                                      cache["context_lens"],
                                      page_size=self.page_size)
            else:
                out = paged_prefill.paged_prefill_attention(
                    q, pool_k, pool_v, cache["ctx"], cache["ctx_mask"],
                    positions, page_size=self.page_size, kv_heads=hkv)
        with jax.named_scope("attn_gate"):
            out = (out.astype(jnp.float32)
                   * gate_activation(gate.astype(jnp.float32))
                   ).astype(cfg.dtype)
        return wo(out), pool_k, pool_v


class Qwen3NextBlock(nn.Module):
    cfg: Qwen3NextConfig
    kind: str
    page_size: int = 0

    @nn.compact
    def __call__(self, x, positions, valid, cache=None):
        """-> (x, this layer's pools by the name of the row's part, the
        delta rule's counts or None, the expert layer's counters)."""
        cfg = self.cfg
        new, counts = {}, None
        h = ZeroCentredNorm(cfg.rms_norm_eps, name="norm")(x)
        if self.kind == LINEAR:
            y, (new["conv"], new["ssm"]), counts = GatedDeltaNet(
                cfg, name="mixer")(h, cache)
        else:
            y, new["k"], new["v"] = GatedFullAttention(
                cfg, self.page_size, name="attn")(h, positions, cache)
        x = x + y
        h = ZeroCentredNorm(cfg.rms_norm_eps, name="mlp_norm")(x)
        y, routed = ExpertLayer(cfg, shared_gate=True, name="moe")(h, valid)
        return x + y, new, counts, routed


class Qwen3NextModel(nn.Module):
    """`forward(tokens, cache)`: with a cache, (logits, pools, counters);
    without, the logits of the whole sequence."""
    cfg: Qwen3NextConfig
    page_size: int = 0

    counters = LagunaModel.counters + OlmoHybridModel.counters

    @nn.compact
    def __call__(self, tokens, cache=None):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     embedding_init=_embed_init, name="embed")(tokens)
        if cache is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
            valid = jnp.ones(tokens.shape, bool)
        else:
            positions = cache["q_pos"]
            # slot 0 is the engine's garbage slot: a token written there
            # is padding and is routed to no expert
            valid = cache["groups"]["full"]["slots"] != 0
        names = ("conv", "ssm", "k", "v")
        pools: Dict[str, list] = {name: [] for name in names}
        routed = jnp.zeros((len(moe.COUNTERS),), jnp.int32)
        delta = jnp.zeros((2,), jnp.int32)
        for i, kind in enumerate(cfg.layer_types):
            layer_cache = None
            if cache is not None:
                full = cache["groups"]["full"]
                layer_cache = {
                    **cache["groups"]["state"],
                    "conv": cache["conv"][i], "ssm": cache["ssm"][i],
                    "block_tables": full.get("block_tables"),
                } if kind == LINEAR else {
                    "k": cache["k"][i], "v": cache["v"][i], **full}
            x, new, counts, counters = Qwen3NextBlock(
                cfg, kind, self.page_size, name=f"layer_{i}")(
                x, positions, valid, layer_cache)
            for name in names:
                pools[name].append(new.get(name))
            routed = routed + jnp.stack(
                [counters[n] for n in moe.COUNTERS]).astype(jnp.int32)
            if counts is not None:
                delta = delta + jnp.stack(
                    [jnp.asarray(c, jnp.int32) for c in counts])
        x = ZeroCentredNorm(cfg.rms_norm_eps, name="final_norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype,
                          kernel_init=_kernel_init, name="lm_head")(x)
        if cache is None:
            return logits
        layers = len(cfg.layer_types)
        held = cfg.experts_held[1] - cfg.experts_held[0]
        return logits, pools, jnp.concatenate([
            routed, jnp.asarray([layers, layers * held], jnp.int32), delta])


def build(cfg: Qwen3NextConfig, page_size: int) -> Qwen3NextModel:
    return Qwen3NextModel(cfg, page_size=page_size)


def config(model: Any) -> Qwen3NextConfig:
    """`LLMEngine(model=...)`'s value as a config: a config, the
    published keys as a dictionary, or a preset's name."""
    if isinstance(model, Qwen3NextConfig):
        return model
    if isinstance(model, dict):
        return Qwen3NextConfig.from_dict(model)
    return getattr(Qwen3NextConfig, str(model))()
