"""The hybrid linear-attention decoder family (`model_type:
olmo_hybrid`): three layers in four are gated-delta-rule mixers (Gated
DeltaNet), whose cache is ONE fixed-size state a sequence, the fourth a
full-attention layer with pages of keys and values, every KV head its
own query's (30 of 30) and no positional encoding.  Written from the
published keys: `layer_types`, `linear_num_key_heads`,
`linear_num_value_heads`, `linear_key_head_dim`,
`linear_value_head_dim`, `linear_conv_kernel_dim`,
`linear_allow_neg_eigval`, `num_attention_heads`,
`num_key_value_heads`, `intermediate_size`, `rms_norm_eps`,
`rope_parameters`, `tie_word_embeddings`.

    x = embed(tokens)
    layer l:  h = x + Norm(mixer_l(x));   x = h + Norm(mlp(h))
              (the norm on each sublayer's OUTPUT, RMSNorm with a weight)
              mlp: W_in D -> 2 x F, silu(gate) * up, W_out F -> D
    logits = lm_head(Norm(x))                              (untied)
    full attention: q, k, v = W_q x, W_k x, W_v x; RMSNorm with a weight
      over the WHOLE width of q and of k, then H heads of d; NO rotary;
      softmax(q k^T / sqrt(d)) v, causal; W_o
    linear attention (H heads; key width dk, value width dv):
      [q~ | k~ | v~] = silu(causal depthwise conv_K(W_qkv x)), no bias
      q = q~ / |q~| / sqrt(dk),  k = k~ / |k~|    a head, eps under the root
      beta = 2 sigmoid(W_b x)   (linear_allow_neg_eigval; else 1 x)
      g = -exp(A_log) softplus(W_a x + dt_bias),  alpha = exp(g)
      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
      o_t = S_t^T q_t                                      S: [dk, dv]
      y = W_o concat_h(RMSNorm_dv(o; w) * silu(W_g x))

What the family's config could say and this module does not write is
refused by its key: biases, a tied head, rotary positions
(`rope_parameters.rope_theta` other than null), another activation,
value heads that are not the key heads' number, an odd number of them
(the state pool holds them in pairs).

The cache by layer (`cache_spec`): a `linear_attention` layer is of kind
`state` (models/cache.py) — `conv`, the last K - 1 inputs of the
convolution over the q, k and v channels side by side, and `ssm`, S of
every head in float32 in the PAIRED layout of `ops/delta_rule.py`,
`(heads / 2, dk, 2 dv)`: 384 lanes for the published 192-wide values,
where a `(heads, dk, dv)` pool would be stored a third larger than its
shape says — a `full_attention` layer of kind `full`.  With the engine's
cache a prefill pass runs the chunk kernel from the lane's state
(`gated_delta_chunk`: the pass's 64 or 256 tokens a lane in chunks of
64), a decode pass the update kernel over the state pool in place
(`gated_delta_update`); without one (`init`, tests) the module runs the
chunk form's XLA twin over the whole sequence.  A prefill pass's full
layers attend through the block-table kernel of `ops/paged_prefill.py`
(`paged_attention_prefill`): a lane's chunk against the pages the lane
holds, read through the pass's own `ctx`, scores and running sums in VMEM
— work by the lane's rows, not by the width of the pass's bucket.

**What the config does not say** is decided HERE and listed under
`assumed` in the configuration file: the block's form and the norms'
places (OLMo 2's and 3's), the norm of q and k over the whole width,
`rope_theta: null` read as no positions, the gate after the per-head
norm, the float32 state, bfloat16 elsewhere, and how the seeded weights
are drawn — `A_log = log U(0, 16)` and `dt_bias` the inverse softplus
of a log-uniform step in (1e-3, 1e-1), the published layer's
initialisation (`granite.dt_bias_init` has why), and the embedding at
unit variance, so that the first layer's gates already depend on the
token.

The model counts on the device (`counters`): `delta_prefill_tokens_total`,
valid tokens x linear layers of a pass, and `delta_prefill_chunks_total`,
the chunk kernel's (lane, chunk) grid cells x linear layers, padding
included; a decode pass counts neither.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.cache import LayerCache, StateCache
from ray_tpu.models.granite import dt_bias_init
from ray_tpu.models.llama import (RMSNorm, _drawn_in_float32, _kernel_init,
                                  dense_attention)
from ray_tpu.ops import delta_rule, paged_prefill, ssm

LINEAR, FULL = "linear_attention", "full_attention"
CHUNK = delta_rule.CHUNK
L2_EPS = 1e-6        # under the root of q's and k's length

_embed_init = _drawn_in_float32(nn.initializers.normal(1.0))


def a_log_init(key, shape, dtype=jnp.float32):
    """A = exp(A_log) with A_log = log U(0, 16): the published layer's
    (the draw is kept off 0, whose logarithm no state survives)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0)
                   ).astype(dtype)


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    dtype: Any = jnp.bfloat16          # activations, keys, values, conv
    param_dtype: Any = jnp.bfloat16    # the stored matrices

    @classmethod
    def from_dict(cls, model: Dict[str, Any]) -> "OlmoHybridConfig":
        """The published keys as a config.  No `layer_types`: three
        linear layers, then a full one, and again.  What the family has
        and this module does not write is refused by its key."""
        unwritten = {
            "attention_bias": lambda v: not v,
            "tie_word_embeddings": lambda v: not v,
            "hidden_act": lambda v: v == "silu",
            "rope_parameters": lambda v: not v
            or v.get("rope_theta") is None,
            "rope_theta": lambda v: v is None,
            "rope_scaling": lambda v: v is None}
        for key, served in unwritten.items():
            if key in model and not served(model[key]):
                raise ValueError(
                    f"{key}: {model[key]!r} is a part of the olmo_hybrid "
                    f"family that models/olmo_hybrid.py does not write")
        names = {f.name for f in fields(cls)}
        layers = int(model.get("num_hidden_layers", cls.num_hidden_layers))
        absent = {"layer_types": tuple(
            FULL if i % 4 == 3 else LINEAR for i in range(layers))}
        given = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in model.items() if k in names}
        cfg = cls(**{**absent, **given})
        if len(cfg.layer_types) != cfg.num_hidden_layers \
                or set(cfg.layer_types) - {LINEAR, FULL}:
            raise ValueError(f"layer_types {cfg.layer_types} for "
                             f"{cfg.num_hidden_layers} layers of "
                             f"{LINEAR!r} / {FULL!r}")
        if cfg.linear_num_value_heads != cfg.linear_num_key_heads \
                or cfg.linear_num_key_heads % 2:
            raise ValueError(
                f"{cfg.linear_num_key_heads} key heads and "
                f"{cfg.linear_num_value_heads} value heads: "
                f"models/olmo_hybrid.py writes a value head a key head, "
                f"in pairs")
        if cfg.hidden_size % cfg.num_attention_heads \
                or cfg.num_attention_heads % cfg.num_key_value_heads \
                or (cfg.kv_rows != cfg.num_key_value_heads
                    != cfg.num_attention_heads):
            # (a cache row padded with heads of zeros takes a query
            # head a row: grouped queries over a padded row are not
            # written)
            raise ValueError(
                f"{cfg.num_attention_heads} heads over "
                f"{cfg.num_key_value_heads} of width {cfg.hidden_size}")
        return cfg

    @classmethod
    def tiny(cls) -> "OlmoHybridConfig":
        """Test size: (three linear layers, a full one), key heads 24
        wide and value heads 48 — neither the other's tile nor the
        chip's."""
        return cls.from_dict(dict(
            vocab_size=256, hidden_size=128, intermediate_size=256,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, linear_num_key_heads=4,
            linear_num_value_heads=4, linear_key_head_dim=24,
            linear_value_head_dim=48, linear_conv_kernel_dim=4,
            linear_allow_neg_eigval=True, max_position_embeddings=512))

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_rows(self) -> int:
        """The heads a cache row holds: the KV heads, then zeros up to
        the next multiple of 8.  The chip stores a `[slots, 30, 128]`
        pool 32 heads tall whatever its shape says, and its kernel
        compiler copies whole tiles only ("Slice shape along dimension
        2 must be aligned to tiling (8), but is 30" is what a page's
        copy out of it gets): the specification states what the memory
        holds, as a latent row's does (models/cache.py)."""
        return -(-self.num_key_value_heads // 8) * 8

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
        full = LayerCache("full", 0, self.kv_rows, self.head_dim)
        return tuple(state if t == LINEAR else full
                     for t in self.layer_types)


# ----------------------------------------------------------------- modules


def _dense(cfg, feats, name, **kw):
    return nn.DenseGeneral(features=feats, use_bias=False, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype,
                           kernel_init=_kernel_init, name=name, **kw)


class FullAttention(nn.Module):
    cfg: OlmoHybridConfig
    page_size: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        heads, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        q = _dense(cfg, heads * d, "wq")(x)
        k = _dense(cfg, hkv * d, "wk")(x)
        v = _dense(cfg, (hkv, d), "wv")(x)
        with jax.named_scope("qk_norm"):
            q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(k)
        q = q.reshape(*q.shape[:2], heads, d)
        k = k.reshape(*k.shape[:2], hkv, d)
        wo = _dense(cfg, cfg.hidden_size, "wo", axis=(-2, -1))
        if cache is None:
            return wo(dense_attention(q, k, v)), None, None
        b, s = x.shape[0], x.shape[1]
        # the cache row holds `kv_rows` heads, zeros behind the model's
        # (`OlmoHybridConfig.kv_rows`); a query head a row, group 1
        rows = cfg.kv_rows
        widen = lambda t: jnp.pad(  # noqa: E731
            t, ((0, 0), (0, 0), (0, rows - hkv), (0, 0)))
        flat = cache["slots"].reshape(-1)
        pool_k = cache["k"].at[flat].set(widen(k).reshape(b * s, rows, d))
        pool_v = cache["v"].at[flat].set(widen(v).reshape(b * s, rows, d))
        if cache.get("block_tables") is not None:
            from ray_tpu.ops.paged_attention import paged_attention

            out = paged_attention(widen(q), pool_k, pool_v,
                                  cache["block_tables"],
                                  cache["context_lens"],
                                  page_size=self.page_size)[:, :, :heads]
        else:
            # a prefill pass: the chunk over the pages its lane holds; the
            # row's heads of zeros are copied with their page and never
            # multiplied
            out = paged_prefill.paged_prefill_attention(
                q, pool_k, pool_v, cache["ctx"], cache["ctx_mask"],
                positions, page_size=self.page_size, kv_heads=hkv)
        return wo(out), pool_k, pool_v


class GatedDeltaNet(nn.Module):
    """The mixer of this family and of every other whose config has its
    keys (models/qwen3_next.py: 16 key heads under 32 value heads — key
    head j serves value heads 2j and 2j + 1, q and k repeated to the
    value heads' number behind their normalisation — and beta in (0, 1),
    `linear_allow_neg_eigval` false)."""
    cfg: Any

    @nn.compact
    def __call__(self, x, cache=None):
        """-> (y, (the conv pool, the state pool), the pass's counts:
        (valid tokens, chunk-grid cells) or None)."""
        cfg = self.cfg
        f32 = jnp.float32
        b, s = x.shape[0], x.shape[1]
        key_heads, heads, dk, dv = (
            cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim)
        kd, vd, taps = cfg.key_dim, cfg.value_dim, cfg.linear_conv_kernel_dim
        with jax.named_scope("gdn_proj"):
            u = _dense(cfg, cfg.conv_dim, "qkv_proj")(x)
            gate = _dense(cfg, vd, "gate_proj")(x)
            # the two gates' pre-activations in float32: 60 columns
            ab = nn.Dense(2 * heads, use_bias=False, dtype=f32,
                          param_dtype=cfg.param_dtype,
                          kernel_init=_kernel_init, name="ab_proj")(x)
        conv_w = self.param(
            "conv_w", _drawn_in_float32(nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1)),
            (taps, cfg.conv_dim), cfg.param_dtype)
        a_log = self.param("a_log", a_log_init, (heads,), f32)
        dt_bias = self.param("dt_bias", dt_bias_init, (heads,), f32)
        norm_w = self.param("norm_w", nn.initializers.ones, (dv,), f32)
        out_proj = _dense(cfg, cfg.hidden_size, "out_proj")
        g = -jnp.exp(a_log) * jax.nn.softplus(ab[..., :heads] + dt_bias)
        beta = jax.nn.sigmoid(ab[..., heads:])
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        no_bias = jnp.zeros((cfg.conv_dim,), cfg.dtype)

        def split(u):
            """silu, the three parts by head, q and k to their lengths."""
            u = nn.silu(u).astype(f32)
            q = u[..., :kd].reshape(*u.shape[:2], key_heads, dk)
            k = u[..., kd:2 * kd].reshape(*u.shape[:2], key_heads, dk)
            v = u[..., 2 * kd:].reshape(*u.shape[:2], heads, dv)
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True)
                                  + L2_EPS) * dk ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True)
                                  + L2_EPS)
            if heads != key_heads:
                q, k = (jnp.repeat(t, heads // key_heads, axis=2)
                        for t in (q, k))
            return q, k, v.astype(cfg.dtype)

        counts = None
        if cache is None:
            # the whole sequence from an empty state: the XLA twin of
            # the chunk kernel over the sequence padded to whole chunks
            pad = -s % CHUNK
            lens = jnp.full((b,), s, jnp.int32)
            conv = jnp.zeros((b, taps - 1, cfg.conv_dim), cfg.dtype)
            cu, _conv = ssm.conv_chunk(u, conv, conv_w, no_bias, lens)
            q, k, v = split(cu)
            widen = lambda t: jnp.pad(  # noqa: E731
                t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            o, _s = delta_rule.gated_delta_chunk_xla(
                widen(q), widen(k), widen(v), widen(g), widen(beta),
                jnp.zeros((b, heads, dk, dv), f32))
            o = o[:, :s]
            pools = (None, None)
        else:
            slots, lens = cache["slots"], cache["lens"]
            fresh = cache["fresh"]
            conv_pool, pool = cache["conv"], cache["ssm"]
            # a chunk that starts its sequence reads no state: the
            # slot's content is its last owner's
            conv0 = jnp.where(fresh[:, None, None], 0, conv_pool[slots])
            with jax.named_scope("gdn_conv"):
                cu, conv1 = ssm.conv_chunk(u, conv0, conv_w, no_bias, lens)
                q, k, v = split(cu)
            conv_pool = conv_pool.at[slots].set(conv1)
            if cache.get("block_tables") is not None:
                o, pool = delta_rule.gated_delta_update(
                    pool, slots, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                    beta[:, 0])
                o = o[:, None]
            else:
                with jax.named_scope("gdn_chunk"):
                    s0 = jnp.where(fresh[:, None, None, None], 0.0,
                                   pool[slots])
                    valid = (jnp.arange(s)[None, :] < lens[:, None]
                             )[..., None]
                    # chunks of 64, or of the whole pass where an
                    # engine's own chunk is shorter
                    chunk = math.gcd(s, CHUNK)
                    o, s1 = delta_rule.gated_delta_chunk(
                        q, k, v, g * valid, beta * valid, s0, chunk=chunk)
                    pool = pool.at[slots].set(s1)
                counts = (jnp.sum(lens), b * (s // chunk))
            pools = (conv_pool, pool)
        with jax.named_scope("gdn_gated_norm"):
            o = o.astype(f32)
            var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            o = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) * norm_w
            y = (o.reshape(b, s, vd) * nn.silu(gate.astype(f32))
                 ).astype(cfg.dtype)
        return out_proj(y), pools, counts


class OlmoMlp(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        f = cfg.intermediate_size
        gate_up = _dense(cfg, 2 * f, "w_in")(x)
        return _dense(cfg, cfg.hidden_size, "w_out")(
            nn.silu(gate_up[..., :f]) * gate_up[..., f:])


class OlmoHybridBlock(nn.Module):
    cfg: OlmoHybridConfig
    kind: str
    page_size: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None):
        """-> (x, this layer's pools by the name of the row's part, its
        counts)."""
        cfg = self.cfg
        new, counts = {}, None
        if self.kind == LINEAR:
            y, (new["conv"], new["ssm"]), counts = GatedDeltaNet(
                cfg, name="mixer")(x, cache)
        else:
            y, new["k"], new["v"] = FullAttention(
                cfg, self.page_size, name="attn")(x, positions, cache)
        h = x + RMSNorm(cfg.rms_norm_eps, name="norm")(y)
        y = OlmoMlp(cfg, name="mlp")(h)
        return h + RMSNorm(cfg.rms_norm_eps, name="mlp_norm")(y), new, counts


class OlmoHybridModel(nn.Module):
    """`forward(tokens, cache)`: with a cache, (logits, pools, counters);
    without, the logits of the whole sequence."""
    cfg: OlmoHybridConfig
    page_size: int = 0

    counters = ("delta_prefill_tokens_total", "delta_prefill_chunks_total")

    @nn.compact
    def __call__(self, tokens, cache=None):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     embedding_init=_embed_init, name="embed")(tokens)
        positions = None if cache is None else cache["q_pos"]
        names = ("conv", "ssm", "k", "v")
        pools: Dict[str, list] = {name: [] for name in names}
        counted = jnp.zeros((2,), jnp.int32)
        for i, kind in enumerate(cfg.layer_types):
            layer_cache = None
            if cache is not None:
                full = cache["groups"]["full"]
                layer_cache = {
                    # the state kind's arrays, and the form of the pass
                    # (a decode pass carries block tables)
                    **cache["groups"]["state"],
                    "conv": cache["conv"][i], "ssm": cache["ssm"][i],
                    "block_tables": full.get("block_tables"),
                } if kind == LINEAR else {
                    "k": cache["k"][i], "v": cache["v"][i], **full}
            x, new, counts = OlmoHybridBlock(
                cfg, kind, self.page_size, name=f"layer_{i}")(
                x, positions, layer_cache)
            for name in names:
                pools[name].append(new.get(name))
            if counts is not None:
                counted = counted + jnp.stack(
                    [jnp.asarray(c, jnp.int32) for c in counts])
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        logits = _dense(cfg, cfg.vocab_size, "lm_head")(x)
        if cache is None:
            return logits
        return logits, pools, counted


def build(cfg: OlmoHybridConfig, page_size: int) -> OlmoHybridModel:
    return OlmoHybridModel(cfg, page_size=page_size)


def config(model: Any) -> OlmoHybridConfig:
    """`LLMEngine(model=...)`'s value as a config: a config, the
    published keys as a dictionary, or a preset's name."""
    if isinstance(model, OlmoHybridConfig):
        return model
    if isinstance(model, dict):
        return OlmoHybridConfig.from_dict(model)
    return getattr(OlmoHybridConfig, str(model))()
