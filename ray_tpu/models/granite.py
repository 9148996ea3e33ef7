"""The hybrid state-space decoder family (`model_type:
granitemoehybrid`): most layers are Mamba-2 mixers, whose cache is ONE
fixed-size state a sequence, a few are attention layers with pages of
keys and values and no positional encoding.  Written from the published
keys: `layer_types`, `mamba_n_heads`, `mamba_d_head`, `mamba_d_state`,
`mamba_n_groups`, `mamba_d_conv`, `mamba_expand`, `mamba_conv_bias`,
`shared_intermediate_size`, `num_attention_heads`,
`num_key_value_heads`, `attention_multiplier`, `embedding_multiplier`,
`residual_multiplier`, `logits_scaling`, `position_embedding_type`.

    x = embed(tokens) * embedding_multiplier
    layer l:  x = x + residual_multiplier * mixer_l(RMSNorm(x))
              x = x + residual_multiplier * mlp(RMSNorm(x))
              mlp: W_in D -> 2 x F, silu(gate) * up, W_out F -> D
    logits = RMSNorm(x) @ E^T / logits_scaling          (the tied head)
    attention: H heads over Hkv, no bias, NO rotary;
               softmax(q k^T * attention_multiplier) v
    Mamba-2 (Hm heads x P, state N, one group, conv K, d_inner = Hm P):
      [z | xBC | dt] = W_in h          d_inner | d_inner + 2 N | Hm
      xBC = silu(causal depthwise conv_K(xBC) + b);  x, B, C = split
      dt = softplus(dt + dt_bias);  A = -exp(A_log)
      head n:  H_t = exp(dt_t A_n) H_{t-1} + dt_t x_t (x) B_t
               y_t = H_t C_t + D_n x_t
      y = RMSNorm(y * silu(z)) * w over all d_inner;  out = W_out y

The family's routed experts (`num_local_experts` > 0) are not written:
a config that has them is refused by name.  Nor are rotary positions,
biases on the projections, more than one group of B and C, or an untied
head.

The cache by layer (`cache_spec`): a `mamba` layer is of kind `state`
(models/cache.py) — `conv`, its last K - 1 convolution inputs, and
`ssm`, H of every head in float32 — an `attention` layer of kind
`full`.  With the engine's cache a prefill pass runs the one-chunk form
of the recurrence from the lane's state (`ops/ssm.ssm_chunk`), a decode
pass the Pallas kernel over the state pool in place
(`ops/ssm.ssm_state_update`); without one (`init`, tests) the module
runs the same chunk form over the whole sequence, a chunk at a time.

Heads 64 wide.  The chip's kernel compiler copies whole 128-lane tiles
only ("Slice shape along dimension 3 must be aligned to tiling (128),
but is 64" is what a page's copy out of a `[slots, 8, 64]` pool gets,
and the array is stored 128 wide anyway), so where `head_dim` is 64 the
cache row holds the KV heads in PAIRS, `[kv_heads / 2, 128]`: the same
bytes and no padding.  A query is asked with zeros on the other head's
half, which leaves its scores what they were, and the half of the
output that is its own head's values is kept (`_paired_queries`,
`_own_half`): twice the kernel's arithmetic for keys it reads once
either way.

What the config does not say is listed under `assumed` in the
configuration file: the float32 state, bfloat16 elsewhere, and how the
seeded weights are drawn — `A_log` as log U(1, 16) and `dt_bias` as the
inverse softplus of a log-uniform step in (1e-3, 1e-1), Mamba-2's own
initialisation (a plain normal draw forgets in a few tokens, and no
comparison then sees a fault in the state), and the query and key
projections at eight times the usual variance, so that scores scaled by
`attention_multiplier` = 1/64 spread by about one, as a trained model's
do, and a fault in the attention shows too; the tied embedding at a
tenth of the usual deviation (`_embed_init` has why).
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.cache import LayerCache, StateCache
from ray_tpu.models.llama import (RMSNorm, _drawn_in_float32, _kernel_init,
                                  cached_attention, dense_attention)
from ray_tpu.ops import ssm

MAMBA, ATTENTION = "mamba", "attention"
CHUNK = 64   # tokens a chunk of the cache-less path (the engine's own
# chunk is its `prefill_chunk`)

# scores of unit-variance queries and keys spread by sqrt(head_dim);
# drawn at 8 x the variance they spread by 8 sqrt(head_dim), by 1 after
# the 1/64 of a 64-wide head
_qk_init = _drawn_in_float32(
    nn.initializers.variance_scaling(8.0, "fan_in", "normal"))
# the embedding is the head too, and enters multiplied by 12: at the
# usual deviation (1 / sqrt(hidden)) a position's largest logit is its
# own INPUT token's, whatever the layers did, and a comparison of picked
# tokens would see none of them; at a tenth of it the layers decide
_embed_init = _drawn_in_float32(
    nn.initializers.variance_scaling(0.01, "fan_in", "normal", out_axis=0))


@dataclass(frozen=True)
class GraniteConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    dtype: Any = jnp.bfloat16          # activations, keys, values, conv
    param_dtype: Any = jnp.bfloat16    # the stored matrices

    @classmethod
    def from_dict(cls, model: Dict[str, Any]) -> "GraniteConfig":
        """The published keys as a config.  A mechanism whose key the
        dictionary lacks is not there: no multiplier, factor 1 (no
        `attention_multiplier`: 1 / sqrt(head_dim)); no
        `mamba_conv_bias`, none; no `layer_types`, every layer a Mamba
        mixer but the last.  What the family has and this module does
        not write is refused by its key."""
        unwritten = {
            "num_local_experts": lambda v: not v,
            "position_embedding_type": lambda v: v in (None, "nope"),
            "mamba_n_groups": lambda v: v == 1,
            "mamba_proj_bias": lambda v: not v,
            "attention_bias": lambda v: not v,
            "tie_word_embeddings": lambda v: bool(v)}
        for key, served in unwritten.items():
            if key in model and not served(model[key]):
                raise ValueError(
                    f"{key}: {model[key]!r} is a part of the "
                    f"granitemoehybrid family that models/granite.py "
                    f"does not write")
        names = {f.name for f in fields(cls)}
        layers = int(model.get("num_hidden_layers", cls.num_hidden_layers))
        heads = int(model.get("num_attention_heads",
                              cls.num_attention_heads))
        hidden = int(model.get("hidden_size", cls.hidden_size))
        absent = {"attention_multiplier": float(hidden // heads) ** -0.5,
                  "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
                  "logits_scaling": 1.0, "mamba_conv_bias": False,
                  "layer_types": (MAMBA,) * (layers - 1) + (ATTENTION,)}
        given = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in model.items() if k in names}
        cfg = cls(**{**absent, **given})
        if len(cfg.layer_types) != cfg.num_hidden_layers \
                or set(cfg.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types {cfg.layer_types} for "
                             f"{cfg.num_hidden_layers} layers of "
                             f"{MAMBA!r} / {ATTENTION!r}")
        if cfg.d_inner != cfg.mamba_expand * cfg.hidden_size:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = {cfg.d_inner} is not "
                f"mamba_expand x hidden_size")
        return cfg

    @classmethod
    def tiny(cls) -> "GraniteConfig":
        """Test size: (two mixers, attention, a mixer), the attention's
        heads 64 wide in one pair as the published ones are."""
        return cls.from_dict(dict(
            vocab_size=256, hidden_size=256, shared_intermediate_size=256,
            num_hidden_layers=4,
            layer_types=[MAMBA, MAMBA, ATTENTION, MAMBA],
            num_attention_heads=4, num_key_value_heads=2,
            attention_multiplier=0.015625, embedding_multiplier=12.0,
            residual_multiplier=0.22, logits_scaling=8.0,
            mamba_n_heads=16, mamba_d_head=32, mamba_d_state=16,
            mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
            mamba_conv_bias=True, max_position_embeddings=256))

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """The convolution's channels: x, B and C side by side."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def paired(self) -> bool:
        """Whether a cache row holds the KV heads two to a 128-lane row
        (the module's text)."""
        return self.head_dim == 64 and self.num_key_value_heads % 2 == 0

    def cache_spec(self) -> Tuple[Any, ...]:
        state = StateCache(
            "state", 0, (self.mamba_d_conv - 1, self.conv_dim),
            (self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state))
        full = LayerCache("full", 0, self.num_key_value_heads // 2, 128) \
            if self.paired else \
            LayerCache("full", 0, self.num_key_value_heads, self.head_dim)
        return tuple(state if t == MAMBA else full
                     for t in self.layer_types)


# ------------------------------------------------- seeded weights (assumed)


def a_log_init(key, shape, dtype=jnp.float32):
    """A = -exp(A_log) with A_log = log U(1, 16): Mamba-2's own."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus(dt_bias) log-uniform in (1e-3, 1e-1): Mamba-2's own."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


# ----------------------------------------------------------------- modules


class GraniteAttention(nn.Module):
    cfg: GraniteConfig
    page_size: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        heads, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)

        def dense(feats, name, init=_kernel_init, **kw):
            return nn.DenseGeneral(
                features=feats, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, kernel_init=init, name=name,
                **kw)

        q = dense((heads, d), "wq", _qk_init)(x)
        k = dense((hkv, d), "wk", _qk_init)(x)
        v = dense((hkv, d), "wv")(x)
        wo = dense(cfg.hidden_size, "wo", axis=(-2, -1))
        scale = float(cfg.attention_multiplier)
        if cache is None:
            return wo(dense_attention(q, k, v, scale=scale)), None, None
        b, s = x.shape[0], x.shape[1]
        row = cache["k"].shape[1:]                  # the pool's row
        flat = cache["slots"].reshape(-1)
        pool_k = cache["k"].at[flat].set(k.reshape(b * s, *row))
        pool_v = cache["v"].at[flat].set(v.reshape(b * s, *row))
        if cfg.paired:
            q = pair_queries(q, hkv)
        if cache.get("block_tables") is not None:
            from ray_tpu.ops.paged_attention import paged_attention

            out = paged_attention(q, pool_k, pool_v, cache["block_tables"],
                                  cache["context_lens"],
                                  page_size=self.page_size, scale=scale)
        else:
            out = cached_attention(q, pool_k, pool_v, cache["ctx"],
                                   cache["ctx_pos"], cache["ctx_mask"],
                                   positions, scale=scale)
        if cfg.paired:
            out = own_half(out, hkv)
        return wo(out), pool_k, pool_v


def pair_queries(q: jax.Array, hkv: int) -> jax.Array:
    """[B, S, H, d] -> [B, S, H, 2d] for a cache whose row holds KV
    heads 2p and 2p + 1 side by side: a query of KV head j lies on half
    j % 2 of its row, zeros on the other.  H = hkv groups of G queries;
    against hkv / 2 rows the groups are of 2 G, the first G on the
    first half."""
    b, s, h, d = q.shape
    g = h // hkv
    q = q.reshape(b, s, hkv // 2, 2, g, 1, d)
    half = jnp.eye(2, dtype=q.dtype).reshape(1, 1, 1, 2, 1, 2, 1)
    return (q * half).reshape(b, s, h, 2 * d)


def own_half(out: jax.Array, hkv: int) -> jax.Array:
    """[B, S, H, 2d] -> [B, S, H, d]: of the paired row's values, the
    half of the query's own KV head (`pair_queries`)."""
    b, s, h, d2 = out.shape
    g = h // hkv
    out = out.reshape(b, s, hkv // 2, 2, g, 2, d2 // 2)
    out = jnp.stack([out[:, :, :, 0, :, 0], out[:, :, :, 1, :, 1]], axis=3)
    return out.reshape(b, s, h, d2 // 2)


class Mamba2Mixer(nn.Module):
    cfg: GraniteConfig

    @nn.compact
    def __call__(self, h, cache=None):
        cfg = self.cfg
        f32 = jnp.float32
        b, s = h.shape[0], h.shape[1]
        heads, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        di, taps = cfg.d_inner, cfg.mamba_d_conv

        def dense(feats, name):
            return nn.Dense(feats, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype,
                            kernel_init=_kernel_init, name=name)

        with jax.named_scope("ssm_in_proj"):
            zxbcdt = dense(di + cfg.conv_dim + heads, "in_proj")(h)
        z, u, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + cfg.conv_dim],
                    zxbcdt[..., di + cfg.conv_dim:])
        conv_w = self.param(
            "conv_w", _drawn_in_float32(nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1)),
            (taps, cfg.conv_dim), cfg.param_dtype)
        conv_b = self.param("conv_b", nn.initializers.zeros,
                            (cfg.conv_dim,), cfg.param_dtype) \
            if cfg.mamba_conv_bias else jnp.zeros((cfg.conv_dim,), cfg.dtype)
        a = -jnp.exp(self.param("a_log", a_log_init, (heads,), f32))
        dt_bias = self.param("dt_bias", dt_bias_init, (heads,), f32)
        d_skip = self.param("d", nn.initializers.ones, (heads,), f32)
        norm_w = self.param("norm_w", nn.initializers.ones, (di,), f32)
        out_proj = dense(cfg.hidden_size, "out_proj")
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)

        def split(u):
            u = nn.silu(u)
            return (u[..., :di].reshape(*u.shape[:2], heads, p),
                    u[..., di:di + n], u[..., di + n:])

        decode = cache is not None \
            and cache.get("block_tables") is not None
        if cache is None:
            # the whole sequence from an empty state, a chunk at a time
            conv = jnp.zeros((b, taps - 1, cfg.conv_dim), cfg.dtype)
            hs = jnp.zeros((b, heads, p, n), f32)
            ys = []
            for lo in range(0, s, CHUNK):
                hi = min(lo + CHUNK, s)
                lens = jnp.full((b,), hi - lo, jnp.int32)
                cu, conv = ssm.conv_chunk(u[:, lo:hi], conv, conv_w,
                                          conv_b, lens)
                x, bb, cc = split(cu)
                y, hs = ssm.ssm_chunk(x, dt[:, lo:hi], a, bb, cc, d_skip,
                                      hs)
                ys.append(y)
            y = jnp.concatenate(ys, axis=1)
            pools = (None, None)
        else:
            slots, lens = cache["slots"], cache["lens"]
            fresh = cache["fresh"]
            conv_pool, ssm_pool = cache["conv"], cache["ssm"]
            # a chunk that starts its sequence reads no state: the
            # slot's content is its last owner's
            conv0 = jnp.where(fresh[:, None, None], 0, conv_pool[slots])
            with jax.named_scope("ssm_conv"):
                cu, conv1 = ssm.conv_chunk(u, conv0, conv_w, conv_b, lens)
                x, bb, cc = split(cu)
            conv_pool = conv_pool.at[slots].set(conv1)
            if decode:
                y, ssm_pool = ssm.ssm_state_update(
                    ssm_pool, slots, x[:, 0], dt[:, 0], a, bb[:, 0],
                    cc[:, 0], d_skip)
                y = y[:, None]
            else:
                with jax.named_scope("ssm_chunk"):
                    h0 = jnp.where(fresh[:, None, None, None], 0.0,
                                   ssm_pool[slots])
                    valid = jnp.arange(s)[None, :] < lens[:, None]
                    y, h1 = ssm.ssm_chunk(x, dt * valid[..., None], a, bb,
                                          cc, d_skip, h0)
                    ssm_pool = ssm_pool.at[slots].set(h1)
            pools = (conv_pool, ssm_pool)
        with jax.named_scope("ssm_gated_norm"):
            y = y.reshape(b, s, di).astype(f32) * nn.silu(z.astype(f32))
            var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
            y = (y * jax.lax.rsqrt(var + cfg.rms_norm_eps)
                 * norm_w).astype(cfg.dtype)
        return out_proj(y), pools


class GraniteMlp(nn.Module):
    cfg: GraniteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        f = cfg.shared_intermediate_size
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=_kernel_init,
            name=name)
        gate_up = dense(2 * f, "w_in")(x)
        return dense(cfg.hidden_size, "w_out")(
            nn.silu(gate_up[..., :f]) * gate_up[..., f:])


class GraniteBlock(nn.Module):
    cfg: GraniteConfig
    kind: str
    page_size: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None):
        """-> (x, this layer's pools by the name of the row's part)."""
        cfg = self.cfg
        res = jnp.asarray(cfg.residual_multiplier, cfg.dtype)
        h = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        new = {}
        if self.kind == MAMBA:
            y, (new["conv"], new["ssm"]) = Mamba2Mixer(
                cfg, name="mixer")(h, cache)
        else:
            y, new["k"], new["v"] = GraniteAttention(
                cfg, self.page_size, name="attn")(h, positions, cache)
        x = x + res * y
        h = RMSNorm(cfg.rms_norm_eps, name="mlp_norm")(x)
        return x + res * GraniteMlp(cfg, name="mlp")(h), new


class GraniteModel(nn.Module):
    """`forward(tokens, cache)`: with a cache, (logits, pools); without,
    the logits of the whole sequence."""
    cfg: GraniteConfig
    page_size: int = 0

    @nn.compact
    def __call__(self, tokens, cache=None):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         embedding_init=_embed_init, name="embed")
        x = embed(tokens) * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
        positions = None if cache is None else cache["q_pos"]
        names = ("conv", "ssm", "k", "v")
        pools: Dict[str, list] = {name: [] for name in names}
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
                } if kind == MAMBA else {
                    "k": cache["k"][i], "v": cache["v"][i], **full}
            x, new = GraniteBlock(cfg, kind, self.page_size,
                                  name=f"layer_{i}")(
                x, positions, layer_cache)
            for name in names:
                pools[name].append(new.get(name))
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        logits = embed.attend(x) / jnp.asarray(cfg.logits_scaling, cfg.dtype)
        if cache is None:
            return logits
        return logits, pools


def build(cfg: GraniteConfig, page_size: int) -> GraniteModel:
    return GraniteModel(cfg, page_size=page_size)


def config(model: Any) -> GraniteConfig:
    """`LLMEngine(model=...)`'s value as a config: a config, the
    published keys as a dictionary, or a preset's name."""
    if isinstance(model, GraniteConfig):
        return model
    if isinstance(model, dict):
        return GraniteConfig.from_dict(model)
    return getattr(GraniteConfig, str(model))()
