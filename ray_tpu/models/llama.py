"""Llama-family decoder-only transformer, TPU-first.

This is the flagship model for the framework's north-star path
(Llama-3-8B under FSDP/GSPMD on a v5e pod slice; the cells: PERF.md).
The reference has no model code of its own — Train wraps user torch
models (reference: python/ray/train/torch/train_loop_utils.py) — so this
is green-field, designed for the MXU and GSPMD from the start:

  - bfloat16 activations/compute; a trainer stores fp32 params +
    optimizer state, the serving module (`build`) stores the bfloat16
    matrices the forward multiplies by (`LlamaConfig.param_dtype`)
  - GQA attention with rotary embeddings; attention runs through a
    pluggable kernel hook so the Pallas flash/ring kernels (ray_tpu/ops)
    swap in without touching the model
  - static shapes everywhere; no data-dependent Python control flow, so
    one jit trace covers the whole step
  - `llama_param_rules` gives PartitionSpecs for tp (heads / mlp hidden)
    and fsdp (everything else) so the same module runs 1-chip or pod

Incremental decoding (the LLM serving tier, serve/llm.py): the same
modules accept an optional paged KV-cache pytree (``make_kv_cache`` /
``decode_cache_args``).  The cache is PAGING-AGNOSTIC here — the model
sees flat per-layer slot pools plus precomputed write-slot and
context-gather index arrays; the serving engine owns the block tables
that map sequence positions to physical page slots.  New keys/values
are written post-rope at their absolute positions.  Chunked prefill
gathers context dense per sequence with a position mask
(``ctx_pos <= q_pos``) for causality; single-token decode carries
page-granular block tables + context lengths and goes through the
Pallas paged-attention kernel (ray_tpu/ops/paged_attention.py), which
reads used pages only — no dense gather.  The model takes the form its
cache group carries.  Both ride static shapes.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.cache import LayerCache


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False  # rematerialize each block (activation checkpointing)
    # what the matrices (q, k, v, o, w1, w2, w3, embedding, lm_head) are
    # stored in.  float32 is a trainer's: masters and moments.  The
    # forward rounds them to `dtype` before every product, so the
    # serving module (`build`) stores them in `dtype`, rounded once.
    # RMSNorm scales multiply in float32 and stay float32 either way.
    param_dtype: Any = jnp.float32

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """Test-size config: compiles in seconds on CPU."""
        return cls(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, hidden_dim=128, max_seq_len=128)

    @classmethod
    def small(cls) -> "LlamaConfig":
        """~110M params: single-chip bench size."""
        return cls(vocab_size=32000, dim=768, n_layers=12, n_heads=12,
                   n_kv_heads=4, hidden_dim=2048, max_seq_len=2048)

    @classmethod
    def bench_1b(cls) -> "LlamaConfig":
        """~600M params sized for one v5e chip's HBM with adamw fp32
        state: big enough to load the MXU (all matmul dims are multiples
        of 128), small enough that params+moments+grads fit in 16 GB."""
        return cls(vocab_size=32000, dim=1536, n_layers=20, n_heads=12,
                   n_kv_heads=4, hidden_dim=4096, max_seq_len=2048)

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls(vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, hidden_dim=14336, max_seq_len=8192)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def cache_spec(self) -> Tuple[LayerCache, ...]:
        """Every layer keeps every position (models/cache.py)."""
        return (LayerCache("full", 0, self.n_kv_heads,
                           self.head_dim),) * self.n_layers

    def num_params(self) -> int:
        embed = self.vocab_size * self.dim
        per_layer = (
            self.dim * self.n_heads * self.head_dim          # wq
            + 2 * self.dim * self.n_kv_heads * self.head_dim  # wk, wv
            + self.n_heads * self.head_dim * self.dim         # wo
            + 3 * self.dim * self.hidden_dim                  # w1, w2, w3
            + 2 * self.dim                                    # norms
        )
        return embed * 2 + per_layer * self.n_layers + self.dim


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding over the last dim. x: [B, S, H, D]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# Self-attention prefills at or above this length route through the
# Pallas flash kernel instead of materializing the [S, S] score matrix.
# Module-level so tests/benches can lower it; sequences must also be a
# multiple of the flash block (128) to qualify.
FLASH_PREFILL_MIN_SEQ = 512


def default_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, window: int = 0,
                      block: int = 0) -> jax.Array:
    """The one attention route of a whole sequence, for every family,
    serving prefill and training alike: XLA fuses the dense math well on
    its own; the Pallas flash kernels (ray_tpu/ops/flash_attention.py,
    forward and backward) replace it for long sequences
    (>= FLASH_PREFILL_MIN_SEQ, multiple of 128).  `window` > 0: a query
    sees the last `window` positions up to its own (causal only).
    `block` > 0: the mask is BLOCK-causal, a query sees every position
    of its own block of `block` and whole earlier blocks (dense math
    only: the flash kernels' mask is the causal one).
    q: [B,S,H,D], k/v: [B,S,Hkv,D]."""
    s, t = q.shape[1], k.shape[1]
    if block:
        return dense_attention(q, k, v, causal, window, block=block)
    if (causal and s == t and s >= FLASH_PREFILL_MIN_SEQ
            and s % 128 == 0):
        from ray_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, True, window=window)
    return dense_attention(q, k, v, causal, window)


def make_mesh_attention(mesh) -> Callable:
    """`default_attention` as an attention kernel for a step partitioned
    over `mesh`: batch over (dp, fsdp), heads over tp, each device
    attending over its own shard.  The partitioner cannot split a Pallas
    kernel by itself ("Mosaic kernels cannot be automatically
    partitioned"), so the flash route has to be wrapped where it is
    called; attention needs no communication along these axes."""
    from jax.sharding import PartitionSpec as P

    spec = P(("dp", "fsdp"), None, "tp", None)

    def attention(q, k, v, causal: bool = True, window: int = 0):
        return jax.shard_map(
            partial(default_attention, causal=causal, window=window),
            mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return attention


def _scaled(logits: jax.Array, d: int, scale: Optional[float]):
    """The scores by the softmax scale: 1 / sqrt(d) where a model states
    none (the division every program before `scale` was compiled with),
    else the model's own factor."""
    if scale is None:
        return logits / jnp.sqrt(d).astype(jnp.float32)
    return logits * jnp.float32(scale)


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    block: int = 0) -> jax.Array:
    """The dense softmax-attention math itself, [S, S] scores and all:
    what :func:`default_attention` runs below the flash threshold, and
    what the flash kernels are tested against."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    q = q.reshape(b, s, hkv, group, d)
    logits = jnp.einsum("bshgd,bthd->bhgst", q, k).astype(jnp.float32)
    logits = _scaled(logits, d, scale)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        if block:
            at = jnp.arange(s)
            mask = at[None, :] < (at[:, None] // block + 1) * block
        if window:
            mask = mask & ~jnp.tril(jnp.ones((s, s), dtype=bool), -window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v)
    return out.reshape(b, s, h, d)


def cached_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                     ctx: jax.Array, ctx_pos: jax.Array,
                     ctx_mask: jax.Array, q_pos: jax.Array,
                     window: Optional[int] = None,
                     scale: Optional[float] = None,
                     block: int = 0) -> jax.Array:
    """Attention over a slot-pool KV cache.

    q: [B,S,H,D] (post-rope); pool_k/pool_v: [T,Hkv,D] flat slot pools
    (already containing this call's keys/values); ctx: [B,L] physical
    slot index of each context entry (garbage entries point at slot 0);
    ctx_pos: [B,L] the token position each entry holds; ctx_mask: [B,L]
    validity; q_pos: [B,S] query positions.  Causality = position mask,
    so one kernel serves chunked prefill (S>1) and decode (S=1).  With
    ``window``, a query sees the last ``window`` positions up to its
    own only.  ``scale``: the factor on the scores where it is not
    1 / sqrt(D).  ``block`` > 0: causality by BLOCKS of that many
    positions, a query sees its whole block (whose rows this call's
    chunk wrote: a chunk ends on a block's end) and every earlier one."""
    b, s, h, d = q.shape
    hkv = pool_k.shape[1]
    group = h // hkv
    ck = pool_k[ctx.reshape(-1)].reshape(b, ctx.shape[1], hkv, d)
    cv = pool_v[ctx.reshape(-1)].reshape(b, ctx.shape[1], hkv, d)
    q5 = q.reshape(b, s, hkv, group, d)
    logits = jnp.einsum("bshgd,blhd->bhgsl", q5, ck).astype(jnp.float32)
    logits = _scaled(logits, d, scale)
    seen = q_pos if not block else (q_pos // block + 1) * block - 1
    mask = (ctx_pos[:, None, :] <= seen[:, :, None]) \
        & ctx_mask[:, None, :]                      # [B,S,L]
    if window is not None:
        mask = mask & (ctx_pos[:, None, :] > q_pos[:, :, None] - window)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bhgsl,blhd->bshgd", probs, cv)
    return out.reshape(b, s, h, d)


def _drawn_in_float32(init: Callable) -> Callable:
    """`init`, drawn in float32 and rounded once to the stored dtype:
    the matrices of a module that stores `cfg.dtype` are number for
    number a float32 master's `.astype(cfg.dtype)` (a bfloat16 draw
    would be another model), and a float32 module's are flax's own."""
    def rounded(key, shape, dtype=jnp.float32):
        return init(key, shape, jnp.float32).astype(dtype)
    return rounded


_kernel_init = _drawn_in_float32(nn.linear.default_kernel_init)
_embed_init = _drawn_in_float32(nn.linear.default_embed_init)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        out = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (out * scale).astype(x.dtype)


class Attention(nn.Module):
    cfg: LlamaConfig
    kernel: Optional[Callable] = None  # pluggable (flash/ring) attention
    page_size: int = 0  # the engine's: what the paged kernel cuts the
    # pools by.  A trainer's module has no cache and leaves it unset

    @nn.compact
    def __call__(self, x, positions, cache=None):
        cfg = self.cfg
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=_kernel_init)
        q = dense(features=(cfg.n_heads, cfg.head_dim), name="wq")(x)
        k = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wk")(x)
        v = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wv")(x)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        wo = dense(features=cfg.dim, axis=(-2, -1), name="wo")
        if cache is not None:
            # incremental path: write post-rope k/v into this layer's
            # flat slot pools, attend over the gathered context.  Slot 0
            # is the engine's designated garbage slot — inactive batch
            # lanes write there and mask it out of their context.
            b, s = k.shape[0], k.shape[1]
            flat = cache["slots"].reshape(-1)
            pool_k = cache["k"].at[flat].set(
                k.reshape(b * s, *k.shape[2:]))
            pool_v = cache["v"].at[flat].set(
                v.reshape(b * s, *v.shape[2:]))
            if cache.get("block_tables") is not None:
                # the form a decode pass's group carries: page-granular
                # block tables + context lengths, through the Pallas
                # paged kernel (one query a lane), no gather
                from ray_tpu.ops.paged_attention import paged_attention

                out = paged_attention(q, pool_k, pool_v,
                                      cache["block_tables"],
                                      cache["context_lens"],
                                      page_size=self.page_size)
            else:
                out = cached_attention(q, pool_k, pool_v, cache["ctx"],
                                       cache["ctx_pos"],
                                       cache["ctx_mask"], positions)
            return wo(out), pool_k, pool_v
        attend = self.kernel or default_attention
        return wo(attend(q, k, v))


class Mlp(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=_kernel_init)
        gate = dense(cfg.hidden_dim, name="w1")(x)
        up = dense(cfg.hidden_dim, name="w3")(x)
        return dense(cfg.dim, name="w2")(nn.silu(gate) * up)


class Block(nn.Module):
    cfg: LlamaConfig
    kernel: Optional[Callable] = None
    page_size: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None):
        attn_in = RMSNorm(self.cfg.norm_eps, name="attn_norm")(x)
        attn = Attention(self.cfg, self.kernel, self.page_size,
                         name="attn")
        if cache is not None:
            a, pool_k, pool_v = attn(attn_in, positions, cache)
            x = x + a
            x = x + Mlp(self.cfg, name="mlp")(
                RMSNorm(self.cfg.norm_eps, name="mlp_norm")(x))
            return x, pool_k, pool_v
        x = x + attn(attn_in, positions)
        x = x + Mlp(self.cfg, name="mlp")(
            RMSNorm(self.cfg.norm_eps, name="mlp_norm")(x))
        return x


def _embed(cfg: LlamaConfig) -> nn.Embed:
    return nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, embedding_init=_embed_init,
                    name="embed")


def _lm_head(cfg: LlamaConfig) -> nn.Dense:
    # bf16 matmul with fp32 accumulation: the biggest single matmul of
    # the model must ride the MXU fast path (loss math upcasts after)
    return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, kernel_init=_kernel_init,
                    name="lm_head")


class LlamaModel(nn.Module):
    cfg: LlamaConfig
    kernel: Optional[Callable] = None
    page_size: int = 0

    @nn.compact
    def __call__(self, tokens, cache=None):
        cfg = self.cfg
        x = _embed(cfg)(tokens)
        if cache is not None:
            # incremental decode/prefill over the paged KV cache: query
            # positions come from the engine, per-layer pools are
            # threaded through and returned updated.  Every layer is of
            # the cache kind "full", whose group carries the write slots
            # and EITHER gather arrays (ctx/ctx_pos/ctx_mask — chunked
            # prefill) OR page-granular block tables + context lengths
            # (decode, through the paged kernel).
            positions = cache["q_pos"]
            new_k, new_v = [], []
            for i in range(cfg.n_layers):
                layer_cache = {"k": cache["k"][i], "v": cache["v"][i],
                               **cache["groups"]["full"]}
                x, pk, pv = Block(cfg, self.kernel, self.page_size,
                                  name=f"layer_{i}")(
                    x, positions, layer_cache)
                new_k.append(pk)
                new_v.append(pv)
            x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
            return _lm_head(cfg)(x), {"k": new_k, "v": new_v}
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1]), tokens.shape)
        block_cls = Block
        if cfg.remat:
            # trade FLOPs for HBM: recompute block internals in the bwd
            # pass, keeping only block boundaries resident
            block_cls = nn.remat(Block, prevent_cse=False)
        for i in range(cfg.n_layers):
            x = block_cls(cfg, self.kernel, name=f"layer_{i}")(x, positions)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        return _lm_head(cfg)(x)


class LlamaStage(nn.Module):
    """A contiguous layer range of :class:`LlamaModel` for MPMD pipeline
    parallelism: stage 0 owns the embedding, the last stage owns the
    final norm + lm_head, and every stage owns ``layers[start:end)``.

    Submodule names match LlamaModel exactly (``embed``, ``layer_i``,
    ``final_norm``, ``lm_head``), so a full-model checkpoint slices into
    per-stage trees (see train/pipeline.py slice_params_for_stage) and
    ``llama_param_rules`` applies unchanged.  Input is tokens [B, S] for
    the first stage and activations [B, S, D] otherwise; output is
    activations for non-last stages and logits for the last.
    """

    cfg: LlamaConfig
    start: int
    end: int            # exclusive layer bound
    first: bool = False  # embed tokens
    last: bool = False   # final_norm + lm_head
    kernel: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if self.first:
            x = _embed(cfg)(x)
            seq_len = x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(seq_len),
                                         x.shape[:2])
        else:
            positions = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                         x.shape[:2])
        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(Block, prevent_cse=False)
        for i in range(self.start, self.end):
            x = block_cls(cfg, self.kernel, name=f"layer_{i}")(x, positions)
        if self.last:
            x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
            x = _lm_head(cfg)(x)
        return x


def kv_pool_bytes(cfg: LlamaConfig, num_slots: int) -> int:
    """Resident bytes of one replica's KV pools (both k and v), which
    like the serving module's matrices are held in `cfg.dtype`."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return (2 * cfg.n_layers * num_slots * cfg.n_kv_heads
            * cfg.head_dim * itemsize)


def build(cfg: LlamaConfig, page_size: int) -> LlamaModel:
    """The serving module of this family (models/__init__.py).  It keeps
    no master weights: its matrices are declared in `cfg.dtype`, what
    the forward multiplies by, and `init` draws them as a float32
    module's rounded once."""
    return LlamaModel(replace(cfg, param_dtype=cfg.dtype),
                      page_size=page_size)


def config(model: Any) -> LlamaConfig:
    """`LLMEngine(model=...)`'s value as a config: a config, its fields
    as a dictionary, or a preset's name."""
    if isinstance(model, LlamaConfig):
        return model
    if isinstance(model, dict):
        return LlamaConfig(**model)
    return getattr(LlamaConfig, str(model))()


# the train side of the family interface (models/__init__.py)


def train_build(cfg: LlamaConfig, kernel: Optional[Callable] = None
                ) -> LlamaModel:
    return LlamaModel(cfg, kernel=kernel)


def llama_param_rules() -> Dict[str, Any]:
    """PartitionSpec rules by parameter-path substring.

    tp shards head and mlp-hidden dims; fsdp shards the other big dim.
    Same layout family as the scaling-book Llama recipe.
    """
    from jax.sharding import PartitionSpec as P

    return {
        "embed": P("tp", "fsdp"),
        "wq/kernel": P("fsdp", "tp", None),
        "wk/kernel": P("fsdp", "tp", None),
        "wv/kernel": P("fsdp", "tp", None),
        "wo/kernel": P("tp", None, "fsdp"),
        "w1/kernel": P("fsdp", "tp"),
        "w3/kernel": P("fsdp", "tp"),
        "w2/kernel": P("tp", "fsdp"),
        "lm_head": P("fsdp", "tp"),
        "norm": P(None),
    }


param_rules = llama_param_rules


def causal_lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token cross entropy with shifted targets.

    Upcasts to fp32 only here — the lm_head matmul stays bf16 — and uses
    the one-hot-free formulation so no [B,S,V] one-hot materializes.
    """
    targets = tokens[:, 1:]
    logits = logits[:, :-1].astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


train_loss = causal_lm_loss
