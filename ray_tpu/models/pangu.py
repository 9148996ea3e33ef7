"""The latent-attention decoder family (`model_type: pangu_ultra_moe`,
and as a SETTING of it `glm_moe_dsa`, below):
attention whose cache row is ONE compressed vector a token, sandwich
norms, leading dense layers and then routed experts scored by a sigmoid
beside a shared expert.  Written from the published keys: `q_lora_rank`,
`kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`rope_theta`, `first_k_dense_replace`, `n_routed_experts`,
`num_experts_per_tok`, `norm_topk_prob`, `routed_scaling_factor`,
`n_shared_experts`, `sandwich_norm`.  For layer l, input x [S, D]:

    h   = RMSNorm_in(x)
    cq  = RMSNorm_q(h Wqa) [S, rq];  q = cq Wqb -> [S, H, dn + dr]
                                       = (q_nope, q_rope)
    ckv = h Wkva [S, r + dr] = (c, k_rope);  c = RMSNorm_kv(c)
    q_rope, k_rope rotated at rope_theta over their dr dimensions
      (k_rope is ONE head, shared by all H)
    THE CACHE ROW of the token: (c, k_rope), r + dr numbers.
    plain:     (k_nope, v) = c Wkvb -> [S, H, dn + dv]
               score_ij = (q_nope_i . k_nope_j + q_rope_i . k_rope_j)
                          / sqrt(dn + dr);  a = causal softmax(score) v
    absorbed:  q~ = q_nope Wkvb_k^T [S, H, r]
               score_ij = (q~_i . c_j + q_rope_i . k_rope_j) / sqrt(dn + dr)
               a~ = softmax(score) c [S, H, r];  a = a~ Wkvb_v
               — the same numbers, and no per-token k or v anywhere
    o = concat_heads(a) Wo
    sandwich:  x = x + RMSNorm_post_attn(o);  h' = RMSNorm_pre_mlp(x)
               x = x + RMSNorm_post_mlp(mlp(h'))
    mlp, l < first_k_dense_replace:  SwiGLU at intermediate_size
    mlp, an expert layer:  s = sigmoid(h' Wr) in float32; top-k of s;
               w = s_top / sum(s_top);  y = SwiGLU_shared(h')
               + routed_scaling_factor * sum over the chosen experts
                 HELD HERE of w_e SwiGLU_e(h')
    final RMSNorm, head over the vocabulary rows held.

Without a cache (a whole sequence: `init`, tests) the module runs the
plain form.  With the engine's cache it runs the ABSORBED form both
ways — a decode pass through the Pallas kernel over the latent pages,
a prefill pass over the gathered context in blocks
(ops/latent_attention.py has why: at the engine's chunk of 64 the
expansion of the context costs more than the wider products).

What the config does not say is one function or one line each (the
configuration file lists them under `assumed`): `router_scores`
(sigmoid, no groups, no bias term), where the two extra norms sit,
plain rotary paired half-split (`laguna._rotary`), the softmax scale,
`laguna.combine_shared` (the shared expert added unscaled).  The extra
prediction layer (`num_nextn_predict_layers`) is a drafter and not part
of this forward.

**Learned sparse attention** (`model_type: glm_moe_dsa`: the keys
`index_n_heads`, `index_head_dim`, `index_topk`, with `rope_interleave`,
`indexer_rope_interleave` and `topk_method: noaux_tc`; no
`sandwich_norm`).  The same block, and in each layer an INDEXER beside
the attention (`Indexer`):

    q^I_j = cq Wq^I_j  [S, J, dI]   from the SAME compressed query
    k^I   = LayerNorm(h Wk^I)  [S, dI]   the token's second cache part
    the first dr numbers of both rotated; w = h Ww^I / sqrt(J dI)
    I_ts  = sum_j w_tj ReLU(q^I_tj . k^I_s)      (ops/sparse_index.py)
    S_t   = the min(index_topk, t + 1) positions s <= t of largest I_ts
    the attention's softmax runs over S_t alone.

Rotary pairs are (2j, 2j + 1) (`_pairs`: the numbers are brought to the
half-split order first, queries and keys alike, which leaves every
product as it is).  The router chooses by score + `moe_router_bias` and
weighs by the score (`laguna.ExpertLayer(selection_bias=)`).  With the
cache, a pass whose context bucket is no wider than `index_topk` is the
dense pass above, unchanged, but for the index key it writes; a wider
prefill pass scores its lanes' index rows, takes each query's threshold
and hands both to the chunk kernel, which masks what was not selected;
a wider decode pass takes each lane's threshold too and walks the lane's
own pages under that mask (ops/sparse_decode.py) while its table has no
more pages than `index_topk` — a copy out of the pool costs its issue up
to a page — and past that takes each lane's `index_topk` positions,
gathers their latent rows and runs the decode kernel over those.  What the
published code does besides (a Hadamard rotation of q^I and k^I, there
for its float8 index cache) is left out: an orthogonal map of both
leaves the products as they are, and the cache here is bfloat16.

This chip may hold a share of a layer, as the Laguna family's does:
`experts_held` of the router's `n_routed_experts`, `vocab_size` rows.
The expert layer, SwiGLU and the rotary helper are that family's
(models/laguna.py), RMSNorm is Llama's.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.cache import (IndexedLatentCache, LayerCache,
                                  latent_row_width)
from ray_tpu.models.laguna import ExpertLayer, SwiGLU, _rotary
from ray_tpu.models.llama import RMSNorm
from ray_tpu.ops import moe


@dataclass(frozen=True)
class PanguConfig:
    vocab_size: int = 153600           # the rows held here
    hidden_size: int = 7680
    intermediate_size: int = 18432     # the dense layers' width
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    first_k_dense_replace: int = 3
    n_routed_experts: int = 256        # the router's width
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_shared_experts: int = 1
    sandwich_norm: bool = True
    # learned sparse attention: 0 heads, no indexer
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    rope_interleave: bool = False          # rotary pairs (2j, 2j + 1)
    indexer_rope_interleave: bool = False
    topk_method: str = ""                  # "noaux_tc": a selection bias
    experts_held: Tuple[int, int] = (0, 256)
    dtype: Any = jnp.bfloat16          # activations and the cache
    param_dtype: Any = jnp.bfloat16    # the stored matrices

    @classmethod
    def from_dict(cls, model: Dict[str, Any]) -> "PanguConfig":
        """The published keys (and `experts_held`) as a config.  A
        mechanism whose key the dictionary lacks is not there: no
        `sandwich_norm`, no norm after a sublayer; no
        `n_shared_experts`, no shared expert; no
        `routed_scaling_factor`, factor 1; no `first_k_dense_replace`,
        every layer sparse; no `experts_held`, all of
        `n_routed_experts`; no `index_*`, no indexer.  `rope_theta` may
        stand inside `rope_parameters` (a `rope_type` other than
        `default` is refused).  Keys that say nothing of the shape are
        read by nobody."""
        names = {f.name for f in fields(cls)}
        absent = {"sandwich_norm": False, "n_shared_experts": 0,
                  "routed_scaling_factor": 1.0, "first_k_dense_replace": 0,
                  "experts_held": (0, int(model.get(
                      "n_routed_experts", cls.n_routed_experts)))}
        rope = dict(model.get("rope_parameters") or {})
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"rope_type {rope['rope_type']!r}: this "
                             f"family rotates plainly")
        if model.get("n_group", 1) != 1 or model.get("topk_group", 1) != 1:
            raise ValueError("a router that chooses by groups of experts")
        given = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in {**rope, **model}.items() if k in names}
        return cls(**{**absent, **given})

    @classmethod
    def tiny(cls) -> "PanguConfig":
        """Test size: a dense layer and two expert layers, 8 experts
        routed over and 4 held."""
        return cls.from_dict(dict(_TINY, rope_theta=10000.0,
                                  sandwich_norm=True))

    @classmethod
    def tiny_sparse(cls) -> "PanguConfig":
        """`tiny` as the sparse setting: an indexer of 2 heads that
        selects 32 rows, interleaved rotary pairs, a selection bias, no
        sandwich norms."""
        return cls.from_dict(dict(
            _TINY,
            rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
            index_n_heads=2, index_head_dim=16, index_topk=32,
            rope_interleave=True, indexer_rope_interleave=True,
            topk_method="noaux_tc"))

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def latent_width(self) -> int:
        """The numbers of a token's cache row: (c, k_rope)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def cache_spec(self) -> Tuple[Any, ...]:
        """Every layer keeps every position, one latent row each — and
        beside it, with an indexer, the index key."""
        layer = IndexedLatentCache("full", 0, self.latent_width,
                                   self.index_head_dim) \
            if self.index_topk else \
            LayerCache("full", 0, 0, 0, self.latent_width)
        return (layer,) * self.num_hidden_layers

    def share(self) -> Dict[str, Any]:
        """What of each layer this chip holds (`device_report`)."""
        return {"experts_held": list(self.experts_held),
                "num_experts": self.n_routed_experts,
                "vocab_rows": self.vocab_size}

    # what `laguna.ExpertLayer` and `laguna.SwiGLU` read of a config,
    # under the names that family's keys have
    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def shared_expert_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def moe_routed_scaling_factor(self) -> float:
        return self.routed_scaling_factor


# the test size's keys, shared by `tiny` and `tiny_sparse`
_TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, max_position_embeddings=256,
    first_k_dense_replace=1, n_routed_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=2.5, n_shared_experts=1,
    experts_held=[0, 4])


# ------------------------------------------------- the assumed conventions


def router_scores(logits: jax.Array) -> jax.Array:
    """assumed (1): each expert's score is the logistic sigmoid of its
    logit — no groups, no bias term (the config has no `scoring_func`,
    `n_group` or `topk_method`)."""
    return jax.nn.sigmoid(logits)


def softmax_scale(cfg: PanguConfig) -> float:
    """assumed (4): 1 / sqrt(the whole query head), no further factor."""
    return float(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def rope_inv_freq(cfg: PanguConfig) -> np.ndarray:
    """assumed (3): plain rotary at `rope_theta`, no scaling (the config
    has no `rope_scaling`)."""
    dim = cfg.qk_rope_head_dim
    return (1.0 / cfg.rope_theta ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


def _pairs(x: jax.Array, dim: int, interleaved: bool) -> jax.Array:
    """The first `dim` numbers of x's last dimension in the order
    `laguna._rotary` pairs them (i, i + dim/2): as they are, or —
    `interleaved`, pairs (2j, 2j + 1) — the even ones and then the odd.
    The same reordering of a query and of the key it meets leaves their
    product as it is."""
    if not interleaved:
        return x
    return jnp.concatenate([x[..., 0:dim:2], x[..., 1:dim:2], x[..., dim:]],
                           axis=-1)


# what the sparse setting counts on the device, a layer a pass (query x
# visible-key pairs its indexer scored; rows its queries could see and
# rows their attention read; rows its decode lanes gathered; queries
# that saw no more than `index_topk` rows; pages of index keys its
# indexer's kernel copied: those that hold a position a lane's queries
# see, of the lanes x table width a gather of the table would fetch)
SPARSE_COUNTERS = ("sparse_index_pairs_total", "sparse_rows_visible_total",
                   "sparse_rows_selected_total", "sparse_decode_rows_total",
                   "sparse_dense_queries_total",
                   "sparse_index_pages_read_total")


# ----------------------------------------------------------------- modules


def plain_attention(q_nope, q_rope, k_nope, k_rope, v, scale: float,
                    chosen=None):
    """The plain form over a whole sequence: [B, S, H, dn], [B, S, H,
    dr], [B, S, H, dn], [B, S, dr] (one head, every head's), [B, S, H,
    dv] -> [B, S, H, dv].  Scores and softmax in float32.  `chosen` [B,
    S, S]: of what a query sees, the rows its softmax runs over."""
    scores = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    s = scores.shape[-1]
    seen = jnp.tril(jnp.ones((s, s), bool))
    if chosen is not None:
        seen = seen & chosen[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    return jnp.einsum("bhst,bthv->bshv", probs.astype(v.dtype), v)


class Indexer(nn.Module):
    """The indexer's three projections: (index queries [B, S, J, dI],
    the tokens' index keys [B, S, dI], head weights [B, S, J] float32)
    from the compressed query and the layer's normed input."""
    cfg: PanguConfig

    @nn.compact
    def __call__(self, cq, h, positions):
        cfg = self.cfg
        heads, dim = cfg.index_n_heads, cfg.index_head_dim
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            features=feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        rot = (cfg.qk_rope_head_dim, rope_inv_freq(cfg), 1.0)
        pairs = (cfg.qk_rope_head_dim, cfg.indexer_rope_interleave)
        with jax.named_scope("index_q"):
            q = _rotary(_pairs(dense((heads, dim), "wq_b")(cq), *pairs),
                        positions, *rot)
        with jax.named_scope("index_k"):
            # (the bias drawn, not zeros: zeros would hide a dropped one)
            k = nn.LayerNorm(epsilon=1e-6, dtype=cfg.dtype,
                             bias_init=nn.initializers.normal(0.1),
                             name="k_norm")(dense(dim, "wk")(h))
            k = _rotary(_pairs(k, *pairs)[:, :, None], positions,
                        *rot)[:, :, 0]
            w = jnp.einsum(
                "bsd,dj->bsj", h,
                self.param("weights", nn.initializers.lecun_normal(),
                           (cfg.hidden_size, heads),
                           cfg.param_dtype).astype(cfg.dtype),
                preferred_element_type=jnp.float32) \
                * float(heads * dim) ** -0.5
        return q, k, w


class LatentAttention(nn.Module):
    cfg: PanguConfig
    page_size: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None):
        from ray_tpu.ops import latent_attention as la
        from ray_tpu.ops import sparse_index as si

        cfg = self.cfg
        heads, r = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        dense = lambda feats, name, **kw: nn.DenseGeneral(  # noqa: E731
            features=feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name, **kw)
        with jax.named_scope("latent_down"):
            cq = RMSNorm(cfg.rms_norm_eps, name="q_norm")(
                dense(cfg.q_lora_rank, "wq_a")(x))
            ckv = dense(r + dr, "wkv_a")(x)
            c = RMSNorm(cfg.rms_norm_eps, name="kv_norm")(ckv[..., :r])
        q = dense((heads, dn + dr), "wq_b")(cq)
        rot = (dr, rope_inv_freq(cfg), 1.0)
        pairs = (dr, cfg.rope_interleave)
        q_nope = q[..., :dn]
        q_rope = _rotary(_pairs(q[..., dn:], *pairs), positions, *rot)
        k_rope = _rotary(_pairs(ckv[..., None, r:], *pairs), positions,
                         *rot)[:, :, 0]
        top_k = cfg.index_topk
        if top_k:
            iq, ik, iw = Indexer(cfg, name="indexer")(cq, x, positions)
        # the expansion of a row: [r, H, dn + dv] = (Wkvb_k, Wkvb_v)
        wkv_b = self.param("wkv_b", nn.initializers.normal(r ** -0.5),
                           (r, heads, dn + dv), cfg.param_dtype)
        wkv_b = wkv_b.astype(cfg.dtype)
        wo = dense(cfg.hidden_size, "wo", axis=(-2, -1))
        scale = softmax_scale(cfg)
        if cache is None:
            kv = jnp.einsum("bsr,rhk->bshk", c, wkv_b)
            chosen = None
            if top_k and x.shape[1] > top_k:
                marks = si.plain_scores(iq, iw, ik)
                chosen = si.selected(marks,
                                     *si.select_threshold(marks, top_k))
            out = plain_attention(q_nope, q_rope, kv[..., :dn], k_rope,
                                  kv[..., dn:], scale, chosen)
            return wo(out), None, None
        # the absorbed form over the cache: the token's row goes into the
        # pool (zeros up to the pool's width, models/cache.py), every
        # head's query into the row's space
        b, s = x.shape[0], x.shape[1]
        pool = cache["latent"]
        pad = pool.shape[-1] - (r + dr)
        row = jnp.concatenate(
            [c, k_rope, jnp.zeros((b, s, pad), c.dtype)], axis=-1)
        pool = pool.at[cache["slots"].reshape(-1)].set(
            row.reshape(b * s, -1))
        with jax.named_scope("latent_absorb"):
            q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, wkv_b[..., :dn])
        q_row = jnp.concatenate(
            [q_abs, q_rope, jnp.zeros((b, s, heads, pad), q_abs.dtype)],
            axis=-1)
        ps = self.page_size
        tables, lens = cache.get("block_tables"), cache.get("context_lens")
        if top_k:
            out, index, counted = self._selected(
                cache, pool, q_row, (iq, ik, iw), positions, scale)
            pool = {"latent": pool, "index": index}
        elif tables is not None:
            out, counted = la.latent_paged_attention(
                q_row, pool, tables, lens, page_size=ps, value_width=r,
                scale=scale), None
        else:
            out, counted = la.latent_chunk_attention(
                q_row, pool, cache["ctx"], cache["ctx_pos"],
                cache["ctx_mask"], positions, page_size=ps,
                value_width=r, scale=scale), None
        with jax.named_scope("latent_unabsorb"):
            out = jnp.einsum("bshr,rhv->bshv", out, wkv_b[..., dn:])
        return wo(out), pool, counted

    def _selected(self, cache, pool, q_row, indexer, positions, scale):
        """The absorbed attention of a config with an indexer over the
        cache: (out [B, S, H, r], the index pool with this pass's keys
        written, this layer's entries of SPARSE_COUNTERS).  A pass whose
        context bucket is no wider than `index_topk` reads every row it
        sees, through the same two calls as a config with no indexer; the
        counters are the selection's, whichever way its rows are
        fetched."""
        from ray_tpu.ops import latent_attention as la
        from ray_tpu.ops import sparse_decode as sd
        from ray_tpu.ops import sparse_index as si

        iq, ik, iw = indexer
        b, s = q_row.shape[:2]
        ps, top_k, r = self.page_size, self.cfg.index_topk, \
            self.cfg.kv_lora_rank
        kernel = dict(page_size=ps, value_width=r, scale=scale)
        # the tokens' index keys go in before their queries score them
        index = cache["index"].at[cache["slots"].reshape(-1)].set(
            ik.reshape(b * s, -1))
        scored = gathered = pages = 0
        tables, lens = cache.get("block_tables"), cache.get("context_lens")
        if tables is not None:
            visible = lens[:, None]
            read = jnp.sum(jnp.minimum(lens, top_k))
            width = tables.shape[1]
            if width * ps <= top_k:
                # no lane can hold more than is selected: every row
                out = la.latent_paged_attention(q_row, pool, tables, lens,
                                                **kernel)
            else:
                with jax.named_scope("index_scores"):
                    marks = si.index_scores(iq, iw, index, tables, lens,
                                            positions, page_size=ps)
                pages = jnp.sum(si.pages_read(lens, positions, ps))
                scored, gathered = jnp.sum(visible), read
                # one softmax over the selected rows; their cheapest
                # fetch follows from the table's width, since a copy out
                # of the pool costs its issue, not its bytes, up to a page
                if width <= top_k:
                    # no more pages than the selection has rows: the
                    # lane's own pages where they lie, and the kernel
                    # masks what was not selected, as the chunk's below
                    with jax.named_scope("index_select"):
                        chosen = si.select_threshold(marks[:, 0], top_k)
                    out = sd.latent_selected_attention(
                        q_row, pool, tables, lens, marks, *chosen, **kernel)
                else:
                    # each lane's `top_k` positions, their rows gathered
                    # a lane after a lane, and the decode kernel over
                    # those
                    with jax.named_scope("index_select"):
                        at = si.select_rows(marks[:, 0], top_k)
                    with jax.named_scope("sparse_gather"):
                        pool = si.gather_rows(pool, tables, at,
                                              page_size=ps)
                    tables = jnp.arange(b * top_k // ps, dtype=jnp.int32
                                        ).reshape(b, top_k // ps)
                    out = la.latent_paged_attention(
                        q_row, pool, tables, jnp.minimum(lens, top_k),
                        **kernel)
        else:
            real = cache["slots"] != 0
            lens = cache["ctx_mask"].sum(-1)
            visible = jnp.where(
                real, jnp.minimum(positions + 1, lens[:, None]), 0)
            select, read = None, jnp.sum(visible)
            if cache["ctx"].shape[1] > top_k:
                # every query's scores of its lane's rows and its
                # threshold; the chunk kernel masks what was not selected
                with jax.named_scope("index_scores"):
                    marks = si.index_scores(
                        iq, iw, index, cache["ctx"][:, ::ps] // ps, lens,
                        positions, page_size=ps)
                with jax.named_scope("index_select"):
                    select = (marks, *si.select_threshold(marks, top_k))
                    picked = si.selected(*select) & (marks > -jnp.inf)
                scored = jnp.sum(visible)
                pages = jnp.sum(si.pages_read(lens, positions, ps))
                read = jnp.sum(jnp.where(real[..., None], picked, False),
                               dtype=jnp.int32)
            out = la.latent_chunk_attention(
                q_row, pool, cache["ctx"], cache["ctx_pos"],
                cache["ctx_mask"], positions, select=select, **kernel)
        return out, index, jnp.stack([
            jnp.asarray(v, jnp.int32) for v in
            (scored, jnp.sum(visible), read, gathered,
             jnp.sum((visible > 0) & (visible <= top_k)), pages)])


class PanguBlock(nn.Module):
    cfg: PanguConfig
    layer: int
    page_size: int = 0

    @nn.compact
    def __call__(self, x, positions, valid, cache=None):
        cfg = self.cfg

        def after(name, y):
            """A sublayer's output through its own norm, where the
            config has sandwich norms."""
            if not cfg.sandwich_norm:
                return y
            with jax.named_scope("sandwich_norm"):
                return RMSNorm(cfg.rms_norm_eps, name=name)(y)

        h = RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x)
        a, pool, sparse = LatentAttention(cfg, self.page_size, name="attn")(
            h, positions, cache)
        x = x + after("post_attn_norm", a)
        h = RMSNorm(cfg.rms_norm_eps, name="mlp_norm")(x)
        counters = None
        if self.layer < cfg.first_k_dense_replace:
            y = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
        else:
            y, counters = ExpertLayer(
                cfg, scores=router_scores,
                selection_bias=cfg.topk_method == "noaux_tc",
                name="moe")(h, valid)
        return x + after("post_mlp_norm", y), pool, counters, sparse


class PanguModel(nn.Module):
    """`forward(tokens, cache)`: with a cache, (logits, pools, counters
    — the vector `counters` names, as `LagunaModel`'s, and behind it
    with an indexer SPARSE_COUNTERS summed over the layers); without,
    the logits of the whole sequence."""
    cfg: PanguConfig
    page_size: int = 0

    counters = tuple(f"moe_{n}_total" for n in moe.COUNTERS) \
        + ("moe_layer_passes_total", "moe_expert_slots_total")

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
        pools, totals, passes, sparse_totals = [], None, 0, 0
        for i in range(cfg.num_hidden_layers):
            layer_cache = None
            if cache is not None:
                layer_cache = {"latent": cache["latent"][i],
                               **cache["groups"]["full"]}
                if cfg.index_topk:
                    layer_cache["index"] = cache["index"][i]
            x, pool, counters, sparse = PanguBlock(
                cfg, i, self.page_size, name=f"layer_{i}")(
                x, positions, valid, layer_cache)
            pools.append(pool)
            if sparse is not None:
                sparse_totals = sparse_totals + sparse
            if counters is not None:
                passes += 1
                vec = jnp.stack([counters[n] for n in moe.COUNTERS])
                totals = vec if totals is None else totals + vec
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name="lm_head")(x)
        if cache is None:
            return logits
        if totals is None:
            totals = jnp.zeros((len(moe.COUNTERS),), jnp.int32)
        held = cfg.experts_held[1] - cfg.experts_held[0]
        vec = jnp.concatenate([
            totals.astype(jnp.int32),
            jnp.asarray([passes, passes * held], jnp.int32)])
        if not cfg.index_topk:
            return logits, {"latent": pools}, vec
        return logits, {name: [p[name] for p in pools]
                        for name in ("latent", "index")}, \
            jnp.concatenate([vec, sparse_totals])


class IndexedPanguModel(PanguModel):
    """`PanguModel` of a config with an indexer: the same module, its
    counter vector named to its end."""
    counters = PanguModel.counters + SPARSE_COUNTERS


def build(cfg: PanguConfig, page_size: int) -> PanguModel:
    if cfg.index_topk:
        return IndexedPanguModel(cfg, page_size=page_size)
    return PanguModel(cfg, page_size=page_size)


def config(model: Any) -> PanguConfig:
    """`LLMEngine(model=...)`'s value as a config: a config, the
    published keys as a dictionary, or a preset's name."""
    if isinstance(model, PanguConfig):
        return model
    if isinstance(model, dict):
        return PanguConfig.from_dict(model)
    return getattr(PanguConfig, str(model))()
