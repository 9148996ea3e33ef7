"""The KV cache a model asks of the serving engine, layer by layer.

A model states, for each layer, what kind of per-sequence state the
layer keeps (`LayerCache`): a `full` layer a row for every position, a
`window` layer rows for the last `window` positions only.  The engine
(serve/llm.py) keeps one page pool and one block table a sequence for
each KIND that occurs, sized by what the kind needs, and hands every
layer the slots, context and tables of its own kind.

Beside its paging kind a layer states the FORM of its row
(`LayerCache.rows`): a key and a value of `[kv_heads, head_dim]` each, or
— `latent` > 0 — ONE vector of that many numbers from which the layer's
attention makes keys and values itself, and no second pool.  A latent
pool's rows are `latent_row_width(latent)` wide, the next multiple of
the chip's 128 lanes, zeros behind the row: the chip stores a `[slots,
576]` array in tiles of 128 lanes, 640 wide, whatever its shape says,
and its kernel compiler copies whole tiles only ("Slice shape along
dimension 2 must be aligned to tiling (128), but is 576" is what a
page's copy out of a 576-wide pool gets), so the pool's shape states
what the memory holds and a page of it is one copy.  A latent layer that
SELECTS the rows its attention reads (learned sparse attention:
models/pangu.py, `IndexedLatentCache`) keeps a SECOND PART a token, the
key its indexer scores, `index` numbers in a pool of its own, `"index"`,
beside `"latent"`: two parts of one row at the same slot, so whatever
moves a page (the copy-on-write split, page shipping) moves both.

A row of FEW WIDE heads (`FlatKVCache`: 2 KV heads of 256) keeps the key
and the value as ONE VECTOR each, `[kv_heads x head_dim]`, the heads side
by side: pools `[slots, 512]`.  The chip tiles an array's last two
dimensions, bfloat16 in tiles of 16 x 128, so a `[slots, 2, 256]` pool is
stored sixteen heads tall, 8 x the bytes its shape says (16,384 B a token
where the row is 2,048); flat, a page of 16 positions is `[16, 512]`,
whole tiles and one copy, and a head is a 256-lane slice of it.  The same
numbers at the same slot, so the paging, the copy-on-write split and page
shipping are `LayerCache`'s; the paged kernels take either form
(`ops/paged_attention.py`, `ops/paged_prefill.py`).

A third kind, `state` (`StateCache`), keeps no row a position at all:
the layer carries a recurrent state, ONE row a SEQUENCE whatever its
length, so the kind's pool has a slot a sequence (slot 0 the garbage
slot, as everywhere) and the engine hands a pass one slot a lane, not
one a token.  Its row has two parts, `"conv"` (the last inputs of the
layer's causal convolution) and `"ssm"` (the state matrix of every
head: of a Mamba-2 layer or of a gated-delta-rule layer, the kind
carries either as it stands).  A row's part states its DTYPE where that is not the model's
(`dtypes()`): the `ssm` part is float32 in a bfloat16 model, because the
pool IS the carry of the recurrence — read, multiplied and written back
every token — and a carry rounded to 8 bits every step forgets what a
float32 one keeps.

The pools themselves are paging-agnostic flat slot arrays, by the name
of the row's part (`"k"`, `"v"`; `"latent"`, `"index"`; `"conv"`,
`"ssm"`) a list
over the layers: `[slots of the layer's kind, *the part's shape]`, None
at a layer whose row has no such part.  Slot 0 of every pool is the
garbage slot that padding writes to.  Everything below goes by what a
layer holds.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Sequence, Tuple

import jax.numpy as jnp


class LayerCache(NamedTuple):
    kind: str        # "full" | "window" (a "state" layer: StateCache)
    window: int      # positions a window layer sees (0 for full)
    kv_heads: int
    head_dim: int
    latent: int = 0  # > 0: the row is one vector of this width

    def rows(self) -> Dict[str, Tuple[int, ...]]:
        """The parts of this layer's row, name -> shape."""
        if self.latent:
            return {"latent": (latent_row_width(self.latent),)}
        part = (self.kv_heads, self.head_dim)
        return {"k": part, "v": part}

    def dtypes(self) -> Dict[str, Any]:
        """The parts that are not in the model's dtype: none."""
        return {}


class FlatKVCache(NamedTuple):
    """A layer of few wide KV heads whose key and value are each stored
    as one vector of `kv_heads * head_dim` numbers (the module's text):
    the shape states what the memory holds, so `kv_pool_bytes` and the
    gauges' page bytes are real bytes.  Paged as `kind` says."""
    kind: str
    window: int
    kv_heads: int
    head_dim: int

    def rows(self) -> Dict[str, Tuple[int, ...]]:
        part = (self.kv_heads * self.head_dim,)
        return {"k": part, "v": part}

    def dtypes(self) -> Dict[str, Any]:
        return {}


class IndexedLatentCache(NamedTuple):
    """A latent layer whose attention reads only the rows an indexer
    selects: the row is the latent vector (`latent` numbers, stored as
    `LayerCache`'s) and, beside it, the indexer's key of `index`
    numbers.  Paged as `kind` says, like a `LayerCache`."""
    kind: str
    window: int
    latent: int
    index: int

    def rows(self) -> Dict[str, Tuple[int, ...]]:
        return {"latent": (latent_row_width(self.latent),),
                "index": (self.index,)}

    def dtypes(self) -> Dict[str, Any]:
        return {}


class StateCache(NamedTuple):
    """A layer that keeps one fixed-size state a sequence (`kind` is
    always "state"): `conv` = (the convolution's taps - 1, its
    channels), `ssm` = the recurrence's carry as the pool STORES it.
    For a Mamba-2 layer that is its shape, (heads, head_dim, state
    size): 128 numbers wide, whole tiles of the chip's lanes.  For a
    gated-delta-rule layer a head's carry is keys x values, (heads,
    dk, dv) = (30, 96, 192) as published, which the chip would store
    256 lanes wide; the layer states the layout it keeps instead,
    (heads / 2, dk, 2 dv): the heads in pairs side by side, the same
    numbers and no padding (ops/delta_rule.py, `pair_state`); 32 value
    heads of 128 x 128 under 16 key heads are kept the same way, (16,
    128, 256).  As with a latent row, the shape states what the memory holds, so
    `state_row_bytes` and `state_pool_bytes` are real bytes."""
    kind: str
    window: int      # 0: the kind has no positions
    conv: Tuple[int, int]
    ssm: Tuple[int, int, int]

    def rows(self) -> Dict[str, Tuple[int, ...]]:
        return {"conv": tuple(self.conv), "ssm": tuple(self.ssm)}

    def dtypes(self) -> Dict[str, Any]:
        """The recurrence's carry is float32 whatever the model's."""
        return {"ssm": jnp.float32}


def latent_row_width(latent: int, lanes: int = 128) -> int:
    """What a latent pool's row is allocated at: the row's `latent`
    numbers, then zeros up to the next multiple of the chip's lanes."""
    return -(-latent // lanes) * lanes


def state_row_bytes(spec: Sequence[Any], dtype: Any) -> int:
    """The bytes ONE sequence's state takes over all the state layers."""
    return sum(math.prod(shape) * jnp.dtype(
        layer.dtypes().get(name, dtype)).itemsize
        for layer in spec if layer.kind == "state"
        for name, shape in layer.rows().items())


def kinds_of(spec: Sequence[LayerCache]) -> Dict[str, int]:
    """The kinds that occur, in order of first occurrence -> the window
    of the kind (0 for full).  One window a kind."""
    out: Dict[str, int] = {}
    for layer in spec:
        if out.setdefault(layer.kind, layer.window) != layer.window:
            raise ValueError(f"layers of kind {layer.kind!r} with "
                             f"different windows")
    return out


def make_pools(spec: Sequence[LayerCache], slots: Dict[str, int],
               dtype: Any) -> Dict[str, Any]:
    """Zeroed pools: layer i holds `slots[spec[i].kind]` rows of each
    part of its row, in the part's own dtype where it states one."""
    rows = [layer.rows() for layer in spec]
    return {name: [jnp.zeros((slots[layer.kind], *r[name]),
                             layer.dtypes().get(name, dtype))
                   if name in r else None
                   for layer, r in zip(spec, rows)]
            for name in dict.fromkeys(n for r in rows for n in r)}


def gather_slots(pools: Dict[str, Any], kinds: Sequence[str],
                 slots: Dict[str, Any]) -> Dict[str, Any]:
    """The rows at `slots[kind]` of every layer's pools as host numpy
    arrays: the export half of KV-page shipping."""
    import numpy as np

    idx = {kind: np.asarray(s, np.int32) for kind, s in slots.items()}
    return {name: [None if p is None else np.asarray(p[idx[kind]])
                   for p, kind in zip(layer_pools, kinds)]
            for name, layer_pools in pools.items()}


def scatter_slots(pools: Dict[str, Any], kinds: Sequence[str],
                  slots: Dict[str, Any], rows: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """Write gathered rows back at `slots[kind]` (the import half).
    Returns the updated pools."""
    idx = {kind: jnp.asarray(s, jnp.int32) for kind, s in slots.items()}
    return {name: [None if p is None
                   else p.at[idx[kind]].set(jnp.asarray(r, p.dtype))
                   for p, kind, r in zip(layer_pools, kinds, rows[name])]
            for name, layer_pools in pools.items()}


def copy_slots(pools: Dict[str, Any], kinds: Sequence[str], kind: str,
               src: Any, dst: Any) -> Dict[str, Any]:
    """Copy rows `src` -> `dst` within every pool of `kind`: the
    copy-on-write split of a shared page.  Returns the updated pools."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return {name: [p.at[dst].set(p[src])
                   if p is not None and k == kind else p
                   for p, k in zip(layer_pools, kinds)]
            for name, layer_pools in pools.items()}
