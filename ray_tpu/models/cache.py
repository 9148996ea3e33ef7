"""The KV cache a model asks of the serving engine, layer by layer.

A model states, for each layer, what kind of per-sequence state the
layer keeps (`LayerCache`): a `full` layer a row for every position, a
`window` layer rows for the last `window` positions only.  The engine
(serve/llm.py) keeps one page pool and one block table a sequence for
each KIND that occurs, sized by what the kind needs, and hands every
layer the slots, context and tables of its own kind.

The pools themselves are paging-agnostic flat slot arrays, one `k` and
one `v` a layer: `[slots of the layer's kind, kv_heads, head_dim]`.
Slot 0 of every pool is the garbage slot that padding writes to.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Sequence

import jax.numpy as jnp


class LayerCache(NamedTuple):
    kind: str        # "full" | "window"
    window: int      # positions a window layer sees (0 for full)
    kv_heads: int
    head_dim: int


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
    """Zeroed pools: layer i holds `slots[spec[i].kind]` rows."""
    shapes = [(slots[s.kind], s.kv_heads, s.head_dim) for s in spec]
    return {"k": [jnp.zeros(shape, dtype) for shape in shapes],
            "v": [jnp.zeros(shape, dtype) for shape in shapes]}


def gather_slots(pools: Dict[str, Any], kinds: Sequence[str],
                 slots: Dict[str, Any]) -> Dict[str, Any]:
    """The rows at `slots[kind]` of every layer's pool as host numpy
    arrays: the export half of KV-page shipping."""
    import numpy as np

    idx = {kind: np.asarray(s, np.int32) for kind, s in slots.items()}
    return {name: [np.asarray(p[idx[kind]])
                   for p, kind in zip(pools[name], kinds)]
            for name in ("k", "v")}


def scatter_slots(pools: Dict[str, Any], kinds: Sequence[str],
                  slots: Dict[str, Any], rows: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """Write gathered rows back at `slots[kind]` (the import half).
    Returns the updated pools."""
    idx = {kind: jnp.asarray(s, jnp.int32) for kind, s in slots.items()}
    return {name: [p.at[idx[kind]].set(jnp.asarray(r, p.dtype))
                   for p, kind, r in zip(pools[name], kinds, rows[name])]
            for name in ("k", "v")}


def copy_slots(pools: Dict[str, Any], kinds: Sequence[str], kind: str,
               src: Any, dst: Any) -> Dict[str, Any]:
    """Copy rows `src` -> `dst` within every pool of `kind`: the
    copy-on-write split of a shared page.  Returns the updated pools."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return {name: [p.at[dst].set(p[src]) if k == kind else p
                   for p, k in zip(pools[name], kinds)]
            for name in ("k", "v")}
