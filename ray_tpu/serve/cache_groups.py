"""The cache groups of `LLMEngine`: one object a KIND of layer cache
(models/cache.py) that occurs in the model, which owns everything the
engine keeps for that kind — what a sequence holds of it, the free
lists, the arrays a pass is told of it by, its counters.  models/cache.py
describes rows and pools; this is the bookkeeping over them, on the host
(NumPy only: the engine owns the pools and runs what touches them).

The interface, the same for every group, under the engine's lock but
for the arrays and the count, which are the stepping thread's:

  max_tokens          a sequence of more tokens can never be held
  fit(total, tokens)  a plan to hold it now (taking nothing), or None;
                      `tokens`: the prompt, where a prefix may be matched
  admit(plan)         take it -> (held, tokens already there, row copy)
  advance(held, lo, hi)      before a pass with queries at [lo, hi)
  prefill_arrays(rows, lanes, cols, width), decode_arrays(rows, lanes,
  width, cols)        the kind's entry of a pass's `groups`, gathered or
                      as block tables; `rows` = [(lane, {kind: held}, lo,
                      hi)], a lane without a row is garbage (slot 0,
                      nothing to see), so `rows=[]` is a warm-up.  A
                      decode pass has `cols` = 1 query a lane, or a
                      model's whole block of them (serve/llm.py)
  count(rows, arrays, decode)    what that pass reads, into `totals`
  written(held, tokens, upto)    rows below `upto` will not change
  row_slots(held, n, take)   the slots of the rows a sequence with `n`
                      tokens ships or (`take`: taken first) receives
  release(held)       the sequence ended
  stats / gauges      under the keys `LLMEngine.stats()` has

`held` is the group's own (`_SeqPages`, a state slot's number) and stays
readable after `release`: a pass being built may still look at it.
"""

from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.models import cache as kv_cache

# prefix-index chain seed: block k's key hashes (parent key || block
# tokens), so one digest equality implies the WHOLE prefix matches
_PREFIX_SEED = b"rtpu-prefix-v1"


def _chain_hash(parent: bytes, block) -> bytes:
    h = hashlib.blake2b(parent, digest_size=16)
    for t in block:
        h.update(int(t).to_bytes(4, "little", signed=True))
    return h.digest()


class _Group:
    """What a group need not override."""

    host_span: Optional[str] = None   # `_StepClock.host` key of its arrays
    no_sharing = ""    # why a model with this group shares no prefix
    max_tokens = float("inf")

    def fit(self, total: int, tokens):
        return total   # sized for `max_batch` sequences: never waited on

    def advance(self, held, lo: int, hi: int) -> None:
        pass

    def written(self, held, tokens, upto: int) -> None:
        pass

    def gauges(self) -> Dict[str, int]:
        return {}


class _SeqPages:
    """One sequence's pages in one paged group: `pages[p]` is the
    physical page of logical page p (in a window group 0 where it holds
    none: given back, or not reached yet), `slots[i]` the slot of
    position i likewise; a window group's live pages are [first, next)."""

    __slots__ = ("pages", "slots", "first", "next")

    def __init__(self, pages, slots):
        self.pages, self.slots = pages, slots
        self.first = self.next = 0


class _Pages(_Group):
    """What the two paged kinds share: the arrays of a pass (the full
    kind's are a window kind's whose window never closes), the grid."""

    window = 0   # 0: every position is seen
    table_width = float("inf")   # no cap on a decode pass's width
    row = 0      # the numbers of the widest cache row: the decode kernel's
    # pages a step depend on them (`paged_attention.pages_per_step`)

    def ctx_width(self, cols: int):
        """The widest context a prefill pass of `cols` queries a lane
        reads of this kind: no cap on the pass's width."""
        return float("inf")

    def __init__(self, kind: str, page_size: int, num_pages: int,
                 ctx_len: int):
        self.kind, self.page_size, self.num_pages = kind, page_size, num_pages
        self.slots = num_pages * page_size   # rows of each of its pools
        self.free: List[int] = list(range(1, num_pages))  # page 0: garbage
        self._arange = np.arange(ctx_len, dtype=np.int32)
        # the steps the decode passes' kernel calls had (lanes x blocks of
        # `pages_per_step` pages of the table) and those that held a page
        self.totals = {"paged_grid_steps_total": 0,
                       "paged_grid_steps_live_total": 0}

    def used(self) -> int:
        return self.num_pages - 1 - len(self.free)

    def prefill_arrays(self, rows, lanes: int, cols: int, width: int):
        """Gathered: each lane's write slots, and as context the last
        `w` positions before its `hi` (all of them for the full kind)."""
        w = min(width, self.ctx_width(cols))
        slots = np.zeros((lanes, cols), np.int32)
        ctx = np.zeros((lanes, w), np.int32)
        ctx_pos = np.zeros((lanes, w), np.int32)
        ctx_mask = np.zeros((lanes, w), bool)
        for lane, held, lo, hi in rows:
            st = held[self.kind]
            slots[lane, :hi - lo] = st.slots[lo:hi]
            start = max(0, hi - w)
            ctx[lane, :hi - start] = st.slots[start:hi]
            ctx_pos[lane, :hi - start] = self._arange[start:hi]
            ctx_mask[lane, :hi - start] = True
        return {"slots": slots, "ctx": ctx, "ctx_pos": ctx_pos,
                "ctx_mask": ctx_mask}

    def decode_arrays(self, rows, lanes: int, width: int, cols: int = 1):
        """Block tables: the pages a lane's queries at [lo, hi) see (one
        query, or a block of `cols` that all see the rows below `hi`), at
        most `w` of them, from position `starts` on where the kind has a
        window."""
        w, ps = min(width, self.table_width), self.page_size
        slots = np.zeros((lanes, cols), np.int32)
        tables = np.zeros((lanes, w), np.int32)
        starts = np.zeros((lanes,), np.int32)
        lens = np.zeros((lanes,), np.int32)
        for lane, held, lo, hi in rows:
            st = held[self.kind]
            slots[lane, :hi - lo] = st.slots[lo:hi]
            first = max(0, hi - self.window) // ps if self.window else 0
            pages = st.pages[first:(hi - 1) // ps + 1][:w]
            tables[lane, :len(pages)] = pages
            starts[lane], lens[lane] = first * ps, hi
        out = {"slots": slots, "block_tables": tables, "context_lens": lens}
        if self.window:
            out["starts"] = starts
        return out

    def count(self, rows, arrays, decode: bool) -> None:
        if not decode:
            return
        from ray_tpu.ops.paged_attention import pages_per_step

        tokens = arrays["context_lens"] - arrays.get("starts", 0)
        lanes, width = arrays["block_tables"].shape
        pages = pages_per_step(width, self.page_size, self.row)
        self.totals["paged_grid_steps_total"] += lanes * -(-width // pages)
        self.totals["paged_grid_steps_live_total"] += \
            int((-(-tokens // (pages * self.page_size))).sum())


class FullPages(_Pages):
    """The `full` kind: a page for every position, all of a sequence's
    pages taken at admission (no mid-decode OOM, at the cost of
    reserving its worst case), refcounted (`refs[p]`: sequences whose
    table includes page p).  COPY-ON-WRITE PREFIX SHARING: `index` maps
    a chain hash over page-aligned token blocks to ONE immutable page
    holding that block's rows, entered as prefill completes it
    (`written`); a sequence whose prompt prefix matches attaches to the
    SAME pages (recycled only at refcount 0) and prefills from the first
    unshared token; a divergence MID-page copies the shared head of a
    page under the same parent chain (`_children`) into a private one —
    the row copy `admit` returns.  Shared pages are immutable: a
    sequence writes at positions >= its own `pos` only.  A latent row is
    a ROW FORM of this kind, not a kind (counted apart, `latent_*`).

    Where the model generates in BLOCKS of `block` positions (a page is
    whole blocks), a row depends on its whole block's tokens and is
    rewritten until the block commits: the engine calls `written` with
    the end of the last COMMITTED block, and a mid-page copy takes whole
    blocks only."""

    def __init__(self, spec, page_size: int, num_pages: int, ctx_len: int,
                 prefix_sharing: bool, refused: str, block: int = 1):
        super().__init__("full", page_size, num_pages, ctx_len)
        if page_size % block:
            raise ValueError(f"pages of {page_size} positions do not hold "
                             f"whole blocks of {block}")
        self.block = block
        self.row = max((layer.kv_heads * layer.head_dim for layer in spec
                        if layer.kind == "full"
                        and "k" in layer.rows()), default=0)
        # refused, not silently wrong, where another group cannot share
        self.prefix_sharing = bool(prefix_sharing) and not refused
        self.sharing_refused = refused if prefix_sharing else ""
        self.refs = [0] * num_pages
        # pages with more than one reference, kept where `refs` is
        # written (`admit`, `release`): a reference count going 1 -> 2
        # adds one, 2 -> 1 takes one away
        self.shared = 0
        self.max_tokens = (num_pages - 1) * page_size
        self.index: Dict[bytes, int] = {}
        self._children: Dict[bytes, set] = {}
        self._page_tokens: Dict[int, tuple] = {}
        self._page_keys: Dict[int, tuple] = {}
        self.totals.update(prefix_hits=0, prefix_tokens_shared=0,
                           cow_splits=0)
        # rows the passes read of layers whose row is one latent vector
        # (a lane's context, a latent layer), and the decode kernel's calls
        self._latent_layers = sum("latent" in layer.rows() for layer in spec)
        if self._latent_layers:
            self.totals.update(latent_decode_rows_total=0,
                               latent_prefill_rows_total=0,
                               latent_decode_calls_total=0)

    def fit(self, total: int, tokens):
        pages = -(-total // self.page_size)
        shared, cow = self._match(tokens) \
            if self.prefix_sharing and tokens is not None else ([], None)
        if pages - len(shared) > len(self.free):
            return None  # head-of-line waits for pages to recycle
        return pages, shared, cow

    def admit(self, plan):
        pages, shared, cow = plan
        ps, own = self.page_size, pages - len(shared)
        table = shared + self.free[:own]   # from the front of the list
        del self.free[:own]
        for p in table:
            self.refs[p] += 1   # a free page's is 0
            self.shared += self.refs[p] == 2
        bt = np.asarray(table, np.int32)
        held = _SeqPages(bt, (bt[:, None] * ps + np.arange(
            ps, dtype=np.int32)).reshape(-1))
        there, copy = len(shared) * ps, None
        if cow is not None:
            src_page, n = cow
            rows = np.arange(n, dtype=np.int32)
            copy = (rows + src_page * ps, rows + table[len(shared)] * ps)
            self.totals["cow_splits"] += 1
            there += n
        if there:
            # prefill starts at the first unshared token: the attached
            # pages already hold this prefix's rows
            self.totals["prefix_hits"] += 1
            self.totals["prefix_tokens_shared"] += there
        return held, there, copy

    def release(self, held: _SeqPages) -> None:
        """Drop one reference a page; pages reaching refcount 0 return
        to the free list and leave the prefix index (a later lookup must
        never attach to a recycled page)."""
        freed = []
        for p in held.pages.tolist():
            self.refs[p] -= 1
            self.shared -= self.refs[p] == 1
            if self.refs[p] <= 0:
                self.refs[p] = 0
                freed.append(p)
                keys = self._page_keys.pop(p, None)
                if keys is not None:
                    parent, own = keys
                    if self.index.get(own) == p:
                        del self.index[own]
                    kids = self._children.get(parent)
                    if kids is not None:
                        kids.discard(p)
                        if not kids:
                            del self._children[parent]
                self._page_tokens.pop(p, None)
        self.free.extend(freed)

    def _match(self, toks):
        """Longest shared-prefix match: (live pages whose rows cover the
        first tokens verbatim, an optional (source page, n tokens)
        mid-page extension to copy into a private page).  At least ONE
        token is left for prefill — the final prompt position's logits
        are what produce the first generated token."""
        ps = self.page_size
        limit = len(toks) - 1
        shared: List[int] = []
        if limit < 1 or not self._children:
            return shared, None
        h = _PREFIX_SEED
        p = 0
        while (p + 1) * ps <= limit:
            block = tuple(toks[p * ps:(p + 1) * ps])
            child = _chain_hash(h, block)
            page = self.index.get(child)
            # digest equality implies the whole prefix matches; the
            # token compare turns a (cosmically unlikely) hash
            # collision into a miss instead of a wrong-KV decode
            if page is None or self.refs[page] <= 0 \
                    or self._page_tokens.get(page) != block:
                break
            shared.append(page)
            h = child
            p += 1
        # mid-page extension: a registered page under the same parent
        # chain whose leading tokens match is a copy-on-write source
        cow = None
        rem = min(limit - p * ps, ps)
        if rem > 0:
            best, best_page = 0, None
            want = toks[p * ps:p * ps + rem]
            for cand in self._children.get(h, ()):
                ct = self._page_tokens.get(cand)
                if not ct or self.refs[cand] <= 0:
                    continue
                m = 0
                for a, b in zip(ct, want):
                    if a != b:
                        break
                    m += 1
                m -= m % self.block   # whole blocks: the class's text
                if m > best:
                    best, best_page = m, cand
            if best > 0:
                cow = (best_page, best)
        return shared, cow

    def written(self, held: _SeqPages, tokens, upto: int) -> None:
        """Enter into the prefix index the pages whose end `upto` (the
        sequence's `pos`) has passed, within the prefill region `tokens`
        only (decode-extended pages are private).  Idempotent: pages
        already registered (or attached FROM the index) are skipped."""
        if not self.prefix_sharing:
            return
        ps = self.page_size
        h = _PREFIX_SEED
        for p in range(min(upto, len(tokens)) // ps):
            block = tuple(tokens[p * ps:(p + 1) * ps])
            child = _chain_hash(h, block)
            page = int(held.pages[p])
            if page not in self._page_keys and self.refs[page] > 0:
                # first registration wins; an identical-content page
                # from another sequence stays unregistered (it will be
                # recycled at its own refcount 0)
                self.index.setdefault(child, page)
                self._children.setdefault(h, set()).add(page)
                self._page_tokens[page] = block
                self._page_keys[page] = (h, child)
            h = child

    def count(self, rows, arrays, decode: bool) -> None:
        super().count(rows, arrays, decode)
        if not self._latent_layers:
            return
        if decode:
            self.totals["latent_decode_rows_total"] += \
                self._latent_layers * int(arrays["context_lens"].sum())
            self.totals["latent_decode_calls_total"] += self._latent_layers
        else:
            self.totals["latent_prefill_rows_total"] += \
                self._latent_layers * sum(hi for *_r, hi in rows)

    def row_slots(self, held: _SeqPages, n: int, take: bool = False):
        return held.slots[:n]   # every position

    def gauges(self) -> Dict[str, int]:
        return {"used": self.used(), "free": len(self.free),
                "shared": self.shared}

    def stats(self, pools) -> Dict[str, Any]:
        return {**self.totals,
                # what the pools of a latent row's parts take, all layers
                **{f"{part}_pool_bytes": sum(int(p.nbytes) for p in
                                             pools[part] if p is not None)
                   for part in ("latent", "index") if part in pools},
                "free_pages": len(self.free), "used_pages": self.used(),
                "kv_pages_in_use": {self.kind: self.used()},
                "shared_pages": self.shared,
                "prefix_sharing": self.prefix_sharing,
                "prefix_sharing_refused": self.sharing_refused,
                **({"latent_pages_in_use": self.used()}
                   if self._latent_layers else {})}


class WindowPages(_Pages):
    """The page group of a `window` cache kind: layers that attend over
    their last `window` positions.  A sequence holds the pages covering
    the positions its next pass can read or write — from the oldest a
    query of the pass still sees to the last it writes — and `advance`
    gives the older ones back.  The pool holds `per_seq` pages for each
    of `max_batch` sequences (`per_seq` = window + one prefill chunk —
    `chunk`, the widest a pass of the engine may carry a lane — in
    pages, + 2: a window and a chunk each start mid-page), so an active
    sequence always finds its next page and admission never waits on
    this group."""

    host_span = "window_arrays"
    no_sharing = ("the model has window layers, whose pages before a "
                  "prefix's end are given back")

    def __init__(self, kind: str, window: int, page_size: int, chunk: int,
                 max_batch: int, pages_per_seq: int):
        self.window = window
        self.per_seq = min(pages_per_seq,
                           -(-(window + chunk) // page_size) + 2)
        super().__init__(kind, page_size, 1 + max_batch * self.per_seq,
                         pages_per_seq * page_size)
        self.allocated_total = 0
        self.released_total = 0
        # pages a decode step's table lists: the window may start mid-page
        self.table_width = -(-window // page_size) + 1

    def ctx_width(self, cols: int) -> int:
        """A chunk's last query sees `window` positions back, its first
        as many back from ITSELF, so window + cols - 1; in whole pages."""
        return -(-(self.window + cols) // self.page_size) * self.page_size

    def admit(self, total: int):
        pages = -(-total // self.page_size)
        return _SeqPages(np.zeros((pages,), np.int32),
                         np.zeros((pages * self.page_size,), np.int32)), \
            0, None

    def advance(self, st: _SeqPages, lo: int, hi: int) -> None:
        """The sequence's next pass has queries at positions [lo, hi)
        and writes their rows: give back the pages no query of it sees
        (all positions <= lo - window), take pages up to the one
        `hi - 1` lies on."""
        ps = self.page_size
        dead = max(0, lo - self.window + 1) // ps
        for p in range(st.first, min(dead, st.next)):
            self.free.append(int(st.pages[p]))
            st.pages[p] = 0
            self.released_total += 1
        st.first = max(st.first, dead)
        st.next = max(st.next, st.first)
        last = (hi - 1) // ps
        while st.next <= last:
            page = self.free.pop()
            self.allocated_total += 1
            st.pages[st.next] = page
            st.slots[st.next * ps:(st.next + 1) * ps] = \
                page * ps + np.arange(ps, dtype=np.int32)
            st.next += 1

    def release(self, st: _SeqPages) -> None:
        for p in range(st.first, st.next):
            self.free.append(int(st.pages[p]))
        st.pages[:] = 0
        st.first = st.next = 0

    def row_slots(self, st: _SeqPages, n: int, take: bool = False):
        """The positions the next query (at `n`) still sees."""
        if take:
            self.advance(st, n, n)
        return st.slots[max(0, n - self.window + 1):n]

    def stats(self, pools) -> Dict[str, Any]:
        return {**self.totals, "kv_pages_in_use": {self.kind: self.used()},
                "kv_window_pages_released_total": self.released_total}


class StateSlots(_Group):
    """The slots of the `state` kind (a recurrent mixer: ONE fixed-size
    row a sequence): slot 0 is the garbage slot, and `max_batch` more
    are enough, a sequence holding a place among the engine's active
    ones from admission to its end.  `held` is the slot's number; it
    follows the sequence, not the lane.  A chunk that starts its
    sequence is `fresh` and reads no state, so what a slot's last owner
    left — or a run-ahead lane-step wrote behind it — is never seen."""

    kind = "state"
    no_sharing = ("the model has state layers, and no state is kept at a "
                  "prefix's end")

    def __init__(self, spec, dtype, max_batch: int):
        self.layers = sum(layer.kind == self.kind for layer in spec)
        self.max_batch, self.slots = max_batch, 1 + max_batch
        self.free: List[int] = list(range(max_batch, 0, -1))
        self.row_bytes = kv_cache.state_row_bytes(spec, dtype)
        # state rows the passes update (a lane with tokens, a state
        # layer), and the decode kernel's calls
        self.totals = {"state_decode_rows_total": 0,
                       "state_prefill_rows_total": 0,
                       "state_decode_calls_total": 0}

    def admit(self, plan):
        return self.free.pop(), 0, None   # never empty: `_Group.fit`

    def release(self, slot: int) -> None:
        # a pass in flight may still update the slot (the run-ahead's
        # lane-step): it runs before any pass of the next owner
        self.free.append(slot)

    def prefill_arrays(self, rows, lanes: int, *_shape):
        """Each lane's slot, its valid tokens in the pass, and whether
        they start its sequence (never in a decode pass: lo > 0)."""
        slots = np.zeros((lanes,), np.int32)
        lens = np.zeros((lanes,), np.int32)
        fresh = np.zeros((lanes,), bool)
        for lane, held, lo, hi in rows:
            slots[lane], lens[lane] = held[self.kind], hi - lo
            fresh[lane] = lo == 0
        return {"slots": slots, "lens": lens, "fresh": fresh}

    decode_arrays = prefill_arrays

    def count(self, rows, arrays, decode: bool) -> None:
        pass_ = "decode" if decode else "prefill"
        self.totals[f"state_{pass_}_rows_total"] += self.layers * len(rows)
        if decode:
            self.totals["state_decode_calls_total"] += self.layers

    def row_slots(self, slot: int, n: int, take: bool = False):
        return [slot]   # the one row that stands for all `n` tokens

    def stats(self, pools) -> Dict[str, Any]:
        return {**self.totals,
                "state_slots_in_use": self.max_batch - len(self.free),
                # a row a slot, the garbage slot among them (part of
                # `kv_pool_bytes`)
                "state_pool_bytes": self.slots * self.row_bytes}


def build(spec, dtype, *, page_size: int, num_pages: int, max_batch: int,
          chunk: int, pages_per_seq: int, prefix_sharing: bool,
          block: int = 1) -> Dict[str, _Group]:
    """The groups of a model whose config states the cache `spec`, by
    kind: `full` (`num_pages` pages, the engine's page budget), then a
    window group for each window kind, then the state kind's slots.
    `block`: the positions a decode pass of the model writes together
    (`FullPages`)."""
    kinds = kv_cache.kinds_of(spec)
    if kinds.pop("full", None) is None:
        raise ValueError("a model with no full-attention layer: the "
                         "engine's page budget is the full kind's")
    # a kind that is neither `full` nor `state` is a window
    state = kinds.pop("state", None) is not None
    groups: Dict[str, _Group] = {
        kind: WindowPages(kind, window, page_size, chunk, max_batch,
                          pages_per_seq)
        for kind, window in kinds.items()}
    if state:
        groups["state"] = StateSlots(spec, dtype, max_batch)
    refused = next((g.no_sharing for g in groups.values()), "")
    return {"full": FullPages(spec, page_size, num_pages,
                              pages_per_seq * page_size, prefix_sharing,
                              refused, block), **groups}


def stats(groups, pools) -> Dict[str, Any]:
    """`LLMEngine.stats()`'s part: every group's, a total two groups
    count (the paged grid's) summed, a dictionary by kind merged."""
    out: Dict[str, Any] = {"kv_window_pages_released_total": 0}
    for group in groups.values():
        for key, value in group.stats(pools).items():
            if isinstance(value, dict):
                out.setdefault(key, {}).update(value)
            elif key.endswith("_total"):
                out[key] = out.get(key, 0) + value
            else:
                out[key] = value
    return out


def pool_bytes(groups, pools) -> Dict[str, int]:
    """`device_report()`'s part: the pools' bytes by what they hold."""
    return {"state_pool_bytes": 0,
            **{key: value for key, value in stats(groups, pools).items()
               if key.endswith("_pool_bytes")}}
