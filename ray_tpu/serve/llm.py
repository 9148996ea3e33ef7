"""LLM serving tier: continuous batching over a paged KV cache.

The request/response Serve data plane re-dispatches one forward per
``@serve.batch`` flush — decode-heavy LLM traffic pays a dispatch per
token step and the accelerator idles between batches.  This module is
the resident-program alternative (the Gemma-on-TPU serving shape): a
replica hosts ONE :class:`LLMEngine` whose decode loop is pinned to an
exec thread through the compiled-DAG dispatch branch
(``__rt_dag_llm_loop__`` in worker.py) and never re-dispatches.  New
sequences are admitted into the running batch at token boundaries
(continuous batching), every sequence owns pages in a paged KV cache
(block-table indexed, recycled on EOS/cancel/disconnect), long prompts
prefill in chunks so they cannot stall in-flight decodes, and generated
tokens stream out per sequence through the existing
``stream_async`` -> SSE path.

Request contract (token-level; tokenization is the client's concern):
  {"tokens": [int, ...],        # prompt token ids
   "max_new_tokens": int,       # decode budget (>= 1)
   "eos": int | None,           # optional stop token
   "request_id": str | None,    # idempotency key: a retried request
                                # re-attaches to the live sequence
   "emit_from": int | None,     # first generation index to emit —
                                # the resume cursor for proxy retries
   "deadline_ms": float | None} # absolute epoch-ms deadline; combined
                                # (tighter wins) with the ambient task
                                # deadline / X-Request-Deadline-Ms
Each streamed item is {"i": <first generation index>, "tokens":
[<id>, ...], "done": <bool>} — items COALESCE every token generated
since the consumer last drained (the decode loop outruns the per-item
transport under load), and the integer "i" is what makes the stream
RESUMABLE: after a mid-stream replica death the HTTP proxy re-submits
with ``emit_from`` = last delivered index + 1 and the client sees at
most one duplicated token boundary.

Admission is a bounded head-of-line queue: a full queue (or a prompt
that can never fit the page budget) raises :class:`LLMOverloadedError`,
which the proxy maps to the PR-3 503 shed gate.  Sequences whose
consumer vanished (SSE disconnect -> generator cancel) keep their pages
only for ``detach_grace_s`` — the re-attach window for transparent
resume — then are cancelled and recycled.

Disaggregated prefill (``llm_deployment(prefill_replicas=N)``): a
sibling replica pool runs ONLY chunked prefill (``prefill_request``),
exports the finished KV pages via models.cache.gather_slots +
object_transfer.pack_kv_pages, and ships them to decode replicas as a
sealed store object over the PR-4 bulk transfer plane (seal-time CRC32,
alternate-holder retry on a corrupt pull).  The decode replica attaches
the pages by request_id (``submit(kv_pack=...)``) and starts at its
first decode step — long prompts never occupy decode-lane steps, and
the deadline admission gate prices the two phases separately
(prefill-only: chunk cost; attach: one decode step).

The model and its cache: ``model=`` resolves to a family of
``ray_tpu.models`` (a dictionary's ``model_type``; Llama's without one)
and the family's config states its cache LAYER BY LAYER
(models/cache.py).  The engine holds one CACHE GROUP a kind that occurs
(``self._groups``, serve/cache_groups.py: full pages with the prefix
index, window pages, state slots) and knows a kind by the groups' one
interface alone: admit a sequence, advance it before a pass, the arrays
of the pass (`_pass_groups`: the `groups` a model's forward takes), the
row slots for page shipping, the counts.  The engine's own: the queue,
lanes, clock, run-ahead, streaming, deadlines, sampling, pass shapes.
Copy-on-write prefix sharing (``prefix_sharing``) is the full group's: a
sequence whose page-aligned prompt prefix matches a live one's attaches
to the SAME pages and prefills from the first unshared token.

A model that GENERATES BY DIFFUSION OVER BLOCKS (its config's
``block_length`` B > 0, models/laguna.py) is served by the same loop with
a decode pass that carries a whole block a lane (`_Block`): the prompt's
whole leading blocks are prefilled (their rows are final: a row depends
on its own block and earlier ones), the prompt's tail opens the first
block, and a BLOCK PASS runs the model on a lane's B positions over the
committed rows and the block's own, which it writes into the sequence's
page before its queries read them and OVERWRITES at every pass.  The
family's sampler, on the device behind the model, unmasks positions; a
pass that finds no mask left changes nothing and is the COMMIT, the last
overwrite, after which the next block opens.  A request may carry
``denoising_steps`` (1 ... B).  A stream's item is a block: it is
delivered when no mask is left in it (the prompt's tail not among its
tokens, the last block cut at ``max_new_tokens``), so ``ttft`` is the
first block and the time between tokens is a block's passes over its
tokens.  The run-ahead stays (`_Block`): where a pass's block stands is
the host's to know a pass late, what is in it the device's.  Pages are
published (prefix index, shipping) up to the last committed block only.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ray_tpu._private import deadlines, tracing
from ray_tpu._private.errors import DeadlineExceededError

__all__ = ["LLMEngine", "LLMOverloadedError", "llm_deployment",
           "run_llm_loop"]


class LLMOverloadedError(RuntimeError):
    """Admission shed: queue full or the prompt cannot be paged in.
    The HTTP proxy maps this to 503 (the serve shed-gate contract)."""


# sequence states
_QUEUED = "queued"
_PREFILL = "prefill"
_DECODE = "decode"
_SHIP = "ship"  # prefill-only sequence whose pages were just exported

_forward_cache: Dict[int, Any] = {}

# The defaults of `LLMEngine`'s arguments, the one place they are set.
PAGE_SIZE = 16          # KV-cache tokens per page
MAX_BATCH = 32          # decode lanes per engine step
PREFILL_CHUNK = 64      # prompt tokens a lane of the WIDE prefill pass
# takes a step
PREFILL_LANES = 8       # sequences prefilling one chunk each per step
# (batched prefill: admitting N streams costs N/lanes steps).  What bounds
# how long the prompts can stall the in-flight decodes is a pass's SLOTS,
# lanes x chunk = 512, which the pass computes whatever they hold — not the
# chunk a lane: the DEEP pass (`_deep_prefill_shape`) lays the same slots
# out as PREFILL_NARROW_LANES lanes of 256, the same matrix products over
# the same rows, and a long prompt advances four times as far a step
PREFILL_NARROW_LANES = 2  # lanes of the NARROW prefill pass, which a step
# with at most that many prompts prefilling runs (`_prefill_shape`), and of
# the deep one: not an argument.  Measured on the v5e (PERF.md section 6,
# PR 35): below the knee one prompt prefills at a time, and the 8-lane pass
# cost 16.2 ms for its 512 slots whatever they held; at 2 x 64 slots the
# pass costs 7.1 ms, its matrix products at the weights' own stream, so
# fewer lanes would buy nothing, and two keep a second prompt that arrives
# while a first one prefills in the narrow pass (the chat cells ran no
# other: 100 %, 99.9 %)
STREAM_FLUSH_TOKENS = 4  # tokens coalesced per stream item after the
# first (the first token flushes immediately for TTFT); each item costs a
# stream push + a ref resolution + an SSE chunk, so this is the per-token
# transport amortizer
MAX_QUEUE = 256         # queued sequences before 503 shed
DETACH_GRACE_S = 2.0    # KV pages survive a vanished consumer this long
# (the re-attach window for proxy resume) before recycling
# The rest are literals in the signature: `num_pages=None` is sized so
# that max_batch sequences can run at max_seq_len at once;
# `prefix_sharing=True` admits a sequence whose page-aligned prompt prefix
# matches a live one's onto the SAME physical pages; `temperature=0.0`,
# `top_k=0` are greedy argmax over the full vocabulary.

# the phases of one engine step: the key of `stats()["phase_secs"]` ->
# the span on the profiler's clock.  `admit` is the first lock section.
# The engine runs one step ahead of its read-backs, so of each kind of
# pass `build` (the numpy arrays) and `dispatch` (the jitted call until
# it returns, and the host state it advances) are THIS step's, and come
# first; `sync` is the `np.asarray` of the PREVIOUS step's output of that
# kind (the wait for the device; the span carries `of`, that step's
# number), `emit` the lock section that hands its tokens out
_PHASES = {"admit": "llm.admit",
           "prefill_build": "llm.prefill.build",
           "prefill_dispatch": "llm.prefill.dispatch",
           "prefill_sync": "llm.prefill.sync",
           "prefill_emit": "llm.prefill.emit",
           "decode_build": "llm.decode.build",
           "decode_dispatch": "llm.decode.dispatch",
           "decode_sync": "llm.decode.sync",
           "decode_emit": "llm.decode.emit"}
# the stepping thread's time OUTSIDE a step, on the same clock: from a
# step's end to the next one's beginning, and the wait on the condition
_OUTSIDE = {"between": "llm.between", "park": "llm.park"}
_CLOCKED = {**_PHASES, **_OUTSIDE}
_SYNCS = frozenset(("prefill_sync", "decode_sync"))
_DISPATCHES = frozenset(("prefill_dispatch", "decode_dispatch"))
_EMITS = frozenset(("prefill_emit", "decode_emit"))
# the phases that are the host's: all but the waits, for the device (the
# two `sync`) and for work (`park`)
_WAITS = frozenset((*_SYNCS, "park"))
# what an idle loop passes through: a step that finds nothing is all
# `admit`
_IDLING = frozenset(("admit", "between", "park"))
_HOST_PHASES = tuple(k for k in _CLOCKED if k not in _WAITS)
# the thread's CPU clock is read at a step's two ends and at an emit's
# (a system call; under the chip machine's sandbox one of 6 us with a
# 10 ms tick, so not at every boundary), which tells four stretches
# apart: an emit, `between` (with the park, which burns next to none),
# and `front`, the rest of a step (admit, builds, dispatches, and the
# syncs, which wait)
_CPU_GROUP = {k: k if k in _EMITS else "between" if k in _OUTSIDE
              else "front" for k in _CLOCKED}
# host work inside a phase, by name (`stats()["host_secs"]` -> the span):
# the lane selection and the groups' advance under `admit`'s lock, the
# gauges (inside whichever phase is open), and in the builds the arrays of
# a group that names the span and a decode pass's count: `_pass_groups`
_HOST_SPANS = {"plan": "llm.plan", "gauges": "llm.gauges",
               "window_arrays": "llm.window_arrays",
               "grid_count": "llm.grid_count"}
_NO_SPAN = contextlib.nullcontext()
# the gauges as they were written last, (name, state) -> value: the
# process's, as the gauges are (`_private/metrics.llm_metrics`)
_gauged: Dict[tuple, int] = {}

def _pow4_widths(first: int, cap: int) -> List[int]:
    """`first`, 4 x `first`, ... up to (and capped at) `cap`: the widths
    a pass's context arrays snap to, one compiled program each."""
    widths, w = [], first
    while True:
        widths.append(min(w, cap))
        if w >= cap:
            return widths
        w *= 4


def _jit_forward(model, params, pools, tokens, q_pos, last_idx, groups,
                 temperature=0.0, top_k=0, rng=None, top2=False, feed=None,
                 head=True):
    """One forward over the paged cache -> (next tokens at ``last_idx``,
    updated pools).  Jitted ONCE per (model, shapes, sampling knobs) —
    the flax module AND the sampling knobs are hashable static
    arguments, so every engine instance with the same config shares the
    compiled executable (`pools`, the cache's slot arrays by the name of
    a row's part — models/cache.py — donated: in-place cache updates).

    ``groups`` maps each cache kind of the model to that kind's arrays
    (serve/cache_groups.py): the write ``slots`` and the context in one
    of two forms, each its own trace — gathered (chunked prefill), or
    page-granular ``block_tables``, which take decode through the
    Pallas paged-attention kernel.

    ``feed`` is a decode pass's: ``(src, outputs)``, the previous step's
    output arrays as the device holds them and, a lane, the place of its
    input token in their concatenation (-1: the host's ``tokens``).  The
    engine dispatches a step before it has read the one before, so a
    sequence's newest token is on the device only (`LLMEngine.step`).

    The pass returns a token an entry of ``last_idx``.  ``tokens`` may
    have fewer lanes than that (the narrow and the deep prefill pass): the
    entries past them read 0, and the output keeps the shape the decode
    programs' ``feed`` was compiled for.

    Sampling is a pair of jit-STATIC knobs (ISSUE 13 satellite / PR-11
    declared headroom (d)): ``temperature == 0`` compiles the exact
    greedy-argmax program the decode-identity tier-1 gate pins down —
    no mask, no categorical, no rng use in the graph; ``temperature >
    0`` compiles logits/temperature + optional static top-k mask +
    jax.random.categorical.  Each distinct (temperature, top_k) pair is
    its own executable; lanes within one engine always share the knobs
    (per-lane temperatures would force them to be traced values).
    ``top2`` is a third one, off in serving, and ``head`` a fourth: see
    `_jitted_forward`."""
    fn = _jitted_forward(temperature, top_k, top2, head)
    if rng is None:
        import jax.numpy as jnp

        rng = jnp.zeros((2,), dtype="uint32")  # unused when greedy
    return fn(model, params, pools, tokens, q_pos, last_idx, rng, groups,
              feed)


def _jitted_forward(temperature=0.0, top_k=0, top2=False, head=True):
    """The process-wide jitted stepper for one set of static knobs.
    A model that counts on the device (`model.counters`) gets its
    counter vector appended to the tokens, one array and one transfer.
    With ``top2`` it returns a third value: each lane's two largest
    logits and their ids ([B, 2] float32, [B, 2] int32) — what a
    comparison of two engines needs to tell a rounding flip from a
    wrong read (`LLMEngine(logit_trace=True)`).  Without ``head`` the
    pass returns zeros for tokens and its logits are computed by nobody:
    the prefill pass of a block model, whose first tokens a block pass
    makes."""
    import jax

    key = (float(temperature), int(top_k), bool(top2), bool(head))
    fn = _forward_cache.get(key)
    if fn is None:
        import jax.numpy as jnp

        def _fwd(model, params, pools, tokens, q_pos, last_idx, rng,
                 groups, feed=None, temperature=key[0], top_k=key[1],
                 top2=key[2]):
            if feed is not None:
                src, outputs = feed
                late = jnp.concatenate(outputs)[jnp.maximum(src, 0)]
                tokens = jnp.where(src[:, None] >= 0,
                                   late[:, None].astype(tokens.dtype),
                                   tokens)
            cache = {**pools, "q_pos": q_pos, "groups": groups}
            logits, pools, *counted = model.apply(
                {"params": params}, tokens, cache)
            # a pass of fewer lanes than `last_idx` has entries (the
            # narrow or the deep prefill pass) looks at the first of them
            lanes = tokens.shape[0]
            if not head:
                tok = jnp.zeros((last_idx.shape[0],), jnp.int32)
                if counted:
                    tok = jnp.concatenate(
                        [tok, counted[0].astype(jnp.int32)])
                return tok, pools
            picked = jnp.take_along_axis(
                logits, last_idx[:lanes, None, None], axis=1)[:, 0]
            if temperature <= 0.0:
                tok = jnp.argmax(picked, axis=-1)
            else:
                scaled = picked / temperature
                if top_k > 0:
                    kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
                    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
                tok = jax.random.categorical(rng, scaled, axis=-1)
            tok = tok.astype(jnp.int32)
            if lanes < last_idx.shape[0]:
                # ... and returns its tokens in the wide pass's shape,
                # so the decode programs see ONE `feed` and the counters
                # stay at one offset
                tok = jnp.pad(tok, (0, last_idx.shape[0] - lanes))
            if counted:
                tok = jnp.concatenate(
                    [tok, counted[0].astype(jnp.int32)])
            if not top2:
                return tok, pools
            f = picked.astype(jnp.float32)
            i1 = jnp.argmax(f, axis=-1)
            rest = jnp.where(jnp.arange(f.shape[-1]) == i1[:, None],
                             -jnp.inf, f)
            i2 = jnp.argmax(rest, axis=-1)
            return tok, pools, (
                jnp.stack([f.max(axis=-1), rest.max(axis=-1)], axis=-1),
                jnp.stack([i1, i2], axis=-1).astype(jnp.int32))

        fn = _forward_cache[key] = jax.jit(
            _fwd, static_argnums=0, donate_argnums=(2,))
    return fn


def _jitted_block_forward():
    """The process-wide jitted BLOCK pass: the model on every lane's
    block, then the family's sampler (`sample`, static like the model:
    `block_sample(cfg, tokens, logits, need)`) on the device.  ``feed``
    = (src, the last block pass's output): a lane whose `src` >= 0 takes
    its block's tokens from there, as the device left them.  Returns one
    int32 array — the blocks' next state [lanes x B], then a lane the
    positions masked at entry, unmasked by the pass, unmasked by the
    threshold alone, then the model's counter vector — and the pools."""
    import jax

    fn = _forward_cache.get("block")
    if fn is None:
        import jax.numpy as jnp

        def _fwd(model, sample, params, pools, tokens, q_pos, need, groups,
                 feed):
            src, last = feed
            lanes, b = tokens.shape
            late = last[:lanes * b].reshape(lanes, b)[jnp.maximum(src, 0)]
            tokens = jnp.where(src[:, None] >= 0, late, tokens)
            cache = {**pools, "q_pos": q_pos, "groups": groups}
            logits, pools, *counted = model.apply(
                {"params": params}, tokens, cache)
            state, *counts = sample(model.cfg, tokens, logits, need)
            return jnp.concatenate(
                [state.reshape(-1), *counts,
                 *(c.astype(jnp.int32) for c in counted)]), pools

        fn = _forward_cache["block"] = jax.jit(
            _fwd, static_argnums=(0, 1), donate_argnums=(3,))
    return fn


class _HostSpan:
    """`with clock.host[key]:` — one named piece of host work inside a
    phase: its span on the profiler's clock and its seconds in
    `host_secs[key]`.  Not a boundary: the phases keep theirs."""

    __slots__ = ("_clock", "_key", "_span", "_t0")

    def __init__(self, clock: "_StepClock", key: str):
        self._clock, self._key = clock, key

    def __enter__(self) -> None:
        self._span = self._clock.span(_HOST_SPANS[self._key])
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        self._clock.host_secs[self._key] += time.perf_counter() - self._t0
        self._span.__exit__(None, None, None)


class _StepClock:
    """Where the stepping thread's time goes, from ONE clock read a
    boundary: `begin` opens a step and its first phase, `phase` closes
    the open phase and opens the next, `end` closes both, so a step's
    phases add up to the step exactly (`secs` by phase, `step_secs`:
    cumulative seconds).  Every call of `LLMEngine.step` counts, also
    one that found nothing to do.  The clock runs on after `end`: the
    thread is then `between` two steps, or in `park` (the loop's wait on
    the condition), so from the first step on no instant is unnamed.
    A stretch with nothing to do is cut into parks of 50 ms and the
    steps between them that find nothing; `llm.idle` spans it whole,
    from its first park to the first phase of a step that found work.

    The same boundaries open and close spans on the profiler's clock
    (`jax.profiler.TraceAnnotation`: `llm.step` with the step's number
    `n`, and the phase's span inside it), recorded while a profile is
    being taken and a flag test otherwise, and name the step and phase
    to `ops.note_phase` for the record of a compile they set off.

    Three more readings at the same boundaries:

    - work or waiting: the thread's CPU clock beside the wall clock at
      a step's two ends and an emit's (`_CPU_GROUP`), so
      `off_cpu_secs[group]` is what the thread held the group's host
      phases open without running (the engine lock, the GIL, the
      machine).  A stretch is on the CPU no longer than its host phases
      lasted; what the CPU clock read beyond that (its tick can be
      coarser than a stretch) is owed to the group's next stretches, so
      a reading is right over many steps, not step by step;
    - what the device waited for, between two bounds: `flight` is the
      engine's passes dispatched and unread, and the device runs them in
      order, so the newest one done means the device has nothing.  The
      clock asks where a phase opens (not a `sync`: the wait tells) and
      where a dispatch ENDS (`dispatched`, while the newest pass in
      flight is still the one that ran beside the host's work).  From
      the first boundary that sees it, the host phases the thread passes
      through, up to and including the next dispatch, go to
      `starved_secs[phase]` — the LOWER bound: the device ran dry
      somewhere inside the ONE phase before the boundary that saw it (for
      `dispatched`: inside the dispatch so far; behind a `sync`, whose
      opening did not ask, the phase before it too, under the sync's
      name), and those seconds go to `starved_before_secs[phase]` when
      the dispatch lands, so lower + before is the UPPER bound and no
      second is in both.  A dispatch onto a pass in flight is `chained`;
      it is `dry` where that pass was done when the dispatch ended or the
      device had been seen dry on the way, and `dry_in_dispatch` where
      only the dispatch's end saw it (the lower bound has nothing of such
      a step).
      While a profile is taken the boundary that saw it leaves `llm.dry`
      (`of`: the step whose pass had finished; `seen`: the phase).
      Nothing where no dispatch follows, nothing onto an empty flight (an
      engine with nothing to do is idle, not starved);
    - the turnaround: from the last read-back of a step (`turnaround`,
      at the boundary behind it) to the return of the next step's first
      `_forward` (`dispatched`), `llm.turnaround` with `of`, the step
      whose pass is on the device meanwhile — the host's critical path a
      step, which has to fit inside one device pass."""

    def __init__(self, flight=()):
        import jax

        from ray_tpu.ops import note_phase

        self.span = jax.profiler.TraceAnnotation
        self._note = note_phase
        self._flight = flight
        self.step_secs = 0.0
        self.secs = dict.fromkeys(_CLOCKED, 0.0)
        self.host_cpu_secs = 0.0
        self.off_cpu_secs = dict.fromkeys(_CPU_GROUP.values(), 0.0)
        self._cpu_owed = dict.fromkeys(self.off_cpu_secs, 0.0)
        self._host_dt = 0.0   # the host phases' seconds since a CPU read
        self.starved_secs = dict.fromkeys(_HOST_PHASES, 0.0)
        self.starved_before_secs = dict.fromkeys(_CLOCKED, 0.0)
        self.chained = self.chained_dry = self.dry_in_dispatch = 0
        self.host_secs = dict.fromkeys(_HOST_SPANS, 0.0)
        self.host = {key: _HostSpan(self, key) for key in _HOST_SPANS}
        self.turnaround_secs = 0.0
        self.turnarounds = 0
        self._n = 0
        self._tid: Optional[int] = None
        self._key: Optional[str] = None
        self._t0 = self._c0 = self._t_step = self._t_turn = 0.0
        self._decode = 0.0   # the open step's decode seconds
        self._dry = False    # the device was seen with nothing to run
        self._dry_since: list = []   # [(phase, seconds)] since then
        # the seconds since the newest pass in flight was last found
        # running (it may have finished anywhere in them, unseen: a
        # `sync`'s opening does not ask); of the open phase, those
        # `starved_before_secs` has; and what the boundary that saw the
        # device dry found in doubt, (phase, seconds), until the next
        # dispatch lands
        self._doubt = self._booked = 0.0
        self._dry_before = ("between", 0.0)
        self._step_span = self._phase_span = None
        self._turn_span = self._idle_span = None

    def _close(self, cpu: bool) -> float:
        """End the open phase: its seconds by every reading, the CPU
        clock's where `cpu` says so or an emit ends."""
        now = time.perf_counter()
        key = self._key
        if key is not None:
            dt = now - self._t0
            self.secs[key] += dt
            if key not in _WAITS:
                self._host_dt += dt
            if not self._dry:
                self._doubt += dt - self._booked
            elif key not in _WAITS:
                self._dry_since.append((key, dt))
                if key in _DISPATCHES:   # the device has a pass again
                    for k, secs in self._dry_since:
                        self.starved_secs[k] += secs
                    k, secs = self._dry_before
                    self.starved_before_secs[k] += secs
                    self._dry = False
                    self._dry_since.clear()
            self._booked = 0.0
            if key.startswith("decode_"):
                self._decode += dt
            self._phase_span.__exit__(None, None, None)
            if cpu or key in _EMITS:
                c, group = time.thread_time(), _CPU_GROUP[key]
                owed = self._cpu_owed[group] + c - self._c0
                wall, self._host_dt, self._c0 = self._host_dt, 0.0, c
                on_cpu = min(max(owed, 0.0), wall)
                self._cpu_owed[group] = owed - on_cpu
                self.host_cpu_secs += on_cpu
                self.off_cpu_secs[group] += wall - on_cpu
        self._t0 = now
        return now

    def _open(self, key: str, args: Dict[str, Any]) -> None:
        dry_of = None
        if not self._flight:
            self._dry = False    # nothing unread: idle, not starved
            self._dry_since.clear()
            self._doubt = 0.0
        elif not self._dry and key not in _SYNCS:
            newest = self._flight[-1]
            if newest.out.is_ready():
                # it ran dry inside the phase this boundary closed (and,
                # behind a `sync`, the one before it)
                self._dry, dry_of = True, newest.step
                self._dry_before = self._key, self._doubt
            self._doubt = 0.0
        if self._turn_span is not None and key in _WAITS:
            # a wait before any dispatch: no pass came of this turnaround
            self._turn_span.__exit__(None, None, None)
            self._turn_span = None
        if key == "park":
            if self._idle_span is None:
                self._idle_span = self.span("llm.idle")
                self._idle_span.__enter__()
        elif self._idle_span is not None and key not in _IDLING:
            self._idle_span.__exit__(None, None, None)
            self._idle_span = None
        self._key = key
        self._phase_span = self.span(_CLOCKED[key], **args)
        self._phase_span.__enter__()
        if dry_of is not None:
            self._mark_dry(dry_of)
        self._note(key if key in _PHASES else None, self._n)

    def _mark_dry(self, of: int) -> None:
        """The boundary that first saw the device dry, on the profiler's
        clock: inside the phase it opens, or the dispatch it ends."""
        with self.span("llm.dry", of=of, seen=self._key):
            pass

    def phase(self, key: str, **args) -> float:
        now = self._close(key in _EMITS)
        self._open(key, args)
        return now

    def begin(self, n: int) -> float:
        tid = threading.get_ident()
        if tid != self._tid:
            # an engine stepped inline, by another thread than the last
            # step's: `between` ran on neither's CPU clock
            self._tid, self._c0 = tid, time.thread_time()
        self._t_step = self._close(True)
        self._n, self._decode = n, 0.0
        self._step_span = self.span("llm.step", n=n)
        self._step_span.__enter__()
        self._open("admit", {})
        return self._t_step

    def end(self) -> float:
        """Close the step; returns its decode pass's seconds."""
        self.step_secs += self._close(True) - self._t_step
        self._step_span.__exit__(None, None, None)
        self._open("between", {})
        return self._decode

    def turnaround(self, of: int) -> None:
        """The step's last read-back has returned (at the boundary just
        crossed: its clock read), with step `of`'s pass on the device.
        None is open: a sync, which ends one no dispatch ended, came
        before."""
        self._t_turn = self._t0
        self._turn_span = self.span("llm.turnaround", of=of)
        self._turn_span.__enter__()

    def dispatched(self) -> None:
        """A `_forward` returned, its pass not in `flight` yet: the first
        one behind a turnaround's beginning ends it; and the newest pass
        in flight is the one the device had while the host prepared this
        one, so a dispatch that ends with it done ended dry."""
        if self._turn_span is not None:
            self.turnaround_secs += time.perf_counter() - self._t_turn
            self.turnarounds += 1
            self._turn_span.__exit__(None, None, None)
            self._turn_span = None
        if not self._flight:
            return   # onto an engine with nothing in flight: idle
        self.chained += 1
        if self._dry:
            self.chained_dry += 1
        elif self._flight[-1].out.is_ready():
            # seen by nobody before: somewhere inside the dispatch so far
            secs = time.perf_counter() - self._t0 - self._booked
            self.starved_before_secs[self._key] += secs
            self._booked += secs
            self.chained_dry += 1
            self.dry_in_dispatch += 1
            self._mark_dry(self._flight[-1].step)

    def pass_secs(self, kind: str) -> float:
        """The seconds of one kind of pass: its four phases."""
        return sum(self.secs[f"{kind}_{part}"]
                   for part in ("build", "dispatch", "sync", "emit"))

    def stats(self) -> Dict[str, Any]:
        """The clock's part of `LLMEngine.stats()`: cumulative seconds
        and counts.  `loop_secs` is everything since the first step;
        `host_*` are over the host's phases (`_HOST_PHASES`) up to the
        CPU clock's last reading."""
        between, park = self.secs["between"], self.secs["park"]
        cpu, off_cpu = self.host_cpu_secs, sum(self.off_cpu_secs.values())
        return {"step_secs": self.step_secs,
                "phase_secs": {k: self.secs[k] for k in _PHASES},
                "between_secs": between, "park_secs": park,
                "loop_secs": self.step_secs + between + park,
                "host_secs": dict(self.host_secs),
                "starved_secs": dict(self.starved_secs),
                "starved_secs_total": sum(self.starved_secs.values()),
                "starved_before_secs": dict(self.starved_before_secs),
                "starved_before_secs_total":
                    sum(self.starved_before_secs.values()),
                "chained_dispatches_total": self.chained,
                "chained_dispatches_dry_total": self.chained_dry,
                "dry_in_dispatch_total": self.dry_in_dispatch,
                "turnaround_secs": self.turnaround_secs,
                "turnarounds_total": self.turnarounds,
                "host_wall_secs": cpu + off_cpu, "host_cpu_secs": cpu,
                "host_off_cpu_secs": off_cpu,
                "off_cpu_secs": dict(self.off_cpu_secs)}


class _Flight:
    """A dispatched pass whose output the host has not read: the step
    that dispatched it, the output as the device holds it, and who is
    owed a token of it — `lanes` = [(lane, sequence)]: every lane of a
    decode pass, of a prefill pass the lanes whose prompt ended in it.
    A block pass's are (lane, sequence, the block's first position, the
    block as the pass read it where the host knew: `_Block`)."""

    __slots__ = ("kind", "step", "out", "top2", "lanes")

    def __init__(self, kind: str, step: int, out, top2, lanes):
        self.kind, self.step, self.out = kind, step, out
        self.top2, self.lanes = top2, lanes


class _Block:
    """Where a sequence of a block model stands, as far as the host
    knows — which is a pass late: a step dispatches pass n + 1 before it
    reads pass n.

    `p0`, `k`: the first position of the block the NEXT pass works on,
    and that pass's number in its block (`sched[k]` positions it has to
    unmask at least).  `cur`: the block's tokens as the next pass will
    read them where `known`, else as the pass in flight read them.
    What the host cannot know is what a denoising pass in flight leaves:
    the next pass then takes the block from the device (`feed`) and is,
    as the device finds it, one more denoising pass or the commit — at
    the same positions either way.  What it can: a pass that READ a
    block with no mask left is that block's commit, so the pass behind
    it opens the next block, with the host's tokens.  `emit_p0`: the
    first block not delivered yet.  `last`: the newest block state read
    back.  `passes`: the record a request asked for (`record_passes`):
    [first position, the block as read, as left] a pass read."""

    __slots__ = ("p0", "k", "cur", "known", "sched", "emit_p0", "last",
                 "passes")

    def __init__(self, p0: int, cur: List[int], sched, record: bool):
        self.p0 = self.emit_p0 = p0
        self.k, self.cur, self.known = 0, cur, True
        self.sched = sched
        self.last = cur
        self.passes: Optional[List[list]] = [] if record else None


class _Seq:
    __slots__ = ("request_id", "prompt", "prefill_tokens", "generated",
                 "max_new", "eos", "cache", "pos", "state", "done",
                 "error", "attach_count", "detached_at", "done_at",
                 "submitted_at", "admitted_at", "first_token_at",
                 "cancelled", "cond", "deadline", "kv_import",
                 "prefill_export", "export_payload", "trace_ctx",
                 "prefix_tokens", "submit_step", "admit_step",
                 "first_token_step", "ahead", "feed", "blk",
                 "prefilled_at", "prefilled_step", "blocked_by")

    def __init__(self, request_id: str, prompt: List[int], max_new: int,
                 eos: Optional[int], preknown: Optional[List[int]] = None):
        self.request_id = request_id
        # cond is per-sequence so a token emit wakes THIS stream's
        # consumer, not every parked thread
        self.cond: Optional[threading.Condition] = None
        self.prompt = list(prompt)
        self.generated: List[int] = list(preknown or [])
        # restored sequences re-prefill prompt + already-known tokens in
        # one pass; fresh sequences prefill just the prompt
        self.prefill_tokens = self.prompt + self.generated
        self.max_new = int(max_new)
        self.eos = eos
        # cache kind -> what its group holds for the sequence, while live
        self.cache: Dict[str, Any] = {}
        # tokens whose KV a DISPATCHED pass has written or will write:
        # the device runs passes in the order they were dispatched, so
        # to every later pass they are in the cache
        self.pos = 0
        # tokens of this sequence computed by dispatched passes and not
        # read back yet (`_Flight`), and the place of the newest in the
        # last step's outputs (`_jit_forward`'s `feed`), valid while
        # `ahead` > 0
        self.ahead = 0
        self.feed = -1
        # a block model's: where its open block stands, and when its
        # prompt's whole blocks were prefilled (`llm.first_block`)
        self.blk: Optional[_Block] = None
        self.prefilled_at: Optional[float] = None
        self.prefilled_step = -1
        self.state = _QUEUED
        self.done = False
        self.error: Optional[BaseException] = None
        self.attach_count = 0
        self.detached_at: Optional[float] = None
        self.done_at: Optional[float] = None
        self.submitted_at = time.monotonic()
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        # for the request's spans (`_record_request_spans`): the caller's
        # trace context, the prompt tokens found in shared pages, and the
        # engine step at which each stage ended
        self.trace_ctx: Optional[tracing.SpanContext] = None
        self.prefix_tokens = 0
        self.submit_step = self.admit_step = self.first_token_step = -1
        # the last reason admission refused it as the queue's head for
        # (`_admit_locked`); empty if it never waited there
        self.blocked_by = ""
        self.cancelled = False
        # absolute wall-clock deadline (epoch seconds; 0 = unbounded):
        # the sweep cancels expired in-flight sequences and recycles
        # their pages instead of decoding for a caller that moved on
        self.deadline = 0.0
        # disaggregated prefill: shipped KV rows waiting to be scattered
        # into this engine's pools (decode side), or the flag/result of
        # a prefill-only pass whose pages are exported instead of
        # decoded (prefill side)
        self.kv_import: Optional[Dict[str, Any]] = None
        self.prefill_export = False
        self.export_payload: Optional[Dict[str, Any]] = None

    @property
    def total_len(self) -> int:
        return len(self.prompt) + self.max_new


class LLMEngine:
    """Continuous-batching decode engine over a paged KV cache.

    One engine per replica.  The pinned loop (``run_loop``) is the ONLY
    caller of ``step()`` in serving; request threads touch the engine
    only through ``submit``/``iter_tokens``/``release`` under the
    engine lock.  (``warm_up`` and the tests instead drive
    ``generate_batch`` inline — an engine is stepped by its loop OR
    inline, never both.)
    """

    def __init__(self, cfg=None, *, model: Any = "tiny",
                 params: Any = None, seed: int = 0,
                 page_size: int = PAGE_SIZE,
                 num_pages: Optional[int] = None,
                 max_batch: int = MAX_BATCH,
                 prefill_chunk: int = PREFILL_CHUNK,
                 max_queue: int = MAX_QUEUE,
                 detach_grace_s: float = DETACH_GRACE_S,
                 prefill_lanes: int = PREFILL_LANES,
                 stream_flush_tokens: int = STREAM_FLUSH_TOKENS,
                 dtype: Any = None,
                 temperature: float = 0.0,
                 top_k: int = 0,
                 prefix_sharing: bool = True,
                 logit_trace: bool = False):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import cache as kv_cache, resolve
        from ray_tpu.ops import count_compile_cache_events, kernel_mode
        from ray_tpu.serve import cache_groups

        self._np = np
        # where this engine runs, found once and reported by stats(): a
        # replica that did not find its chip serves from the CPU and the
        # Pallas interpreter, and must say so
        self.platform = jax.devices()[0].platform
        self.kernel_mode = kernel_mode()
        count_compile_cache_events()  # before this engine's compiles
        # debugging aid, off in serving: keep the two largest logits
        # behind every generated token (device_report()["logit_trace"]),
        # request id -> [[index in generated, logit1, id1, logit2,
        # id2], ...].  Grows with every token; costs a host copy a step.
        self.logit_trace = bool(logit_trace)
        self._logit_trace: Dict[str, List[list]] = {}
        # the model: its family's module (config, build) and its config,
        # which states the cache layer by layer (ray_tpu/models)
        self.family, cfg = resolve(cfg if cfg is not None else model)
        if dtype is not None:
            import dataclasses

            cfg = dataclasses.replace(cfg, dtype=dtype)
        self.cfg = cfg
        # a model that generates by diffusion over blocks: B positions a
        # lane a decode pass (0: a token); the module's text
        self._block = int(getattr(cfg, "block_length", 0) or 0)
        if self._block and (float(temperature) > 0 or logit_trace
                            or int(page_size) % self._block
                            or int(prefill_chunk) % self._block):
            raise ValueError(
                f"a model of blocks of {self._block} is sampled greedily, "
                f"records passes (`record_passes`) where another records "
                f"logits, and needs pages and prefill chunks of whole blocks")
        self.page_size = int(page_size)
        self.max_batch = int(max_batch)
        self.prefill_chunk = int(prefill_chunk)
        self.max_queue = int(max_queue)
        self.detach_grace_s = float(detach_grace_s)
        self.prefill_lanes = max(1, min(int(prefill_lanes), self.max_batch))
        self.stream_flush_tokens = max(1, int(stream_flush_tokens))
        self.pages_per_seq = -(-cfg.max_seq_len // self.page_size)
        if num_pages is None:
            # sized so max_batch sequences can run at max_seq_len at
            # once; +1: page 0 is the garbage page, never allocated
            num_pages = 1 + self.max_batch * self.pages_per_seq
        self.num_pages = max(int(num_pages), 2)
        self.ctx_len = self.pages_per_seq * self.page_size
        # the prefill programs (`_prefill_shape`): the context widths, the
        # narrow pass's (lanes, width) and the deep pass's (lanes, chunk)
        self._prefill_widths = self._prefill_ctx_buckets()
        self._narrow_prefill = self._narrow_prefill_shape()
        self._deep_prefill = self._deep_prefill_shape()
        self._model = self.family.build(cfg, self.page_size)
        # the cache, by what the model's specification says: the kind of
        # each layer, and one group a kind that occurs
        spec = cfg.cache_spec()
        self._kinds = [layer.kind for layer in spec]
        self._groups = cache_groups.build(
            spec, cfg.dtype, page_size=self.page_size,
            num_pages=self.num_pages, max_batch=self.max_batch,
            chunk=self._widest_chunk(), pages_per_seq=self.pages_per_seq,
            prefix_sharing=prefix_sharing, block=self._block or 1)
        # seconds of this replica's start-up, by part: until the weights
        # the engine serves from were on the device, the call that
        # allocates the KV pools, and `warm_up`'s compiles.  The device
        # fills the weights while the host goes on to the pools and the
        # warm-up, so a waiter thread notes when they were there and
        # nothing is held up for the clock.
        #
        # The engine holds its tree in the dtypes its serving module
        # declares, what the forward multiplies by.  `init` draws it so,
        # a leaf at a time.  A tree passed in (a trainer's float32
        # checkpoint) is brought to them leaf by leaf, rounded once and
        # not once a pass; a leaf already as declared is taken as it is.
        t0 = time.perf_counter()
        init_args = (jax.random.PRNGKey(int(seed)),
                     np.zeros((1, 8), np.int32))
        if params is None:
            params = self._model.init(*init_args)["params"]
        else:
            params = jax.tree.map(
                lambda leaf, declared: leaf if leaf.dtype == declared.dtype
                else jnp.asarray(leaf, declared.dtype), params,
                jax.eval_shape(self._model.init, *init_args)["params"])
        self._params = params
        t1 = time.perf_counter()
        self._pools = kv_cache.make_pools(
            spec, {kind: g.slots for kind, g in self._groups.items()},
            cfg.dtype)
        self.startup_secs = {"params": 0.0,
                             "pools": time.perf_counter() - t1, "warm": 0.0}

        def weights_ready() -> None:
            jax.block_until_ready(params)
            self.startup_secs["params"] = time.perf_counter() - t0

        self._weights_waiter = threading.Thread(
            target=weights_ready, daemon=True, name="llm-weights-ready")
        self._weights_waiter.start()
        # sampling knobs are jit-STATIC: temperature=0 (the default)
        # compiles the exact greedy program the decode-identity gate
        # covers; >0 adds temperature scaling + optional top-k masking
        # (0 = the full vocabulary) + categorical sampling, seeded per
        # engine so a fixed seed replays the same stream
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._sample_rng = (jax.random.PRNGKey(int(seed))
                            if self.temperature > 0 else None)
        # the jitted stepper is shared process-wide (_jit_forward keys
        # on the STATIC model + shapes + sampling knobs): every engine
        # with the same config/pool geometry reuses one executable —
        # two compiles total in steady state (decode [B,1] and
        # prefill [1,C])
        self._step_fn = _jit_forward

        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._kv_pages_shipped_out = 0
        self._kv_pages_shipped_in = 0
        self._queued: deque = deque()
        self._active: List[_Seq] = []
        self._by_rid: Dict[str, _Seq] = {}
        self._stopped = threading.Event()
        self._loop_running = False
        self._arange = np.arange(self.ctx_len, dtype=np.int32)
        self._steps = 0
        self._cancelled_total = 0
        self._last_batch = 0
        self._metrics = None
        self._paged_warm = False
        self._prefill_warm = False
        # pass accumulators (a reader takes mean step cost as a delta
        # between two stats() snapshots): the passes dispatched, and of
        # `_clock`, which has every phase and the whole step, a kind's
        # four phases less its one-time warm-up (`_warm_secs`)
        self._decode_steps = 0
        self._prefill_steps = 0
        self._warm_secs = {"prefill": 0.0, "decode": 0.0}
        # what is dispatched and not read (the stepping thread's own: an
        # engine is stepped by its loop OR inline), and the last step's
        # outputs, the decode pass's and the prefill pass's, for the
        # next decode pass to take its tokens from: zeros where the step
        # had no such pass, the same program
        self._flight: deque = deque()
        self._clock = _StepClock(self._flight)
        counters = len(getattr(self._model, "counters", ()))
        # a lane's entries in a decode pass's output: its token, or its
        # block's next state and three counts (`_jitted_block_forward`)
        self._lane_out = self._block + 3 if self._block else 1
        self._no_feed = (jnp.zeros((self.max_batch * self._lane_out
                                    + counters,), jnp.int32),
                         jnp.zeros((self.prefill_lanes + counters,),
                                   jnp.int32))
        self._feed = list(self._no_feed)
        # cumulative, as `stats()` gives them.  Work: prompt tokens
        # prefilled, the token slots (the pass's own lanes x chunk) the
        # prefill passes had for them, the passes that were narrow and
        # those that were deep (over prefill_steps), decode lanes stepped
        # (over decode_steps: the mean batch).  Requests by stage
        # (finished = ended and not cancelled), and the seconds they
        # waited for the next one.
        # Context: the rows the prefill passes' real lanes read, the
        # columns (the pass's lanes x width) the passes gathered for
        # them, and the passes by the width they took.  Run-ahead: decode
        # passes dispatched while the step before was unread (over
        # decode_steps: how often the device had its next pass queued),
        # and lane-steps computed for a sequence that had ended by the
        # time they were read (its `eos`, a cancel or its deadline came
        # while they were in flight; over decode_lane_steps_total).
        self._totals = {"prefill_tokens_total": 0,
                        "prefill_slots_total": 0,
                        "prefill_narrow_passes_total": 0,
                        "prefill_deep_passes_total": 0,
                        "prefill_ctx_rows_total": 0,
                        "prefill_ctx_cols_total": 0,
                        "decode_lane_steps_total": 0,
                        "decode_lane_steps_wasted_total": 0,
                        "runahead_decode_steps_total": 0,
                        "submitted_total": 0, "admitted_total": 0,
                        "first_tokens_total": 0, "finished_total": 0,
                        "queue_wait_secs_total": 0.0,
                        "prefill_wait_secs_total": 0.0}
        # a block model's (the module's text), by what a lane's pass
        # turned out to be when it was read — `denoise`: it found a mask;
        # `commit`: none, the block's last overwrite — and a whole pass
        # `denoise` where any lane of it was.  Read: blocks delivered (no
        # mask left; all but a sequence's last are then committed),
        # positions unmasked, those the threshold alone allowed, those
        # computed past `max_new`.  Dispatched: rows the block kernel
        # read (a lane's context, a layer) and open rows overwritten (a
        # block's passes after its first, a layer).
        self._block_totals: Dict[str, Any] = {} if not self._block else {
            "block_passes_total": {"denoise": 0, "commit": 0},
            "block_lane_passes_total": {"denoise": 0, "commit": 0},
            "blocks_committed_total": 0,
            "block_tokens_transferred_total": 0,
            "block_tokens_over_threshold_total": 0,
            "block_tokens_discarded_total": 0,
            "block_rows_read_total": 0,
            "block_open_rows_rewritten_total": 0}
        self._prefill_passes_by_width = dict.fromkeys(
            self._prefill_widths, 0)
        # steps whose admission left the queue's head queued, by what
        # refused it: no lane, or a cache group (by kind) with no room
        self._admit_blocked = dict.fromkeys(("lanes", *self._groups), 0)
        # what the model counts on the device (`model.counters`: names
        # of the vector it returns beside its logits), summed by pass
        self._model_counters = {
            name: {"decode": 0, "prefill": 0}
            for name in getattr(self._model, "counters", ())}
        # EWMA of one engine step's wall time — the deadline-admission
        # estimate of "prefill + one decode step" cost (0 until the
        # first measured step; cold engines only refuse already-expired
        # budgets)
        self._step_ewma = 0.0
        self._deadline_expired_total = 0

    # ------------------------------------------------------------ admission

    def submit(self, request: Dict[str, Any],
               kv_pack: Optional[tuple] = None) -> _Seq:
        """Admit (or re-attach to) one sequence.  Raises
        LLMOverloadedError when the admission queue is full, ValueError
        on requests that can never fit.

        ``kv_pack`` is an unpacked (meta, rows) KV shipment from a
        prefill replica (object_transfer.unpack_kv_pages): the sequence
        skips prefill entirely — the step loop scatters the rows into
        this engine's pools and the sequence enters decode at the
        shipped position.  A pack that does not match the request's
        prompt is discarded (local prefill is always correct, just
        slower).  A request carrying ``_phase == "prefill"`` is
        prefill-ONLY: its pages are exported and recycled at the end of
        prefill instead of decoding (see prefill_request)."""
        import uuid

        if not isinstance(request, dict) or not request.get("tokens"):
            raise ValueError("llm request must be a dict with 'tokens'")
        prompt = [int(t) for t in request["tokens"]]
        max_new = int(request.get("max_new_tokens", 16))
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        eos = request.get("eos")
        eos = int(eos) if eos is not None else None
        rid = str(request.get("request_id") or uuid.uuid4().hex[:16])
        prefill_only = request.get("_phase") == "prefill"
        block = self._block
        steps = int(request.get("denoising_steps")
                    or getattr(self.cfg, "denoising_steps", 0) or block)
        if block:
            if not 1 <= steps <= block:
                raise ValueError(f"denoising_steps must be 1 ... {block}")
            if self.cfg.mask_token_id in prompt:
                raise ValueError("the prompt holds the mask's id")
            if prefill_only and len(prompt) < block:
                raise ValueError("a prompt shorter than a block has no "
                                 "rows to ship")
        # the rows a prompt's prefill leaves: all of it, or its whole blocks
        prefilled = len(prompt) - len(prompt) % (block or 1)
        if kv_pack is not None:
            meta = kv_pack[0]
            # the shipment must describe exactly this prompt: the rows
            # are attached positionally, so any mismatch would decode
            # against another request's KV
            if (list(meta.get("tokens") or []) != prompt
                    or int(meta.get("n", -1)) != prefilled):
                kv_pack = None
        # end-to-end deadline: the ambient context (stamped into the
        # replica task by the handle / the X-Request-Deadline-Ms
        # ingress header) combined with an explicit request-dict
        # "deadline_ms" — tighter wins
        dl = deadlines.effective_deadline() or 0.0
        req_dl = deadlines.from_header(request.get("deadline_ms"))
        if req_dl:
            dl = min(dl, req_dl) if dl else req_dl
        if dl:
            rem = dl - time.time()
            # admission refusal: a sequence whose remaining budget
            # cannot cover its prefill + ONE decode step would only
            # burn pages and batch lanes producing tokens its caller
            # will never read.  Cost model: measured step EWMA x
            # (prefill chunks + 1); a cold engine (no measured step
            # yet) only refuses already-expired budgets.  The two
            # disaggregated phases price separately: a prefill-only
            # pass needs its chunks but no decode step, and a sequence
            # arriving WITH shipped KV needs one decode step but no
            # prefill chunks.  A block model's first item is its first
            # block: as many passes as its schedule has.
            need = 0.0
            if self._step_ewma > 0.0:
                # (the fewest passes: deep chunks, then the tail's)
                far = self._widest_chunk()
                chunks = prefilled // far \
                    + -(-(prefilled % far) // self.prefill_chunk)
                first = steps if block else 1
                if kv_pack is not None:
                    need = self._step_ewma * first
                elif prefill_only:
                    need = self._step_ewma * chunks
                else:
                    need = self._step_ewma * (chunks + first)
            if rem <= need:
                self._deadline_expired_total += 1
                deadlines.count_exceeded("admission")
                raise DeadlineExceededError(
                    f"remaining budget {max(rem, 0.0) * 1000:.0f}ms cannot "
                    f"cover prefill + one decode step "
                    f"(~{need * 1000:.0f}ms)", where="admission")
        with self._lock:
            seq = self._by_rid.get(rid)
            if seq is not None and seq.cancelled:
                # a grace-swept/cancelled sequence is TRUNCATED — a
                # retry must re-generate, not replay a partial result
                # presented as done
                del self._by_rid[rid]
                seq = None
            if seq is not None:
                # idempotent re-attach: a proxy retry after replica or
                # connection trouble resumes the SAME sequence (replay
                # of already-generated tokens + live continuation)
                seq.attach_count += 1
                seq.detached_at = None
                return seq
            if len(prompt) + max_new > min(self.cfg.max_seq_len,
                                           self.ctx_len):
                raise ValueError(
                    f"prompt+max_new_tokens = {len(prompt) + max_new} "
                    f"exceeds max_seq_len {self.cfg.max_seq_len}")
            total = len(prompt) + max_new
            room = min(g.max_tokens for g in self._groups.values())
            if total > room:
                raise LLMOverloadedError(
                    f"request needs {-(-total // self.page_size)} KV pages; "
                    f"replica has {room // self.page_size}")
            if len(self._queued) >= self.max_queue:
                raise LLMOverloadedError(
                    f"admission queue full ({self.max_queue})")
            seq = _Seq(rid, prompt, max_new, eos)
            if block:
                seq.blk = self._open_first_block(
                    seq, steps, bool(request.get("record_passes")))
            seq.trace_ctx = tracing.current_context()
            seq.submit_step = self._steps
            self._totals["submitted_total"] += 1
            seq.deadline = dl
            seq.cond = threading.Condition(self._lock)
            seq.attach_count = 1
            seq.prefill_export = prefill_only
            if kv_pack is not None:
                seq.kv_import = {"meta": kv_pack[0], "rows": kv_pack[1]}
            self._by_rid[rid] = seq
            self._queued.append(seq)
            self._cond.notify_all()  # wake the parked decode loop
        return seq

    def iter_tokens(self, seq: _Seq, emit_from: int = 0):
        """Blocking generator of token items for one consumer.

        Items are COALESCED: each carries every token generated since
        the consumer last drained (``{"i": <first index>, "tokens":
        [...], "done": bool}``) — under load the decode loop outruns
        the per-item streaming path (one stream push + one ref
        resolution + one SSE chunk each), so batching tokens into items
        is what lets 64+ concurrent streams ride one engine without the
        transport dominating.  TTFT is unaffected: the first item
        leaves the moment the first token exists.  Parked waits rely on
        per-sequence notifies and re-check every 2s — that bound (not
        the next token) is the worst-case latency for a pending
        cancellation async-exc on an idle consumer; an actively-fed
        consumer sees it within one flush interval."""
        i = max(0, int(emit_from))
        first = True
        while True:
            with self._cond:
                while True:
                    if seq.error is not None:
                        raise seq.error
                    n = len(seq.generated)
                    if seq.done and i >= n:
                        return
                    # the FIRST item flushes on one token (TTFT);
                    # after that, wait for stream_flush_tokens (or the
                    # end) so a fast decode loop doesn't pay the
                    # push+resolve+chunk transport per single token
                    flush = 1 if first else self.stream_flush_tokens
                    if n - i >= flush or (seq.done and n > i):
                        item = {"i": i, "tokens": list(seq.generated[i:n]),
                                "done": bool(seq.done)}
                        break
                    # per-seq notifies (flush boundaries, finish,
                    # cancel) do the real waking; the 2s timeout only
                    # bounds how long a pending cancellation async-exc
                    # can sit on a parked thread.  A short poll here
                    # melts down at scale: 256 parked streams polling
                    # at 10Hz is ~2.5k futex syscalls/s
                    (seq.cond or self._cond).wait(2.0)
            yield item
            first = False
            if item["done"]:
                return
            i = n

    def release(self, seq: _Seq) -> None:
        """One consumer detached (finished, disconnected, cancelled).
        The last detach of an unfinished sequence starts the grace
        clock; past it the loop cancels the sequence and recycles its
        pages instead of decoding to max_seq_len for nobody."""
        with self._lock:
            seq.attach_count = max(0, seq.attach_count - 1)
            if seq.attach_count == 0 and not seq.done:
                seq.detached_at = time.monotonic()

    def cancel(self, request_id: str) -> bool:
        with self._lock:
            seq = self._by_rid.get(request_id)
            if seq is None or seq.done:
                return False
            self._finish_seq(seq, cancelled=True)
            self._cond.notify_all()
            return True

    # ------------------------------------------------------------- stepping

    def _forward(self, tokens, q_pos, last_idx, groups, feed=None):
        """Dispatch one jitted forward with this engine's static sampling
        knobs; the per-call rng split only happens on the sampling path,
        so greedy engines run the exact pre-sampling program.  `groups`
        is `_pass_groups`'s; `feed` is a decode pass's (`_jit_forward`).
        The pools become the pass's; returns what stays on the device
        until it is read back: (the tokens, with the model's counter
        vector behind them if it counts; under `logit_trace` the two
        largest logits and their ids, else None)."""
        rng = None
        if self._sample_rng is not None:
            import jax

            self._sample_rng, rng = jax.random.split(self._sample_rng)
        if self._block:
            # a block model's token-shaped pass is its prefill, whose
            # logits nobody reads
            tok, self._pools = self._step_fn(
                self._model, self._params, self._pools, tokens, q_pos,
                last_idx, groups, head=False)
            self._clock.dispatched()
            return tok, None
        tok, self._pools, *top2 = self._step_fn(
            self._model, self._params, self._pools, tokens, q_pos,
            last_idx, groups, temperature=self.temperature,
            top_k=self.top_k, rng=rng, top2=self.logit_trace, feed=feed)
        self._clock.dispatched()
        return tok, (top2[0] if top2 else None)

    def _forward_block(self, tokens, q_pos, need, groups, feed):
        """Dispatch one block pass (`_jitted_block_forward`); returns
        its output as the device holds it."""
        out, self._pools = _jitted_block_forward()(
            self._model, self.family.block_sample, self._params,
            self._pools, tokens, q_pos, need, groups, feed)
        self._clock.dispatched()
        return out

    def _split_counters(self, next_tok, lanes: int, phase: str):
        """The host copy of a pass's output: its `lanes` tokens, and the
        model's counter vector behind them added to `phase`'s sums."""
        for name, value in zip(self._model_counters, next_tok[lanes:]):
            self._model_counters[name][phase] += int(value)
        return next_tok[:lanes]

    def _pass_groups(self, rows, lanes: int, cols: int, width: int,
                     decode: bool = False) -> Dict[str, Any]:
        """The `groups` of one pass of `lanes` x `cols` queries: every
        cache group's arrays, gathered at context `width` for a prefill
        pass, block tables `width` pages wide for a decode pass.  `rows`
        = [(lane, the sequence's `cache`, lo, hi)], the lane's queries at
        [lo, hi); a lane without a row is garbage, so `rows=[]` is a
        warm-up's pass: the same tree structure, and nothing counted
        (a real pass adds what it reads to the groups' totals)."""
        out = {}
        for kind, group in self._groups.items():
            with self._clock.host.get(group.host_span, _NO_SPAN):
                out[kind] = group.decode_arrays(rows, lanes, width, cols) \
                    if decode else group.prefill_arrays(rows, lanes, cols,
                                                        width)
        if rows:
            with self._clock.host["grid_count"] if decode else _NO_SPAN:
                for kind, group in self._groups.items():
                    group.count(rows, out[kind], decode)
        return out

    def _prefill_inputs(self, prefill_args, lanes: int, chunk: int,
                        width: int):
        """`_forward`'s arguments of a prefill pass of `lanes` lanes x
        `chunk` tokens at context `width` (a program of
        `_prefill_programs`) over `prefill_args` (`_plan_locked`); the
        lanes past them are garbage: [] is a warm-up's pass."""
        np = self._np
        tokens = np.zeros((lanes, chunk), np.int32)
        q_pos = np.zeros((lanes, chunk), np.int32)
        # a token an entry comes back (`_jit_forward`): the wide pass's
        # count whatever the lanes, the shape the decode pass feeds on
        last_idx = np.zeros((self.prefill_lanes,), np.int32)
        rows = []
        for lane, (seq, lo, hi, held) in enumerate(prefill_args):
            tokens[lane, :hi - lo] = seq.prefill_tokens[lo:hi]
            q_pos[lane, :hi - lo] = self._arange[lo:hi]
            last_idx[lane] = hi - lo - 1
            rows.append((lane, held, lo, hi))
        return tokens, q_pos, last_idx, self._pass_groups(
            rows, lanes, chunk, width)

    def _decode_inputs(self, decode_args, width: int, feed):
        """(`_forward`'s arguments, its `feed`) of a decode pass at
        block-table `width` over `decode_args` (`_plan_locked`), its
        input tokens taken from `feed` where the host has not read them;
        the lanes past them are garbage: [] is a warm-up's pass."""
        np = self._np
        b = self.max_batch
        if self._block:
            return self._block_inputs(decode_args, width, feed)
        tokens = np.zeros((b, 1), np.int32)
        src = np.full((b,), -1, np.int32)
        q_pos = np.zeros((b, 1), np.int32)
        rows = []
        for lane, (_seq, last, late, n, held) in enumerate(decode_args):
            tokens[lane, 0] = last
            src[lane] = late
            q_pos[lane, 0] = n - 1
            rows.append((lane, held, n - 1, n))
        return (tokens, q_pos, np.zeros((b,), np.int32),
                self._pass_groups(rows, b, 1, width, decode=True)), \
            (src, tuple(feed))

    def _block_inputs(self, decode_args, width: int, feed):
        """`_decode_inputs` of a block pass: (`_forward_block`'s
        arguments but `feed`, `feed`) over `decode_args` = [(sequence,
        the block's tokens or None where the device has them, the lane
        of the last pass's output they are in, the block's first
        position, the schedule's count, ...)]."""
        np = self._np
        b, n = self.max_batch, self._block
        tokens = np.zeros((b, n), np.int32)
        src = np.full((b,), -1, np.int32)
        q_pos = np.zeros((b, n), np.int32)
        need = np.zeros((b,), np.int32)
        rows = []
        for lane, (_seq, cur, late, p0, count, *_r, held) in enumerate(
                decode_args):
            if cur is not None:
                tokens[lane] = cur
            src[lane], need[lane] = late, count
            q_pos[lane] = self._arange[p0:p0 + n]
            rows.append((lane, held, p0, p0 + n))
        return (tokens, q_pos, need,
                self._pass_groups(rows, b, n, width, decode=True)), \
            (src, feed[0])

    def _trace_top2(self, seq: _Seq, lane: int, top2) -> None:
        """Lock held, just before `_emit_token`: the two largest logits
        of `lane` in the pass read back (`top2`: its host copy, None
        without `logit_trace`), for the token about to be emitted."""
        if top2 is not None:
            vals, ids = top2
            self._logit_trace.setdefault(seq.request_id, []).append(
                [len(seq.generated), float(vals[lane, 0]), int(ids[lane, 0]),
                 float(vals[lane, 1]), int(ids[lane, 1])])

    def _paged_width_buckets(self) -> List[int]:
        """Block-table width buckets the decode pass can emit:
        powers of four from 4 up to (and capped at) pages_per_seq.
        Coarser-than-pow-2 buckets trade at most a 4x width overshoot
        at small contexts (cheap: a grid step past a lane's last page
        fetches and computes nothing) for half the per-bucket jit
        compiles the warm-up burst has to pay."""
        return _pow4_widths(4, self.pages_per_seq)

    def _prefill_ctx_buckets(self) -> List[int]:
        """Context widths a prefill pass can gather: powers of four from
        4 x prefill_chunk up to (and capped at) ctx_len, the shape of
        `_paged_width_buckets`.  A pass takes the smallest that covers
        the longest context any of its lanes reads; the columns it drops
        are masked in every lane.  Below 4 chunks a narrower program
        saves less than its compile costs every replica's start-up, so
        an engine whose ctx_len is at most that has one: its ctx_len."""
        return _pow4_widths(4 * self.prefill_chunk, self.ctx_len)

    def _narrow_prefill_shape(self):
        """(lanes, width) of the ONE narrow prefill program, or None for
        an engine that has none.  A pass computes lanes x chunk slots
        whatever they hold, so a step with few prompts waiting wants few
        lanes; but every program is a compile at every replica's
        start-up, so the narrow pass exists at one context width, the
        SECOND of `_prefill_ctx_buckets`: wide enough for nearly every
        chat prompt, and at PREFILL_NARROW_LANES lanes it gathers what
        the wide pass gathers at its narrowest.  An engine with one
        prefill width has no narrow program, for the reason it has one
        width; nor has one whose wide pass is no wider."""
        if len(self._prefill_widths) < 2 \
                or self.prefill_lanes <= PREFILL_NARROW_LANES:
            return None
        return PREFILL_NARROW_LANES, self._prefill_widths[1]

    def _deep_prefill_shape(self):
        """(lanes, chunk) of the DEEP prefill pass, or None for an engine
        that has none: the wide pass's slots, `prefill_lanes` x
        `prefill_chunk`, laid out as PREFILL_NARROW_LANES lanes (whole
        blocks a lane, where the model generates in blocks) — the same
        matrix products over as many rows, so the same stall of the
        decodes behind it, and a prompt advances `prefill_lanes` /
        PREFILL_NARROW_LANES times as far a step.  An engine that has no
        narrow program has no deep one, for the same reasons."""
        if not self._narrow_prefill:
            return None
        chunk = self.prefill_lanes * self.prefill_chunk \
            // PREFILL_NARROW_LANES
        chunk -= chunk % (self._block or 1)
        return (PREFILL_NARROW_LANES, chunk) \
            if chunk > self.prefill_chunk else None

    def _widest_chunk(self) -> int:
        """The most tokens a lane of a prefill pass takes: the deep
        pass's, where there is one."""
        return (self._deep_prefill or (0, self.prefill_chunk))[1]

    def _prefill_programs(self) -> List[tuple]:
        """Every (lanes, chunk, width) a prefill pass can have, the
        programs `_warm_prefill_buckets` compiles: the wide pass at each
        context width — at the SECOND alone where there is a deep pass,
        which takes every context beyond it, so that an engine compiles
        as many programs as it did without one (a program is seconds of
        every replica's start-up; three short prompts at once gather the
        second width's columns, masked) — the narrow pass at its one
        width, and the deep pass at the second width and every wider one
        (a deep chunk is where the wide pass's first width ends:
        `_prefill_ctx_buckets`), so that a long prompt is deep from its
        first chunk."""
        widths, chunk = self._prefill_widths, self.prefill_chunk
        narrow, deep = self._narrow_prefill, self._deep_prefill
        programs = [(self.prefill_lanes, chunk, w)
                    for w in (widths[1:2] if deep else widths)]
        if narrow:
            programs.append((narrow[0], chunk, narrow[1]))
        if deep:
            programs += [(*deep, w) for w in widths[1:]]
        return programs

    def _prefill_shape(self, waiting):
        """(lanes, chunk, width) of the pass for `waiting` = [(pos, end)],
        the prompts in prefill in admission order (as many as the wide
        pass has lanes), each with its rows [pos, end) still to prefill.
        One of three, from what the step observes alone:

        DEEP (`_deep_prefill_shape`), where one of the prompts that would
        ride it — the first PREFILL_NARROW_LANES — has a whole deep chunk
        left, so a deep lane would be full; or where any prompt's context
        lies beyond the narrow program's width: the wide pass would pay
        its slots at a wide context there, and the deep pass reads a
        quarter of the lanes.  It advances its first prompts by up to a
        deep chunk each; the others keep their lanes and pages and wait
        their turn, first come first served.
        NARROW, where at most PREFILL_NARROW_LANES prompts wait and fit
        its width; else WIDE, `prefill_lanes` lanes at the smallest width
        that covers the longest context (at the narrow width, never
        another, where there is a deep pass).  The chunk is settled here,
        BEFORE the plan advances anything by it."""
        chunk, widths = self.prefill_chunk, self._prefill_widths
        narrow, deep = self._narrow_prefill, self._deep_prefill
        longest = max(min(pos + chunk, end) for pos, end in waiting)
        if deep:
            lanes, far = deep
            if longest > narrow[1] or any(end - pos >= far
                                          for pos, end in waiting[:lanes]):
                reach = max(min(pos + far, end)
                            for pos, end in waiting[:lanes])
                return lanes, far, next(w for w in widths[1:] if w >= reach)
        if narrow and len(waiting) <= narrow[0] and longest <= narrow[1]:
            return narrow[0], chunk, narrow[1]
        return self.prefill_lanes, chunk, next(
            w for w in (widths[1:] if deep else widths) if w >= longest)

    def _warm_prefill_buckets(self, but) -> None:
        """Compile every prefill program up front (`_prefill_programs`),
        at the FIRST prefill pass, for the reason `_warm_paged_buckets`
        gives for decode (the deployment warm-up request lands here):
        garbage lanes only (slot 0, every context column masked).  `but`
        is the (lanes, chunk, width) that pass is about to run itself, so
        an engine with one program runs nothing here."""
        for shape in self._prefill_programs():
            if shape != but:
                self._forward(*self._prefill_inputs([], *shape))

    def _warm_paged_buckets(self) -> None:
        """Compile every paged block-table width bucket up front, at
        the FIRST decode step.  A bucket-crossing jit compile costs
        seconds (interpret mode especially), and a compile stalling a
        DEADLINED in-flight request past deadline_force_cancel_grace_s
        gets the whole worker force-killed — so pay all compiles in one
        burst while nothing is at stake (the deployment warm-up request
        lands here).  Garbage lanes only; the jit cache is process-wide,
        so engines sharing a config/geometry pay once."""
        forward = self._forward_block if self._block else self._forward
        for width in self._paged_width_buckets():
            inputs, feed = self._decode_inputs([], width, self._no_feed)
            forward(*inputs, feed=feed)

    def _lower_decode(self, width: int):
        """The decode step at block-table `width`, lowered and not run:
        for its text (`device_report`) or the compiler's analysis."""
        inputs, feed = self._decode_inputs([], width, self._no_feed)
        if self._block:
            return _jitted_block_forward().lower(
                self._model, self.family.block_sample, self._params,
                self._pools, *inputs, feed)
        tokens, q_pos, last_idx, groups = inputs
        return _jitted_forward(self.temperature, self.top_k,
                               self.logit_trace).lower(
            self._model, self._params, self._pools, tokens, q_pos, last_idx,
            self._np.zeros((2,), "uint32"),  # rng, unused
            groups, feed)

    def device_report(self) -> Dict[str, Any]:
        """`ops.device_report()` plus what this engine put on the device
        (`param_bytes`: the tree as held, in the dtypes the serving module
        declares) and how its decode step lowered: the Pallas kernel is a
        `tpu_custom_call` when compiled for the chip, and absent from the
        text under the interpreter.  Traces the decode step once more
        (nothing runs); not for a hot path."""
        import dataclasses

        import jax

        from ray_tpu.ops import device_report
        from ray_tpu.serve import cache_groups

        def nbytes(tree) -> int:
            return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))

        rep = device_report()
        share = getattr(self.cfg, "share", None)
        # `attention_impl` is a constant: the engine has one decode path.
        # benchmarks/kinds/serve.py and serve_laguna.py index it; theirs
        # to drop (the next `benchmark` PR), then this line's too
        rep.update(attention_impl="paged",
                   # the config's fields, and: the family, what of each
                   # layer this chip holds (a config that is a share
                   # says), the cache specification by layer
                   model={**{f.name: getattr(self.cfg, f.name)
                             for f in dataclasses.fields(self.cfg)
                             if "dtype" not in f.name},
                          "family": self.family.__name__.rsplit(".", 1)[-1],
                          "share": share() if share else None,
                          "cache_spec": [list(layer) for layer
                                         in self.cfg.cache_spec()]},
                   dtype=str(jax.numpy.dtype(self.cfg.dtype)),
                   page_size=self.page_size,
                   param_bytes=nbytes(self._params),
                   kv_pool_bytes=nbytes(self._pools),
                   **cache_groups.pool_bytes(self._groups, self._pools),
                   # executables behind the jitted stepper, all engines
                   # of this process: constant once warm-up is done
                   compiled_steps=sum(fn._cache_size()
                                      for fn in _forward_cache.values()))
        if self.logit_trace:
            with self._lock:
                rep["logit_trace"] = {rid: list(rows) for rid, rows
                                      in self._logit_trace.items()}
        text = self._lower_decode(self._paged_width_buckets()[0]).as_text()
        rep["decode_has_tpu_custom_call"] = "tpu_custom_call" in text
        return rep

    def _finish_seq(self, seq: _Seq, cancelled: bool = False) -> None:
        """Lock held.  Mark done and give back at once what the cache
        groups hold for the sequence."""
        seq.done = True
        seq.cancelled = cancelled
        if cancelled:
            self._cancelled_total += 1
        else:
            self._totals["finished_total"] += 1
        seq.done_at = time.monotonic()
        if seq.trace_ctx is not None and seq.trace_ctx.sampled:
            self._record_request_spans(seq)
        if seq.cond is not None:
            seq.cond.notify_all()
        # a pass being built may hold the dictionary: a new one, so that
        # a second call gives nothing back twice
        held, seq.cache = seq.cache, {}
        for kind, st in held.items():
            self._groups[kind].release(st)
        seq.kv_import = None
        if seq in self._active:
            self._active.remove(seq)
        try:
            self._queued.remove(seq)
        except ValueError:
            pass

    def _record_request_spans(self, seq: _Seq) -> None:
        """Lock held, the sequence just ended: its stages as spans under
        the caller's trace — `llm.queue` (submit to admission),
        `llm.prefill` (to the first token), `llm.decode` (to the end);
        a block model's `llm.prefill` ends with its prompt's whole
        blocks and `llm.first_block` follows it (to the first block
        delivered); a stage it never reached has none, the one it ended
        in carries the error.  Once a request, not once a token.  `first_step` and
        `last_step` are `llm.step`'s `n` on the profiler's timeline."""
        wall = time.time() - time.monotonic()  # monotonic -> epoch
        attrs = {"request_id": seq.request_id,
                 "prompt_tokens": len(seq.prompt),
                 "prefix_tokens_shared": seq.prefix_tokens,
                 "tokens_generated": len(seq.generated)}
        first = (("llm.prefill", seq.admitted_at, seq.first_token_at,
                  seq.admit_step, seq.first_token_step),)
        if seq.blk is not None:
            first = (("llm.prefill", seq.admitted_at, seq.prefilled_at,
                      seq.admit_step, seq.prefilled_step),
                     ("llm.first_block", seq.prefilled_at,
                      seq.first_token_at, seq.prefilled_step,
                      seq.first_token_step))
        stages = (
            ("llm.queue", seq.submitted_at, seq.admitted_at,
             seq.submit_step, seq.admit_step), *first,
            ("llm.decode", seq.first_token_at, seq.done_at,
             seq.first_token_step, self._steps))
        for name, start, end, first, last in stages:
            if start is None:
                break
            ended_here = end is None or name == "llm.decode"
            if end is None:
                end, last = seq.done_at, self._steps
            more = {"blocked_by": seq.blocked_by} \
                if name == "llm.queue" else {}
            tracing.record_span(
                name, wall + start, wall + end, seq.trace_ctx,
                attributes=dict(attrs, first_step=first, last_step=last,
                                **more),
                error="cancelled" if ended_here and seq.cancelled else "")

    def _sweep(self, now: float) -> None:
        """Lock held: expire sequences past their deadline (pages
        recycle NOW; the consumer sees the typed error), cancel
        sequences abandoned past the grace window, and forget finished
        ones past the replay TTL."""
        from ray_tpu._private.config import config

        wall = time.time()
        for seq in list(self._active) + list(self._queued):
            if seq.deadline and wall >= seq.deadline and not seq.done:
                self._deadline_expired_total += 1
                # a sequence still parked at admission expired WAITING,
                # not decoding — the queued/running split is the signal
                # operators act on (shed earlier vs loosen budgets)
                where = "queued" if seq.state == _QUEUED else "running"
                deadlines.count_exceeded(where)
                seq.error = DeadlineExceededError(
                    f"sequence {seq.request_id} exceeded its deadline "
                    f"while {where} ({len(seq.generated)}/{seq.max_new} "
                    f"tokens generated)", where=where)
                self._finish_seq(seq, cancelled=True)
                continue
            if (seq.attach_count == 0 and seq.detached_at is not None
                    and now - seq.detached_at > self.detach_grace_s):
                self._finish_seq(seq, cancelled=True)
        ttl = float(config.llm_done_seq_ttl_s)
        for rid, seq in list(self._by_rid.items()):
            if seq.done and seq.done_at is not None \
                    and now - seq.done_at > ttl:
                del self._by_rid[rid]

    def _admit_locked(self) -> None:
        """Lock held, once a step: admit from the queue's head while a
        lane and every group have room.  Where the head stays queued the
        step counts as blocked, by what refused it: `lanes`, or the kind
        of the first group whose `fit` had no plan."""
        blocked = "lanes"
        while self._queued and len(self._active) < self.max_batch:
            seq = self._queued[0]
            # shipped rows are attached whole: no prefix to look for
            tokens = seq.prefill_tokens if seq.kv_import is None else None
            plans = {kind: group.fit(seq.total_len, tokens)
                     for kind, group in self._groups.items()}
            if None in plans.values():
                # head-of-line waits for a group to have room
                blocked = next(k for k, plan in plans.items() if plan is None)
                break
            self._queued.popleft()
            there, split = 0, False
            for kind, group in self._groups.items():
                seq.cache[kind], n, copy = group.admit(plans[kind])
                there = max(there, n)
                if copy is not None:
                    # the shared head of a page into the private one
                    # (inside a step: never concurrent with a forward)
                    from ray_tpu.models.cache import copy_slots

                    self._pools = copy_slots(self._pools, self._kinds,
                                             kind, *copy)
                    split = True
            if there:
                # prefill starts at the first token no group has yet
                seq.pos = seq.prefix_tokens = there
                m = self.metrics()
                if m is not None:
                    m["prefix_hits"].inc(
                        tags={"kind": "cow" if split else "page"})
            seq.state = _PREFILL
            seq.admitted_at = time.monotonic()
            seq.admit_step = self._steps
            self._totals["admitted_total"] += 1
            self._totals["queue_wait_secs_total"] += \
                seq.admitted_at - seq.submitted_at
            self._active.append(seq)
            if self._block and seq.kv_import is None \
                    and seq.pos >= self._prefill_end(seq):
                # shorter than a block, or every whole block shared
                self._prefilled(seq)
                if seq.prefill_export:
                    self._export_seq_locked(seq, None)
        if self._queued:
            self._admit_blocked[blocked] += 1
            self._queued[0].blocked_by = blocked
            # the rest of `admit` under a span that says so
            self._clock.phase("admit", blocked=blocked)

    def _prefill_end(self, seq: _Seq) -> int:
        """The tokens a sequence's prefill passes hold: all it was given
        — or their whole blocks, the rest opening the first block."""
        n = len(seq.prefill_tokens)
        return n - n % self._block if self._block else n

    def _prefilled(self, seq: _Seq) -> None:
        """Lock held: the last chunk of the prompt is dispatched (or
        there was none to dispatch)."""
        seq.state = _SHIP if seq.prefill_export else _DECODE
        seq.prefilled_at = time.monotonic()
        seq.prefilled_step = self._steps

    def _open_first_block(self, seq: _Seq, steps: int,
                          record: bool = False) -> _Block:
        """The block the tokens a sequence was given end in: their tail
        past the last whole block, then masks; with the schedule of
        `steps` passes a block (B / T a pass, the remainder to the first
        passes)."""
        n, mask = self._block, self.cfg.mask_token_id
        p0 = self._prefill_end(seq)
        tail = seq.prefill_tokens[p0:]
        sched = tuple(n // steps + (k < n % steps) for k in range(steps))
        return _Block(p0, tail + [mask] * (n - len(tail)), sched, record)

    def _note_first_token(self, seq: _Seq) -> None:
        """Lock held: the sequence has a token; on its first, count it
        and the time since admission and since submit."""
        if seq.first_token_at is not None:
            return
        seq.first_token_at = time.monotonic()
        seq.first_token_step = self._steps
        self._totals["first_tokens_total"] += 1
        self._totals["prefill_wait_secs_total"] += \
            seq.first_token_at - seq.admitted_at
        m = self.metrics()
        if m is not None:
            m["ttft"].observe(seq.first_token_at - seq.submitted_at)

    def _emit_token(self, seq: _Seq, token: int) -> None:
        """Lock held: append one generated token, finish on EOS/budget,
        and wake THIS sequence's consumer at flush boundaries only —
        an engine-wide notify_all per step would thundering-herd every
        parked stream thread per token."""
        seq.generated.append(int(token))
        n = len(seq.generated)
        self._note_first_token(seq)
        if (seq.eos is not None and int(token) == seq.eos) \
                or n >= seq.max_new:
            self._finish_seq(seq)
        elif seq.cond is not None \
                and (n - 1) % self.stream_flush_tokens == 0:
            # aligned with the consumer cursor AFTER the n=1 TTFT item
            # (i=1): wakes land exactly when a full flush quota exists
            # past it (n = 1, F+1, 2F+1, ...), not one window late
            seq.cond.notify_all()

    def _emit_block(self, seq: _Seq, tokens: List[int]) -> None:
        """Lock held: a block with no mask left, delivered — `tokens`,
        what the sequence was not given of it — cut at the budget or
        behind an `eos`; a block is an item of the stream."""
        room = seq.max_new - len(seq.generated)
        self._block_totals["block_tokens_discarded_total"] += \
            max(len(tokens) - room, 0)
        tokens = tokens[:room]
        ended = seq.eos is not None and seq.eos in tokens
        if ended:
            tokens = tokens[:tokens.index(seq.eos) + 1]
        seq.generated.extend(tokens)
        self._note_first_token(seq)
        m = self.metrics()
        if m is not None:
            m["tokens"].inc(len(tokens), tags={"phase": "decode"})
        if ended or len(seq.generated) >= seq.max_new:
            self._finish_seq(seq)
        elif seq.cond is not None:
            seq.cond.notify_all()

    # ------------------------------------------- disaggregated prefill
    # Export and import both touch the KV pools, so they only ever run
    # INSIDE a step, under the engine lock, never concurrent with a
    # forward (whose donated pool buffers would be invalidated under a
    # concurrent reader/writer).

    def _kv_row_slots(self, seq: _Seq, n: int, take: bool = False):
        """Lock held.  By cache kind, the slots of the rows a sequence
        with `n` tokens ships or (`take`: taken first) receives."""
        return {kind: group.row_slots(seq.cache[kind], n, take)
                for kind, group in self._groups.items()}

    def _written(self, seq: _Seq) -> None:
        """Lock held: every pass that writes the sequence's rows below
        `seq.pos` is dispatched, so to any later pass they stand."""
        for kind, group in self._groups.items():
            group.written(seq.cache[kind], seq.prefill_tokens, seq.pos)

    def _attach_imports_locked(self) -> bool:
        """Scatter shipped KV rows for freshly-admitted sequences into
        this engine's pools; the sequence enters decode at the shipped
        position with the prefill replica's first generated token
        already emitted.  Returns True when any import happened."""
        imports = [s for s in self._active
                   if s.kv_import is not None and s.state == _PREFILL]
        for seq in imports:
            pack, seq.kv_import = seq.kv_import, None
            n = int(pack["meta"]["n"])
            first_tok = pack["meta"]["first_token"]
            from ray_tpu.models.cache import scatter_slots

            self._pools = scatter_slots(
                self._pools, self._kinds,
                self._kv_row_slots(seq, n, take=True), pack["rows"])
            seq.pos = n
            n_pages = -(-n // self.page_size)
            self._kv_pages_shipped_in += n_pages
            m = self.metrics()
            if m is not None:
                m["shipped"].inc(n_pages, tags={"direction": "in"})
            # imported pages carry a complete prompt prefix: later
            # same-prefix admissions share instead of re-importing
            self._written(seq)
            seq.state = _DECODE
            if self._block:
                self._prefilled(seq)   # its first tokens: a block pass's
            else:
                self._emit_token(seq, first_tok)
        return bool(imports)

    def _export_seq_locked(self, seq: _Seq, first_token: Optional[int]
                           ) -> None:
        """Prefill-only sequence finished its last chunk: gather its KV
        rows to host memory, stash them as the export payload, and
        finish the sequence (pages recycle NOW — the payload is a host
        copy).  ``prefill_request`` wakes on the finish notify.  A block
        model's prefill makes no token (`first_token` None) and ships
        the rows of the prompt's whole blocks."""
        from ray_tpu.models.cache import gather_slots

        self._note_first_token(seq)
        if first_token is not None:
            first_token = int(first_token)
            seq.generated.append(first_token)
        n = seq.pos
        n_pages = -(-n // self.page_size)
        seq.export_payload = {
            "meta": {"request_id": seq.request_id,
                     "tokens": list(seq.prompt),
                     "first_token": first_token,
                     "n": n, "pages": n_pages,
                     "page_size": self.page_size},
            "rows": gather_slots(self._pools, self._kinds,
                                 self._kv_row_slots(seq, n)),
        }
        self._kv_pages_shipped_out += n_pages
        m = self.metrics()
        if m is not None:
            m["shipped"].inc(n_pages, tags={"direction": "out"})
        seq.state = _SHIP
        self._finish_seq(seq)

    def prefill_request(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """Run ONLY the prefill phase for ``request`` and return the
        export payload ({"meta", "rows"}) for shipping to a decode
        replica.  Drives the engine inline when no pinned loop is
        running (bench/test harnesses); under a loop it parks on the
        sequence condition like any consumer.  Idempotent by
        request_id within the done-seq TTL: a retried prefill replays
        the stashed payload instead of recomputing."""
        req = dict(request)
        req["_phase"] = "prefill"
        seq = self.submit(req)
        try:
            while True:
                with self._lock:
                    if seq.export_payload is not None:
                        return seq.export_payload
                    if seq.error is not None:
                        raise seq.error
                    if seq.done:
                        # swept (deadline/grace) before export finished
                        raise LLMOverloadedError(
                            f"prefill for {seq.request_id} was cancelled "
                            f"before its pages could be exported")
                    inline = not self._loop_running
                    if not inline:
                        (seq.cond or self._cond).wait(0.1)
                if inline:
                    if not self.step():
                        time.sleep(0.001)
        finally:
            self.release(seq)

    def step(self) -> bool:
        """One engine iteration, ONE STEP AHEAD of its read-backs: admit;
        build and dispatch this step's prefill pass (one chunk of each
        waiting prompt) and decode pass (every decoding sequence); THEN
        read back the previous step's outputs and emit their tokens.
        The device starts step n+1 the moment step n ends, and the
        host's read-back, emission, admission, build and dispatch run
        while it computes.

        Nothing the host needs for the next pass depends on the last
        one's result but the token itself, which the next decode pass
        takes on the device (`_jit_forward`'s `feed`): positions, slots,
        tables and window pages advance at dispatch, and a sequence is
        never dispatched past its `max_new` count.  What the host learns
        a step late is an `eos`, a cancel or an expiry: that sequence's
        lane-step in flight is dropped when read
        (`decode_lane_steps_wasted_total`); it wrote one row into a page
        of the sequence's own reservation, and whoever takes the page
        next writes after it, the device running passes in the order
        they were dispatched.

        Returns False when there was nothing to dispatch and nothing to
        read (the loop then parks on the condition)."""
        t_step = self._clock.begin(self._steps)
        try:
            return self._step(t_step)
        finally:
            decode_dt = self._clock.end()
            m = self.metrics()
            if m is not None and decode_dt > 0.0:
                m["decode_step"].observe(decode_dt)

    def drain(self) -> bool:
        """Read back and emit whatever is in flight, dispatching nothing:
        for whoever needs the engine's results to be the sequences'
        (`generate_batch`'s end, `save_state`, `stop`).  The stepping
        thread's: an engine with a pinned loop is drained by its loop.
        A step of its own on the clock.  True when something was read."""
        self._clock.begin(self._steps)
        try:
            return self._read_back()
        finally:
            self._clock.end()

    def _drain_inline(self) -> None:
        """`drain` for a caller that is not the stepping thread's loop:
        an engine stepped inline is drained here, a pinned loop reads
        what it has in flight itself."""
        with self._lock:
            inline = not self._loop_running
        if inline:
            self.drain()

    def _step(self, t_step: float) -> bool:
        """`step`'s body.  `phase(key)` is the clock's boundary: it ends
        the open phase, opens `key` and returns its one clock read."""
        now = time.monotonic()
        with self._lock:
            self._sweep(now)
            self._admit_locked()
            imported = self._attach_imports_locked()
            with self._clock.host["plan"]:
                shape, prefill_args, decode_args = self._plan_locked()
        step = self._steps
        # an unread step before this one: its tokens are on the device
        ahead = bool(self._flight)
        feed, self._feed = self._feed, list(self._no_feed)
        step_tokens = 0
        if prefill_args:
            step_tokens += self._dispatch_prefill(step, prefill_args, shape)
        if decode_args:
            self._dispatch_decode(step, decode_args, feed)
            self._totals["runahead_decode_steps_total"] += ahead
            step_tokens += len(decode_args) * (self._block or 1)
        # the previous step's outputs; this step's too where a prompt
        # ended whose pages are to be shipped (`prefill_request` waits
        # for them, and the export reads the pools behind every pass)
        exports = any(seq.prefill_export and hi == self._prefill_end(seq)
                      for seq, _lo, hi, *_r in prefill_args)
        read = self._read_back(None if exports else step)
        if not (prefill_args or decode_args or read):
            with self._lock:
                self._last_batch = 0
                self._set_gauges()  # idle must publish zeros, not
                # freeze the last busy step's values into the ring
            return imported  # an import that finished immediately
            # (max_new=1 / eos) still counts as work done
        self._steps += 1
        self._last_batch = len(decode_args)
        # step-cost estimate for deadline admission (prefill + one
        # decode step).  Admission wants "can this POSSIBLY finish", so
        # the estimate must be a floor-ish typical cost: a faster step
        # pulls it down immediately (the first post-compile step erases
        # the multi-second jit-compile sample), and slow outliers (a GC
        # pause, a compile for a new shape) are clamped so one huge
        # step cannot poison the estimate into shedding healthy traffic
        dt = time.perf_counter() - t_step
        if self._step_ewma == 0.0 or dt < self._step_ewma:
            self._step_ewma = dt
        else:
            self._step_ewma = 0.9 * self._step_ewma \
                + 0.1 * min(dt, 5.0 * self._step_ewma)
        self._set_gauges(len(decode_args), step_tokens)
        return True

    def _plan_locked(self):
        """Lock held: what this step's passes will hold — the prefill
        pass's shape (`_prefill_shape`; None without one) and a chunk of
        it for each prefilling sequence it has a lane for, a position of
        each decoding one — with every group advanced over it, and what
        they hold for it now (a sequence that ends meanwhile gets a new
        `cache`)."""
        waiting = [s for s in self._active
                   if s.state == _PREFILL][:self.prefill_lanes]
        shape, prefill_args = None, []
        if waiting:
            rows = [(s.pos, self._prefill_end(s)) for s in waiting]
            lanes, chunk, _width = shape = self._prefill_shape(rows)
            for seq, (lo, end) in zip(waiting[:lanes], rows):
                hi = min(lo + chunk, end)
                self._advance(seq, lo, hi)
                prefill_args.append((seq, lo, hi, seq.cache))
        if self._block:
            return shape, prefill_args, self._plan_blocks_locked()
        # the decoding sequences as this step found them (one whose
        # prompt ends in this step's prefill pass decodes from the next
        # on), but for those whose every token is dispatched
        decode_args = []
        for seq in self._active:
            if seq.state != _DECODE \
                    or len(seq.generated) + seq.ahead >= seq.max_new:
                continue
            # the newest token: on the device while unread (`last` is
            # then not looked at), else the host's
            last = (seq.generated[-1] if seq.generated
                    else seq.prefill_tokens[-1])
            self._advance(seq, seq.pos, seq.pos + 1)
            decode_args.append((seq, last, seq.feed if seq.ahead else -1,
                                seq.pos + 1, seq.cache))
        return shape, prefill_args, decode_args

    def _plan_blocks_locked(self):
        """`_plan_locked`'s decode part for a block model: the next pass
        of every decoding sequence that has a block left — [(sequence,
        the block's tokens where the host knows them, else None and the
        lane of the last pass's output that holds them, the block's
        first position, the positions the schedule has the pass unmask
        at least, the pass's number in its block, whether it is the
        commit where the host knows, the sequence's `cache`)]: `_Block`."""
        decode_args = []
        n, mask = self._block, self.cfg.mask_token_id
        for seq in self._active:
            blk = seq.blk
            if seq.state != _DECODE \
                    or blk.p0 >= len(seq.prompt) + seq.max_new:
                continue   # or every block of it is dispatched
            cur, late, commit = None, seq.feed, None
            if blk.known:
                cur, late, commit = blk.cur, -1, mask not in blk.cur
            count = blk.sched[blk.k] if blk.k < len(blk.sched) else n
            self._advance(seq, blk.p0, blk.p0 + n)
            decode_args.append((seq, cur, late, blk.p0, count, blk.k,
                                commit, seq.cache))
        return decode_args

    def _next_block(self, seq: _Seq) -> None:
        """Lock held: the block's commit is dispatched, so its rows stand
        and may be published; the next pass opens the block behind it."""
        blk, n = seq.blk, self._block
        blk.p0 += n
        blk.k, blk.known = 0, True
        blk.cur = [self.cfg.mask_token_id] * n
        seq.pos = blk.p0
        self._written(seq)

    def _advance(self, seq: _Seq, lo: int, hi: int) -> None:
        """Lock held: the sequence's next pass has queries at [lo, hi)."""
        for kind, group in self._groups.items():
            group.advance(seq.cache[kind], lo, hi)

    def _dispatch_prefill(self, step: int, prefill_args, shape) -> int:
        """Chunked prefill, batched across lanes: the sequences of
        `prefill_args` advance one chunk each in ONE pass of the fixed
        `shape` (lanes, chunk, width) the plan settled (`_prefill_shape`;
        empty lanes are garbage), in the steps where a prompt waits — a
        burst of N short admissions costs N/lanes passes, while a LONG
        prompt still shares the loop with in-flight decodes instead of
        monopolizing it: a pass has at most the wide pass's slots.  Its
        cost tracks USED slots and context, at one program a shape.
        Returns the prompt tokens the pass holds."""
        phase = self._clock.phase
        phase("prefill_build")
        ctx_rows = [hi for _s, _lo, hi, *_r in prefill_args]
        lanes, chunk, width = shape
        if not self._prefill_warm:
            self._prefill_warm = True
            t0 = time.perf_counter()
            self._warm_prefill_buckets(but=shape)
            self._warm_secs["prefill"] += time.perf_counter() - t0
        inputs = self._prefill_inputs(prefill_args, *shape)
        phase("prefill_dispatch", width=width, lanes=lanes, chunk=chunk)
        out, top2 = self._forward(*inputs)
        self._feed[1] = out
        self._prefill_steps += 1
        chunk_tokens = sum(hi - lo for _s, lo, hi, *_r in prefill_args)
        self._totals["prefill_tokens_total"] += chunk_tokens
        self._totals["prefill_slots_total"] += lanes * chunk
        self._totals["prefill_narrow_passes_total"] += \
            lanes < self.prefill_lanes and chunk == self.prefill_chunk
        self._totals["prefill_deep_passes_total"] += \
            chunk != self.prefill_chunk
        self._totals["prefill_ctx_rows_total"] += sum(ctx_rows)
        self._totals["prefill_ctx_cols_total"] += lanes * width
        self._prefill_passes_by_width[width] += 1
        owed = []
        with self._lock:
            for lane, (seq, lo, hi, *_rest) in enumerate(prefill_args):
                if seq.done:
                    continue  # cancelled mid-build: pages already back
                seq.pos = hi
                # the pages this chunk completes are immutable from this
                # pass on, which is dispatched: later admissions with the
                # same prompt prefix may share them (their passes run after)
                self._written(seq)
                if hi < self._prefill_end(seq):
                    continue
                self._prefilled(seq)
                if not self._block or seq.prefill_export:
                    # a token is owed: the pass's at this lane, or (a
                    # block model's) the read that ships the pages
                    seq.ahead += 1
                    seq.feed = self._no_feed[0].shape[0] + lane
                    owed.append((lane, seq))
        self._flight.append(_Flight("prefill", step, out, top2, owed))
        m = self.metrics()
        if m is not None:
            m["tokens"].inc(chunk_tokens, tags={"phase": "prefill"})
        return chunk_tokens

    def _dispatch_decode(self, step: int, decode_args, feed) -> None:
        """The token-level decode batch: one position of every decoding
        sequence, its input token taken from `feed` (the last step's
        outputs, on the device) where the host has not read it yet."""
        phase = self._clock.phase
        phase("decode_build")
        if not self._paged_warm:
            self._paged_warm = True
            t0 = time.perf_counter()
            self._warm_paged_buckets()
            self._warm_secs["decode"] += time.perf_counter() - t0
        # page-granular context: block tables + context lengths.  The
        # table width snaps to the smallest _paged_width_buckets() entry
        # covering the max used pages across lanes: decode cost tracks
        # USED context, and the jit retrace per bucket is
        # O(log pages_per_seq) traces total.
        block = self._block
        max_used = max(-(-(arg[3] + block) // self.page_size)
                       for arg in decode_args)
        width = next(w for w in self._paged_width_buckets()
                     if w >= max_used)
        inputs, feed = self._decode_inputs(decode_args, width, feed)
        phase("decode_dispatch")
        if block:
            out, top2 = self._forward_block(*inputs, feed=feed), None
        else:
            out, top2 = self._forward(*inputs, feed=feed)
        self._feed[0] = out
        self._decode_steps += 1
        self._totals["decode_lane_steps_total"] += len(decode_args)
        owed = []
        with self._lock:
            for lane, (seq, *rest) in enumerate(decode_args):
                if seq.done:
                    continue  # cancelled while we built
                seq.ahead += 1
                seq.feed = lane
                if not block:
                    seq.pos += 1
                    owed.append((lane, seq))
                    continue
                cur, _late, p0, _count, k, commit, _held = rest
                owed.append((lane, seq, p0, cur))
                layers = len(self._kinds)
                self._block_totals["block_rows_read_total"] += \
                    layers * (p0 + block)
                self._block_totals["block_open_rows_rewritten_total"] += \
                    layers * block * (k > 0)
                if commit:
                    self._next_block(seq)
                else:
                    seq.blk.k, seq.blk.known = k + 1, False
        self._flight.append(_Flight("decode", step, out, top2, owed))
        m = self.metrics()
        if m is not None and not block:   # a block's: when delivered
            m["tokens"].inc(len(decode_args), tags={"phase": "decode"})

    def _read_back(self, before: Optional[int] = None) -> bool:
        """Read the passes in flight that steps before `before`
        dispatched (all of them without it), oldest first, and emit
        their tokens: the `sync` phase is the wait for the device, the
        `emit` phase the lock section.  A sequence that ended while its
        token was in flight gets none.  True when a pass was read."""
        np = self._np
        phase = self._clock.phase
        flight, read = self._flight, False

        def due() -> bool:
            return bool(flight) and (before is None
                                     or flight[0].step < before)

        while due():
            rec = flight.popleft()
            read = True
            phase(rec.kind + "_sync", of=rec.step)
            host = np.asarray(rec.out)  # device sync: the pass is done
            top2 = rec.top2 and tuple(np.asarray(x) for x in rec.top2)
            phase(rec.kind + "_emit")
            if flight and not due():
                # the last read of this step, a newer pass on the device:
                # what the host does from here to its next dispatch has
                # that pass's time to fit in
                self._clock.turnaround(of=flight[-1].step)
            lanes = host.shape[0] - len(self._model_counters)
            toks = self._split_counters(host, lanes, rec.kind)
            if self._block and rec.kind == "decode":
                self._read_blocks(rec, toks)
                continue
            with self._lock:
                for lane, seq in rec.lanes:
                    seq.ahead -= 1
                    if seq.done:
                        # it ended (eos, cancel, expiry) while this
                        # token was in flight; its pages are back
                        self._totals["decode_lane_steps_wasted_total"] \
                            += rec.kind == "decode"
                    elif seq.prefill_export:
                        self._export_seq_locked(
                            seq, None if self._block else int(toks[lane]))
                    else:
                        self._trace_top2(seq, lane, top2)
                        self._emit_token(seq, int(toks[lane]))
        return read

    def _read_blocks(self, rec: _Flight, out) -> None:
        """`_read_back`'s lock section for a block pass: `out`, its host
        copy without the model's counters (`_jitted_block_forward`).  A
        lane's pass is counted by what it found; a block with no mask
        left is delivered, once; and what the sequence's `_Block` says
        of the pass to come is brought up to what this one left."""
        n, mask, b = self._block, self.cfg.mask_token_id, self.max_batch
        state = out[:b * n].reshape(b, n)
        masked, moved, over = out[b * n:].reshape(3, b)
        totals = self._block_totals
        lanes = {"denoise": 0, "commit": 0}
        with self._lock:
            for lane, seq, p0, read_as in rec.lanes:
                seq.ahead -= 1
                lanes["denoise" if masked[lane] else "commit"] += 1
                totals["block_tokens_transferred_total"] += int(moved[lane])
                totals["block_tokens_over_threshold_total"] += \
                    int(over[lane])
                if seq.done:
                    # it ended (its last block, an eos, a cancel, an
                    # expiry) while this pass was in flight
                    self._totals["decode_lane_steps_wasted_total"] += 1
                    continue
                blk, cur = seq.blk, state[lane].tolist()
                if blk.passes is not None:
                    blk.passes.append(
                        [p0, list(blk.last if read_as is None else read_as),
                         cur])
                blk.last = cur
                if p0 == blk.emit_p0 and mask not in cur:
                    blk.emit_p0 += n
                    totals["blocks_committed_total"] += 1
                    self._emit_block(
                        seq, cur[max(len(seq.prefill_tokens) - p0, 0):])
                    if seq.done:
                        continue
                if p0 != blk.p0 or blk.known:
                    continue   # an earlier block's pass, or its commit
                # the pass behind this one, dispatched or to come, reads
                # the block as this one left it
                blk.cur = cur
                if not seq.ahead:
                    blk.known = True
                elif mask not in cur:
                    self._next_block(seq)   # the pass in flight commits
            for kind, count in lanes.items():
                totals["block_lane_passes_total"][kind] += count
        m = self.metrics()
        for kind, count in lanes.items():
            if m is not None and count:
                m["block_passes"].inc(count, tags={"kind": kind})
        if rec.lanes:
            totals["block_passes_total"][
                "denoise" if lanes["denoise"] else "commit"] += 1

    def warm_up(self) -> None:
        """Compile every program traffic can reach, before any loop or
        traffic, by running one tiny request inline: its first prefill
        pass compiles every prefill program (`_prefill_programs`: wide,
        narrow and deep), its first decode step every decode width."""
        t0 = time.perf_counter()
        # a block model prefills whole blocks only: one, and a tail
        self.generate_batch([{"tokens": [1] * (self._block + 1),
                              "max_new_tokens": 2}])
        self.startup_secs["warm"] = time.perf_counter() - t0
        self._weights_waiter.join(60.0)  # a forward ran: they are there

    def run_loop(self) -> Dict[str, Any]:
        """The pinned decode loop: step while there is work, park on the
        engine condition while idle.  Single-flight — a second install
        (controller restart re-ensuring loops) returns immediately."""
        with self._lock:
            if self._loop_running:
                return {"already_running": True}
            self._loop_running = True
        try:
            while not self._stopped.is_set():
                if not self.step():
                    self._clock.phase("park")
                    with self._cond:
                        if not self._queued and not self._active:
                            self._cond.wait(0.05)
                    self._clock.phase("between")
            self.drain()  # stopped with a step in flight: its tokens
            return {"steps": self._steps}
        except BaseException as e:
            # a broken engine must fail its consumers, not hang them
            self._flight.clear()
            with self._lock:
                for seq in list(self._active) + list(self._queued):
                    if not seq.done:
                        seq.error = e
                        self._finish_seq(seq, cancelled=True)
                        if seq.cond is not None:
                            seq.cond.notify_all()
                self._cond.notify_all()
            raise
        finally:
            with self._lock:
                self._loop_running = False

    def stop(self) -> None:
        """End the pinned loop, which reads what it has in flight on its
        way out; an engine stepped inline is drained here."""
        self._stopped.set()
        with self._cond:
            self._cond.notify_all()
        self._drain_inline()

    # ------------------------------------------------------- inline driving

    def generate_batch(self, requests: List[Dict[str, Any]]
                       ) -> List[List[int]]:
        """Admit the whole batch and step the engine inline until every
        sequence of it is done: `warm_up`'s request and the tests'
        driver.  Only for engines with no pinned loop."""
        seqs = []
        try:
            for r in requests:
                seqs.append(self.submit(r))
        except BaseException:
            # a failed admission mid-list must not strand the earlier
            # sequences: nothing will ever drive or consume them, so
            # they would hold pages and decode for nobody
            with self._lock:
                for s in seqs:
                    self._finish_seq(s, cancelled=True)
            raise
        while any(not s.done for s in seqs):
            if not self.step():
                time.sleep(0.001)
        self.drain()  # a lane-step behind an `eos`: nothing stays unread
        for s in seqs:
            self.release(s)
        return [list(s.generated) for s in seqs]

    # ------------------------------------------------------- observability

    def metrics(self):
        if self._metrics is None:
            try:
                from ray_tpu._private.metrics import (llm_block_metrics,
                                                      llm_metrics,
                                                      llm_prefix_metrics)

                (tokens, pages, batch, ttft, queue, tps,
                 decode_step) = llm_metrics()
                prefix_hits, shipped = llm_prefix_metrics()
                self._metrics = {"tokens": tokens, "pages": pages,
                                 "batch": batch, "ttft": ttft,
                                 "queue": queue, "tps": tps,
                                 "decode_step": decode_step,
                                 "prefix_hits": prefix_hits,
                                 "shipped": shipped,
                                 "block_passes": llm_block_metrics()}
            except Exception:
                return None
        return self._metrics

    def _set_gauges(self, batch: int = 0, step_tokens: int = 0) -> None:
        """Publish the step's own counts (an idle step's are zero): a
        gauge is written where its value differs from the last written,
        so a scrape reads what it always read and a step whose counts
        stand pays six comparisons."""
        m = self.metrics()
        if m is None:
            return
        with self._clock.host["gauges"]:
            now = [("batch", None, batch), ("tps", None, step_tokens),
                   ("queue", None, len(self._queued))]
            for group in self._groups.values():
                now += [("pages", state, pages)
                        for state, pages in group.gauges().items()]
            for name, state, value in now:
                if _gauged.get((name, state)) != value:
                    # a set takes the gauge's lock and builds its labels
                    _gauged[name, state] = value
                    m[name].set(value, tags=state and {"state": state})

    def stats(self) -> Dict[str, Any]:
        """Counters and gauges of this engine.  Every `*_total`,
        `*_secs` and `*_steps` key is cumulative and never falls: a
        reader takes the change between two calls.  For a model that
        generates in blocks, `decode_steps` and `decode_secs` count
        BLOCK passes, `decode_lane_steps_total` and
        `decode_lane_steps_wasted_total` a lane's passes of a whole block
        each, and the `block_*` keys (the constructor has what each
        counts) are there; for every other model they are not.

        What the device waited for, in every run (`_StepClock`):
        `starved_secs_total` over `loop_secs` is the LOWER bound of the
        share of the stepping thread's time in which the device had
        nothing to run until the next dispatch landed — the host phases
        passed from the first clock boundary that found the newest pass
        in flight done, up to and including the next dispatch — and
        with `starved_before_secs_total` added the UPPER bound: the one
        phase before each boundary that first saw the device dry, where
        the dispatch's end saw it the dispatch so far; a trace's idle
        share less its lulls lies between the two.
        `chained_dispatches_dry_total` over `chained_dispatches_total`:
        a dispatch is chained when a pass was in flight as it began, and
        dry when that pass was done as the jitted call returned, or the
        clock had seen the device with nothing to run on the way — in
        how many steps the host, not the device, set the pace;
        `dry_in_dispatch_total` of them were seen by the dispatch's end
        alone.  `admit_blocked_steps_total` over `steps`: steps whose
        admission left the head of the queue queued;
        `admit_blocked_steps` says by what — `lanes` (every lane taken)
        or the kind of the first cache group with no room (`full`:
        pages; `window`; `state`: a slot)."""
        from ray_tpu.ops import compile_counts
        from ray_tpu.serve import cache_groups

        with self._lock:
            return {"steps": self._steps,
                    "platform": self.platform,
                    "kernel_mode": self.kernel_mode,
                    "decode_steps": self._decode_steps,
                    "decode_secs": self._clock.pass_secs("decode")
                    - self._warm_secs["decode"],
                    "prefill_steps": self._prefill_steps,
                    "prefill_secs": self._clock.pass_secs("prefill")
                    - self._warm_secs["prefill"],
                    **self._clock.stats(),
                    **self._totals,
                    **{key: dict(v) if isinstance(v, dict) else v
                       for key, v in self._block_totals.items()},
                    **{name: dict(by_pass) for name, by_pass
                       in self._model_counters.items()},
                    "prefill_passes_by_width":
                        dict(self._prefill_passes_by_width),
                    "admit_blocked_steps": dict(self._admit_blocked),
                    "admit_blocked_steps_total":
                        sum(self._admit_blocked.values()),
                    **compile_counts(),
                    "startup_secs": dict(self.startup_secs),
                    "queued": len(self._queued),
                    "active": len(self._active),
                    "cancelled": self._cancelled_total,
                    "deadline_expired": self._deadline_expired_total,
                    **cache_groups.stats(self._groups, self._pools),
                    "kv_pages_shipped_out": self._kv_pages_shipped_out,
                    "kv_pages_shipped_in": self._kv_pages_shipped_in,
                    "loop_running": self._loop_running,
                    "last_batch": self._last_batch}

    # ------------------------------------------------------- save / restore

    def save_state(self) -> Dict[str, Any]:
        """Snapshot of in-flight sequences for ``__rt_save__``: prompt +
        tokens generated so far.  Tiny (token ids only) — params and KV
        pages are reconstructed, not saved.  An engine stepped inline is
        drained first; under a pinned loop a token in flight is the
        loop's to read, and one the snapshot misses is computed again by
        the engine that restores it."""
        self._drain_inline()
        with self._lock:
            seqs = []
            for seq in list(self._active) + list(self._queued):
                if seq.done:
                    continue
                seqs.append({"request_id": seq.request_id,
                             "tokens": list(seq.prompt),
                             "generated": list(seq.generated),
                             "max_new_tokens": seq.max_new,
                             "eos": seq.eos})
            return {"seqs": seqs}

    def restore_state(self, state: Dict[str, Any]) -> None:
        """Re-admit saved sequences: each re-prefills prompt + known
        tokens and continues decoding.  Consumers re-attach by
        request_id within the grace window (their ``emit_from`` skips
        what they already saw)."""
        now = time.monotonic()
        with self._lock:
            for s in (state or {}).get("seqs", []):
                rid = s["request_id"]
                if rid in self._by_rid:
                    continue
                seq = _Seq(rid, s["tokens"], s["max_new_tokens"],
                           s.get("eos"), preknown=s.get("generated"))
                seq.cond = threading.Condition(self._lock)
                if len(seq.generated) >= seq.max_new:
                    continue  # finished before the snapshot landed
                if self._block:
                    seq.blk = self._open_first_block(
                        seq, self.cfg.denoising_steps or self._block)
                seq.detached_at = now  # grace window for re-attach
                self._totals["submitted_total"] += 1
                self._by_rid[rid] = seq
                self._queued.append(seq)
            self._cond.notify_all()


# ----------------------------------------------------------- replica target


class _LLMCallable:
    """The deployment target hosted by each ``llm_deployment`` replica.

    ``__call__`` is the streaming endpoint: it admits the request and
    yields token items as the PINNED loop (installed by the controller
    through ``__rt_dag_llm_loop__``) produces them.  The generator's
    finally detaches the consumer, so an abandoned stream (SSE
    disconnect -> generator cancel) frees its KV pages after the grace
    window instead of decoding to max_seq_len."""

    def __init__(self, warm: bool = True, **engine_kwargs):
        self._engine = LLMEngine(**engine_kwargs)
        if warm:
            # compile every jitted shape (each prefill program: wide,
            # narrow and deep; each decode width) HERE, inside
            # the replica constructor: the deploy health gate
            # (serve_replica_health_timeout_s) covers it, so the first
            # real request never pays ~seconds of XLA compile while
            # reconcile health probes run against their 5s timeout
            self._engine.warm_up()

    def __call__(self, request):
        emit_from = 0
        kv_pack = None
        if isinstance(request, dict):
            emit_from = int(request.get("emit_from") or 0)
            if request.get("kv_ref") is not None:
                # disaggregated prefill: resolve the shipped KV pages
                # (the get pulls over the checksummed bulk plane when
                # the prefill replica lives on another node).  ANY
                # failure — pull error, pack corruption — falls back to
                # a local prefill: always correct, just slower.
                request = dict(request)
                ref = request.pop("kv_ref")
                try:
                    import ray_tpu
                    from ray_tpu._private.object_transfer import \
                        unpack_kv_pages

                    kv_pack = unpack_kv_pages(
                        ray_tpu.get(ref, timeout=30.0))
                except Exception:
                    kv_pack = None
        seq = self._engine.submit(request, kv_pack=kv_pack)
        try:
            yield from self._engine.iter_tokens(seq, emit_from)
        finally:
            self._engine.release(seq)

    def prefill(self, request):
        """Prefill-pool endpoint: run the prefill phase only, put the
        packed KV pages into the object store, and return the shipping
        metadata the handle forwards to a decode replica.  The decode
        replica's pull of the ref rides the bulk transfer plane."""
        import ray_tpu
        from ray_tpu._private.object_transfer import pack_kv_pages

        payload = self._engine.prefill_request(request)
        buf = pack_kv_pages(payload["meta"], payload["rows"])
        meta = payload["meta"]
        return {"request_id": meta["request_id"],
                "kv_ref": ray_tpu.put(buf),
                "first_token": meta["first_token"],
                "n": meta["n"], "pages": meta["pages"],
                "nbytes": len(buf)}

    def generate(self, request):
        """Non-streaming convenience: the full generation as one list
        (still continuous-batched with everything else in flight)."""
        toks: List[int] = []
        for item in self(request):
            toks.extend(item["tokens"])
        return {"request_id": None, "tokens": toks}

    def stats(self):
        return self._engine.stats()

    def device_report(self):
        return self._engine.device_report()

    def profile_start(self, trace_dir: str) -> None:
        """Start a `jax.profiler` trace of this replica's process into
        `trace_dir`: the device's operations and, on the same clock, the
        engine's `llm.*` spans and jax's own host spans (no Python
        frames).  Only the process that holds the chip can trace it."""
        import os

        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    def profile_stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def __rt_save__(self):
        return self._engine.save_state()

    def __rt_restore__(self, state):
        self._engine.restore_state(state)


def run_llm_loop(worker, instance, *_args) -> Dict[str, Any]:
    """Worker-side entry for the ``__rt_dag_llm_loop__`` system method
    (see CoreWorker._execute_inner): pins this exec thread to the
    replica engine's decode loop until the replica dies."""
    target = getattr(instance, "_callable", instance)
    engine = getattr(target, "_engine", None)
    if not isinstance(engine, LLMEngine):
        raise TypeError(
            "__rt_dag_llm_loop__ requires an llm_deployment replica "
            f"(got {type(target).__name__})")
    # `exit_worker` must be able to end a loop that holds the worker's
    # main exec thread (CoreWorker.rpc_exit_worker).  Never cleared: the
    # engine lives as long as its worker, and a second install returns
    # at once while the first still runs
    worker._pinned_stop = engine.stop
    return engine.run_loop()


def llm_deployment(name: str = "llm", *, num_replicas: Any = 1,
                   max_ongoing_requests: int = 64,
                   ray_actor_options: Optional[Dict[str, Any]] = None,
                   autoscaling_config: Optional[Dict[str, Any]] = None,
                   request_timeout_s: Optional[float] = None,
                   hedge_after_s: Any = None, idempotent: bool = False,
                   prefill_replicas: int = 0,
                   **engine_kwargs):
    """Build an LLM serving Application: replicas host an
    :class:`LLMEngine` and the controller installs the pinned decode
    loop on each one.  ``engine_kwargs`` go to :class:`LLMEngine`
    (model=, page_size=, num_pages=, max_batch=, prefill_chunk=,
    max_queue=, seed=, detach_grace_s=, prefix_sharing=, and the
    debugging aid logit_trace=).

    ``prefill_replicas > 0`` disaggregates the two serving phases: a
    sibling ``{name}-prefill`` pool (same engine config) runs chunked
    prefill on dedicated replicas and ships the finished KV pages to
    this deployment's decode replicas over the bulk transfer plane;
    decode lanes never stall behind a long prompt.

    Usage::

        app = serve.llm_deployment("chat", model="tiny", max_batch=16)
        handle = serve.run(app)
        # stream over HTTP: POST /chat with Accept: text/event-stream
    """
    from ray_tpu.serve.api import Deployment

    d = Deployment(_LLMCallable, name, num_replicas=num_replicas,
                   max_ongoing_requests=max_ongoing_requests,
                   ray_actor_options=dict(ray_actor_options or {}),
                   autoscaling_config=dict(autoscaling_config)
                   if autoscaling_config else None,
                   llm=True, request_timeout_s=request_timeout_s,
                   hedge_after_s=hedge_after_s, idempotent=idempotent,
                   prefill_replicas=int(prefill_replicas))
    return d.bind(**engine_kwargs)
