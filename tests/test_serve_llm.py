"""LLM serving tier tests (serve/llm.py): continuous batching over a
paged KV cache, admission/shed, streaming + disconnect, resume.

Engine-level tests run without a cluster (fast, deterministic).  The
cluster tests share ONE module-scoped cluster + HTTP proxy — tier-1
budget is tight, so every deployment in this module rides the same
cluster and warms its jit cache with a 1-token request before any
timed assertion.
"""

import itertools
import json
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import narrow_prefill_cases
import ray_tpu
from ray_tpu import serve
from ray_tpu.models.llama import LlamaConfig, LlamaModel
from ray_tpu.serve.llm import LLMEngine, LLMOverloadedError

# one tiny fp32 config for everything: fp32 keeps greedy argmax
# bit-stable across the cached and full-forward paths
MODEL = {"vocab_size": 64, "dim": 32, "n_layers": 2, "n_heads": 4,
         "n_kv_heads": 2, "hidden_dim": 64, "max_seq_len": 64}


def _cfg(**over):
    d = dict(MODEL, **over)
    return LlamaConfig(dtype=jnp.float32, **d)


# flax init is eager and costs seconds per call in this sandbox: build
# the (deterministic, seed-0) param tree once per distinct config
_params_cache = {}


def _engine(**kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 33)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_queue", 8)
    kw.setdefault("detach_grace_s", 60.0)
    cfg = kw.pop("cfg", None) or _cfg()
    if "params" not in kw:
        if cfg not in _params_cache:
            probe = LLMEngine(cfg, **kw)
            _params_cache[cfg] = probe._params
            return probe
        kw["params"] = _params_cache[cfg]
    return LLMEngine(cfg, **kw)


def _ref_greedy(engine, prompt, n):
    """Greedy decode through the NON-batched full forward — the
    correctness oracle for the continuous-batching path."""
    model, params = engine._model, engine._params
    toks = list(prompt)
    for _ in range(n):
        lg = model.apply({"params": params}, np.array([toks], np.int32))
        toks.append(int(np.argmax(np.asarray(lg[0, -1]))))
    return toks[len(prompt):]


def _assert_greedy(engine, prompt, generated, n=None):
    """Teacher-forcing oracle: ONE full non-batched forward over
    prompt+generated proves token-identity with greedy decode (each
    generated token must be the argmax at its prefix position).
    Equivalent to _ref_greedy but one eager apply instead of one per
    token — eager ops cost ~ms each in this sandbox."""
    if n is not None:
        assert len(generated) == n, (len(generated), n)
    assert generated, "nothing generated"
    full = list(prompt) + list(generated)
    lg = engine._model.apply({"params": engine._params},
                             np.array([full], np.int32))
    lg = np.asarray(lg[0])
    for j, tok in enumerate(generated):
        pos = len(prompt) + j - 1
        assert int(np.argmax(lg[pos])) == int(tok), \
            (j, tok, int(np.argmax(lg[pos])))


def _drain(engine, rounds=200):
    for _ in range(rounds):
        if not engine.step():
            break


def _eager_logits(eng, toks):
    return np.asarray(eng._model.apply(
        {"params": eng._params}, np.array([toks], np.int32))[0])


def _assert_trace_is_the_full_forwards(eng, seq, full=_eager_logits):
    """Tokens and top-two logits of `seq` against ONE no-cache forward
    over prompt + generated (`full`), to the tolerance two float32
    programs of one forward hold."""
    p, gen = list(seq.prompt), list(seq.generated)
    rows = eng.device_report()["logit_trace"][seq.request_id]
    assert [r[0] for r in rows] == list(range(len(gen)))
    assert [r[2] for r in rows] == gen  # greedy: id1 is the token
    lg = full(eng, p + gen)
    for j, (_i, l1, i1, l2, i2) in enumerate(rows):
        at = lg[len(p) + j - 1]
        order = np.argsort(at)
        assert (i1, i2) == (order[-1], order[-2]), (len(p), j)
        np.testing.assert_allclose([l1, l2], at[[i1, i2]],
                                   rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- engine units


def test_decode_matches_full_forward():
    """The acceptance gate: greedy decode of a fixed prompt set through
    the continuous-batching path (staggered admission, chunked prefill,
    shared decode lanes, paged non-contiguous KV slots) is
    token-identical to the single-sequence full forward."""
    eng = _engine()
    prompts = [[5, 9, 3], [7, 11, 2, 4, 8, 1, 9, 10, 3, 2], [1, 2],
               [3, 3, 3, 3, 3, 3, 3, 3, 3, 3, 3, 3, 3]]
    seqs = [eng.submit({"tokens": p, "max_new_tokens": 6})
            for p in prompts[:3]]
    for _ in range(3):
        eng.step()
    # token-boundary admission: the 4th sequence joins mid-flight
    late = eng.submit({"tokens": prompts[3], "max_new_tokens": 5})
    _drain(eng)
    for p, s in zip(prompts, seqs):
        _assert_greedy(eng, p, s.generated, n=6)
    _assert_greedy(eng, prompts[3], late.generated, n=5)
    # every page recycled after EOS
    st = eng.stats()
    assert st["used_pages"] == 0 and st["free_pages"] == 32, st


def test_logit_trace_rows_are_the_full_forwards_top_two():
    """`logit_trace=True` keeps, for every generated token, the two
    largest logits and their ids; they are the full forward's at that
    position, for the prefill's token and for every decode step's."""
    eng = _engine(logit_trace=True)
    prompts = {"a": [5, 9, 3], "b": [7, 11, 2, 4, 8, 1, 9, 10, 3, 2]}
    seqs = {rid: eng.submit({"tokens": p, "max_new_tokens": 6,
                             "request_id": rid})
            for rid, p in prompts.items()}
    _drain(eng)
    for seq in seqs.values():
        assert len(seq.generated) == 6
        _assert_trace_is_the_full_forwards(eng, seq)
    # off (the default): the serving program and report are unchanged
    assert "logit_trace" not in _engine().device_report()


def test_engine_defaults():
    """An engine built with no sizing argument has the values the twelve
    `llm_*` config knobs held, and the process-wide config has none of
    them left: the constructor is the one way to set an engine."""
    from ray_tpu._private.config import config

    eng = LLMEngine(_cfg())
    pages_per_seq = MODEL["max_seq_len"] // 16
    assert (eng.page_size, eng.max_batch, eng.prefill_chunk,
            eng.prefill_lanes, eng.stream_flush_tokens, eng.max_queue,
            eng.detach_grace_s, eng.stats()["prefix_sharing"],
            eng.temperature,
            eng.top_k) == (16, 32, 64, 8, 4, 256, 2.0, True, 0.0, 0)
    # llm_kv_pages 0: sized for max_batch sequences at max_seq_len
    assert eng.num_pages == 1 + 32 * pages_per_seq
    assert eng.device_report()["attention_impl"] == "paged"  # was "auto"
    for name in ("llm_page_size", "llm_kv_pages", "llm_max_batch_size",
                 "llm_prefill_chunk", "llm_prefill_lanes",
                 "llm_stream_flush_tokens", "llm_admission_queue",
                 "llm_detach_grace_s", "llm_prefix_sharing",
                 "llm_attention_impl", "llm_temperature", "llm_top_k"):
        with pytest.raises(AttributeError):
            getattr(config, name)
    # the two without a constructor twin stay
    assert config.llm_done_seq_ttl_s == 30.0
    assert config.llm_disagg_min_prompt == 0


# ------------------------------------------- prefill context-width buckets
# One geometry for every test below, so the process-wide jit cache holds
# its eight programs once: chunk 16 under a context of 1024 gives the
# prefill pass three widths (64, 256, 1024) — the 4-lane pass and the
# 2-lane pass of 16 run at the second (256), the deep pass (2 x 32) at the
# last two — page 8 the decode pass four.

WIDE_CHUNK, WIDE_CTX = 16, 1024
WIDE_BUCKETS = [64, 256, 1024]
NARROW = narrow_prefill_cases.NARROW   # 2 lanes x 16 x the second bucket
DEEP = narrow_prefill_cases.DEEP       # 2 lanes x 32
PROGRAMS = [(4, 16, 256), NARROW, (*DEEP, 256), (*DEEP, 1024)]


def _wide_engine(**kw):
    kw.setdefault("cfg", _cfg(max_seq_len=WIDE_CTX))
    kw.setdefault("num_pages", 1 + 3 * (WIDE_CTX // 8))
    kw.setdefault("prefill_chunk", WIDE_CHUNK)
    kw.setdefault("logit_trace", True)
    return _engine(**kw)


def _wide_prompt(n, salt=0):
    return [1 + (7 * i + i // 5 + salt) % 60 for i in range(n)]


@pytest.fixture(scope="module")
def wide_eng():
    """One warmed engine for the cases below: each drains it, and gives
    its prompts a first token of their own so none hits another's
    prefix."""
    eng = _wide_engine()
    eng.warm_up()
    return eng


_wide_full = {}


def _wide_logits(eng, toks):
    """The no-cache forward at ONE jitted shape for every length:
    causal, so the padding behind `toks` moves no row before it.  1016
    is a length the flash routing (multiples of 128) leaves dense."""
    if "fn" not in _wide_full:
        _wide_full["fn"] = jax.jit(
            lambda params, t: eng._model.apply({"params": params}, t)[0])
    padded = np.zeros((1, WIDE_CTX - 8), np.int32)
    padded[0, :len(toks)] = toks
    return np.asarray(_wide_full["fn"](eng._params, padded))


@pytest.mark.parametrize("n_prompt,company", [
    (63, "alone"), (64, "alone"), (65, "alone"),
    (255, "alone"), (256, "alone"), (257, "alone"), (1000, "alone"),
    (64, "with_a_short_prompt"), (256, "with_a_short_prompt"),
    (1000, "with_a_short_prompt")])
def test_prefill_width_buckets_keep_the_full_forwards_tokens(
        wide_eng, n_prompt, company):
    """A prompt ending one row under, on and over each bucket edge, and
    one far in the last bucket: the pass that holds its last chunk —
    deep, or narrow where less than a deep chunk is left within the
    narrow width — takes the bucket that covers it, also when a short
    prompt shares that pass and reads the same wider context, and every
    token is the no-cache forward's."""
    eng = wide_eng
    assert eng._prefill_ctx_buckets() == WIDE_BUCKETS
    assert eng._prefill_programs() == PROGRAMS
    salt = n_prompt + 2 * (company != "alone")
    long = eng.submit({"tokens": _wide_prompt(n_prompt, salt),
                       "max_new_tokens": 3})
    seqs = [long]
    while long.pos + eng._prefill_shape([(long.pos, n_prompt)])[1] < n_prompt:
        eng.step()
    assert long.state == "prefill" and long.pos > n_prompt - DEEP[1] - 1
    if company == "with_a_short_prompt":
        seqs.append(eng.submit({"tokens": _wide_prompt(5, salt + 1),
                                "max_new_tokens": 3}))
    before = eng.stats()["prefill_passes_by_width"]
    eng.step()   # the long prompt's last chunk, beside the short prompt
    eng.drain()  # its tokens are read a step late
    assert all(s.generated for s in seqs)
    after = eng.stats()["prefill_passes_by_width"]
    width = NARROW[2] if n_prompt <= NARROW[2] else WIDE_CTX
    assert {w: after[w] - before[w] for w in WIDE_BUCKETS} == {
        w: int(w == width) for w in WIDE_BUCKETS}
    _drain(eng)
    for s in seqs:
        assert len(s.generated) == 3
        _assert_trace_is_the_full_forwards(eng, s, full=_wide_logits)
    assert eng.stats()["used_pages"] == 0


@pytest.mark.parametrize("warmed_by", ["warm_up", "its_first_pass"])
def test_every_prefill_width_is_compiled_before_the_second_pass(warmed_by):
    """After `warm_up()`, and just as well after the first prefill pass
    and decode step of an engine nobody warmed, prompts that reach every
    bucket and every shape, alone and together, compile nothing: no new
    executable behind the stepper and no backend compile in the
    process."""
    eng = _wide_engine()
    shapes, forward = [], eng._forward

    def spy(tokens, q_pos, last_idx, groups, **kw):
        if "ctx" in groups["full"]:
            shapes.append((*tokens.shape, groups["full"]["ctx"].shape[1]))
        return forward(tokens, q_pos, last_idx, groups, **kw)

    eng._forward = spy
    assert eng._prefill_programs() == PROGRAMS
    if warmed_by == "warm_up":
        eng.warm_up()   # its one-token prompt runs the narrow pass itself
        assert shapes == [p for p in PROGRAMS if p != NARROW] + [NARROW]
    else:
        for n in (30, 5, 9):
            eng.submit({"tokens": _wide_prompt(n, n), "max_new_tokens": 2})
        eng.step()   # the first pass, wide: the other shapes, then its own
        assert shapes == PROGRAMS[1:] + PROGRAMS[:1]
        _drain(eng)  # the first decode step warms decode's widths
    assert sorted(set(shapes)) == sorted(PROGRAMS)
    assert eng.stats()["prefill_passes_by_width"][1024] == 0  # not counted
    steps = eng.device_report()["compiled_steps"]
    compiles = eng.stats()["compiles_total"]
    assert steps >= len(PROGRAMS) + len(eng._paged_width_buckets())
    del shapes[:]
    for lengths in ([20], [20, 25, 30], [63, 64, 65], [255, 5],
                    [256, 257, 300], [1000, 40, 7], [300], [70, 9, 3, 12]):
        seqs = [eng.submit({"tokens": _wide_prompt(n, salt=n),
                            "max_new_tokens": 2}) for n in lengths]
        _drain(eng, rounds=400)
        assert all(s.done and len(s.generated) == 2 for s in seqs)
    st = eng.stats()
    by_width = st["prefill_passes_by_width"]
    # (no program at the first width where there is a deep pass)
    assert by_width[64] == 0 and by_width[256] > 0 and by_width[1024] > 0
    narrow, deep = (st["prefill_narrow_passes_total"],
                    st["prefill_deep_passes_total"])
    assert narrow > 0 and deep > 0 and narrow + deep < st["prefill_steps"]
    assert set(shapes) == set(PROGRAMS)
    assert eng.device_report()["compiled_steps"] == steps
    assert eng.stats()["compiles_total"] == compiles


def test_prefill_context_counters_say_what_was_gathered():
    """`prefill_slots_total`, `prefill_ctx_cols_total`,
    `prefill_narrow_passes_total` and `prefill_deep_passes_total` against
    a hand count: a pass adds its OWN lanes x chunk and lanes x width,
    narrow, deep or wide."""
    eng = _wide_engine()
    lanes, (n_lanes, chunk, n_width) = eng.prefill_lanes, NARROW
    assert lanes == 4 and chunk == WIDE_CHUNK
    st0 = eng.stats()
    assert st0["prefill_passes_by_width"] == dict.fromkeys(WIDE_BUCKETS, 0)
    assert st0["prefill_narrow_passes_total"] == 0
    assert st0["prefill_deep_passes_total"] == 0
    # a 20-token prompt alone: two NARROW passes (16 + 4 tokens) reading
    # 16 and 20 rows, each gathering 2 lanes x the narrow pass's width
    eng.generate_batch([{"tokens": _wide_prompt(20), "max_new_tokens": 2}])
    st = eng.stats()
    assert st["prefill_steps"] == st["prefill_narrow_passes_total"] == 2
    assert st["prefill_tokens_total"] == 20
    assert st["prefill_slots_total"] == 2 * n_lanes * WIDE_CHUNK
    assert st["prefill_ctx_rows_total"] == 16 + 20
    assert st["prefill_ctx_cols_total"] == 2 * n_lanes * n_width
    assert st["prefill_passes_by_width"] == {64: 0, 256: 2, 1024: 0}
    # three prompts at once, the first with deep chunks to go: two DEEP
    # passes of 2 x 32 — 32 rows of the long one beside the 9, then 32
    # more beside the 5, which waited — and its last 6 rows in a narrow
    # one: the rows of every live lane, the columns of the pass
    eng.generate_batch([{"tokens": _wide_prompt(70), "max_new_tokens": 2},
                        {"tokens": _wide_prompt(9, 1), "max_new_tokens": 2},
                        {"tokens": _wide_prompt(5, 2), "max_new_tokens": 2}])
    st = eng.stats()
    assert st["prefill_steps"] == 2 + 3
    assert st["prefill_narrow_passes_total"] == 2 + 1
    assert st["prefill_deep_passes_total"] == 2
    assert st["prefill_passes_by_width"] == {64: 0, 256: 5, 1024: 0}
    assert st["prefill_tokens_total"] == 20 + 70 + 9 + 5
    assert st["prefill_slots_total"] == \
        3 * n_lanes * WIDE_CHUNK + 2 * DEEP[0] * DEEP[1]
    assert st["prefill_ctx_rows_total"] == 36 + (32 + 9) + (64 + 5) + 70
    assert st["prefill_ctx_cols_total"] == 5 * n_lanes * n_width
    # three with no deep chunk among them: a WIDE pass while all three
    # wait (the short ones end in it), at the wide program's one width
    # (256), then the last 4 rows of the first in a narrow one
    eng.generate_batch([{"tokens": _wide_prompt(20, 3), "max_new_tokens": 2},
                        {"tokens": _wide_prompt(9, 4), "max_new_tokens": 2},
                        {"tokens": _wide_prompt(5, 5), "max_new_tokens": 2}])
    st = eng.stats()
    assert st["prefill_steps"] == 5 + 2
    assert st["prefill_narrow_passes_total"] == 3 + 1
    assert st["prefill_deep_passes_total"] == 2
    assert sum(st["prefill_passes_by_width"].values()) == st["prefill_steps"]
    assert st["prefill_passes_by_width"] == {64: 0, 256: 7, 1024: 0}
    assert st["prefill_tokens_total"] == 20 + 70 + 9 + 5 + 20 + 9 + 5
    assert st["prefill_slots_total"] == \
        (4 * n_lanes + 1 * lanes) * WIDE_CHUNK + 2 * DEEP[0] * DEEP[1]
    assert st["prefill_ctx_rows_total"] == \
        36 + 41 + 69 + 70 + (16 + 9 + 5) + 20
    assert st["prefill_ctx_cols_total"] == (6 * n_lanes + lanes) * n_width
    assert st["prefill_ctx_rows_total"] <= st["prefill_ctx_cols_total"]


def test_the_chat_canaries_never_meet_the_deep_program():
    """The engine's default constants, and the rule alone (no pass run):
    the deep pass is PREFILL_NARROW_LANES lanes of the wide pass's 512
    slots; prompts of the benchmark's chat canaries' lengths, alone or
    in any company of their own kind, at any point of their prefill,
    pick the narrow or the wide program and never the deep one; a prompt
    with a deep chunk to go, or a context past the narrow width, does."""
    from ray_tpu.serve import llm

    eng = LLMEngine(_cfg(max_seq_len=4096), max_batch=8, num_pages=600)
    assert (eng.prefill_chunk, eng.prefill_lanes) == (
        llm.PREFILL_CHUNK, llm.PREFILL_LANES) == (64, 8)
    assert eng._deep_prefill == (llm.PREFILL_NARROW_LANES, 256) == (2, 256)
    assert eng._prefill_widths == [256, 1024, 4096]
    assert eng._prefill_programs() == [
        (8, 64, 1024), (2, 64, 1024), (2, 256, 1024), (2, 256, 4096)]
    canaries = (24, 150, 80, 200)
    states = [(pos, n) for n in canaries for pos in range(0, n, 64)]
    for k in (1, 2, 3):
        for waiting in itertools.product(states, repeat=k):
            lanes, chunk, width = eng._prefill_shape(list(waiting))
            assert chunk == 64 and (lanes, width) == (
                (2, 1024) if k <= 2 else (8, 1024))
    assert eng._prefill_shape([(0, 255)]) == (2, 64, 1024)
    assert eng._prefill_shape([(0, 256)]) == (2, 256, 1024)
    assert eng._prefill_shape([(0, 24), (768, 1024)]) == (2, 256, 1024)
    assert eng._prefill_shape([(1024, 1030)]) == (2, 256, 4096)
    assert eng._prefill_shape([(0, 24), (0, 80), (1000, 1030)]) \
        == (2, 256, 1024)
    assert eng._prefill_shape([(0, 24), (0, 80), (0, 3000)]) \
        == (8, 64, 1024)


class _NarrowKit:
    """This family's kit for `narrow_prefill_cases`: the wide geometry
    above, its tokens held to the no-cache forward's argmax."""

    make = staticmethod(_wide_engine)
    prompt = staticmethod(_wide_prompt)

    @staticmethod
    def make_one_width():
        return _engine(cfg=_cfg(max_seq_len=64), prefill_chunk=WIDE_CHUNK)

    @staticmethod
    def check(eng, prompts, outs):
        for p, out in zip(prompts, outs):
            _assert_greedy(eng, p, out)


@pytest.mark.parametrize("case", narrow_prefill_cases.CASES,
                         ids=lambda case: case.__name__)
def test_narrow_prefill_pass(case):
    case(_NarrowKit)


def test_paged_grid_counters_are_the_hand_count():
    """Two lanes, page 16: `paged_grid_steps_total` is what the decode
    passes' kernel calls had as a grid (lanes x blocks of
    `pages_per_step` pages of the table), `paged_grid_steps_live_total`
    the steps of it that held a page."""
    from ray_tpu.ops.paged_attention import pages_per_step

    eng = _engine(cfg=_cfg(max_seq_len=512), page_size=16, num_pages=65,
                  max_batch=2, prefill_chunk=64)
    assert eng._paged_width_buckets() == [4, 16, 32]
    assert [pages_per_step(w, 16, 1024) for w in (4, 16)] == [4, 8]
    assert eng.stats()["paged_grid_steps_total"] == 0   # warm-up: none
    long = [1 + (5 * i) % 60 for i in range(130)]
    before = eng.stats()
    out = eng.generate_batch([{"tokens": long, "max_new_tokens": 4},
                              {"tokens": [7, 3, 9], "max_new_tokens": 6}])
    assert [len(o) for o in out] == [4, 6]
    after = eng.stats()
    steps, total, live = (after[k] - before[k] for k in (
        "decode_steps", "paged_grid_steps_total",
        "paged_grid_steps_live_total"))
    # the short prompt's first token comes with the long one's first
    # chunk; it decodes alone while chunks two and three pass (one page
    # in a table of 4: one block a lane), then both decode three times:
    # 9 pages beside 1 in a table of 16, two blocks of 8 pages a lane,
    # both of the long lane's held and one of the short lane's
    assert steps == 2 + 3
    assert total == 2 * (2 * 1) + 3 * (2 * 2)
    assert live == 2 * 1 + 3 * (2 + 1)
    assert 0 < live < total


@pytest.mark.parametrize("max_seq_len,chunk,buckets", [
    (4096, 64, [256, 1024, 4096]),   # the benchmark's serving cells
    (WIDE_CTX, WIDE_CHUNK, WIDE_BUCKETS),
    (2048, 64, [256, 1024, 2048]),   # capped at ctx_len, not a power
    (512, 64, [256, 512]),
    (256, 64, [256]),                # the bench rehearsal: one program
    (64, 16, [64]),
    (64, 64, [64]),
    (1024, 512, [1024]),
])
def test_prefill_ctx_buckets_by_geometry(max_seq_len, chunk, buckets):
    """Powers of four from 4 x chunk, capped at ctx_len; an engine whose
    ctx_len is at most 4 x chunk has one width, its ctx_len: the program
    it ran before there were buckets."""
    eng = _engine(cfg=_cfg(max_seq_len=max_seq_len), prefill_chunk=chunk,
                  num_pages=3)
    assert eng.ctx_len == max_seq_len
    assert eng._prefill_ctx_buckets() == buckets
    assert list(eng.stats()["prefill_passes_by_width"]) == buckets
    if max_seq_len <= 4 * chunk:
        assert buckets == [eng.ctx_len]


def test_sampling_knobs_are_static_and_seeded():
    """temperature/top_k ride the decode as jit-STATIC knobs (ISSUE 13
    satellite): a sampled engine draws valid tokens deterministically
    per seed (same seed replays the same stream, different seeds
    diverge), while the default temperature=0 engine still compiles the
    exact greedy program the decode-identity gate above pins down."""
    prompt, n = [5, 9, 3], 6
    greedy = _engine()
    g = greedy.submit({"tokens": prompt, "max_new_tokens": n})
    _drain(greedy)
    _assert_greedy(greedy, prompt, g.generated, n=n)

    def sampled(seed):
        eng = _engine(seed=seed, temperature=0.8, top_k=5,
                      params=greedy._params)
        s = eng.submit({"tokens": prompt, "max_new_tokens": n})
        _drain(eng)
        assert eng.stats()["used_pages"] == 0
        return list(s.generated)

    a, b, c = sampled(7), sampled(7), sampled(8)
    assert a == b, "same seed must replay the same tokens"
    assert len(a) == n
    vocab = greedy.cfg.vocab_size
    assert all(0 <= t < vocab for t in a)
    # with top_k=5 every sampled token must come from the top-5 logits
    # at its position (teacher-forced oracle, like _assert_greedy)
    full = list(prompt) + a
    lg = np.asarray(greedy._model.apply(
        {"params": greedy._params}, np.array([full], np.int32))[0])
    for j, tok in enumerate(a):
        pos = len(prompt) + j - 1
        top5 = set(np.argsort(lg[pos])[-5:].tolist())
        assert int(tok) in top5, (j, tok, top5)
    if a != c:
        pass  # different seeds usually diverge; equality is not an error


def test_eos_stops_and_recycles():
    eng = _engine()
    probe = eng.submit({"tokens": [5, 9, 3], "max_new_tokens": 6})
    _drain(eng)
    ref = list(probe.generated)
    eos = ref[2]  # stop at the 3rd generated token
    s = eng.submit({"tokens": [5, 9, 3], "max_new_tokens": 6, "eos": eos})
    _drain(eng)
    assert s.generated == ref[:3]
    _assert_greedy(eng, [5, 9, 3], ref, n=6)
    assert eng.stats()["used_pages"] == 0


def test_chunked_prefill_does_not_stall_decodes():
    """A long prompt prefills one chunk per step while short sequences
    keep decoding — the Orca-style chunked-prefill property."""
    eng = _engine(max_batch=4, prefill_chunk=8)
    short = eng.submit({"tokens": [1, 2], "max_new_tokens": 3})
    eng.step()  # short enters decode
    long_prompt = [7] * 40  # 5 prefill chunks
    long = eng.submit({"tokens": long_prompt, "max_new_tokens": 3})
    _drain(eng)
    _assert_greedy(eng, [1, 2], short.generated, n=3)
    _assert_greedy(eng, long_prompt, long.generated, n=3)
    # the short sequence finished BEFORE the long prompt produced its
    # first token (it only needed 2 more steps; the prefill needed 5)
    assert short.first_token_at < long.first_token_at


def test_admission_shed_and_page_bounds():
    eng = _engine(num_pages=9, max_batch=1, max_queue=1)  # 1 seq + 1 queued
    a = eng.submit({"tokens": [1, 2, 3], "max_new_tokens": 20})
    eng.step()
    b = eng.submit({"tokens": [4, 5], "max_new_tokens": 4})
    with pytest.raises(LLMOverloadedError):
        eng.submit({"tokens": [6], "max_new_tokens": 2})
    with pytest.raises(ValueError):  # can never fit: not a shed
        eng.submit({"tokens": [1] * 40, "max_new_tokens": 40})
    _drain(eng)
    assert a.done and b.done and eng.stats()["used_pages"] == 0


def test_cancel_recycles_pages():
    eng = _engine()
    s = eng.submit({"tokens": [5, 9, 3], "max_new_tokens": 30,
                    "request_id": "c1"})
    for _ in range(4):
        eng.step()
    assert not s.done and eng.stats()["used_pages"] > 0
    assert eng.cancel("c1")
    st = eng.stats()
    assert st["used_pages"] == 0 and st["cancelled"] == 1
    # consumers see end-of-stream, not a hang
    assert [i for i in eng.iter_tokens(s, len(s.generated))] == []


def test_detach_grace_cancels_abandoned_sequence():
    eng = _engine(detach_grace_s=0.05)
    s = eng.submit({"tokens": [5, 9, 3], "max_new_tokens": 60})
    eng.step()
    eng.release(s)  # last consumer gone
    time.sleep(0.08)
    _drain(eng, rounds=5)
    assert s.done and s.cancelled
    assert eng.stats()["used_pages"] == 0


def test_save_restore_resumes_generation():
    """Fast chaos unit: a replica dies mid-decode; a new engine restores
    the __rt_save__ snapshot, re-prefills prompt + known tokens, and a
    re-attached consumer (same request_id, emit_from past what it saw)
    receives the identical remainder — at most one duplicated boundary."""
    eng = _engine()
    s = eng.submit({"tokens": [5, 9, 3], "max_new_tokens": 6,
                    "request_id": "r1"})
    for _ in range(3):
        eng.step()
    k = len(s.generated)
    assert 0 < k < 6
    snap = eng.save_state()

    eng2 = _engine(params=eng._params)
    eng2.restore_state(snap)
    s2 = eng2.submit({"tokens": [5, 9, 3], "max_new_tokens": 6,
                      "request_id": "r1", "emit_from": k})
    out = []
    t = threading.Thread(
        target=lambda: out.extend(eng2.iter_tokens(s2, max(0, k - 1))))
    t.start()
    _drain(eng2)
    t.join(10)
    assert not t.is_alive()
    _assert_greedy(eng, [5, 9, 3], s2.generated, n=6)
    # consumer resumed at k-1: exactly one duplicated token boundary,
    # delivered as coalesced multi-token items
    flat = [(o["i"] + j, t) for o in out
            for j, t in enumerate(o["tokens"])]
    assert [i for i, _ in flat] == list(range(k - 1, 6))
    assert [t for _, t in flat] == s2.generated[k - 1:]


def test_deadline_admission_refused():
    """The fourth deadline-enforcement site: an engine refuses admission
    when the remaining budget cannot cover prefill + one decode step —
    typed DeadlineExceededError(where=admission), no pages touched."""
    from ray_tpu._private import deadlines as dl
    from ray_tpu._private.errors import DeadlineExceededError

    eng = _engine()
    # cold engine: only an already-expired budget refuses
    token = dl.activate(time.time() - 0.5)
    try:
        with pytest.raises(DeadlineExceededError) as ei:
            eng.submit({"tokens": [1, 2], "max_new_tokens": 4})
    finally:
        dl.restore(token)
    assert ei.value.where == "admission"
    # warmed engine: a budget smaller than (prefill chunks + 1) x the
    # measured step EWMA refuses too — tokens that can't reach the
    # caller in time must not burn pages/lanes
    eng._step_ewma = 0.2  # 2 chunks + 1 decode = 0.6s needed
    with pytest.raises(DeadlineExceededError):
        eng.submit({"tokens": [1] * 16, "max_new_tokens": 4,
                    "deadline_ms": (time.time() + 0.2) * 1000.0})
    # a roomy budget admits normally
    s = eng.submit({"tokens": [1, 2], "max_new_tokens": 2,
                    "deadline_ms": (time.time() + 60.0) * 1000.0})
    assert s.deadline > 0
    _drain(eng)
    assert eng.stats()["used_pages"] == 0
    assert eng.stats()["deadline_expired"] >= 2


def test_deadline_expiry_mid_decode_recycles_pages():
    """An in-flight sequence past its deadline is cancelled by the
    engine sweep: its consumer gets the typed error and its KV pages
    return to the free pool (asserted via the ray_tpu_llm_kv_pages
    gauge, not just stats)."""
    from ray_tpu._private.errors import DeadlineExceededError
    from ray_tpu._private.metrics import llm_metrics

    eng = _engine()
    pages_gauge = llm_metrics()[1]

    def gauge(state):
        for k, v in pages_gauge._values.items():
            if ("state", state) in k:
                return v
        return None

    eng._set_gauges()
    free_baseline = gauge("free")
    s = eng.submit({"tokens": [5, 9, 3], "max_new_tokens": 60,
                    "deadline_ms": (time.time() + 0.15) * 1000.0})
    for _ in range(3):
        eng.step()
    assert not s.done and eng.stats()["used_pages"] > 0
    time.sleep(0.2)  # let the deadline pass
    eng.step()  # sweep runs at step start
    assert s.done and s.cancelled
    assert isinstance(s.error, DeadlineExceededError)
    assert s.error.where == "running"
    with pytest.raises(DeadlineExceededError):
        list(eng.iter_tokens(s, len(s.generated)))
    assert eng.stats()["used_pages"] == 0
    assert gauge("free") == free_baseline, "kv pages not back to baseline"


def test_loop_single_flight_and_stop():
    eng = _engine()
    t = threading.Thread(target=eng.run_loop, daemon=True)
    t.start()
    deadline = time.time() + 5
    while not eng.stats()["loop_running"] and time.time() < deadline:
        time.sleep(0.01)
    assert eng.stats()["loop_running"]
    # second install is a no-op (controller-restart re-ensure)
    assert eng.run_loop() == {"already_running": True}
    s = eng.submit({"tokens": [5, 9, 3], "max_new_tokens": 4})
    toks = [t for o in eng.iter_tokens(s) for t in o["tokens"]]
    _assert_greedy(eng, [5, 9, 3], toks, n=4)
    eng.stop()
    t.join(5)
    assert not t.is_alive()


def test_exit_worker_ends_a_loop_that_holds_the_main_exec_thread():
    """Whichever exec thread dequeues `__rt_dag_llm_loop__` is pinned; if
    that is the worker's MAIN thread, `exit_worker`'s sentinel is read by
    the concurrency threads only and the replica outlived its kill (one
    rehearsal in ten waited 60 s for the chip, PR 28).  `run_llm_loop`
    leaves the worker the way to end the loop, `rpc_exit_worker` uses
    it, and the thread goes back to the queue, where the sentinel is."""
    import asyncio
    import queue
    import types

    from ray_tpu._private.worker import CoreWorker
    from ray_tpu.serve.llm import run_llm_loop

    eng = _engine()
    worker = types.SimpleNamespace(_task_queue=queue.Queue(),
                                   _pinned_stop=None)
    replica = types.SimpleNamespace(_engine=eng)
    out = {}
    t = threading.Thread(
        target=lambda: out.update(run_llm_loop(worker, replica)),
        daemon=True)
    t.start()
    deadline = time.time() + 5
    while not eng.stats()["loop_running"] and time.time() < deadline:
        time.sleep(0.01)
    assert eng.stats()["loop_running"] and worker._pinned_stop is not None
    # a second install (the controller re-ensuring loops) returns at once
    # and must leave the way to end the FIRST loop in place
    assert run_llm_loop(worker, replica) == {"already_running": True}
    asyncio.run(CoreWorker.rpc_exit_worker(worker))
    t.join(5)
    assert not t.is_alive() and "steps" in out
    assert worker._task_queue.get_nowait() is None
    # a worker with no pinned loop: the sentinel and nothing else
    plain = types.SimpleNamespace(_task_queue=queue.Queue(),
                                  _pinned_stop=None)
    asyncio.run(CoreWorker.rpc_exit_worker(plain))
    assert plain._task_queue.get_nowait() is None


# --------------------------------------------- prefix sharing (CoW pages)


def _gauge_value(state):
    from ray_tpu._private.metrics import llm_metrics

    pages_gauge = llm_metrics()[1]
    for k, v in pages_gauge._values.items():
        if ("state", state) in k:
            return v
    return None


def test_prefix_sharing_decode_identity():
    """The tentpole's correctness gate: a second sequence admitted onto
    SHARED physical KV pages (full-page hits) plus a copy-on-write
    split for a mid-page divergence decodes token-identically to the
    teacher-forcing full forward."""
    eng = _engine()
    base = list(range(1, 25))  # 3 full pages at page_size=8
    s1 = eng.submit({"tokens": base, "max_new_tokens": 6,
                     "request_id": "p1"})
    for _ in range(4):
        eng.step()  # s1 past prefill: its pages are registered
    assert len(eng._groups["full"].index) == 3
    # identical prompt: 2 full shared pages + a CoW extension of 7
    # tokens (one token always left to prefill for first-token logits)
    s2 = eng.submit({"tokens": base, "max_new_tokens": 6,
                     "request_id": "p2"})
    # mid-page divergence: shares 2 full pages, CoW-copies 4 tokens
    div = base[:20] + [60, 61, 62, 63]
    eng.step()
    s3 = eng.submit({"tokens": div, "max_new_tokens": 6,
                     "request_id": "p3"})
    _drain(eng)
    st = eng.stats()
    assert st["prefix_hits"] == 2 and st["cow_splits"] == 2, st
    assert st["prefix_tokens_shared"] == 23 + 20, st
    _assert_greedy(eng, base, s1.generated, n=6)
    _assert_greedy(eng, base, s2.generated, n=6)
    assert list(s1.generated) == list(s2.generated)
    _assert_greedy(eng, div, s3.generated, n=6)
    assert st["used_pages"] == 0 and st["free_pages"] == 32, st


def test_prefix_sharing_flag_off():
    eng = _engine(prefix_sharing=False)
    base = list(range(1, 25))
    s1 = eng.submit({"tokens": base, "max_new_tokens": 4})
    for _ in range(4):
        eng.step()
    s2 = eng.submit({"tokens": base, "max_new_tokens": 4})
    _drain(eng)
    st = eng.stats()
    assert st["prefix_hits"] == 0 and st["shared_pages"] == 0
    assert list(s1.generated) == list(s2.generated)


def test_shared_pages_recycle_only_at_refcount_zero():
    """The refcount hard paths: with two sequences sharing prefix
    pages, killing one — disconnect-cancel, mid-decode deadline
    expiry, or abandoned-consumer death (the replica-OOM analogue:
    the consumer process vanishes and the grace sweep fires) — must
    NOT recycle the shared pages while the survivor decodes on them;
    the kv-pages gauge returns to baseline only when BOTH are gone."""
    from ray_tpu._private.errors import DeadlineExceededError

    base = list(range(1, 25))

    def run_pair(eng, kill, second_req=None):
        eng._set_gauges()
        free_baseline = _gauge_value("free")
        s1 = eng.submit({"tokens": base, "max_new_tokens": 40,
                         "request_id": "k1"})
        for _ in range(4):
            eng.step()
        req2 = {"tokens": base, "max_new_tokens": 6,
                "request_id": "k2", **(second_req or {})}
        s2 = eng.submit(req2)
        eng.step()
        assert eng.stats()["prefix_hits"] == 1
        refs = eng._groups["full"].refs
        shared = [p for p in s2.cache["full"].pages if refs[p] > 1]
        assert shared, "second sequence landed on no shared pages"
        assert eng.stats()["shared_pages"] == len(shared)
        kill(eng, s1)  # first holder dies mid-decode
        assert s1.done and s1.cancelled
        for p in shared:
            assert refs[p] == 1, \
                "shared page recycled while the survivor holds it"
        _drain(eng)
        assert s2.done and not s2.cancelled
        _assert_greedy(eng, base, s2.generated, n=6)
        st = eng.stats()
        assert st["used_pages"] == 0 and st["shared_pages"] == 0, st
        eng._set_gauges()
        assert _gauge_value("free") == free_baseline, \
            "kv pages gauge not back to baseline"

    # disconnect-cancel (client dropped the stream)
    run_pair(_engine(), lambda e, s: e.cancel("k1"))

    # mid-decode deadline expiry (PR-13 sweep)
    def expire(e, s):
        s.deadline = time.time() - 0.01
        e.step()  # sweep runs at step start
        assert isinstance(s.error, DeadlineExceededError)

    run_pair(_engine(), expire,
             second_req={"deadline_ms": (time.time() + 60.0) * 1000.0})

    # abandoned consumer past the grace window (replica-OOM analogue)
    def abandon(e, s):
        e.release(s)
        time.sleep(0.08)
        e.step()

    run_pair(_engine(detach_grace_s=0.05), abandon)


# ------------------------------------------------- disaggregated prefill


def test_disagg_prefill_ship_attach_identity():
    """Engine-level disaggregation: prefill_request on engine P
    exports the KV pages, the pack/unpack wire format round-trips
    byte-checksummed, and engine D attaches the shipped pages by
    request_id, emits the shipped first token, and decodes
    token-identically to the full forward — without ever running
    prefill itself."""
    from ray_tpu._private.object_transfer import (pack_kv_pages,
                                                  unpack_kv_pages)

    P = _engine()
    D = _engine(params=P._params)
    prompt = list(range(2, 21))  # 19 tokens -> 3 pages shipped
    payload = P.prefill_request({"tokens": prompt, "max_new_tokens": 6,
                                 "request_id": "ship1"})
    assert payload["meta"]["n"] == len(prompt)
    assert payload["meta"]["pages"] == 3
    stp = P.stats()
    assert stp["kv_pages_shipped_out"] == 3 and stp["used_pages"] == 0
    # the wire format: magic + crc32 header, verified on unpack
    buf = pack_kv_pages(payload["meta"], payload["rows"])
    meta, rows = unpack_kv_pages(buf)
    assert meta["first_token"] == payload["meta"]["first_token"]

    s = D.submit({"tokens": prompt, "max_new_tokens": 6,
                  "request_id": "ship1"}, kv_pack=(meta, rows))
    _drain(D)
    assert s.done and len(s.generated) == 6
    # first generated token is the prefill replica's shipped token
    assert s.generated[0] == meta["first_token"]
    _assert_greedy(D, prompt, s.generated, n=6)
    std = D.stats()
    assert std["kv_pages_shipped_in"] == 3 and std["used_pages"] == 0


def test_disagg_kv_pack_corruption_detected():
    from ray_tpu._private.object_transfer import (TransferError,
                                                  pack_kv_pages,
                                                  unpack_kv_pages)

    P = _engine()
    payload = P.prefill_request({"tokens": [5, 9, 3, 7],
                                 "max_new_tokens": 2})
    buf = bytearray(pack_kv_pages(payload["meta"], payload["rows"]))
    buf[len(buf) // 2] ^= 0xFF
    with pytest.raises(TransferError):
        unpack_kv_pages(bytes(buf))


def test_disagg_mismatched_pack_falls_back_to_local_prefill():
    """A shipment that does not describe the request's prompt is
    discarded — the sequence prefills locally and still decodes
    correctly (disaggregation must never be a correctness risk)."""
    P = _engine()
    D = _engine(params=P._params)
    payload = P.prefill_request({"tokens": [5, 9, 3, 7],
                                 "max_new_tokens": 2})
    other = [1, 2, 3, 4, 5, 6]
    s = D.submit({"tokens": other, "max_new_tokens": 4},
                 kv_pack=(payload["meta"], payload["rows"]))
    _drain(D)
    _assert_greedy(D, other, s.generated, n=4)
    assert D.stats()["kv_pages_shipped_in"] == 0


# ------------------------------------------------- serve.batch timer fix


def test_batch_full_flushes_on_notify_not_timer():
    """A batch that fills to max_batch_size must flush immediately on
    the submitting thread's notify — with a 30s wait timer, the old
    poll-the-clock flusher passes only if the notify path works."""
    from ray_tpu.serve.api import _BatchState

    calls = []
    state = _BatchState(4, 30.0)

    def call(items):
        calls.append(list(items))
        return [x * 2 for x in items]

    results = []
    threads = [threading.Thread(
        target=lambda i=i: results.append(state.submit(i, call)))
        for i in range(4)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert all(not t.is_alive() for t in threads), \
        "full batch waited out the 30s timer"
    assert time.monotonic() - t0 < 8.0
    assert sorted(results) == [0, 2, 4, 6]
    assert len(calls) == 1 and sorted(calls[0]) == [0, 1, 2, 3]


def test_batch_timer_deadline_uses_injected_clock():
    """Deadline math runs on the injectable clock: jumping the fake
    clock past the deadline flushes a partial batch with no real
    sleeping."""
    from ray_tpu.serve.api import _BatchState

    now = [0.0]
    state = _BatchState(8, 5.0, clock=lambda: now[0])
    calls = []

    def call(items):
        calls.append(list(items))
        return list(items)

    result = []
    t = threading.Thread(target=lambda: result.append(state.submit(1, call)))
    t.start()
    time.sleep(0.2)  # flusher parked on the condition
    assert not calls, "flushed before deadline with a frozen clock"
    now[0] = 10.0  # past the 5s deadline
    with state.lock:
        state.full.notify()
    t.join(5)
    assert not t.is_alive() and result == [1] and calls == [[1]]


# ------------------------------------------------------------ cluster e2e


@pytest.fixture(scope="module")
def llm_cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=96 * 1024 * 1024)
    deployed = []

    def deploy(name, **kw):
        kw.setdefault("model", dict(MODEL))
        kw.setdefault("dtype", jnp.float32)
        kw.setdefault("page_size", 8)
        kw.setdefault("num_pages", 33)
        kw.setdefault("max_batch", 4)
        kw.setdefault("prefill_chunk", 8)
        extra = {k: kw.pop(k) for k in ("num_replicas",
                                        "max_ongoing_requests",
                                        "ray_actor_options")
                 if k in kw}
        handle = serve.run(serve.llm_deployment(name, **extra, **kw))
        deployed.append(name)
        # warm every replica's jit cache (prefill + decode shapes) so
        # timed assertions never pay a compile
        for _ in range(extra.get("num_replicas", 1)):
            for ref in handle.stream({"tokens": [1], "max_new_tokens": 1}):
                ray_tpu.get(ref, timeout=120)
        return handle

    host, port = serve.start_http()
    try:
        yield {"deploy": deploy, "host": host, "port": port}
    finally:
        try:
            serve.shutdown_http()
        except Exception:
            pass
        for name in deployed:
            try:
                serve.delete(name)
            except Exception:
                pass
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


def _sse_request(host, port, name, payload, timeout=60, headers=None):
    """One streaming request over a raw socket; returns (status, items,
    sock, resp).  Caller closes sock (or uses _read_sse to drain)."""
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request("POST", f"/{name}", body=json.dumps(payload),
                 headers={"Content-Type": "application/json",
                          "Accept": "text/event-stream",
                          **(headers or {})})
    resp = conn.getresponse()
    return conn, resp


def _read_items(resp):
    return [json.loads(ln) for ln in resp.read().decode().splitlines()
            if ln.strip()]


def test_llm_sse_end_to_end(llm_cluster, llm_big):
    """Tokens stream over SSE through proxy -> handle.stream_async ->
    pinned decode loop, token-identical to the non-batched forward
    (same seed => same params as the local oracle)."""
    h = llm_big
    local = _engine()  # same seed: identical params for the oracle
    conn, resp = _sse_request(llm_cluster["host"], llm_cluster["port"],
                              "llm_big",
                              {"tokens": [5, 9, 3], "max_new_tokens": 6})
    assert resp.status == 200
    items = _read_items(resp)
    conn.close()
    flat = [(it["i"] + j, t) for it in items
            for j, t in enumerate(it["tokens"])]
    _assert_greedy(local, [5, 9, 3], [t for _, t in flat], n=6)
    assert [i for i, _ in flat] == list(range(6))
    assert items[-1]["done"] is True
    st = ray_tpu.get(h.method("stats")(), timeout=30)
    assert st["loop_running"] and st["used_pages"] == 0


@pytest.fixture(scope="module")
def llm_big(llm_cluster):
    """One bigger-context deployment shared by the end-to-end and trace
    tests (replica processes pay ~10s of eager flax init here — one
    deployment, two tests)."""
    return llm_cluster["deploy"]("llm_big",
                                 model=dict(MODEL, max_seq_len=256),
                                 num_pages=33, max_queue=1,
                                 detach_grace_s=0.3)


def test_llm_stream_trace_reaches_into_the_engine(llm_cluster, llm_big):
    """One trace from the caller to the decode loop: `serve.stream`
    (client) -> the replica's execute span (server) -> the engine's
    `llm.queue`, `llm.prefill`, `llm.decode`, flushed worker -> head
    like any other span."""
    from ray_tpu._private import tracing
    from ray_tpu.util.state import get_trace

    caller = tracing.start_span("test caller", parent=None)
    token = tracing.activate(caller.context())
    try:
        items = [ray_tpu.get(ref, timeout=60) for ref in llm_big.stream(
            {"tokens": [7, 2, 9, 4], "max_new_tokens": 5})]
    finally:
        tracing.restore(token)
    assert sum(len(it["tokens"]) for it in items) == 5
    deadline = time.monotonic() + 60
    while True:   # spans flush on the task-event cadence
        try:
            spans = get_trace(caller.trace_id)["spans"]
        except ValueError:
            spans = []
        by_name = {s["name"]: s for s in spans}
        if {"serve.stream llm_big", "llm.queue", "llm.prefill",
                "llm.decode"} <= set(by_name):
            break   # the driver's span and the worker's flush apart
        assert time.monotonic() < deadline, sorted(by_name)
        time.sleep(0.3)
    stream = by_name["serve.stream llm_big"]
    assert stream["parent_id"] == caller.span_id
    execute = next(s for s in spans if s["kind"] == tracing.KIND_SERVER)
    assert execute["parent_id"] in {s["span_id"] for s in spans}
    for name in ("llm.queue", "llm.prefill", "llm.decode"):
        assert by_name[name]["parent_id"] == execute["span_id"], name
        assert by_name[name]["attrs"]["prompt_tokens"] == 4
    assert by_name["llm.decode"]["attrs"]["tokens_generated"] == 5


@pytest.fixture(scope="module")
def llm_slow_steps(llm_cluster):
    """A deliberately BIGGER model (~15-40ms/step vs ~2ms for the tiny
    config) shared by the shed, disconnect and deadline tests: all need
    the decode to still be RUNNING when their trigger lands — the tiny
    config's 240 tokens can finish before a second request, a disconnect
    RST or a sub-second deadline is even noticed.  One queue slot (the
    shed test's; the others send one request at a time)."""
    return llm_cluster["deploy"]("llm_drop",
                                 model=dict(MODEL, dim=192, n_layers=4,
                                            hidden_dim=512,
                                            max_seq_len=256),
                                 num_pages=33, max_queue=1,
                                 detach_grace_s=0.3)


def test_llm_queue_full_sheds_503(llm_cluster, llm_slow_steps):
    """Admission past the bounded queue answers 503 BEFORE any SSE
    bytes (the first-item prefetch maps LLMOverloadedError to the shed
    gate) — and below capacity a queued request gets 200, not shed."""
    h = llm_slow_steps
    host, port = llm_cluster["host"], llm_cluster["port"]
    # hold EVERY page (32 of 8 slots for 253 tokens) with a generation as
    # long as the context allows, on the slow model: 250 steps of 15 ms
    # and more cannot end before the two requests below have landed...
    c1, r1 = _sse_request(host, port, "llm_drop",
                          {"tokens": [1, 2, 3], "max_new_tokens": 250})
    assert r1.status == 200
    r1.read(1)  # first token arrived: sequence is active
    # ...so a second request parks in the single queue slot (on a
    # thread: its response line only arrives once its first token does,
    # i.e. after the holder's pages are back)
    q_result = {}

    def _queued_request():
        c2, r2 = _sse_request(host, port, "llm_drop",
                              {"tokens": [4, 5], "max_new_tokens": 20},
                              timeout=120)
        q_result["status"] = r2.status
        q_result["items"] = _read_items(r2)
        c2.close()

    t = threading.Thread(target=_queued_request)
    t.start()
    deadline = time.time() + 60
    st = {}
    while time.time() < deadline:
        st = ray_tpu.get(h.method("stats")(), timeout=30)
        if st["queued"] >= 1:
            break
        time.sleep(0.05)
    assert st["queued"] >= 1 and st["active"] == 1, st
    # the third concurrent stream sheds with a real status code
    c3, r3 = _sse_request(host, port, "llm_drop",
                          {"tokens": [6], "max_new_tokens": 2})
    assert r3.status == 503, r3.status
    c3.close()
    # drop the holder (RST): its pages come back after the grace window
    c1.sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                       b"\x01\x00\x00\x00\x00\x00\x00\x00")
    c1.close()
    t.join(120)
    assert not t.is_alive()
    # below capacity = no shed: the queued request completed normally
    assert q_result["status"] == 200
    assert sum(len(it["tokens"]) for it in q_result["items"]) == 20


def test_llm_disconnect_frees_kv_pages(llm_cluster, llm_slow_steps):
    """Client vanishes mid-stream: the chunk writer's failure closes the
    stream chain, the handle cancels the replica-side generator, and
    the engine recycles the sequence's pages after the grace window —
    instead of decoding another ~200 tokens for nobody."""
    h = llm_slow_steps
    before = ray_tpu.get(h.method("stats")(), timeout=30)
    conn, resp = _sse_request(llm_cluster["host"], llm_cluster["port"],
                              "llm_drop",
                              {"tokens": [5, 9, 3], "max_new_tokens": 240})
    assert resp.status == 200
    resp.read(1)  # at least one token delivered
    conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         b"\x01\x00\x00\x00\x00\x00\x00\x00")  # RST
    conn.close()
    deadline = time.time() + 60
    st = {}
    while time.time() < deadline:
        st = ray_tpu.get(h.method("stats")(), timeout=30)
        if st["cancelled"] > before["cancelled"] \
                and st["used_pages"] == 0:
            break
        time.sleep(0.1)
    assert st.get("cancelled", 0) > before["cancelled"] \
        and st.get("used_pages") == 0, (before, st)


def test_llm_stream_deadline_expires_mid_decode(llm_cluster,
                                                llm_slow_steps):
    """Deadline-vs-stream interaction (ISSUE 14 satellite): an SSE
    stream whose X-Request-Deadline-Ms budget expires mid-decode closes
    with a TYPED error chunk (DeadlineExceededError, never a silent
    truncation) AND the sequence's KV pages recycle back to baseline."""
    h = llm_slow_steps
    before = ray_tpu.get(h.method("stats")(), timeout=30)
    # self-calibrating budget: decode speed varies box to box, so walk
    # the budget down until the deadline bites mid-stream (a too-roomy
    # budget lets the whole stream finish; that attempt just retries
    # tighter).  TTFT is warm (<~50ms), so even the tightest budget
    # still covers admission + first token.
    token_items = err_items = None
    for budget_s in (0.8, 0.4, 0.2, 0.1):
        deadline_ms = (time.time() + budget_s) * 1000.0
        conn, resp = _sse_request(
            llm_cluster["host"], llm_cluster["port"], "llm_drop",
            {"tokens": [5, 9, 3], "max_new_tokens": 240},
            headers={"X-Request-Deadline-Ms": str(deadline_ms)})
        assert resp.status == 200, \
            f"budget {budget_s}s did not even cover TTFT"
        items = _read_items(resp)
        conn.close()
        token_items = [it for it in items if "i" in it]
        err_items = [it for it in items if "error" in it]
        if sum(len(it["tokens"]) for it in token_items) < 240:
            break  # the deadline bit mid-decode
    assert token_items, "no tokens before the deadline"
    assert sum(len(it["tokens"]) for it in token_items) < 240, \
        "stream finished under every budget — deadline never bit"
    assert err_items and "DeadlineExceededError" in err_items[-1]["error"], \
        (items[-3:] if items else items)
    # KV pages back to baseline (the engine expired the sequence and
    # recycled; the free-page gauge is stats' source of truth)
    deadline = time.time() + 60
    st = {}
    while time.time() < deadline:
        st = ray_tpu.get(h.method("stats")(), timeout=30)
        if st["used_pages"] == 0 \
                and st["deadline_expired"] > before["deadline_expired"]:
            break
        time.sleep(0.1)
    assert st.get("used_pages") == 0, st
    assert st.get("deadline_expired", 0) > before["deadline_expired"], st


@pytest.mark.slow
def test_llm_replica_death_resumes_stream(llm_cluster):
    """Chaos ride: SIGKILL the replica worker mid-decode.  The proxy's
    resumable retry re-submits with emit_from on a survivor, which
    re-prefills (greedy decode is deterministic) — the client's SSE
    stream is the exact token sequence with at most one duplicated
    token boundary."""
    llm_cluster["deploy"]("llm_chaos", num_replicas=2,
                          model=dict(MODEL, max_seq_len=256),
                          num_pages=40, detach_grace_s=5.0)
    n = 120
    conn, resp = _sse_request(llm_cluster["host"], llm_cluster["port"],
                              "llm_chaos",
                              {"tokens": [5, 9, 3], "max_new_tokens": n,
                               "request_id": "chaos1"}, timeout=120)
    assert resp.status == 200
    # stream a few items, then SIGKILL the serving replica's worker
    buf = b""
    while buf.count(b"\n") < 8:
        buf += resp.read1(4096)
    w = ray_tpu.api._worker()
    victims = []
    for a in w.head.call("list_actors", timeout=30)["actors"]:
        if a.get("name", "").startswith("serve:llm_chaos") \
                and a.get("state") == "ALIVE":
            victims.append(a)
    # kill whichever replica holds the live sequence
    killed = False
    for a in victims:
        try:
            hdl = ray_tpu.get_actor(a["name"])
            st = ray_tpu.get(
                hdl.handle_request.remote("stats", (), {}), timeout=30)
            if st["active"] >= 1:
                ray_tpu.kill(hdl)
                killed = True
                break
        except Exception:
            continue
    assert killed, "no replica owned the live sequence"
    rest = resp.read()  # proxy resumes on a survivor
    conn.close()
    lines = [ln for ln in (buf + rest).decode().splitlines() if ln.strip()]
    items = [json.loads(ln) for ln in lines]
    errs = [it for it in items if not (isinstance(it, dict) and "i" in it)]
    assert not errs, f"stream carried errors: {errs}"
    flat = [(it["i"] + j, t) for it in items
            for j, t in enumerate(it["tokens"])]
    idx = [i for i, _ in flat]
    # at-most-one duplicated boundary, then strictly resuming
    dups = [i for i in set(idx) if idx.count(i) > 1]
    assert len(dups) <= 1, idx
    seen = dict(flat)
    assert sorted(seen) == list(range(n)), sorted(seen)[-5:]
    local = _engine()  # same seed: identical params for the oracle
    _assert_greedy(local, [5, 9, 3], [seen[i] for i in range(n)], n=n)


# ----------------------------------- disaggregated prefill: e2e + chaos


def test_disagg_kv_ship_survives_corrupt_transfer(tmp_path, monkeypatch):
    """Acceptance E2E: a prompt prefilled on one engine (the prefill
    replica) ships its packed KV pages over the bulk transfer plane;
    the transfer is chaos-corrupted ONCE, caught by the seal-time CRC,
    re-pulled from an alternate holder, unpacked (byte-checksummed wire
    format), and attached on a second engine (the decode replica) —
    whose decode is token-identical to the full forward."""
    import asyncio
    import uuid

    from ray_tpu._private import fault_injection
    from ray_tpu._private.head import HeadService
    from ray_tpu._private.node_agent import NodeAgent
    from ray_tpu._private.object_transfer import (pack_kv_pages,
                                                  unpack_kv_pages)

    P = _engine()
    D = _engine(params=P._params)
    prompt = list(range(2, 21))
    payload = P.prefill_request({"tokens": prompt, "max_new_tokens": 6,
                                 "request_id": "kvchaos"})
    buf = pack_kv_pages(payload["meta"], payload["rows"])
    MB = 1024 * 1024
    # the tiny model's KV pack is a few tens of KB — below the default
    # 1MB directory floor no holder would ever be announced, and the
    # alternate-holder retry needs the directory to know both copies
    monkeypatch.setenv("RT_LOCALITY_MIN_BYTES", "1024")

    async def ship():
        head = HeadService()
        head_port = await head.start()
        agents = []
        for i in range(3):
            ag = NodeAgent(("127.0.0.1", head_port), str(tmp_path),
                           {"CPU": 1},
                           arena_path=str(
                               tmp_path /
                               f"arena-{i}-{uuid.uuid4().hex[:6]}"),
                           capacity=32 * MB)
            await ag.start()
            agents.append(ag)
        a, b, c = agents
        try:
            loc = a.store.create("kvship", len(buf), primary=True)
            if loc["location"] == "shm":
                a.store.arena.view[loc["offset"]:loc["offset"] + len(buf)] \
                    = buf
            else:
                with open(loc["path"], "r+b") as f:
                    f.write(buf)
            a.store.seal("kvship")
            # a second holder so an alternate exists in the directory
            r = await b.rpc_ensure_local("kvship", src=[a.host, a.port])
            assert r.get("ok"), r
            deadline = time.monotonic() + 10
            while len(head.dir.locations("kvship")) < 2:
                assert time.monotonic() < deadline, "no second holder"
                await asyncio.sleep(0.05)
            fault_injection.inject("xfer.send", "corrupt", count=1,
                                   target="kvship")
            r = await c.rpc_ensure_local("kvship")
            assert r.get("ok"), r
            assert c.xfer_stats["checksum_failures"] == 1
            assert c.xfer_stats["alt_source_retries"] == 1
            entry = c.store.objects["kvship"]
            if entry.location == "shm":
                return bytes(c.store.arena.view[
                    entry.offset:entry.offset + len(buf)])
            with open(entry.path, "rb") as f:
                return f.read()
        finally:
            fault_injection.clear()
            for ag in agents:
                try:
                    await ag.stop()
                except Exception:
                    pass
            await head.stop()

    data = asyncio.run(ship())
    assert data == buf  # survived the corrupted transfer byte-exact
    meta, rows = unpack_kv_pages(data)
    s = D.submit({"tokens": prompt, "max_new_tokens": 6,
                  "request_id": "kvchaos"}, kv_pack=(meta, rows))
    _drain(D)
    assert s.done and s.generated[0] == meta["first_token"]
    _assert_greedy(D, prompt, s.generated, n=6)
    assert D.stats()["kv_pages_shipped_in"] == 3


def test_llm_disaggregated_prefill_serve_e2e(llm_cluster):
    """llm_deployment(prefill_replicas=1) deploys TWO pools; an SSE
    request's prefill phase runs on the dedicated pool (the handle's
    prefill hop), its KV pages ship by kv_ref, and the decode replica
    attaches them — token-identical to the local oracle, with the
    shipped-page counters moving on both sides."""
    h = llm_cluster["deploy"]("llm_disagg", prefill_replicas=1,
                              detach_grace_s=5.0)
    pf = serve.get_handle("llm_disagg-prefill")
    prompt = list(range(3, 22))  # 19 tokens -> 3 shipped pages
    conn, resp = _sse_request(llm_cluster["host"], llm_cluster["port"],
                              "llm_disagg",
                              {"tokens": prompt, "max_new_tokens": 6})
    assert resp.status == 200
    items = _read_items(resp)
    conn.close()
    flat = [(it["i"] + j, t) for it in items
            for j, t in enumerate(it["tokens"])]
    assert [i for i, _ in flat] == list(range(6))
    local = _engine()  # same seed: identical params for the oracle
    _assert_greedy(local, prompt, [t for _, t in flat], n=6)
    # the decode replica imported the shipped pages instead of
    # prefilling; the prefill replica exported them and recycled
    std = ray_tpu.get(h.method("stats")(), timeout=30)
    assert std["kv_pages_shipped_in"] >= 3, std
    stp = ray_tpu.get(pf.method("stats")(), timeout=30)
    assert stp["kv_pages_shipped_out"] >= 3, stp
    assert stp["used_pages"] == 0 and std["used_pages"] == 0
