"""The three shapes of a prefill pass (serve/llm.py, `_prefill_shape`),
by case.  NARROW: a step with at most PREFILL_NARROW_LANES prompts
prefilling, none past the narrow program's one context width.  DEEP: the
wide pass's slots as PREFILL_NARROW_LANES lanes of a deep chunk, where
one of the first two prompts has a deep chunk left or a context lies
past the narrow width.  WIDE: every other step.  A case takes a family's
kit — `make(**engine_kwargs)`: an engine of chunk 16 and 4 decode lanes
(so 4 prefill lanes and a deep pass of 2 x 32) whose context gives three
prefill widths, 64, 256 and one past it; `make_one_width()`: the same
with a context of at most 4 chunks; `prompt(n, salt)`; and `check(eng,
prompts, outs)`, which holds every generated token to the argmax of the
family's no-cache forward.  Each family's test file parametrises one
test over CASES and calls the case with its own kit.
"""

import itertools

from ray_tpu.serve.llm import PREFILL_NARROW_LANES

CHUNK, LANES = 16, 4
DEEP_CHUNK = LANES * CHUNK // PREFILL_NARROW_LANES
NARROW = (PREFILL_NARROW_LANES, CHUNK, 256)
DEEP = (PREFILL_NARROW_LANES, DEEP_CHUNK)

_KEYS = ("prefill_steps", "prefill_narrow_passes_total",
         "prefill_deep_passes_total", "prefill_slots_total",
         "prefill_ctx_cols_total", "prefill_tokens_total")


def teacher_forced_check(ref, sizes):
    """A kit's `check` from a benchmark reference module: every token
    is the reference's argmax given the engine's own earlier tokens."""
    def check(eng, prompts, outs):
        refs = ref.teacher_forced(eng._params, prompts, outs, sizes)
        for p, out, r in zip(prompts, outs, refs):
            assert out and out == r["top_id"], f"prompt of {len(p)}"
    return check


def _counts(eng):
    st = eng.stats()
    return [st[k] for k in _KEYS]


def _step_shape(eng):
    """One step; the (lanes, chunk, width) of its prefill pass by the
    counters, None when it had none."""
    before = _counts(eng)
    eng.step()
    passes, narrow, deep, slots, cols, _t = (
        b - a for a, b in zip(before, _counts(eng)))
    if not passes:
        return None
    assert passes == 1 and narrow + deep <= 1
    lanes = PREFILL_NARROW_LANES if narrow or deep else eng.prefill_lanes
    return lanes, slots // lanes, cols // lanes


def _drain(eng, rounds=600):
    for _ in range(rounds):
        if not eng.step():
            return eng.drain()
    raise AssertionError("the engine did not go idle")


def _spy_shapes(eng):
    """Every `_forward` of the engine from here on, as (lanes, columns,
    context width or table width)."""
    shapes, forward = [], eng._forward

    def spy(tokens, q_pos, last_idx, groups, **kw):
        full = groups["full"]
        width = full["ctx" if "ctx" in full else "block_tables"].shape[1]
        shapes.append((*tokens.shape, width))
        return forward(tokens, q_pos, last_idx, groups, **kw)

    eng._forward = spy
    return shapes


def one_two_three_prompts(kit):
    """(a) and (e): one, three, then two prompts prefilling together,
    none with a deep chunk to go — narrow, wide (at the narrow width: an
    engine with a deep pass has no wide program at the first), narrow;
    the counters are the hand count of those passes."""
    eng = kit.make()
    assert eng.prefill_lanes == LANES
    assert (*eng._narrow_prefill, eng._deep_prefill) == (
        NARROW[0], NARROW[2], DEEP)
    wide_last = eng._prefill_widths[-1]
    assert eng._prefill_widths == [64, 256, wide_last]
    prompts = [kit.prompt(30, salt) for salt in (1, 2, 3)]
    start = _counts(eng)
    seqs = [eng.submit({"tokens": prompts[0], "max_new_tokens": 6})]
    shapes = [_step_shape(eng)]          # 2 chunks each: 16, 14
    seqs += [eng.submit({"tokens": p, "max_new_tokens": 6})
             for p in prompts[1:]]
    # A alone; A's last chunk beside B and C: three wait; B and C
    shapes += [_step_shape(eng) for _ in range(3)]
    assert shapes == [NARROW, (LANES, CHUNK, 256), NARROW, None]
    assert all(s.state == "decode" for s in seqs)
    _drain(eng)
    passes, narrow, deep, slots, cols, tokens = (
        b - a for a, b in zip(start, _counts(eng)))
    assert (passes, narrow, deep, tokens) == (3, 2, 0, 3 * 30)
    assert slots == (2 * NARROW[0] + LANES) * CHUNK
    assert cols == 2 * NARROW[0] * NARROW[2] + LANES * 256
    kit.check(eng, prompts, [list(s.generated) for s in seqs])
    assert eng.stats()["used_pages"] == 0


def prompt_ends_in_a_narrow_pass(kit):
    """(b): prompts that END in a narrow pass decode in the next step
    from the token that pass left on the device: its output has the wide
    pass's shape, `seq.feed` points into it, and the stream is right."""
    eng = kit.make()
    prompts = [kit.prompt(20, 4), kit.prompt(24, 5)]
    seqs = [eng.submit({"tokens": p, "max_new_tokens": 8}) for p in prompts]
    assert [_step_shape(eng), _step_shape(eng)] == [NARROW, NARROW]
    base = eng._no_feed[0].shape[0]
    assert eng._feed[1].shape == eng._no_feed[1].shape
    assert eng._feed[1].shape[0] >= eng.prefill_lanes > NARROW[0]
    for lane, seq in enumerate(seqs):
        assert seq.state == "decode" and not seq.generated
        assert (seq.ahead, seq.feed) == (1, base + lane)
    ahead = eng.stats()["runahead_decode_steps_total"]
    eng.step()   # the decode pass is dispatched BEFORE that token is read
    assert eng.stats()["runahead_decode_steps_total"] == ahead + 1
    for lane, seq in enumerate(seqs):
        assert len(seq.generated) == 1 and (seq.ahead, seq.feed) == (1, lane)
    _drain(eng)
    assert eng.stats()["decode_lane_steps_wasted_total"] == 0
    kit.check(eng, prompts, [list(s.generated) for s in seqs])


def context_past_the_narrow_width(kit):
    """(c): a long prompt is deep from its first chunk, a short one
    beside it riding the second lane; past the narrow program's width
    its TAIL (less than a deep chunk) is deep too — the wide program does
    not exist there — and a short prompt that arrives meanwhile rides
    that pass."""
    eng = kit.make()
    wide_last = eng._prefill_widths[-1]
    prompts = [kit.prompt(300, 6), kit.prompt(5, 7)]
    seqs = [eng.submit({"tokens": p, "max_new_tokens": 4}) for p in prompts]
    shapes = [_step_shape(eng) for _ in range(9)]
    # chunks ending at 32 .. 256 fit the second width; 288 does not
    assert shapes == [(*DEEP, 256)] * 8 + [(*DEEP, wide_last)]
    assert seqs[1].state == "decode" and seqs[0].pos == 288
    late = eng.submit({"tokens": kit.prompt(7, 9), "max_new_tokens": 3})
    # 12 rows to go, no deep chunk: deep by the context alone
    assert _step_shape(eng) == (*DEEP, wide_last)
    assert all(s.state == "decode" for s in seqs + [late])
    assert (LANES, CHUNK, wide_last) not in eng._prefill_programs()
    _drain(eng)
    kit.check(eng, prompts + [kit.prompt(7, 9)],
              [list(s.generated) for s in seqs + [late]])
    assert eng.stats()["used_pages"] == 0


def warm_up_then_mixed_compiles_nothing(kit):
    """(d): `warm_up()` warms every prefill program — the wide pass at
    the second width, the ONE narrow program, the deep pass from the
    second width up: as many as there are widths, + 1, what an engine
    held to the chunk compiles — and the decode widths; a mixed run of
    narrow, wide and deep passes, a long prompt among them, then compiles
    nothing."""
    eng = kit.make()
    shapes = _spy_shapes(eng)
    eng.warm_up()
    widths = eng._prefill_widths
    prefill = {(LANES, CHUNK, widths[1]), NARROW} \
        | {(*DEEP, w) for w in widths[1:]}
    assert set(eng._prefill_programs()) == prefill
    assert len(prefill) == len(widths) + 1
    decode = {(eng.max_batch, 1, w) for w in eng._paged_width_buckets()}
    assert set(shapes) == prefill | decode
    assert len(set(shapes)) == len(prefill) + len(decode)
    # its own one-token prompt ran the narrow pass: warmed once, not twice
    assert shapes.count(NARROW) == 1
    steps = eng.device_report()["compiled_steps"]
    before = eng.stats()
    del shapes[:]
    for lengths in ([20], [30, 14, 25], [300, 5], [70, 9, 3, 12], [17, 33]):
        seqs = [eng.submit({"tokens": kit.prompt(n, n), "max_new_tokens": 3})
                for n in lengths]
        _drain(eng)
        assert all(s.done and len(s.generated) == 3 for s in seqs)
    after = eng.stats()
    narrow, deep, passes = (after[k] - before[k] for k in (
        "prefill_narrow_passes_total", "prefill_deep_passes_total",
        "prefill_steps"))
    assert narrow > 0 and deep > 0 and narrow + deep < passes
    assert {s for s in shapes if s[1] > 1} == prefill
    assert eng.device_report()["compiled_steps"] == steps
    assert after["compiles_total"] == before["compiles_total"]


def one_prefill_width_has_no_narrow_pass(kit):
    """(d), the other half: an engine with ONE prefill width (a context
    of at most 4 chunks: the bench rehearsal's sizes) warms what it
    warmed before there was a narrow pass, and never runs one."""
    eng = kit.make_one_width()
    assert eng._prefill_widths == [eng.ctx_len] and eng.ctx_len <= 4 * CHUNK
    assert eng._narrow_prefill is None and eng._deep_prefill is None
    assert eng._prefill_programs() == [(eng.prefill_lanes, CHUNK,
                                        eng.ctx_len)]
    shapes = _spy_shapes(eng)
    eng.warm_up()
    lanes = eng.prefill_lanes
    assert set(shapes) == {(lanes, CHUNK, eng.ctx_len)} | {
        (eng.max_batch, 1, w) for w in eng._paged_width_buckets()}
    assert shapes.count((lanes, CHUNK, eng.ctx_len)) == 1
    before = eng.stats()
    prompts = [kit.prompt(n, n) for n in (20, 9, 30)]
    outs = [eng.generate_batch([{"tokens": p, "max_new_tokens": 3}
                                for p in prompts[:upto]])
            for upto in (1, 2, 3)]
    after = eng.stats()
    assert after["prefill_narrow_passes_total"] == 0
    assert after["prefill_deep_passes_total"] == 0
    assert after["prefill_slots_total"] - before["prefill_slots_total"] \
        == (after["prefill_steps"] - before["prefill_steps"]) * lanes * CHUNK
    assert after["compiles_total"] == before["compiles_total"]
    for upto, out in zip((1, 2, 3), outs):
        kit.check(eng, prompts[:upto], out)



def shape_table(kit):
    """The rule alone, no pass run: which of narrow / wide / deep a step
    picks for the prompts it finds in prefill, `(pos, end)` each in
    admission order — and over a grid of such steps never a program that
    warm-up does not compile."""
    eng = kit.make()
    last = eng._prefill_widths[-1]
    wide = lambda w: (LANES, CHUNK, w)     # noqa: E731
    deep = lambda w: (*DEEP, w)            # noqa: E731
    table = [
        # short prompts: the parent's choice stands
        ([(0, 5)], NARROW), ([(0, 31)], NARROW), ([(16, 31), (0, 20)], NARROW),
        ([(0, 5), (0, 9), (0, 3)], wide(256)),
        ([(0, 31), (16, 30), (0, 31), (0, 4)], wide(256)),
        ([(64, 80), (0, 9), (0, 3)], wide(256)),
        # a whole deep chunk to go in one of the first two lanes: deep,
        # as wide as the deep lanes' own contexts ask
        ([(0, 32)], deep(256)), ([(0, 300)], deep(256)),
        ([(0, 5), (0, 40)], deep(256)), ([(224, 300)], deep(256)),
        ([(256, 300)], deep(last)), ([(0, 9), (240, 300)], deep(last)),
        ([(0, 300), (0, 5), (0, 9), (0, 3)], deep(256)),
        # ... but not in the third: it waits its turn, the pass is wide
        ([(0, 5), (0, 9), (0, 300)], wide(256)),
        ([(0, 5), (0, 9), (230, 300)], wide(256)),
        # a context past the narrow width, whoever's, no deep chunk left:
        # deep by the context, at the width its two lanes read
        ([(288, 300)], deep(last)), ([(290, 300), (0, 5)], deep(last)),
        ([(0, 5), (0, 9), (250, 270)], deep(256)),
        ([(0, 5), (288, 300), (0, 9)], deep(last)),
    ]
    for waiting, shape in table:
        assert eng._prefill_shape(waiting) == shape, waiting
    programs = set(eng._prefill_programs())
    ends = (1, 15, 16, 31, 32, 33, 64, 65, 255, 256, 257, last - 1, last)
    one = [(pos, end) for end in ends
           for pos in {0, max(0, end - 1), max(0, end - 31),
                       max(0, end - 32), end // 2}]
    for n in (1, 2, 3):
        for waiting in itertools.product(one, repeat=n):
            assert eng._prefill_shape(list(waiting)) in programs, waiting
    flat = kit.make_one_width()
    for waiting in itertools.product(
            [(0, 1), (0, 40), (30, 64), (0, 64)], repeat=2):
        assert eng._prefill_shape(list(waiting)) in programs
        assert flat._prefill_shape(list(waiting)) \
            == (flat.prefill_lanes, CHUNK, flat.ctx_len)


def _held_to_the_chunk(eng):
    """The engine as it was before there was a deep pass: every lane a
    chunk a step, the wide pass at every width."""
    eng._deep_prefill = None
    assert len(eng._prefill_programs()) == len(eng._prefill_widths) + 1
    return eng


def deep_passes_first_come_first_served(kit):
    """Three prompts at once, two of them with deep chunks to go: the
    deep pass advances the first two by 32 a step while the third keeps
    its lane and waits; when no deep chunk is left the narrow pass ends
    them.  The counters are the hand count; the tokens are the
    reference's, and those of an engine held to chunks of 16."""
    eng = kit.make()
    prompts = [kit.prompt(n, n) for n in (100, 70, 20)]
    start = _counts(eng)
    seqs = [eng.submit({"tokens": p, "max_new_tokens": 6}) for p in prompts]
    shapes = [_step_shape(eng) for _ in range(3)]
    # A to 96, B to its end at 70; C has not moved
    assert shapes == [(*DEEP, 256)] * 3
    assert [s.pos for s in seqs] == [96, 70, 0]
    assert [s.state for s in seqs] == ["prefill", "decode", "prefill"]
    # A's last 4 rows beside C's first chunk; C's 4 alone
    assert [_step_shape(eng) for _ in range(3)] == [NARROW, NARROW, None]
    _drain(eng)
    passes, narrow, deep, slots, cols, tokens = (
        b - a for a, b in zip(start, _counts(eng)))
    assert (passes, narrow, deep, tokens) == (5, 2, 3, 190)
    assert slots == 3 * LANES * CHUNK + 2 * NARROW[0] * CHUNK
    assert cols == 5 * NARROW[0] * 256
    outs = [list(s.generated) for s in seqs]
    kit.check(eng, prompts, outs)
    held = _held_to_the_chunk(kit.make())
    assert held.generate_batch([{"tokens": p, "max_new_tokens": 6}
                                for p in prompts]) == outs
    st = held.stats()
    assert st["prefill_deep_passes_total"] == 0 and st["prefill_steps"] == 7
    assert eng.stats()["used_pages"] == 0


def a_long_prompt_alone_is_deep_to_its_end(kit):
    """One prompt of 300 rows alone: ten deep passes, eight at the second
    width and two past it (the last of 12 rows, deep by its context),
    where chunks of 16 take 19 — with prefix sharing off and, where the
    family shares, on (a second prompt then starts behind the first
    one's pages, mid-prompt, and is deep from there)."""
    prompt = kit.prompt(300, 11)
    request = {"tokens": prompt, "max_new_tokens": 5}
    outs = {}
    for sharing in (False, True):
        eng = kit.make(prefix_sharing=sharing)
        last = eng._prefill_widths[-1]
        seq = eng.submit(dict(request))
        shapes = [_step_shape(eng) for _ in range(11)]
        assert shapes == [(*DEEP, 256)] * 8 + [(*DEEP, last)] * 2 + [None]
        st = eng.stats()
        assert st["prefill_deep_passes_total"] == st["prefill_steps"] == 10
        assert st["prefill_slots_total"] == 10 * LANES * CHUNK
        assert st["prefill_tokens_total"] == 300
        assert st["prefill_passes_by_width"] == {64: 0, 256: 8, last: 2}
        # the same prompt again while the first one lives
        again = eng.submit(dict(request, request_id="again"))
        _drain(eng)
        shared = eng.stats()["prefix_tokens_shared"]
        assert (shared > 0) == eng.stats()["prefix_sharing"]
        assert list(again.generated) == list(seq.generated)
        outs[sharing] = list(seq.generated)
        kit.check(eng, [prompt], [outs[sharing]])
        assert eng.stats()["used_pages"] == 0
    assert outs[False] == outs[True]
    held = _held_to_the_chunk(kit.make())
    assert held.generate_batch([request]) == [outs[False]]
    assert held.stats()["prefill_steps"] == 19


CASES = [one_two_three_prompts, prompt_ends_in_a_narrow_pass,
         context_past_the_narrow_width, warm_up_then_mixed_compiles_nothing,
         one_prefill_width_has_no_narrow_pass, shape_table,
         deep_passes_first_come_first_served,
         a_long_prompt_alone_is_deep_to_its_end]
