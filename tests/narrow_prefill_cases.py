"""The narrow prefill pass (serve/llm.py, `_prefill_shape`), by case: a
step with at most PREFILL_NARROW_LANES prompts prefilling, none past the
narrow program's one context width, runs that program; every other step
the wide one.  A case takes a family's kit — `make(**engine_kwargs)`: an
engine of chunk 16 and 4 decode lanes (so 4 prefill lanes) whose context
gives three prefill widths, 64, 256 and one past it; `make_one_width()`:
the same with a context of at most 4 chunks; `prompt(n, salt)`; and
`check(eng, prompts, outs)`, which holds every generated token to the
argmax of the family's no-cache forward.  Each family's test file
parametrises one test over CASES and calls the case with its own kit.
"""

from ray_tpu.serve.llm import PREFILL_NARROW_LANES

CHUNK, LANES = 16, 4
NARROW = (PREFILL_NARROW_LANES, 256)

_KEYS = ("prefill_steps", "prefill_narrow_passes_total",
         "prefill_slots_total", "prefill_ctx_cols_total",
         "prefill_tokens_total")


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
    """One step; the (lanes, width) of its prefill pass by the counters,
    None when it had none."""
    before = _counts(eng)
    eng.step()
    passes, narrow, slots, cols, _t = (
        b - a for a, b in zip(before, _counts(eng)))
    if not passes:
        return None
    lanes = slots // CHUNK
    assert passes == 1 and narrow == (lanes < eng.prefill_lanes)
    return lanes, cols // lanes


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
    """(a) and (e): one, two, then three prompts prefilling together —
    narrow, narrow, wide — and narrow again when the first has ended;
    the counters are the hand count of those five passes."""
    eng = kit.make()
    assert eng.prefill_lanes == LANES and eng._narrow_prefill == NARROW
    wide_last = eng._prefill_widths[-1]
    assert eng._prefill_widths == [64, 256, wide_last]
    prompts = [kit.prompt(40, salt) for salt in (1, 2, 3)]
    start = _counts(eng)
    seqs, shapes = [], []
    for p in prompts:      # 3 chunks each: 16, 16, 8
        seqs.append(eng.submit({"tokens": p, "max_new_tokens": 6}))
        shapes.append(_step_shape(eng))
    # A alone; A and B; A's last chunk beside B and C: three wait
    assert shapes == [NARROW, NARROW, (LANES, 64)]
    assert seqs[0].state == "decode" and seqs[1].state == "prefill"
    # B's last chunk beside C's second; C alone; nobody
    assert [_step_shape(eng) for _ in range(3)] == [NARROW, NARROW, None]
    assert all(s.state == "decode" for s in seqs)
    _drain(eng)
    passes, narrow, slots, cols, tokens = (
        b - a for a, b in zip(start, _counts(eng)))
    assert (passes, narrow, tokens) == (5, 4, 3 * 40)
    assert slots == (4 * NARROW[0] + LANES) * CHUNK
    assert cols == 4 * NARROW[0] * NARROW[1] + LANES * 64
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
    """(c): two prompts prefill, so the lanes would fit, but the longer
    one's context passes the narrow program's width: from that chunk on
    the pass is the wide program at the width that covers it."""
    eng = kit.make()
    wide_last = eng._prefill_widths[-1]
    prompts = [kit.prompt(300, 6), kit.prompt(5, 7)]
    seqs = [eng.submit({"tokens": p, "max_new_tokens": 4}) for p in prompts]
    shapes = [_step_shape(eng) for _ in range(19)]
    # chunks ending at 16 .. 256 fit; those ending at 272, 288, 300 do not
    assert shapes == [NARROW] * 16 + [(LANES, wide_last)] * 3
    assert all(s.state == "decode" for s in seqs)
    # a second short prompt beside the long one's wide chunks: still wide
    long2 = eng.submit({"tokens": kit.prompt(290, 8), "max_new_tokens": 3})
    for _ in range(16):
        assert _step_shape(eng) == NARROW
    late = eng.submit({"tokens": kit.prompt(7, 9), "max_new_tokens": 3})
    assert _step_shape(eng) == (LANES, wide_last)
    assert late.state == "decode" and long2.pos == 272
    _drain(eng)
    kit.check(eng, prompts + [kit.prompt(290, 8), kit.prompt(7, 9)],
              [list(s.generated) for s in seqs + [long2, late]])
    assert eng.stats()["used_pages"] == 0


def warm_up_then_mixed_compiles_nothing(kit):
    """(d): `warm_up()` warms the prefill widths, the ONE narrow program
    and the decode widths; a mixed run of narrow and wide passes then
    compiles nothing."""
    eng = kit.make()
    shapes = _spy_shapes(eng)
    eng.warm_up()
    prefill = {(LANES, CHUNK, w) for w in eng._prefill_widths}
    prefill.add((NARROW[0], CHUNK, NARROW[1]))
    decode = {(eng.max_batch, 1, w) for w in eng._paged_width_buckets()}
    assert set(shapes) == prefill | decode
    assert len(set(shapes)) == len(eng._prefill_widths) + 1 \
        + len(eng._paged_width_buckets())
    # its own one-token prompt ran the narrow pass: warmed once, not twice
    assert shapes.count((NARROW[0], CHUNK, NARROW[1])) == 1
    steps = eng.device_report()["compiled_steps"]
    before = eng.stats()
    del shapes[:]
    for lengths in ([20], [30, 40, 50], [300, 5], [70, 9, 3, 12], [17, 33]):
        seqs = [eng.submit({"tokens": kit.prompt(n, n), "max_new_tokens": 3})
                for n in lengths]
        _drain(eng)
        assert all(s.done and len(s.generated) == 3 for s in seqs)
    after = eng.stats()
    narrow = after["prefill_narrow_passes_total"] \
        - before["prefill_narrow_passes_total"]
    assert 0 < narrow < after["prefill_steps"] - before["prefill_steps"]
    assert {s[0] for s in shapes if s[1] == CHUNK} == {NARROW[0], LANES}
    assert eng.device_report()["compiled_steps"] == steps
    assert after["compiles_total"] == before["compiles_total"]


def one_prefill_width_has_no_narrow_pass(kit):
    """(d), the other half: an engine with ONE prefill width (a context
    of at most 4 chunks: the bench rehearsal's sizes) warms what it
    warmed before there was a narrow pass, and never runs one."""
    eng = kit.make_one_width()
    assert eng._prefill_widths == [eng.ctx_len] and eng.ctx_len <= 4 * CHUNK
    assert eng._narrow_prefill is None
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
    assert after["prefill_slots_total"] - before["prefill_slots_total"] \
        == (after["prefill_steps"] - before["prefill_steps"]) * lanes * CHUNK
    assert after["compiles_total"] == before["compiles_total"]
    for upto, out in zip((1, 2, 3), outs):
        kit.check(eng, prompts[:upto], out)



CASES = [one_two_three_prompts, prompt_ends_in_a_narrow_pass,
         context_past_the_narrow_width, warm_up_then_mixed_compiles_nothing,
         one_prefill_width_has_no_narrow_pass]
