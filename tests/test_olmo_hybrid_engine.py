"""The engine's state slots (serve/cache_groups.py) on the hybrid linear-attention
family: sequences changing lanes, slots re-used behind a step in flight,
pages and state shipped, the narrow and the wide prefill pass — against
the plain reference (seeded random weights, small size, float32, CPU)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_olmo as ref
from ray_tpu.models import cache as kv_cache
from ray_tpu.models.olmo_hybrid import OlmoHybridConfig
from ray_tpu.serve.llm import LLMEngine

CFG = dataclasses.replace(OlmoHybridConfig.tiny(), dtype=jnp.float32,
                          param_dtype=jnp.float32)
PAGE = 16
SIZES = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
         if "dtype" not in f.name}
ATOL = 2e-5    # float32 sums in another order (tests/test_olmo_hybrid_model.py)


@pytest.fixture(scope="module", autouse=True)
def short_reference():
    """The reference pads to 256 here, not to the chip's lengths."""
    was, ref.LENGTHS = ref.LENGTHS, (256, 512)
    yield
    ref.LENGTHS = was


def _drain(eng):
    while eng.step():
        pass
    eng.drain()


def _engine(**kw):
    kw.setdefault("max_batch", 4)
    return LLMEngine(CFG, seed=5, page_size=PAGE, **kw)


def _prompt(n, salt=0):
    return [int(t) for t in np.random.RandomState(100 + salt).randint(
        1, 256, n)]


def _assert_references(eng, prompts, outs, top2=None):
    """Every token is the reference's argmax given the engine's own
    earlier tokens; with the engine's logit trace, its two largest
    logits are the reference's."""
    refs = ref.teacher_forced(eng._params, prompts, outs, SIZES)
    for p, out, r in zip(prompts, outs, refs):
        assert out and out == r["top_id"], f"prompt of {len(p)}"
    return refs


def test_engine_logits_under_churn_are_the_references():
    """Eleven requests through four lanes and four state slots: the
    wide, the narrow and the deep prefill pass (2 x 128), chunks of 64
    and of 128 through the state pool, sequences that change lanes as
    others end, slots re-used by
    later sequences with a step in flight — every generated token's two
    largest LOGITS (the engine's logit trace) are the reference's."""
    # a context of 512: two prefill widths, so a narrow program too
    eng = LLMEngine(CFG, seed=5, page_size=PAGE, max_batch=4,
                    logit_trace=True)
    lengths = (5, 70, 130, 64, 20, 200, 3, 90, 128, 33, 65)
    news = (6, 4, 9, 3, 12, 5, 7, 2, 8, 10, 4)
    reqs = [{"tokens": _prompt(n, i), "max_new_tokens": m,
             "request_id": f"r{i}"}
            for i, (n, m) in enumerate(zip(lengths, news))]
    assert eng._deep_prefill == (2, 128)
    lane_passes, dispatch = [], eng._dispatch_prefill

    def counting(step, prefill_args, shape):
        lane_passes.append(len(prefill_args))
        return dispatch(step, prefill_args, shape)

    eng._dispatch_prefill = counting
    outs = eng.generate_batch(reqs)
    prompts = [r["tokens"] for r in reqs]
    _assert_references(eng, prompts, outs)
    st = eng.stats()
    narrow, deep = (st["prefill_narrow_passes_total"],
                    st["prefill_deep_passes_total"])
    assert narrow > 0 and deep > 0 and st["prefill_steps"] > narrow + deep
    assert st["runahead_decode_steps_total"] > 0
    assert st["state_slots_in_use"] == 0 and st["used_pages"] == 0
    assert sorted(eng._groups["state"].free) == [1, 2, 3, 4]
    # the counters are the hand counts: a state layer a lane with tokens
    assert st["state_decode_rows_total"] == 3 * st["decode_lane_steps_total"]
    assert st["state_decode_calls_total"] == 3 * st["decode_steps"]
    # (a prompt's passes: chunks of 64, fewer where it rode deep ones)
    chunks = sum(-(-n // 64) for n in lengths)
    assert sum(-(-n // 128) for n in lengths) < sum(lane_passes) < chunks
    assert st["state_prefill_rows_total"] == 3 * sum(lane_passes)
    # the model's own counters: valid tokens and the chunk kernel's
    # (lane, chunk) grid cells, a linear layer each; none in decode
    assert st["delta_prefill_tokens_total"] == {
        "decode": 0, "prefill": 3 * sum(lengths)}
    cells = st["delta_prefill_chunks_total"]["prefill"]
    assert cells % 3 == 0 and cells // 3 >= chunks
    assert st["delta_prefill_chunks_total"]["decode"] == 0
    assert st["state_pool_bytes"] == 5 * kv_cache.state_row_bytes(
        CFG.cache_spec(), CFG.dtype) == eng.device_report()[
        "state_pool_bytes"]
    # the logits themselves
    trace = eng.device_report()["logit_trace"]
    worst = 0.0
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        lg = np.asarray(ref.logits(eng._params, prompt + out[:-1], SIZES))
        for j, l1, id1, l2, id2 in trace[f"r{i}"]:
            row = lg[len(prompt) - 1 + j]
            worst = max(worst, abs(row[id1] - l1), abs(row[id2] - l2))
            assert id1 == out[j] == int(row.argmax())
    assert worst < ATOL


def test_a_slot_retaken_behind_an_eos_under_runahead_starts_fresh():
    """One lane, one state slot: a sequence meets its `eos` with a
    lane-step in flight, which updates the slot AFTER it was given
    back; the next sequence takes the same slot and decodes what it
    decodes on an engine nobody used before."""
    eng = _engine(max_batch=1)
    prompt = _prompt(70)
    free = eng.generate_batch([{"tokens": prompt, "max_new_tokens": 20}])[0]
    k = next(i for i in range(3, 20) if free[i] not in free[:i])
    before = eng.stats()
    out = eng.generate_batch([{"tokens": prompt, "max_new_tokens": 40,
                               "eos": free[k]}])[0]
    assert out == free[:k + 1]
    assert eng.stats()["decode_lane_steps_wasted_total"] \
        - before["decode_lane_steps_wasted_total"] == 1
    assert eng._groups["state"].free == [1]
    other = _prompt(90, salt=7)
    got = eng.generate_batch([{"tokens": other, "max_new_tokens": 12}])[0]
    want = _engine(max_batch=1).generate_batch(
        [{"tokens": other, "max_new_tokens": 12}])[0]
    assert got == want
    _assert_references(eng, [other], [got])


def test_a_stale_slot_would_show():
    """The same, with `fresh` never set: the second owner reads what the
    first left and its logits move — the check above is not blind."""
    eng = _engine(max_batch=1, logit_trace=True)
    group = eng._groups["state"]
    arrays = group.prefill_arrays

    def stale(rows, lanes, *shape):
        return {**arrays(rows, lanes, *shape),
                "fresh": np.zeros((lanes,), bool)}

    first, other = _prompt(70), _prompt(90, salt=7)
    eng.generate_batch([{"tokens": first, "max_new_tokens": 4}])
    group.prefill_arrays = stale
    eng.generate_batch([{"tokens": other, "max_new_tokens": 4,
                         "request_id": "stale"}])
    lg = np.asarray(ref.logits(eng._params, other, SIZES))[-1]
    _j, l1, id1, _l2, _id2 = eng.device_report()["logit_trace"]["stale"][0]
    assert abs(lg[id1] - l1) > 100 * ATOL


def test_pages_and_state_ship_from_a_prefill_engine():
    """`prefill_request` exports the attention layers' rows a position
    AND the state layers' one row; an engine that imports them decodes
    what the local one decodes, with no prefill pass of its own.
    Prefix sharing is refused with its reason."""
    prompt = _prompt(100, salt=3)
    alone = _engine()
    want = alone.generate_batch([{"tokens": prompt, "max_new_tokens": 8}])[0]
    st = alone.stats()
    assert not st["prefix_sharing"]
    assert "state layers" in st["prefix_sharing_refused"]
    payload = alone.prefill_request({"tokens": prompt, "max_new_tokens": 8,
                                     "request_id": "ship"})
    rows = payload["rows"]
    assert sorted(rows) == ["conv", "k", "ssm", "v"]
    kinds = [layer.kind for layer in CFG.cache_spec()]
    for name, per_layer in rows.items():
        for kind, r in zip(kinds, per_layer):
            held = (kind == "state") == (name in ("conv", "ssm"))
            assert (r is not None) == held
            if r is not None:
                assert r.shape[0] == (1 if kind == "state" else 100)
    assert rows["ssm"][0].dtype == np.float32
    decoder = _engine(params=alone._params)
    # the decoder's slots are dealt in another order than the sender's
    blocker = decoder.submit({"tokens": _prompt(9), "max_new_tokens": 30})
    decoder.step()
    shipped = decoder.submit(
        {"tokens": prompt, "max_new_tokens": 8, "request_id": "ship"},
        kv_pack=(payload["meta"], payload["rows"]))
    _drain(decoder)
    assert list(shipped.generated) == want and blocker.done
    assert decoder.stats()["prefill_steps"] == 1   # the blocker's
    assert decoder.stats()["state_slots_in_use"] == 0


def test_a_prompt_in_chunks_through_the_prefill_kernel_is_the_dense_forward():
    """A prompt served in chunks, with a second, longer sequence in the
    same passes: every generated token and its two largest logits are
    the ONE-PASS dense forward's (the module with no cache: plain causal
    attention over the whole sequence, no page, no kernel) — and the
    prefill pass's program attends through `paged_attention_prefill`,
    with no decode kernel in it."""
    import jax

    from ray_tpu.models.olmo_hybrid import build

    eng = _engine(logit_trace=True)
    programs, lanes, step_fn = [], [], eng._step_fn

    def traced(*args, **kw):
        tokens = args[3]
        if tokens.shape[1] > 1:
            lanes.append(int((np.asarray(args[4]).max(-1) > 0).sum()))
            if not programs:
                programs.append(str(jax.make_jaxpr(
                    lambda: step_fn(*args, **kw))()))
        return step_fn(*args, **kw)

    eng._step_fn = traced
    prompts = [_prompt(150, salt=11), _prompt(333, salt=12)]
    outs = eng.generate_batch([
        {"tokens": p, "max_new_tokens": 5, "request_id": f"d{i}"}
        for i, p in enumerate(prompts)])
    assert "paged_attention_prefill" in programs[0]
    assert "paged_attention_decode" not in programs[0]
    # the two prompts rode the same passes until the shorter one ended,
    # in chunks
    live = [n for n in lanes if n]      # (a warm-up's pass carries none)
    assert live[0] == 2 and live[-1] == 1 and len(live) < 333 // 64
    dense = jax.jit(build(CFG, PAGE).apply)
    trace = eng.device_report()["logit_trace"]
    worst = 0.0
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        assert len(out) == 5
        lg = np.asarray(dense({"params": eng._params},
                              jnp.asarray([prompt + out[:-1]])))[0]
        for j, l1, id1, l2, id2 in trace[f"d{i}"]:
            row = lg[len(prompt) - 1 + j]
            assert id1 == out[j] == int(row.argmax())
            worst = max(worst, abs(row[id1] - l1), abs(row[id2] - l2))
    assert worst < ATOL, worst
