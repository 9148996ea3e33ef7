"""LLMEngine on the qwen3_next family — state slots beside pages of a flat
KV row, experts in every layer — against the plain reference (seeded
weights, toy widths, float32, CPU): logits through every prefill pass
shape and both pools, lanes joining and leaving, a slot reused, and a
canary asked again in turn after traffic, its carried state read from the
slot it re-used."""

import dataclasses

import numpy as np
import pytest

from benchmarks import reference_qwen3next as ref
from ray_tpu.models import cache as kv_cache
from ray_tpu.models.qwen3_next import Qwen3NextConfig
from ray_tpu.ops import moe
from ray_tpu.serve.llm import LLMEngine

CFG = Qwen3NextConfig.tiny()
PAGE = 16
SIZES = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
         if "dtype" not in f.name}
ATOL = 5e-5    # float32 sums in another order (the chunk form, tiles)


@pytest.fixture(scope="module", autouse=True)
def short_reference():
    was, ref.LENGTHS = ref.LENGTHS, (256, 512)
    yield
    ref.LENGTHS = was


def _prompt(n, salt=0):
    return [int(t) for t in np.random.RandomState(100 + salt).randint(
        1, 256, n)]


def test_engine_logits_under_churn_are_the_references():
    """Eleven requests through four lanes and four state slots: the wide,
    the narrow and the deep prefill pass, decode through the state pool
    and the flat pages, sequences that change lanes as others end, slots
    re-used by later sequences — every generated token's two largest
    LOGITS (the engine's logit trace) are the reference's; then a canary
    asked alone, in turn, answers what it answered before the traffic."""
    eng = LLMEngine(CFG, seed=5, page_size=PAGE, max_batch=4,
                    logit_trace=True)
    canary = {"tokens": _prompt(77, salt=40), "max_new_tokens": 6}
    before = eng.generate_batch([dict(canary)])[0]
    lengths = (5, 70, 130, 64, 20, 200, 3, 90, 128, 33, 65)
    news = (6, 4, 9, 3, 12, 5, 7, 2, 8, 10, 4)
    reqs = [{"tokens": _prompt(n, i), "max_new_tokens": m,
             "request_id": f"r{i}"}
            for i, (n, m) in enumerate(zip(lengths, news))]
    assert eng._deep_prefill == (2, 128) and eng._narrow_prefill
    outs = eng.generate_batch(reqs)
    prompts = [r["tokens"] for r in reqs]
    st = eng.stats()
    assert st["prefill_narrow_passes_total"] > 0 \
        and st["prefill_deep_passes_total"] > 0 \
        and st["prefill_steps"] > st["prefill_narrow_passes_total"] \
        + st["prefill_deep_passes_total"]
    assert st["state_slots_in_use"] == 0 and st["used_pages"] == 0
    assert sorted(eng._groups["state"].free) == [1, 2, 3, 4]
    # the state counters are the hybrid family's, the expert counters
    # Laguna's: three state layers a lane-step; every valid token routed
    # in each of the four layers, 2 picks of which about half land here
    assert st["state_decode_rows_total"] == 3 * st["decode_lane_steps_total"]
    tokens = sum(lengths) + 77
    assert st["delta_prefill_tokens_total"] == {
        "decode": 0, "prefill": 3 * tokens}
    assert st["moe_layer_passes_total"]["prefill"] \
        == 4 * st["prefill_steps"]
    assert st["moe_expert_slots_total"]["decode"] \
        == 4 * 4 * st["decode_steps"]
    routed = st["moe_assignments_total"]
    assert 0 < routed["prefill"] < 4 * 2 * tokens
    assert 0 < routed["decode"] <= 4 * 2 * st["decode_lane_steps_total"]
    assert routed["decode"] >= st["moe_expert_calls_total"]["decode"] > 0
    assert len(eng._model.counters) == len(moe.COUNTERS) + 4
    # the pools are as large as their shapes: the flat row's 2 x 64
    # numbers a token a full layer, the state's float32 carry
    rep = eng.device_report()
    pages = eng.num_pages * PAGE
    assert rep["kv_pool_bytes"] - rep["state_pool_bytes"] \
        == pages * 2 * 64 * 4
    assert rep["state_pool_bytes"] == 5 * kv_cache.state_row_bytes(
        CFG.cache_spec(), CFG.dtype)
    assert rep["model"]["share"] == {"experts_held": [0, 4],
                                     "num_experts": 8, "vocab_rows": 256}
    # the logits themselves
    trace, worst = rep["logit_trace"], 0.0
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        lg = np.asarray(ref.logits(eng._params, prompt + out[:-1], SIZES))
        for j, l1, id1, l2, id2 in trace[f"r{i}"]:
            row = lg[len(prompt) - 1 + j]
            worst = max(worst, abs(row[id1] - l1), abs(row[id2] - l2))
            assert id1 == out[j] == int(row.argmax())
    assert worst < ATOL
    # the reference's margins come with its picks
    refs = ref.teacher_forced(eng._params, prompts, outs, SIZES)
    assert all(r["top_id"] == out for r, out in zip(refs, outs))
    assert all(0 < m < 50 for r in refs for m in r["margin"])
    # asked again in turn, on whatever slots and pages the traffic left:
    # the same tokens, and the state its sequence leaves in the re-used
    # slot is the reference's token-by-token state
    seq, slot = eng.submit(dict(canary)), None
    while eng.step():
        slot = seq.cache.get("state", slot)
    eng.drain()
    assert list(seq.generated) == before and slot
    eng.release(seq)
    states = ref.carried_states(eng._params, canary["tokens"], before, SIZES)
    heads, dv = CFG.linear_num_value_heads, CFG.linear_value_head_dim
    rows = [np.asarray(pool[slot]) for pool in eng._pools["ssm"]
            if pool is not None]
    mine = [np.moveaxis(r.reshape(r.shape[0], r.shape[1], -1, dv), 2, 1
                        ).reshape(heads, r.shape[1], dv) for r in rows]
    dist = ref.carry_distance(mine, states)
    assert len(dist["layers"]) == 3 and max(dist["layers"]) < 1e-4
