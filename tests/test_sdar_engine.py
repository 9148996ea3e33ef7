"""A block-diffusion model served by LLMEngine (serve/llm.py, `_Block`):
prefill in chunks, then blocks, against the plain reference's cache-free
loop (benchmarks/reference_sdar.py) token for token AND pass for pass;
lanes out of phase; the run-ahead kept; pages given back from inside an
open block; and no page published before its last block commits.

One small float32 config (the engine's tokens ARE the reference's, so a
wrong row, mask or slot shows as a wrong token), page 16, chunk 16."""

import dataclasses
import time

import numpy as np
import pytest

from benchmarks import reference_sdar as ref
from ray_tpu.models.laguna import LagunaConfig
from ray_tpu.serve.llm import LLMEngine

CFG = LagunaConfig.tiny_blocks()
MASK = CFG.mask_token_id
SIZES = dict(num_hidden_layers=CFG.num_hidden_layers, head_dim=CFG.head_dim,
             rope_theta=1000000, rms_norm_eps=CFG.rms_norm_eps,
             num_experts_per_tok=CFG.num_experts_per_tok,
             norm_topk_prob=True,
             generation=dict(block_length=4, denoising_steps=4,
                             confidence_threshold=0.9, mask_token_id=MASK))


def _engine(cfg=CFG, **kw):
    kw = {"page_size": 16, "max_batch": 4, "prefill_chunk": 16,
          "prefill_lanes": 2, "seed": 3, **kw}
    return LLMEngine(cfg, **kw)


def _prompt(n, salt=0):
    rs = np.random.RandomState(1000 + 7 * n + salt)
    return [int(t) for t in rs.randint(1, MASK, n)]


def _passes(eng, rid):
    return eng._by_rid[rid].blk.passes


def test_chunks_then_blocks_are_the_references_loop_pass_for_pass():
    """Prompt tails 0 to 3 (16, 9, 70, 3 and 43 tokens: none, one chunk,
    several, shorter than a block), budgets that cut the last block (7,
    5, 10, 9) or end on it (4), more sequences than lanes; then the same
    engine asked for 1 and 2 passes a block."""
    eng = _engine()
    cases = [(16, 4, None), (9, 7, None), (70, 10, None), (3, 5, None),
             (43, 9, None), (18, 6, 1), (21, 11, 2), (7, 8, 3)]
    reqs = [{"tokens": _prompt(n), "max_new_tokens": new,
             "request_id": f"r{i}", "record_passes": True,
             **({"denoising_steps": steps} if steps else {})}
            for i, (n, new, steps) in enumerate(cases)]
    outs = eng.generate_batch(reqs)
    for req, out, (n, new, steps) in zip(reqs, outs, cases):
        want = ref.generate(eng._params, req["tokens"], new, SIZES,
                            steps=steps)
        assert out == want["tokens"], f"prompt of {n}"
        assert _passes(eng, req["request_id"]) == want["passes"], \
            f"prompt of {n}"
        assert len(out) == new and MASK not in out
    st = eng.stats()
    lane = st["block_lane_passes_total"]
    # lanes out of phase: passes that held a commit beside a denoising
    # lane are counted `denoise`, so fewer passes were all commits than
    # lanes committed
    assert st["block_passes_total"]["commit"] < lane["commit"]
    assert sum(st["block_passes_total"].values()) == st["decode_steps"]
    assert sum(lane.values()) == st["decode_lane_steps_total"]
    assert st["block_tokens_transferred_total"] >= sum(
        new for _n, new, _s in cases)
    assert st["block_tokens_over_threshold_total"] == 0   # never at 0.9
    assert st["block_tokens_discarded_total"] > 0         # a cut block
    assert st["runahead_decode_steps_total"] > 0          # kept
    assert st["kv_pages_in_use"] == {"full": 0}


def _step_until(eng, cond, rounds=400):
    for _ in range(rounds):
        eng.step()
        if cond():
            return
    raise AssertionError("the engine never got there")


def test_a_cancel_and_an_expiry_inside_an_open_block_give_the_pages_back():
    eng = _engine()
    gone = eng.submit({"tokens": _prompt(20), "max_new_tokens": 40,
                       "request_id": "gone"})
    late = eng.submit({"tokens": _prompt(9), "max_new_tokens": 40,
                       "request_id": "late",
                       "deadline_ms": (time.time() + 3600) * 1000})
    stays = eng.submit({"tokens": _prompt(33), "max_new_tokens": 9,
                        "request_id": "stays", "record_passes": True})
    # until both are inside a block: some of it unmasked, not all
    _step_until(eng, lambda: all(
        s.blk.k > 0 and not s.done for s in (gone, late)))
    assert eng.cancel("gone")
    late.deadline = time.time() - 1.0
    _step_until(eng, lambda: stays.done)
    eng.drain()
    assert gone.cancelled and late.cancelled and late.error is not None
    st = eng.stats()
    assert st["kv_pages_in_use"] == {"full": 0} and st["active"] == 0
    assert st["decode_lane_steps_wasted_total"] >= 2
    want = ref.generate(eng._params, _prompt(33), 9, SIZES)
    assert list(stays.generated) == want["tokens"]


def test_what_a_block_engine_refuses_and_says():
    with pytest.raises(ValueError, match="blocks of 4"):
        _engine(temperature=0.7)
    with pytest.raises(ValueError, match="blocks of 4"):
        _engine(page_size=6)
    eng = _engine()
    with pytest.raises(ValueError, match="denoising_steps"):
        eng.submit({"tokens": [1, 2, 3], "denoising_steps": 5})
    with pytest.raises(ValueError, match="mask"):
        eng.submit({"tokens": [1, MASK, 3]})
    assert "block" in LLMEngine.stats.__doc__
    text = eng._lower_decode(4).as_text(debug_info=True)
    for scope in ("diffusion_sample", "diffusion_transfer", "qk_norm"):
        assert scope in text, scope


@pytest.mark.parametrize("sharing", [False, True],
                         ids=["unshared", "shared"])
def test_deep_prefill_passes_leave_the_blocks_as_chunks_of_16_do(sharing):
    """Four prefill lanes give a block model a DEEP pass too (2 x 32, a
    lane whole blocks): a prompt of 150 tokens prefills its 148 rows of
    whole blocks in five passes where chunks of 16 take ten, two shorter
    ones beside and behind it, and every token and every pass of every
    block is the reference's loop — and the engine's held to two lanes
    of 16.  Nothing compiles after the warm-up."""
    eng = _engine(prefill_lanes=4, prefix_sharing=sharing)
    assert eng._deep_prefill == (2, 32)
    assert eng._prefill_programs() == [
        (4, 16, 256), (2, 16, 256), (2, 32, 256)]
    eng.warm_up()
    before = eng.stats()
    cases = [(150, 9), (70, 6), (21, 5)]
    reqs = [{"tokens": _prompt(n), "max_new_tokens": new,
             "request_id": f"d{i}", "record_passes": True}
            for i, (n, new) in enumerate(cases)]
    outs = eng.generate_batch(reqs)
    st = eng.stats()
    assert st["compiles_total"] == before["compiles_total"]
    # the first two ride the deep pass until neither has 32 rows to go
    assert st["prefill_deep_passes_total"] == 4
    held = _engine(params=eng._params, prefix_sharing=sharing)
    assert held._deep_prefill is None
    assert held.generate_batch(
        [dict(r, request_id="h" + r["request_id"]) for r in reqs]) == outs
    for req, out, (n, new) in zip(reqs, outs, cases):
        want = ref.generate(eng._params, req["tokens"], new, SIZES)
        assert out == want["tokens"], f"prompt of {n}"
        assert _passes(eng, req["request_id"]) == want["passes"]
        assert _passes(held, "h" + req["request_id"]) == want["passes"]
    assert st["kv_pages_in_use"] == {"full": 0}
