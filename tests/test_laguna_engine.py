"""A Laguna-family model served by LLMEngine: chunked prefill and decode
through a cache of two kinds (full layers and window layers) against the
plain reference's full forward pass; the window group's pages; prefix
sharing refused; save and restore; and a Llama engine left as it was.

One small config (float32 activations over the stored bfloat16
matrices, so the engine's tokens ARE the reference's argmax and a wrong
page, mask or slot shows as a wrong token), page 8, chunk 16, window 32.
"""

import dataclasses

import jax.numpy as jnp
import narrow_prefill_cases
import numpy as np
import pytest

from benchmarks import reference_laguna as ref
from ray_tpu.models.laguna import LagunaConfig
from ray_tpu.serve.llm import LLMEngine

CFG = dataclasses.replace(LagunaConfig.tiny(), dtype=jnp.float32,
                          max_position_embeddings=384)
SIZES = dict(layer_types=list(CFG.layer_types),
             mlp_layer_types=list(CFG.mlp_layer_types),
             sliding_window=CFG.sliding_window,
             rope_parameters={k: dict(v)
                              for k, v in dict(CFG.rope_parameters).items()},
             num_experts_per_tok=CFG.num_experts_per_tok,
             norm_topk_prob=True, moe_routed_scaling_factor=2.5,
             rms_norm_eps=CFG.rms_norm_eps, experts_held=[0, 4])
PAGE, CHUNK, WINDOW = 8, 16, 32


def _engine(**kw):
    return LLMEngine(CFG, page_size=PAGE, max_batch=4, prefill_chunk=CHUNK,
                     seed=3, **kw)


def _prompt(n, salt=0):
    rs = np.random.RandomState(1000 + 7 * n + salt)
    return [int(t) for t in rs.randint(1, 256, n)]


def _drain(eng, rounds=600):
    for _ in range(rounds):
        if not eng.step():
            return
    raise AssertionError("the engine did not go idle")


def test_prefill_in_chunks_then_decode_is_the_references_forward():
    """Prompts shorter than (5, 20), equal to (32) and several times the
    window (100, 150), with chunks of 16 that straddle it (40 = 2 chunks
    and a half), four lanes at once; 12 tokens each through the decode
    kernel (windowed for three layers of five).  Every token is the
    reference's argmax given the engine's own earlier tokens."""
    eng = _engine()
    prompts = [_prompt(n) for n in (5, 20, 32, 40, 100, 150)]
    outs = eng.generate_batch([{"tokens": p, "max_new_tokens": 12}
                               for p in prompts])
    refs = ref.teacher_forced(eng._params, prompts, outs, SIZES)
    for p, out, r in zip(prompts, outs, refs):
        assert out == r["top_id"], f"prompt of {len(p)}"
    st = eng.stats()
    assert st["kv_pages_in_use"] == {"full": 0, "window": 0}
    assert st["used_pages"] == 0
    # the experts' work was counted, by pass, and followed the routing
    calls, slots = st["moe_expert_calls_total"], st["moe_expert_slots_total"]
    for which in ("decode", "prefill"):
        assert 0 < calls[which] < slots[which]
        assert st["moe_assignments_total"][which] >= calls[which]
        assert slots[which] == 4 * st["moe_layer_passes_total"][which]
        # a touched expert fills a row tile or more
        assert st["moe_row_tiles_active_total"][which] >= calls[which]
    assert st["moe_layer_passes_total"]["decode"] == 4 * st["decode_steps"]


class _NarrowKit:
    """This family's kit for `narrow_prefill_cases`: a context of 384
    gives the prefill pass the widths 64, 256 and 384; the window
    group's arrays follow the pass's lanes."""

    make = staticmethod(_engine)
    prompt = staticmethod(_prompt)

    @staticmethod
    def make_one_width():
        return LLMEngine(
            dataclasses.replace(CFG, max_position_embeddings=64),
            page_size=PAGE, max_batch=4, prefill_chunk=CHUNK, seed=3)

    check = staticmethod(narrow_prefill_cases.teacher_forced_check(
        ref, SIZES))


@pytest.mark.parametrize("case", narrow_prefill_cases.CASES,
                         ids=lambda case: case.__name__)
def test_narrow_prefill_pass(case):
    case(_NarrowKit)


def test_window_arrays_take_the_form_of_their_pass():
    """One form a kind of pass: a prefill pass gathers the window's
    positions, a decode pass lists its pages from `starts` on; a lane
    without a row is garbage in both."""
    eng = _engine()
    group = eng._groups["window"]
    seq = eng.submit({"tokens": _prompt(100), "max_new_tokens": 8})
    while len(seq.generated) < 3:
        eng.step()
    n, st = seq.pos, seq.cache["window"]
    row = [(1, seq.cache, n - 1, n)]
    dec = eng._pass_groups(row, 4, 1, 16, decode=True)["window"]
    assert set(dec) == {"slots", "block_tables", "context_lens", "starts"}
    assert dec["block_tables"].shape == (4, group.table_width)
    start = int(dec["starts"][1])
    assert start % PAGE == 0 and start <= n - WINDOW < start + PAGE
    live = -(-(n - start) // PAGE)
    assert dec["block_tables"][1, :live].tolist() \
        == st.pages[start // PAGE:start // PAGE + live].tolist()
    assert dec["context_lens"].tolist() == [0, n, 0, 0]
    assert dec["slots"][:, 0].tolist() == [0, st.slots[n - 1], 0, 0]
    # ... as wide as the pass's own chunk asks: the window and the chunk
    for cols in (1, CHUNK, 2 * CHUNK):
        width = group.ctx_width(cols)
        assert width == -(-(WINDOW + cols) // PAGE) * PAGE
        pre = eng._pass_groups([(1, seq.cache, n - cols, n)], 4, cols,
                               256)["window"]
        assert set(pre) == {"slots", "ctx", "ctx_pos", "ctx_mask"}
        assert pre["slots"].shape == (4, cols)
        assert pre["ctx"].shape == (4, width)
        assert pre["ctx_mask"].sum(axis=1).tolist() == [0, width, 0, 0]
        assert pre["ctx_pos"][1, :width].tolist() \
            == list(range(n - width, n))
    assert eng.cancel(seq.request_id)


def test_window_pages_come_back_while_a_sequence_runs():
    """A context of 40 pages (320 tokens) holds the window's pages, not
    40: at most `per_seq` = (32 + 32) / 8 + 2 = 10 of the window kind (the
    window and the widest chunk a pass carries, the deep pass's 2 x 32),
    while the full kind holds all 40 from admission; what the sequence
    moved past is given back as it goes, and its end leaves both groups
    as it found them."""
    eng = _engine()
    group, full = eng._groups["window"], eng._groups["full"]
    assert eng._deep_prefill == (2, 2 * CHUNK)
    assert group.per_seq == 10 and group.num_pages == 1 + 4 * 10
    free_full, free_win = len(full.free), len(group.free)
    seq = eng.submit({"tokens": _prompt(300), "max_new_tokens": 20})
    held = []
    while not seq.done:
        eng.step()
        in_use = eng.stats()["kv_pages_in_use"]
        held.append(in_use["window"])
        if not seq.done:
            assert in_use["full"] == 40
    assert max(held) <= group.per_seq
    assert max(held) >= WINDOW // PAGE      # it did hold the window
    st = eng.stats()
    assert st["kv_window_pages_released_total"] >= 40 - group.per_seq
    assert st["kv_pages_in_use"] == {"full": 0, "window": 0}
    assert len(full.free) == free_full
    assert sorted(group.free) == list(range(1, group.num_pages))
    assert len(group.free) == free_win
    # cancelled mid-flight: the same
    seq = eng.submit({"tokens": _prompt(200), "max_new_tokens": 20})
    for _ in range(4):
        eng.step()
    assert eng.stats()["kv_pages_in_use"]["window"] > 0
    assert eng.cancel(seq.request_id)
    assert eng.stats()["kv_pages_in_use"] == {"full": 0, "window": 0}


def test_prefix_sharing_is_refused_for_window_layers_and_says_so():
    eng = _engine(prefix_sharing=True)
    st = eng.stats()
    assert st["prefix_sharing"] is False
    assert "window layers" in st["prefix_sharing_refused"]
    shared = _prompt(64)
    a, b = eng.generate_batch(
        [{"tokens": shared + [7], "max_new_tokens": 4},
         {"tokens": shared + [9], "max_new_tokens": 4}])
    st = eng.stats()
    assert st["prefix_hits"] == 0 and st["shared_pages"] == 0
    refs = ref.teacher_forced(eng._params, [shared + [7], shared + [9]],
                              [a, b], SIZES)
    assert [a, b] == [r["top_id"] for r in refs]
    # a Llama engine shares as before and reports no refusal
    llama = LLMEngine(model="tiny", page_size=PAGE, max_batch=4,
                      prefix_sharing=True)
    st = llama.stats()
    assert st["prefix_sharing"] is True and st["prefix_sharing_refused"] == ""
    assert st["kv_pages_in_use"] == {"full": 0}
    assert st["kv_window_pages_released_total"] == 0


def test_save_and_restore_round_trip_through_the_two_kind_cache():
    """A sequence saved mid-decode (past the window) and restored into a
    fresh engine re-prefills prompt + known tokens through both page
    groups and goes on to the same tokens."""
    whole = _engine().generate_batch(
        [{"tokens": _prompt(70), "max_new_tokens": 16}])[0]
    eng = _engine()
    seq = eng.submit({"tokens": _prompt(70), "max_new_tokens": 16,
                      "request_id": "r1"})
    while len(seq.generated) < 6:
        eng.step()
    state = eng.save_state()
    assert state["seqs"][0]["generated"] == whole[:len(seq.generated)]
    fresh = _engine()
    fresh.restore_state(state)
    again = fresh.submit({"tokens": _prompt(70), "max_new_tokens": 16,
                          "request_id": "r1"})     # re-attaches
    _drain(fresh)
    assert again.generated == whole
    assert fresh.stats()["kv_pages_in_use"] == {"full": 0, "window": 0}


def test_shipped_kv_rows_carry_the_window_layers_live_rows():
    """Disaggregated prefill through a cache of two kinds: the export
    holds every row of a full layer and the rows a window layer's next
    query still sees; the import takes window pages for them and decodes
    on to the tokens a local prefill gives."""
    prompt = _prompt(90)
    local = _engine().generate_batch(
        [{"tokens": prompt, "max_new_tokens": 8}])[0]
    payload = _engine().prefill_request({"tokens": prompt,
                                         "max_new_tokens": 8,
                                         "request_id": "ship"})
    assert [r.shape[0] for r in payload["rows"]["k"]] == \
        [90, WINDOW - 1, WINDOW - 1, WINDOW - 1, 90]
    dec = _engine()
    seq = dec.submit({"tokens": prompt, "max_new_tokens": 8,
                      "request_id": "ship"},
                     kv_pack=(payload["meta"], payload["rows"]))
    _drain(dec)
    assert seq.generated == local
    assert dec.stats()["kv_pages_in_use"] == {"full": 0, "window": 0}
    assert dec.stats()["prefill_steps"] == 0


def test_window_pages_released_and_reused_under_runahead():
    """Decoding far past the window with a step always in flight:
    `advance` gives a window page back while the pass that last read it
    may still be queued, and another sequence's later pass takes it —
    safe because the device runs passes in the order they were
    dispatched.  Every token is the reference's argmax."""
    eng = _engine()
    prompts = [_prompt(n, salt=9) for n in (30, 45, 70, 12)]
    seqs = [eng.submit({"tokens": p, "max_new_tokens": m})
            for p, m in zip(prompts, (60, 40, 50, 70))]
    while any(s.state != "decode" for s in seqs):
        eng.step()
    before = eng.stats()
    group = eng._groups["window"]
    allocated = group.allocated_total
    _drain(eng)
    st = eng.stats()
    # decoding alone gave pages back and took pages that had been held
    assert st["kv_window_pages_released_total"] \
        - before["kv_window_pages_released_total"] >= 12
    assert group.allocated_total - allocated >= 12
    steps = st["decode_steps"] - before["decode_steps"]
    assert (st["runahead_decode_steps_total"]
            - before["runahead_decode_steps_total"]) / steps > 0.9
    assert st["decode_lane_steps_wasted_total"] == 0
    # the paged kernel's grid, the full kind's call and the window kind's
    # in the same two sums: at page 8 both tables (4 to 16 pages wide;
    # 4 or 5) are one block a lane, so a pass has 2 x 4 lanes of steps
    # and a decoding lane holds one of each kind
    assert st["paged_grid_steps_total"] \
        - before["paged_grid_steps_total"] == 2 * 4 * steps
    assert st["paged_grid_steps_live_total"] \
        - before["paged_grid_steps_live_total"] \
        == 2 * (st["decode_lane_steps_total"]
                - before["decode_lane_steps_total"])
    outs = [list(s.generated) for s in seqs]
    refs = ref.teacher_forced(eng._params, prompts, outs, SIZES)
    for p, out, r in zip(prompts, outs, refs):
        assert out == r["top_id"], f"prompt of {len(p)}"
    assert st["kv_pages_in_use"] == {"full": 0, "window": 0}


def test_a_llama_engine_is_the_parents():
    """ISSUE 28 point 2: for a model whose cache has one kind the engine
    allocates the pools and compiles the programs the parent did.  The
    decode step of `LlamaConfig.tiny` (page 8, 4 lanes, width 4) costs
    what it cost at the parent commit, by the compiler's own analysis,
    and the bytes held are the parent's (numbers read there with this
    jax).  Re-pinned by ISSUE 29, which changed them by design: the
    engine stores its 106,496 matrix entries in bfloat16, so
    `param_bytes` 427,264 -> 214,272, and the step no longer rounds them
    (a flop an entry by the compiler's count): flops 1,340,640 ->
    1,234,144, bytes accessed 3,119,880 -> 2,906,888, transcendentals
    and the pools as they were (8d027c8 -> ISSUE 29's commit).  ISSUE 31
    added the token feed (a gather of 4 lanes from the last step's
    outputs and a select): flops + 36, bytes accessed + 192.  ISSUE 33
    changed the paged kernel's grid by design: at width 4 a lane's pages
    are ONE grid step (the interpreter's program copies the four pages
    into the kernel's buffer and takes one softmax over 32 keys a lane
    where it took four over 8): flops 1,234,180 -> 1,281,390, bytes
    accessed 2,907,080 -> 3,161,810, transcendentals 1380 -> 1572; the
    parameters and the pools as they were."""
    eng = LLMEngine(model="tiny", page_size=8, max_batch=4)
    rep = eng.device_report()
    assert (rep["param_bytes"], rep["kv_pool_bytes"]) == (214272, 133120)
    assert rep["model"]["family"] == "llama" and rep["model"]["share"] is None
    assert list(eng._groups) == ["full"] and eng.stats()["prefix_sharing"]
    assert list(eng._pass_groups([], 4, 1, 4, decode=True)) == ["full"]
    cost = eng._lower_decode(4).compile().cost_analysis()
    assert (cost["flops"], cost["bytes accessed"],
            cost["transcendentals"]) == (1281390.0, 3161810.0, 1572.0)
    assert "moe_assignments_total" not in eng.stats()
