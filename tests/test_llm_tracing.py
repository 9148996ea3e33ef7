"""What the serving engine says of itself (serve/llm.py): the step's
phases and the requests' stages as cumulative counters in `stats()`,
the compile record of `ray_tpu.ops`, request spans under the caller's
trace, and `llm.*` spans on the profiler's clock.

Engine-level, no cluster: every test drives `step()` inline or runs
`run_loop` on a thread of its own.  The model is tiny and float32; its
widths are this file's own, so that the process-wide jit cache holds no
program of another test's engine when a test counts compiles.
"""

import os
import sys
import threading
import time

import jax.numpy as jnp
import pytest

from ray_tpu import ops
from ray_tpu._private import tracing
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.llm import _PHASES, LLMEngine, _LLMCallable

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # benchmarks/, for trace_reduce

MODEL = {"vocab_size": 64, "dim": 48, "n_layers": 2, "n_heads": 4,
         "n_kv_heads": 2, "hidden_dim": 96, "max_seq_len": 64}
CHUNK, LANES = 8, 2
_params = []


def _engine(**kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("prefill_lanes", LANES)
    kw.setdefault("prefix_sharing", False)
    if _params:   # flax init costs seconds here: once for the file
        kw["params"] = _params[0]
    eng = LLMEngine(LlamaConfig(dtype=jnp.float32, **MODEL), **kw)
    _params[:1] = [eng._params]
    eng.warm_up()
    return eng


def _request(i, n_prompt=11, max_new=4):
    return {"tokens": [1 + (i + j) % 60 for j in range(n_prompt)],
            "max_new_tokens": max_new}


def _drain(eng, seqs, limit_s=120.0):
    deadline = time.monotonic() + limit_s
    while any(not s.done for s in seqs):
        assert time.monotonic() < deadline, "the engine did not finish"
        eng.step()


def _delta(after, before):
    """The change of every number of two `stats()`, nested groups too."""
    out = {}
    for k, v in after.items():
        if isinstance(v, dict):
            out[k] = _delta(v, before[k])
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = v - before[k]
    return out


def test_phases_add_up_to_the_step_and_the_passes_keep_their_meaning():
    eng = _engine()
    assert set(eng.stats()["phase_secs"]) == set(_PHASES)
    before = eng.stats()
    _drain(eng, [eng.submit(_request(i)) for i in range(5)])
    d = _delta(eng.stats(), before)
    p = d["phase_secs"]
    assert all(v > 0 for v in p.values()), p
    assert sum(p.values()) == pytest.approx(d["step_secs"], rel=1e-9)
    # a pass's seconds are its four phases: this step's build and
    # dispatch, the wait for the step before and its emission
    for kind in ("decode", "prefill"):
        assert sum(p[f"{kind}_{part}"] for part in
                   ("build", "dispatch", "sync", "emit")) \
            == pytest.approx(d[f"{kind}_secs"], rel=1e-9)
    assert d["decode_steps"] > 0 and d["prefill_steps"] > 0
    # a step that finds nothing to do is a step too: all of it is `admit`
    idle = eng.stats()
    assert eng.step() is False
    d = _delta(eng.stats(), idle)
    assert d["steps"] == 0
    assert d["step_secs"] == pytest.approx(d["phase_secs"]["admit"])
    assert d["step_secs"] > 0
    # start-up: the weights, the pools, the warm-up's compiles
    assert set(eng.startup_secs) == {"params", "pools", "warm"}
    assert all(v > 0 for v in eng.stats()["startup_secs"].values())


def test_a_sync_span_names_the_step_it_waits_for():
    """The engine reads step n's outputs after it dispatched step n+1:
    `llm.prefill.sync` and `llm.decode.sync` inside `llm.step` n+1 carry
    `of` = n, and every pass dispatched is read back once."""
    eng = _engine()
    spans, real = [], eng._clock.span

    def spy(name, **args):
        spans.append((name, args))
        return real(name, **args)

    eng._clock.span = spy
    before = eng.stats()
    _drain(eng, [eng.submit(_request(i, max_new=6)) for i in range(3)])
    d = _delta(eng.stats(), before)
    step, waited = None, {"llm.prefill.sync": [], "llm.decode.sync": []}
    for name, args in spans:
        if name == "llm.step":
            step = args["n"]
        elif name in waited:
            assert args["of"] == step - 1, (name, args, step)
            waited[name].append(args["of"])
    assert len(waited["llm.decode.sync"]) == d["decode_steps"] > 0
    assert len(waited["llm.prefill.sync"]) == d["prefill_steps"] > 0
    for ofs in waited.values():
        assert ofs == sorted(set(ofs))


def test_request_and_work_counters_equal_what_was_sent():
    eng = _engine()   # two lanes
    before = eng.stats()
    n, n_prompt, max_new = 5, 11, 4
    seqs = [eng.submit(_request(i, n_prompt, max_new)) for i in range(n)]
    _drain(eng, seqs)
    d = _delta(eng.stats(), before)
    for key in ("submitted_total", "admitted_total", "first_tokens_total",
                "finished_total"):
        assert d[key] == n, key
    assert d["cancelled"] == 0
    # the third request was submitted behind a full batch and waited for
    # a lane; the counter is the sum of every request's wait
    waits = [s.admitted_at - s.submitted_at for s in seqs]
    assert waits[2] > waits[0] >= 0 and waits[2] > 1e-4
    assert d["queue_wait_secs_total"] == pytest.approx(sum(waits))
    assert d["prefill_wait_secs_total"] == pytest.approx(
        sum(s.first_token_at - s.admitted_at for s in seqs))
    # work: every prompt token prefilled once, in passes of lanes x chunk
    # slots; every token but a request's first came from a decode lane
    assert d["prefill_tokens_total"] == n * n_prompt
    assert d["prefill_slots_total"] == d["prefill_steps"] * LANES * CHUNK
    assert d["decode_lane_steps_total"] == n * (max_new - 1)
    assert 1.0 <= d["decode_lane_steps_total"] / d["decode_steps"] <= 2.0
    # a cancelled request ends, and is not finished
    s = eng.submit(_request(9, max_new=30))
    eng.step()
    assert eng.cancel(s.request_id)
    d = _delta(eng.stats(), before)
    assert d["submitted_total"] == n + 1 and d["finished_total"] == n
    assert d["cancelled"] == 1


def _spans_of(fn):
    """The spans recorded while `fn` ran (the buffer is the process's)."""
    tracing.drain()
    fn()
    return tracing.drain()


def test_request_spans_ride_the_callers_trace():
    eng = _engine()
    caller = tracing.start_span("caller", parent=None)
    assert caller is not None, "tracing is on by default"

    def traced():
        token = tracing.activate(caller.context())
        try:
            seqs = [eng.submit(_request(i)) for i in range(3)]
        finally:
            tracing.restore(token)
        _drain(eng, seqs)
        return seqs

    seqs = []
    spans = _spans_of(lambda: seqs.extend(traced()))
    assert len(spans) == 9   # three a request, not one a token
    for seq in seqs:
        mine = [s for s in spans
                if s["attrs"]["request_id"] == seq.request_id]
        assert [s["name"] for s in mine] == ["llm.queue", "llm.prefill",
                                             "llm.decode"]
        for s in mine:
            assert s["trace_id"] == caller.trace_id
            assert s["parent_id"] == caller.span_id
            assert s["kind"] == tracing.KIND_INTERNAL
            assert s["attrs"]["prompt_tokens"] == 11
            assert s["attrs"]["tokens_generated"] == 4
            assert s["attrs"]["prefix_tokens_shared"] == 0
            assert s["attrs"]["first_step"] <= s["attrs"]["last_step"]
        # one stage ends where the next begins, on the wall clock
        q, p, dec = mine
        assert q["end"] == pytest.approx(p["start"], abs=1e-3)
        assert p["end"] == pytest.approx(dec["start"], abs=1e-3)
        assert abs(q["start"] - time.time()) < 300
        # the step numbers join a request to `llm.step` of a profile
        assert p["attrs"]["last_step"] == dec["attrs"]["first_step"]
        assert dec["attrs"]["last_step"] - dec["attrs"]["first_step"] == 3

    # sampled out, or no caller's trace at all: nothing is recorded
    def unsampled():
        with tracing.suppressed():
            s1 = eng.submit(_request(4))
        _drain(eng, [s1, eng.submit(_request(5))])

    assert _spans_of(unsampled) == []

    # a request cancelled in the queue has only the stage it reached
    def cancelled():
        token = tracing.activate(caller.context())
        try:
            held = [eng.submit(_request(i, max_new=20)) for i in (6, 7)]
            eng.step()
            waiting = eng.submit(_request(8))
        finally:
            tracing.restore(token)
        assert eng.cancel(waiting.request_id)
        for s in held:
            eng.cancel(s.request_id)

    spans = _spans_of(cancelled)
    by_rid = {}
    for s in spans:
        by_rid.setdefault(s["attrs"]["request_id"], []).append(s)
    shapes = sorted([s["name"] for s in rows] for rows in by_rid.values())
    assert shapes == [["llm.queue"],
                      ["llm.queue", "llm.prefill"],
                      ["llm.queue", "llm.prefill"]] or shapes == [
        ["llm.queue"], ["llm.queue", "llm.prefill", "llm.decode"],
        ["llm.queue", "llm.prefill", "llm.decode"]]
    for rows in by_rid.values():
        assert rows[-1]["status"] == "cancelled"
        assert all("status" not in s for s in rows[:-1])


def test_compiles_are_counted_and_say_which_phase_and_step():
    eng = _engine()
    _drain(eng, [eng.submit(_request(i)) for i in range(3)])
    warm = eng.stats()
    assert warm["compiles_total"] > 0 and warm["compile_secs_total"] > 0
    for _ in range(2):   # the same traffic again compiles nothing
        _drain(eng, [eng.submit(_request(i)) for i in range(3)])
    assert eng.stats()["compiles_total"] == warm["compiles_total"]
    # a prefill pass of another shape is a new program
    eng.prefill_lanes = 1
    step = eng.stats()["steps"]
    _drain(eng, [eng.submit(_request(0))])
    after = eng.stats()
    assert after["compiles_total"] > warm["compiles_total"]
    assert after["compile_secs_total"] > warm["compile_secs_total"]
    report = eng.device_report()
    assert report["compiles_total"] >= after["compiles_total"]
    # (the decode pass behind it takes that pass's output, of another
    # length, as its token feed: a new program too, in ITS dispatch)
    new = report["recent_compiles"][
        warm["compiles_total"] - after["compiles_total"]:]
    first = new[0]
    assert first["phase"] == "prefill_dispatch" and first["step"] == step
    assert first["secs"] > 0
    assert {c["phase"] for c in new} == {"prefill_dispatch",
                                         "decode_dispatch"}
    # a thread that named nothing: its compiles carry no phase
    ops.note_phase(None)
    jnp.zeros((3, 5, 7)).block_until_ready()
    assert ops.device_report()["recent_compiles"][-1]["phase"] is None


def test_a_profile_holds_the_step_its_phases_and_the_park(tmp_path):
    from benchmarks import trace_reduce

    unattributed = "engine host, unattributed"
    replica = _LLMCallable(warm=False, model=LlamaConfig(
        dtype=jnp.float32, **MODEL), params=_engine()._params, page_size=8,
        max_batch=2, prefill_chunk=CHUNK, prefill_lanes=LANES)
    eng = replica._engine
    loop = threading.Thread(target=eng.run_loop, daemon=True)
    loop.start()

    def serve(n):
        seqs = [eng.submit(_request(i, max_new=6)) for i in range(n)]
        deadline = time.monotonic() + 120
        while any(not s.done for s in seqs):
            assert time.monotonic() < deadline
            time.sleep(0.005)

    try:
        serve(2)            # whatever compiles does so before the capture
        replica.profile_start(str(tmp_path))
        try:
            serve(3)
            # the idle loop parks between two bursts; shorter than one
            # park (50 ms), so that one span covers the device's gap
            time.sleep(0.03)
            serve(3)
        finally:
            replica.profile_stop()
    finally:
        eng.stop()
        loop.join(30)
    assert not loop.is_alive()
    rows = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    names = {name for _s, _e, name in rows["host"]}
    assert {"llm.step", "llm.park"} | set(_PHASES.values()) <= names
    # a phase lies inside a step: one clock, one thread
    steps = sorted((s, e) for s, e, n in rows["host"] if n == "llm.step")
    for s, e, n in rows["host"]:
        if n in _PHASES.values():
            assert any(s0 - 1e-6 <= s and e <= e0 + 1e-6
                       for s0, e0 in steps), n
    reduced = trace_reduce.reduce_events(**rows, unattributed=unattributed)
    gaps = dict(reduced["idle_gaps"])
    # the step in flight when the profile stops has no `llm.step` span in
    # the capture, so the device's gap under it has no name: one step's
    # worth at most, once in some 25 runs
    assert gaps.get(unattributed, 0.0) <= max(e - s for s, e in steps), gaps
    assert gaps["llm.park"] >= 0.02, gaps


def test_flax_names_the_model_parts_and_pallas_the_kernels():
    """Whoever opens a trace finds `embed`, `attn`, `mlp` and `lm_head`
    in an operation's name (flax scopes each module under its name), and
    the two kernels under names of their own."""
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaModel
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.paged_attention import paged_attention

    model = LlamaModel(LlamaConfig(dtype=jnp.float32, **MODEL))
    tokens = np.zeros((1, 8), np.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))
    text = jax.jit(model.apply).lower(shapes, tokens).as_text(
        debug_info=True)
    for scope in ("embed", "layer_1/attn", "layer_1/mlp", "lm_head"):
        assert f"/{scope}/" in text, scope
    q = jnp.zeros((2, 1, 4, 8), jnp.float32)
    pool = jnp.zeros((4 * 8, 2, 8), jnp.float32)
    text = jax.jit(lambda: paged_attention(
        q, pool, pool, jnp.zeros((2, 2), jnp.int32),
        jnp.ones((2,), jnp.int32), page_size=8)).lower().as_text(
        debug_info=True)
    assert "paged_attention_decode" in text
    x = jnp.zeros((1, 128, 4, 8), jnp.float32)
    text = jax.jit(lambda: flash_attention(x, x[:, :, :2], x[:, :, :2])
                   ).lower().as_text(debug_info=True)
    assert "flash_attention_fwd" in text
