"""Deployment kind "serve_laguna": `kinds/serve.py` for a model of the
Laguna family (`ray_tpu/models/laguna.py`) — the same entry points

    ray_tpu.init -> Deployment(LagunaReplica, llm=True).bind(engine args)
    -> serve.run -> handle.stream from the open-loop client

with the engine's `model=` made of the configuration's published keys
(`model_type: laguna` picks the family in `ray_tpu.models.resolve`), the
plain reference of `reference_laguna.py`, and a comparison that says
what it does about routing.  Everything that is not the model's —
canaries asked on the idle engine, the poller, the tracer, the window,
the sweep — is `kinds/serve.py`'s own code, imported.

**Routing is discontinuous, and this is what the comparison does about
it.**  A token's ten experts are the ten largest of 256 router
probabilities; where the 10th and the 11th lie close, a correct program
that rounds its activations to bfloat16 picks the other one, and its
logits then differ from the reference's by an expert, not by a rounding.
The reference returns each position's MARGIN (the smallest, over the
sparse layers, gap between the 10th and the 11th router logit).  The
engine's tokens are measured exactly as `kinds/serve.py` measures them
(bfloat16 spacings under the reference's largest logit, teacher-forced)
on the positions whose margin exceeds `ROUTER_TIE_TAU`; the share set
aside is printed (`reference.near_tie_share`), and a run that sets aside
more than half, or judges fewer than `MIN_JUDGED` positions, is not
`correct`.

ISSUE 28 asked that every judged position lie within `LOGIT_TOL_ULPS`.
The chip said otherwise (PERF.md section 6, PR 28): the bfloat16
activations move a router logit by half a typical gap, so positions with
margins of 0.005 to 0.011 — far over any tau that keeps half the
positions — still route an expert differently, and three judged
positions of 90 lay 4.7, 10.0 and 15.8 spacings off in the first run of
a correct program (every other within 1.6).  A flip cannot be told from
a fault at ONE position; it can by how many positions are off.  So the
limit this kind brings is on the SHARE of judged positions beyond
`LOGIT_TOL_ULPS`, `MAX_OFF_SHARE`, between its two readings: what the
program gives over its seeds, and what the reference gives with every
matrix rounded to float8_e4m3fn, the nearest precision below the stated
bfloat16 (`--sweep` runs print that second reading as
`reference_lower_precision`; it has to come out not correct).  What the
comparison cannot see — int8 experts, a bfloat16 or float16 router:
each finer than the bfloat16 activations' own noise — is a tested fact
(tests/bench_harness/test_bench_reference_laguna.py) and an open
question in PERF.md, not a secret.

**What a token depends on besides the weights, and what the comparison
does about that.**  A prefill pass gathers a context as wide as its
LONGEST lane needs (`_prefill_ctx_buckets`), so the program that
computes a chunk depends on which sequences share its pass; the wider
program sums the same numbers in another order, an activation lands one
bfloat16 rounding away, and here a rounding can pick another expert.  On
the chip (PERF.md section 6, PR 28) the eight canaries sent together,
twice, or one at a time, before or after traffic, give the same tokens;
sent 20 ms apart, two of the eight change a token.  `kinds/serve.py`
sends its canaries together and asks for the same tokens after the
window: with this model that holds only while the eight reach the engine
within a step of each other, which they did in the builder's 34 runs;
the driver's check then met a run that was not `correct` among its first
six (its log is not the builder's to read: of this kind's checks, that
one alone moves with a clock).  So the canaries are asked twice: TOGETHER
(several sequences in a pass, a decode batch of many lanes: what the
reference judges, by shares, which no packing moves far), and IN TURN,
each alone on the idle engine, the next sent when the last has its
tokens: one packing whatever the clocks do, and those tokens must come
back the same after the window, to the last id.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import time
from typing import Any, Dict, List

from ray_tpu import serve
from ray_tpu.serve.api import Deployment

from benchmarks.cluster import (bounded, check, get, wait_chips_free,
                                wait_gone)
from benchmarks.kinds.serve import (CANARY_NEW, LOGIT_TOL_ULPS, NAME,
                                    ask_canaries, call_all, latency_ms,
                                    merge_traces, ms, one_window, sweep,
                                    ulps_below_top, wait_idle,
                                    window_polls)
from benchmarks.replica_laguna import LagunaReplica
from benchmarks.stats import percentile

# A tree without the model fails here, before any cluster starts.  (The
# check is of the file: importing `ray_tpu.models.laguna` would import
# jax into this process, which must never hold the chip.)
_MODEL = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "ray_tpu", "models", "laguna.py")
if not os.path.isfile(_MODEL):
    raise ImportError(f"this tree has no {_MODEL}: the program cannot "
                      f"run a model of the laguna family")

# The margin (a gap of two router logits) up to which a position is set
# aside.  Measured (PERF.md section 6, PR 28: the bfloat16 program
# against this reference at the published hidden width, 5 layers, 192
# positions): a router logit of the program lies 0.013 (first sparse
# layer) to 0.025 (fourth) from the reference's, standard deviation — the
# bfloat16 activations' doing, ten times what one rounding of the
# router's input gives — so a gap of two logits moves by 0.019 to 0.035,
# while the gap between the 10th and 11th of 256 is 0.045 on average
# (median 0.03).  The spread is as large as the gaps: no threshold that
# keeps half of the positions sets aside every position that may route
# differently in a correct program.  So tau is set from the other side,
# by the share it may cost.  On the chip (PERF.md section 6, PR 28) 0.004
# set aside 23 to 44 % of 128 positions over nine seeds, mean 30 %, one
# seed within a standard deviation of MAX_NEAR_TIE_SHARE; 0.003 sets
# aside 16 to 31 % over 27 seeds (mean 23 %, sd 3 %), which leaves the
# limit eight standard deviations away and 89 to 107 positions judged.
# The positions that remain still hold flips, which MAX_OFF_SHARE has to
# carry.
ROUTER_TIE_TAU = 0.003
MAX_NEAR_TIE_SHARE = 0.5
MIN_JUDGED = 32
# The share of judged positions that may lie beyond LOGIT_TOL_ULPS.
# First reading, the program (PERF.md section 6, PR 28, my chip runs, 27
# seeds at this tau): 0.094 of the judged positions (9 of 96) the
# largest, 0.044 the mean, 0.023 the standard deviation.  Second
# reading, the float8 reference on the chip in the same contexts: 0.467
# (49 of 105; 0.357 at tau 0.004).  The limit lies between, four and a
# half standard deviations over the program's mean and a third of the
# second reading; a wrong page, mask, rotary or kernel moves most
# positions, far over either.
MAX_OFF_SHARE = 0.15
LOWER_PRECISION = "float8_e4m3fn"
# shorter than, about and several times the window of 512; three past
# the 64-token prefill chunk's multiples; 8 x 16 tokens = 128 positions,
# so that half of them set aside still leaves twice MIN_JUDGED
CANARY_LENGTHS = (24, 150, 80, 200, 330, 520, 700, 1100)

# the configuration's keys the model is made of (`LagunaConfig` reads
# what it knows of them; `num_experts` is the router's width there)
MODEL_KEYS = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_key_value_heads", "head_dim",
              "max_position_embeddings", "rms_norm_eps",
              "num_experts_per_tok", "moe_intermediate_size",
              "shared_expert_intermediate_size", "norm_topk_prob",
              "moe_routed_scaling_factor", "sliding_window", "layer_types",
              "mlp_layer_types", "num_attention_heads_per_layer",
              "rope_parameters", "experts_held")


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """`LLMEngine(model=...)` for this configuration.  Refuses a file
    whose per-layer lists do not have one entry a layer."""
    n = cfg["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(cfg[key]) != n:
            raise ValueError(f"{key} has {len(cfg[key])} entries for "
                             f"{n} layers")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError(f"experts_held {cfg['experts_held']} is not the "
                         f"{cfg['num_experts']} experts the file says "
                         f"are held")
    return {**{k: cfg[k] for k in MODEL_KEYS},
            "num_experts": cfg["num_experts_routed_over"]}


def canary_requests(seed: int, vocab: int, lengths=CANARY_LENGTHS,
                    limit: int = 0) -> List[Dict[str, Any]]:
    """Seeded prompts of these lengths with distinct first tokens (see
    generators/open_loop.py); `limit` > 0 cuts each to what a toy
    engine's context holds."""
    rnd = random.Random(f"canary-{seed}")
    if limit:
        lengths = [min(n, limit) for n in lengths]
    firsts = rnd.sample(range(1, vocab), len(lengths))
    return [{"tokens": [first] + [rnd.randrange(1, vocab)
                                  for _ in range(n - 1)],
             "max_new_tokens": CANARY_NEW}
            for first, n in zip(firsts, lengths)]


def ask_in_turn(replicas, requests) -> List[List[List[int]]]:
    """`ask_canaries`, one canary at a time: each is alone on its engine
    from its first chunk to its last token, so the programs that compute
    it (the context width of every prefill pass, the table width of
    every decode step) follow from its length alone and the same tokens
    come back whenever it is asked (the module's text has why that is
    not so for canaries sent together)."""
    return [[get(r.handle_request.remote("generate", (q,), {}),
                 "a canary asked alone", 300)["tokens"] for q in requests]
            for r in replicas]


def check_canaries(canaries, answers, refs, tau: float = ROUTER_TIE_TAU,
                   tol_ulps: float = LOGIT_TOL_ULPS,
                   max_off_share: float = MAX_OFF_SHARE) -> Dict[str, Any]:
    """`kinds/serve.check_canaries`'s measure on the positions whose
    router margin in the reference exceeds `tau`.  Returns the counts
    (positions, judged, near ties set aside, picks that were not the
    reference's argmax, judged positions beyond `tol_ulps`), the worst
    distance in bfloat16 spacings among the judged (and the five
    largest) and among those set aside, and what is wrong (`off`): more
    than `max_off_share` of the judged beyond the tolerance, too many
    set aside, too few judged."""
    off, beyond, n, judged, not_argmax, dists = [], [], 0, 0, 0, []
    worst = worst_tie = 0.0
    for q, toks, ref in zip(canaries, answers, refs):
        for j, tok in enumerate(toks):
            n += 1
            d = 0.0 if tok == ref["top_id"][j] else \
                ulps_below_top(ref["top"][j], ref["picked"][j])
            if ref["margin"][j] <= tau:
                worst_tie = max(worst_tie, d)
                continue
            judged += 1
            not_argmax += tok != ref["top_id"][j]
            worst = max(worst, d)
            dists.append(d)
            if d > tol_ulps:
                beyond.append(f"canary of {len(q['tokens'])} tokens, token "
                           f"{j} (router margin {ref['margin'][j]:.4f}): "
                           f"the engine picked {tok}, which the reference "
                           f"puts {d:.1f} bfloat16 spacings under its own "
                           f"{ref['top_id'][j]}")
    share = 1.0 - judged / n if n else 1.0
    off_share = len(beyond) / judged if judged else 1.0
    if off_share > max_off_share:
        off.append(f"{len(beyond)} of {judged} judged positions "
                   f"({off_share:.0%}, limit {max_off_share:.0%}) lie more "
                   f"than {tol_ulps} bfloat16 spacings under the "
                   f"reference's choice; the first: {beyond[0]}")
    if share > MAX_NEAR_TIE_SHARE:
        off.append(f"{share:.0%} of the positions are near ties of the "
                   f"router (margin <= {tau}): more than half set aside")
    if judged < MIN_JUDGED:
        off.append(f"only {judged} positions judged; {MIN_JUDGED} needed")
    return {"positions": n, "judged": judged, "near_tie_share": share,
            "not_argmax": not_argmax, "off_share": off_share,
            "worst_ulps": worst,
            "worst_ulps_near_ties": worst_tie,
            "largest_ulps": sorted(dists, reverse=True)[:5], "off": off}


def run(ctx) -> Dict[str, Any]:
    cfg, traffic = ctx.config, ctx.traffic
    dep = cfg["deployment"]
    n_rep = int(dep.get("replicas", 1))
    check(n_rep == ctx.cell["chips"],
          f"{n_rep} one-chip replica(s) in a cell of {ctx.cell['chips']} "
          f"chip(s)")
    model = model_kwargs(cfg)
    vocab = int(cfg["vocab_size"])
    engine_kwargs = dict(dep.get("engine", {}), model=model, seed=ctx.seed,
                         sizes=cfg)
    t_run = time.monotonic()
    app = Deployment(
        LagunaReplica, NAME, num_replicas=n_rep,
        max_ongoing_requests=int(dep.get("max_ongoing_requests", 64)),
        ray_actor_options={"resources": {"TPU": 1}}, llm=True,
    ).bind(**engine_kwargs)
    handle = bounded(f"serve.run: {n_rep} TPU:1 replica(s) to be scheduled, "
                     f"build their engines and warm up", 1100, serve.run,
                     app)
    ready_s = time.monotonic() - t_run
    replicas = list(handle._replicas)
    check(len(replicas) == n_rep, f"{len(replicas)} replicas, not {n_rep}")
    reports = call_all(replicas, "device_report")
    rep0 = reports[0]
    problems: List[str] = []

    def expect(cond: bool, what: str) -> None:
        if not cond:
            problems.append(what)

    if not ctx.rehearse:
        for rep in reports:
            check(rep["platform"] == "tpu",
                  f"a replica's jax runs on {rep['platform']!r}, not a TPU")
            check(rep["device_count"] == 1,
                  f"a TPU:1 replica sees {rep['device_count']} devices")
            expect(rep["kernel_mode"] == "compiled"
                   and rep["decode_has_tpu_custom_call"],
                   f"decode step without a compiled Pallas kernel "
                   f"(kernels {rep['kernel_mode']!r}, tpu_custom_call "
                   f"{rep['decode_has_tpu_custom_call']})")
    check(len({rep["pid"] for rep in reports}) == n_rep,
          "replicas share a process")
    for rep in reports:
        got = rep["model"]
        check(got["family"] == "laguna", f"the engine runs {got['family']}")
        check(got["share"] == {"experts_held": list(cfg["experts_held"]),
                               "num_experts": cfg["num_experts_routed_over"],
                               "vocab_rows": vocab},
              f"the engine holds {got['share']}")
        kinds = ["window" if t == "sliding_attention" else "full"
                 for t in cfg["layer_types"]]
        check([layer[0] for layer in got["cache_spec"]] == kinds,
              f"the engine's cache is {got['cache_spec']}")
    ctx.say("replicas", ready_s=ready_s, n=n_rep,
            built_s=[s["built_s"] for s in
                     call_all(replicas, "bench_state")],
            param_bytes=rep0["param_bytes"],
            kv_pool_bytes=rep0["kv_pool_bytes"],
            compiled_steps=[r["compiled_steps"] for r in reports],
            cache_hits=[r["compile_cache_hits"] for r in reports],
            cache_misses=[r["compile_cache_misses"] for r in reports],
            cache_dir=rep0["compile_cache_dir"],
            attention_impl=rep0["attention_impl"],
            model=rep0["model"]["share"],
            cache_spec=rep0["model"]["cache_spec"])

    # ---- correctness sample, before: canaries on the idle engines, sent
    # together and then in turn (the module's text); the judged tokens
    # of those sent together against the plain reference on the engine's
    # own weights, teacher-forced with the engine's answer
    limit = int(cfg["max_position_embeddings"]) - CANARY_NEW
    canaries = canary_requests(ctx.seed, vocab, limit=limit)
    together = ask_canaries(replicas, canaries)
    wait_idle(replicas)
    before = ask_in_turn(replicas, canaries)
    expect(all(len(toks) == CANARY_NEW
               for toks in together[0] + before[0]),
           f"a canary answered other than {CANARY_NEW} tokens")
    for row in before[1:]:
        expect(row == before[0], "replicas of one seed answer a canary "
                                 "differently")
    if not problems:
        refs = call_all(replicas[:1], "bench_reference",
                        [q["tokens"] for q in canaries], together[0],
                        seconds=600)[0]
        held = check_canaries(canaries, together[0], refs)
        held["moved_asked_alone"] = sum(
            a != b for a, b in zip(together[0], before[0]))
        problems.extend(held.pop("off")[:5])
        ctx.say("reference", **held, tolerance_ulps=LOGIT_TOL_ULPS,
                tau=ROUTER_TIE_TAU, max_off_share=MAX_OFF_SHARE)
        if ctx.sweep:
            # the second reading (a builder's run): what the reference
            # picks with its matrices in the nearest lower precision, in
            # the engine's contexts, against the reference proper
            prompts = [q["tokens"] for q in canaries]
            lower = call_all(replicas[:1], "bench_reference", prompts,
                             together[0], matrices=LOWER_PRECISION,
                             seconds=600)[0]
            picks = [r["top_id"] for r in lower]
            said = check_canaries(
                canaries, picks,
                call_all(replicas[:1], "bench_reference", prompts,
                         together[0], picks=picks, seconds=600)[0])
            ctx.say("reference_lower_precision", matrices=LOWER_PRECISION,
                    **{**said, "off": said["off"][:2]})
    compiles0 = [s["backend_compiles"]
                 for s in call_all(replicas, "bench_state")]

    generate = ctx.spec.generator(traffic["generator"])
    outcome: Dict[str, Any] = {}
    if ctx.sweep:
        sweep(ctx, handle, replicas, generate, traffic, vocab)
        outcome["sweep_only"] = True
    else:
        plan = generate(traffic, ctx.seed, ctx.seconds, vocab)
        run, polls, s = one_window(ctx, handle, replicas, plan, traffic,
                                   vocab, trace=ctx.trace)
        check(not s["hung"], f"streams {s['hung'][:5]} never ended")
        ctx.say("replica_stalls", since_warm_up=call_all(replicas,
                                                         "bench_stalls"))
        wait_idle(replicas)
        traces: Dict[str, Any] = {}
        if ctx.trace:
            parts = call_all(replicas, "profile_reduce", seconds=300,
                             unattributed="engine host, unattributed")
            traces = merge_traces(parts)
            if traces:
                traces["span_stats"] = [p.get("span_stats") for p in parts]
        outcome.update(
            window_start_epoch=run["w0_epoch"],
            attempted=s["attempted"], failed=s["failed"],
            e2e={**latency_ms(s, qs=(75, 95)),
                 "serve_tokens_per_s":
                     s["tokens_in_window"] / s["window_s"]},
            obs={"kind": "serve", "summary": s, "ready_s": ready_s,
                 "polls": [window_polls(r, run["w0_epoch"], s["window_s"])
                           for r in polls],
                 "trace": traces, "model": cfg,
                 "engine": {"param_bytes": rep0["param_bytes"],
                            "dtype": rep0["dtype"],
                            "page_size": rep0["page_size"]}})
        ctx.say("client", attempted=s["attempted"], failed=s["failed"],
                failed_rids=s["failed_rids"], finished=s["finished"],
                open_at_end=s["open_at_end"],
                late_p95_ms=ms(percentile(s["late_s"], 95)),
                **latency_ms(s, qs=(50,)),
                samples_ttft=len(s["ttft_s"]), samples_tpot=len(s["tpot_s"]),
                offered_rps=len(plan["requests"])
                / (plan["lead_in_s"] + plan["window_s"]))
        with open(os.path.join(ctx.out_dir, "requests.json"), "w") as f:
            json.dump({"w0": run["w0"], "w1": run["w1"],
                       "records": [r.as_dict() for r in run["records"]],
                       "polls": polls}, f)

    # ---- correctness sample, after: the same canaries in turn, the same
    # tokens; nothing compiled since warm-up; both page groups as they were
    after = ask_in_turn(replicas, canaries)
    expect(after == before, "a canary's tokens changed over the window "
                            "(a recycled or mis-shared page)")
    wait_idle(replicas)
    states = call_all(replicas, "bench_state")
    reports1 = call_all(replicas, "device_report")
    for r0, r1, c0, s1 in zip(reports, reports1, compiles0, states):
        expect(r1["compiled_steps"] == r0["compiled_steps"]
               and s1["backend_compiles"] == c0,
               f"compiles after warm-up: compiled_steps "
               f"{r0['compiled_steps']} -> {r1['compiled_steps']}, backend "
               f"compiles {c0} -> {s1['backend_compiles']}")
        expect(not any(s1["kv_pages_in_use"].values()),
               f"pages still held on an idle engine: "
               f"{s1['kv_pages_in_use']}")
    pids = [r["pid"] for r in reports]
    serve.delete(NAME)
    wait_chips_free(n_rep, f"the replicas (pids {pids})")
    check(wait_gone(pids),
          f"a replica process of {pids} outlived its lease")
    if not ctx.keep_trace:
        for i in range(n_rep):
            shutil.rmtree(os.path.join(ctx.out_dir, f"trace-r{i}"),
                          ignore_errors=True)
    if problems:
        ctx.say("incorrect", problems=problems)
    outcome.update(
        correct=not problems,
        device={"platform": rep0["platform"], "kind": rep0["device_kind"],
                "count": sum(r["device_count"] for r in reports),
                "memory_peak_bytes": max(s["memory_peak_bytes"]
                                         for s in states)})
    return outcome
