"""Deployment kind "serve_qwen3next": `kinds/serve.py` for a model of
the hybrid expert family (`ray_tpu/models/qwen3_next.py`) — the same
entry points

    ray_tpu.init -> Deployment(Qwen3NextReplica, llm=True).bind(engine args)
    -> serve.run -> handle.stream from the open-loop client

with the engine's `model=` made of the configuration's published keys
(`model_type: qwen3_next` picks the family in `ray_tpu.models.resolve`),
the plain reference of `reference_qwen3next.py` (the gated delta rule
token by token, plain attention, a loop over the experts held, float32),
and a comparison that is `kinds/serve_olmo.py`'s in its FORM joined with
`kinds/serve_laguna.py`'s treatment of ROUTING: the engine's tokens,
teacher-forced through the reference, each measured by how far under its
own choice the reference puts it (bfloat16 spacings at the size of its
largest logit); positions whose router margin in the reference is at most
`ROUTER_TIE_TAU` are SET ASIDE and counted (a correct bfloat16 program
may pick another expert there: that file's text); of the positions that
remain, `correct` is decided by the SHARE beyond two tolerances, not by
the worst one.  Everything that is not the model's is imported from
`kinds/serve.py`, `kinds/serve_laguna.py` and `kinds/serve_granite.py`;
this file restates `run` and brings its own values.

**What the canaries cover.**  The cell is many streams decoding at once
over states updated in place, 2-head pages and experts read for a row or
two.  The canaries reach from less than one prefill chunk to the mix's
longest request, 8,192 prompt tokens (128 chunks of carried state) and
1,536 decoded ones through the state pool, 1,840 judged positions in
all, every prefill pass shape and decode table width the cell's traffic
uses.  They are asked TOGETHER (several sequences a pass, lanes changing
as the short ones end: what the reference judges) and then IN TURN, each
alone on the idle engine: those tokens must come back the same after the
window, to the last id, from whatever state slots and pages the window's
traffic left behind (`ask_in_turn`: routing makes a token depend on
which sequences share a pass).

**Two limits on the logits and one on the carry**, each between two
readings (PERF.md section 6, PR 55): the largest the program gave over
its seeds on the chip, and the smallest that the reference's other
readings (`reference_qwen3next.READINGS`) give against the reference
proper.  `MAX_OFF_SHARE` of the judged positions may lie beyond
`LOGIT_TOL_ULPS`; `MAX_FAR_SHARE` beyond `FAR_TOL_ULPS`; and
`MAX_CARRY_OFF` bounds how far the worst head of the FIRST linear layer
carries its state from the reference's behind the longest canary
(`kinds/serve_olmo.py`'s text has why that layer and why the worst head:
a carry rounded to bfloat16 moves no logit far enough to be refused by a
share of positions) AND behind the shortest one, asked alone again on
slots that earlier canaries used: a state that does not start at zero
(a slot's last owner's) has decayed to nothing behind 8,192 tokens and
moves 4 judged positions of 1,380, but stands whole behind 24 + 15.  A
run is not `correct` if it passes any.

A builder's run asks for other readings in the environment
(`QWEN3NEXT_READINGS=all`, or names between commas): each is printed as a
`reference_reading` line — its picks judged against the reference proper
as a program with that fault would be, and its carry.  A run of the cell
sets nothing and computes none.
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

from benchmarks.cluster import (bounded, check, wait_chips_free, wait_gone)
from benchmarks.kinds.serve import (LOGIT_TOL_ULPS, NAME, ask_canaries,
                                    call_all, latency_ms, merge_traces, ms,
                                    one_window, sweep, wait_idle,
                                    window_polls)
from benchmarks.kinds.serve_granite import shares_beyond
from benchmarks.kinds.serve_laguna import ask_in_turn, check_canaries
from benchmarks.replica_qwen3next import Qwen3NextReplica
from benchmarks.stats import percentile

# A tree without the model fails here, before any cluster starts.  (The
# check is of the file: importing `ray_tpu.models.qwen3_next` would
# import jax into this process, which must never hold the chip.)
_MODEL = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "ray_tpu", "models", "qwen3_next.py")
if not os.path.isfile(_MODEL):
    raise ImportError(f"this tree has no {_MODEL}: the program cannot "
                      f"run a model of the qwen3_next family")

# The router margin (a gap of two router logits) up to which a position
# is set aside: Laguna's (`kinds/serve_laguna.py` has how it was set, from
# the share it may cost).  Here it sets aside 24.0 to 25.8 % of 1,840
# positions on five seeds (four expert layers of top-10 of 512), which
# leaves 1,365 to 1,399 judged and MAX_NEAR_TIE_SHARE twice over.
ROUTER_TIE_TAU = 0.003
# The share of judged positions that may lie beyond LOGIT_TOL_ULPS:
# between the program's largest over its seeds on the chip (0.0022: three
# positions of 1,366; 0.0007 to 0.0022 on seven seeds, and 0.0 on two
# before the q/k norms' draw was set) and the smallest of the other
# readings' that it has to refuse (0.0945 float8_e4m3fn; no_qk_norm 0.160,
# no_attn_gate 0.177, rotary_all 0.241, topk_not_normalised 0.31,
# other_share_added 0.41, beta_doubled 0.42 and up): PERF.md section 6,
# PR 55.  What it does NOT refuse is the carry's to refuse (below).
MAX_OFF_SHARE = 0.02
# ... and the share that may lie beyond FAR_TOL_ULPS, where one position
# of the program reached 12.9 spacings on one seed (an expert flipped
# outside tau) and none went further (0.0 on every seed); the readings
# put 0.0015 (no_qk_norm) to 0.047 (beta_doubled) and 0.33 to 1.0 there
FAR_TOL_ULPS = 24.0
MAX_FAR_SHARE = 0.003
# ... and how far the worst head of the first linear layer may carry its
# state from the reference's, behind the longest canary and behind the
# shortest: between the program's largest over its seeds (0.0052 behind
# the longest of nine seeds, 0.0044 behind the shortest of seven) and the
# smallest of what it has to refuse — `bfloat16_state` 0.0166 and 0.0254
# behind the longest (0.0071 behind the shortest: that one is the long
# canary's to refuse), `stale_slot` 0.357 behind the shortest (0.001
# behind the longest: the short canary's to refuse), float8 0.074 / 0.084
MAX_CARRY_OFF = 0.009
READINGS = ("float8_e4m3fn", "beta_doubled", "keys_tiled", "alpha_one",
            "bfloat16_state", "stale_slot", "no_attn_gate", "rotary_all",
            "no_qk_norm", "w_not_one_plus_w", "no_shared_gate",
            "topk_not_normalised", "other_share_added")
# (prompt tokens, tokens decoded): less than a chunk, one chunk, a few,
# past the 256-, 1024- and 4096-column context buckets, and the mix's
# longest request; decode tables of 4, 16, 64, 256 and 1024 pages.
# 1,840 positions
CANARIES = ((24, 16), (64, 16), (150, 32), (330, 16), (900, 64),
            (1100, 32), (3000, 128), (8192, 1536))
# the configuration's keys the model is made of: every published key
# (`Qwen3NextConfig.from_dict` reads what it knows and refuses, by name,
# the parts of the family it does not write) and the share's
MODEL_KEYS = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "hidden_act", "max_position_embeddings", "rms_norm_eps",
    "tie_word_embeddings", "full_attention_interval",
    "partial_rotary_factor", "rope_theta", "rope_scaling",
    "use_sliding_window", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
    "experts_held", "num_experts_routed_over")


def judge(canaries, answers, refs) -> Dict[str, Any]:
    """`check_canaries` under both limits (the module's text), near ties
    of the router set aside: its counts at `LOGIT_TOL_ULPS`, `far_share`
    beyond `FAR_TOL_ULPS`, the shares at other tolerances (of ALL
    positions: `shares_beyond` sets none aside), and in `off` what either
    limit refuses."""
    held = check_canaries(canaries, answers, refs, tau=ROUTER_TIE_TAU,
                          max_off_share=MAX_OFF_SHARE)
    far = check_canaries(canaries, answers, refs, tau=ROUTER_TIE_TAU,
                         tol_ulps=FAR_TOL_ULPS, max_off_share=MAX_FAR_SHARE)
    held["far_share"] = far["off_share"]
    if far["off_share"] > MAX_FAR_SHARE:
        first = far["off"][0].partition("the first: ")[2]
        held["off"].append(
            f"{far['off_share']:.2%} of the judged positions (limit "
            f"{MAX_FAR_SHARE:.2%}) lie more than {FAR_TOL_ULPS:g} bfloat16 "
            f"spacings under the reference's choice, farther than a "
            f"rounding or a flipped expert goes; the first: {first}")
    held["off_share_beyond"] = shares_beyond(canaries, answers, refs)
    return held


def judge_carry(carry: Dict[str, Any], which: str = "longest"
                ) -> Dict[str, Any]:
    """`Qwen3NextReplica.bench_carry`'s distances under `MAX_CARRY_OFF`
    behind the `which` canary: `carry_off`, the worst head's of the FIRST
    linear layer, the worst layer's whole and the worst head's anywhere
    beside it, every layer's, and in `off` what the limit refuses."""
    first = max(carry["heads"][0])
    off = [] if first <= MAX_CARRY_OFF else [
        f"a head of the first linear layer carries a state {first:.2e} of "
        f"its norm from the reference's behind the {which} canary (limit "
        f"{MAX_CARRY_OFF:.1e}): farther than the layer's rounded inputs "
        f"put it"]
    return {"carry_off": first, "carry_layer_off": max(carry["layers"]),
            "carry_head_off": max(max(h) for h in carry["heads"]),
            "carry_layers": carry["layers"],
            "carry_heads_first": sorted(carry["heads"][0])[-5:],
            "off": off}


def asked_readings() -> List[str]:
    """The readings a builder's run asks for in `QWEN3NEXT_READINGS`
    ("all", or names of `READINGS` between commas); none in a run of the
    cell."""
    asked = os.environ.get("QWEN3NEXT_READINGS", "")
    names = list(READINGS) if asked == "all" else \
        [name for name in asked.split(",") if name]
    unknown = [name for name in names if name not in READINGS]
    if unknown:
        raise ValueError(f"QWEN3NEXT_READINGS names {unknown}: not of "
                         f"{READINGS}")
    return names


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """`LLMEngine(model=...)` for this configuration.  Refuses a file
    whose `experts_held` is not the experts it says are held."""
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError(f"experts_held {cfg['experts_held']} is not the "
                         f"{cfg['num_experts']} experts the file says "
                         f"are held")
    return {k: cfg[k] for k in MODEL_KEYS}


def canary_requests(seed: int, vocab: int, limit: int = 0
                    ) -> List[Dict[str, Any]]:
    """Seeded prompts of CANARIES' lengths with distinct first tokens
    (see generators/open_loop.py); `limit` > 0 cuts each prompt and
    answer to what a toy engine's context holds."""
    rnd = random.Random(f"canary-{seed}")
    sizes = [(min(n, limit // 2), min(m, limit // 8)) if limit else (n, m)
             for n, m in CANARIES]
    firsts = rnd.sample(range(1, vocab), len(sizes))
    return [{"tokens": [first] + [rnd.randrange(1, vocab)
                                  for _ in range(n - 1)],
             "max_new_tokens": m}
            for first, (n, m) in zip(firsts, sizes)]


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
        Qwen3NextReplica, NAME, num_replicas=n_rep,
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
        check(got["family"] == "qwen3_next",
              f"the engine runs {got['family']}")
        check(got["share"] == {"experts_held": list(cfg["experts_held"]),
                               "num_experts": cfg["num_experts_routed_over"],
                               "vocab_rows": vocab},
              f"the engine holds {got['share']}")
        every = int(cfg["full_attention_interval"])
        kinds = ["full" if i % every == every - 1 else "state"
                 for i in range(cfg["num_hidden_layers"])]
        check([layer[0] for layer in got["cache_spec"]] == kinds,
              f"the engine's cache is {got['cache_spec']}")
    warm = call_all(replicas, "bench_state")
    ctx.say("replicas", ready_s=ready_s, n=n_rep,
            built_s=[s["built_s"] for s in warm],
            # the engine's OWN peak, every pass shape warmed up and no
            # reference beside it yet
            engine_peak_bytes=[s["memory_peak_bytes"] for s in warm],
            param_bytes=rep0["param_bytes"],
            kv_pool_bytes=rep0["kv_pool_bytes"],
            state_pool_bytes=rep0["state_pool_bytes"],
            compiled_steps=[r["compiled_steps"] for r in reports],
            cache_hits=[r["compile_cache_hits"] for r in reports],
            cache_misses=[r["compile_cache_misses"] for r in reports],
            cache_dir=rep0["compile_cache_dir"],
            attention_impl=rep0["attention_impl"],
            model=rep0["model"]["share"],
            cache_spec=rep0["model"]["cache_spec"],
            cache_kinds=[layer[0] for layer in rep0["model"]["cache_spec"]])

    # ---- correctness sample, before: canaries on the idle engines, sent
    # together and then in turn (the module's text); the judged tokens
    # of those sent together against the plain reference on the engine's
    # own weights, teacher-forced with the engine's answer
    canaries = canary_requests(
        ctx.seed, vocab,
        limit=int(cfg["max_position_embeddings"]) if ctx.rehearse else 0)
    together = ask_canaries(replicas, canaries)
    wait_idle(replicas)
    before = ask_in_turn(replicas, canaries)
    expect(all(len(toks) == q["max_new_tokens"]
               for row in (together[0], before[0])
               for toks, q in zip(row, canaries)),
           "a canary answered other than the tokens asked for")
    for row in before[1:]:
        expect(row == before[0], "replicas of one seed answer a canary "
                                 "differently")
    if not problems:
        prompts = [q["tokens"] for q in canaries]
        refs = call_all(replicas[:1], "bench_reference", prompts,
                        together[0], seconds=1500)[0]
        # positions inside a router margin of ROUTER_TIE_TAU are set
        # aside and counted
        held = judge(canaries, together[0], refs)
        held["moved_asked_alone"] = sum(
            a != b for a, b in zip(together[0], before[0]))
        problems.extend(held.pop("off")[:5])
        ctx.say("reference", **held, tolerance_ulps=LOGIT_TOL_ULPS,
                tau=ROUTER_TIE_TAU, max_off_share=MAX_OFF_SHARE,
                far_tolerance_ulps=FAR_TOL_ULPS,
                max_far_share=MAX_FAR_SHARE)
        # the carry itself: the longest canary's states, read from its
        # slot behind its last token, against the reference's
        longest = canaries[-1]
        carry = call_all(replicas[:1], "bench_carry", longest,
                         seconds=1500)[0]
        expect(carry.pop("tokens") == before[0][-1],
               "the longest canary, asked alone again, answers other tokens")
        said = judge_carry(carry)
        problems.extend(said.pop("off"))
        ctx.say("carry", **said, max_carry_off=MAX_CARRY_OFF)
        # ... and the SHORTEST canary's, asked alone again on the slots
        # the canaries before it left: a state that did not start at zero
        # has decayed behind 8,192 tokens and stands whole behind 24
        shortest = canaries[0]
        carry = call_all(replicas[:1], "bench_carry", shortest,
                         seconds=600)[0]
        expect(carry.pop("tokens") == before[0][0],
               "the shortest canary, asked alone again, answers other "
               "tokens")
        said = judge_carry(carry, "shortest")
        problems.extend(said.pop("off"))
        ctx.say("carry_short", **said, max_carry_off=MAX_CARRY_OFF)
        for reading in asked_readings():
            # the other readings (a builder's run): what the reference
            # picks with that one thing changed, in the engine's
            # contexts, judged against the reference proper as a program
            # with that fault would be; and that reading's carry
            picks = [r["top_id"] for r in call_all(
                replicas[:1], "bench_reference", prompts, together[0],
                reading=reading, seconds=1500)[0]]
            proper = call_all(replicas[:1], "bench_reference", prompts,
                              together[0], picks=picks, seconds=1500)[0]
            said = judge(canaries, picks, proper)
            carry = call_all(replicas[:1], "bench_carry", longest,
                             answer=before[0][-1], reading=reading,
                             seconds=1500)[0]
            carry.pop("tokens")
            short = call_all(replicas[:1], "bench_carry", shortest,
                             answer=before[0][0], reading=reading,
                             seconds=600)[0]
            short.pop("tokens")
            ctx.say("reference_reading", reading=reading,
                    **{**said, "off": said["off"][:2]},
                    carry=judge_carry(carry),
                    carry_short=judge_carry(short, "shortest"))
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
    # tokens; nothing compiled since warm-up; every page given back
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
        expect(not any(s1["kv_pages_in_use"].values())
               and not s1["state_slots_in_use"],
               f"pages or state slots still held on an idle engine: "
               f"{s1['kv_pages_in_use']}, "
               f"{s1['state_slots_in_use']} slots")
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
