"""Deployment kind "serve_glm": `kinds/serve.py` for the latent-attention
family's SPARSE setting (`model_type: glm_moe_dsa`,
`ray_tpu/models/pangu.py`: an indexer a layer selects the rows the
attention reads; `ops/sparse_index.py`) — the same entry points

    ray_tpu.init -> Deployment(GlmReplica, llm=True).bind(engine args)
    -> serve.run -> handle.stream from the open-loop client

with the engine's `model=` made of the configuration's published keys
(`model_type` picks the family in `ray_tpu.models.resolve`), the plain
reference of `reference_glm.py`, and `kinds/serve_laguna.py`'s
comparison: its text says why routing makes the comparison one of SHARES
and why the canaries are asked together and then in turn.  The method,
`check_canaries`, `canary_requests` and `ask_in_turn` are imported from
there, everything that is not the model's from `kinds/serve.py`; this
file restates `run` and brings its own values.

What differs from `kinds/serve_pangu.py`.  TWO discontinuities: the
router's choice of 8 experts and the indexer's choice of 2,048 rows.  A
position is set aside where EITHER margin of the reference is under its
tau (`fold_margins`: both margins over their taus, the smaller of the
two against 1).  The canaries reach past `index_topk` rows into every
context bucket to the widest (30,000 tokens: 469 prefill passes asked
alone), so that every prefill program and every decode table width is
compared with the reference, and the short ones take the dense path.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from typing import Any, Dict, List

from ray_tpu import serve
from ray_tpu.serve.api import Deployment

from benchmarks import model_math_glm
from benchmarks.cluster import (bounded, check, wait_chips_free, wait_gone)
from benchmarks.kinds.serve import (CANARY_NEW, LOGIT_TOL_ULPS, NAME,
                                    ask_canaries, call_all, latency_ms,
                                    merge_traces, ms, one_window, sweep,
                                    wait_idle, window_polls)
from benchmarks.kinds.serve_laguna import (ask_in_turn, canary_requests,
                                           check_canaries)
from benchmarks.replica_glm import GlmReplica
from benchmarks.stats import percentile

# A tree without the model fails here, before any cluster starts.  (The
# check is of the FILE: importing `ray_tpu.ops.sparse_index` would import
# jax into this process, which must never hold the chip.)
_MODEL = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "ray_tpu", "ops", "sparse_index.py")
if not os.path.isfile(_MODEL):
    raise ImportError(f"this tree has no {_MODEL}: the program cannot "
                      f"run a model of the glm_moe_dsa family")

# The margins up to which a position is set aside — the router's (a gap
# of two sigma + b) and the selection's (a gap of two index scores) —
# and the share of judged positions that may lie beyond LOGIT_TOL_ULPS:
# `kinds/serve_laguna.py`'s method, this configuration's readings
# (PERF.md section 6, PR 42).
# Readings on the chip (128 positions a seed; PERF.md has the seeds): the
# program 0.010-0.087 of the judged positions beyond the tolerance over
# its first six seeds (about 4.6 positions of ~100 on average, so one
# run in fifty would read 0.10 or more); the reference with its matrices
# in float8 or with one mechanism wrong 0.276-0.671.  The limit lies
# between, at the geometric mean of the two nearest readings.  The
# selection's tau is 0: the reference's own margin between its 2,048-th
# and 2,049-th index score is 2e-7 to 3e-6 at the canaries' contexts,
# under what bfloat16 index queries and keys move a score by, so a tau
# that set such positions aside would set all aside; only exact ties are.
ROUTER_TIE_TAU = 0.0005
SELECT_TIE_TAU = 0.0
MAX_OFF_SHARE = 0.15
LOWER_PRECISION = "float8_e4m3fn"
# `reference_glm.VARIANTS`, restated: importing that module would bring
# jax into this process (a harness test holds the two equal)
MUTANTS = ("dense", "topk_half", "topk_double", "no_relu", "no_weights",
           "unrotated_keys", "no_bias")
# the dense path (one chunk; past the 256- and 1024-column buckets), and
# past index_topk = 2,048 rows into the 4096-, 16384- and 32768-column
# buckets; decode tables of 4 to 2,048 pages; 8 x 16 tokens = 128 positions
CANARY_LENGTHS = (24, 700, 1900, 2300, 4200, 9000, 17000, 30000)

# the configuration's keys the model is made of (`PanguConfig` reads
# them; `n_routed_experts` is the router's width there)
MODEL_KEYS = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rope_parameters", "rms_norm_eps", "max_position_embeddings",
              "first_k_dense_replace", "num_experts_per_tok",
              "norm_topk_prob", "routed_scaling_factor", "n_shared_experts",
              "index_n_heads", "index_head_dim", "index_topk",
              "rope_interleave", "indexer_rope_interleave", "topk_method",
              "n_group", "topk_group", "experts_held")


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """`LLMEngine(model=...)` for this configuration.  Refuses a file
    whose held experts are not the count it states, that asks for the
    extra prediction layer (a drafter: not part of the served forward),
    or whose router does not score by a sigmoid."""
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError(f"experts_held {cfg['experts_held']} is not the "
                         f"{cfg['n_routed_experts']} experts the file says "
                         f"are held")
    if cfg.get("num_nextn_predict_layers", 0):
        raise ValueError("num_nextn_predict_layers: the engine serves no "
                         "drafting layer")
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"scoring_func {cfg['scoring_func']!r}")
    return {**{k: cfg[k] for k in MODEL_KEYS},
            "n_routed_experts": cfg["num_experts_routed_over"]}


def fold_margins(refs: List[Dict[str, Any]], router_tau: float,
                 select_tau: float) -> List[Dict[str, Any]]:
    """`refs` with "margin" the smaller of (router margin / its tau,
    selection margin / its tau): `check_canaries(tau=1.0)` then sets a
    position aside where either margin is at or under its tau.  A tau of
    0 sets aside exact ties only."""
    def over(margin: float, tau: float) -> float:
        if tau > 0:
            return margin / tau
        return float("inf") if margin > 0 else 0.0

    return [{**ref, "margin": [
        min(over(r, router_tau), over(s, select_tau))
        for r, s in zip(ref["margin"], ref["select_margin"])]}
        for ref in refs]


def compare(canaries, picks, refs) -> Dict[str, Any]:
    """The comparison that decides `correct`, and every mutant's."""
    return check_canaries(
        canaries, picks, fold_margins(refs, ROUTER_TIE_TAU, SELECT_TIE_TAU),
        tau=1.0, max_off_share=MAX_OFF_SHARE)


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
        GlmReplica, NAME, num_replicas=n_rep,
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
        check(got["family"] == "pangu", f"the engine runs {got['family']}")
        check(got["index_topk"] == cfg["index_topk"],
              f"the engine selects {got['index_topk']} rows")
        check(got["share"] == {"experts_held": list(cfg["experts_held"]),
                               "num_experts": cfg["num_experts_routed_over"],
                               "vocab_rows": vocab},
              f"the engine holds {got['share']}")
        # a latent layer, paged as a full one: a row of 576 numbers and
        # beside it the indexer's key of 128
        row = ["full", 0, model_math_glm.latent_row_numbers(cfg),
               cfg["index_head_dim"]]
        check([list(layer) for layer in got["cache_spec"]]
              == [row] * cfg["num_hidden_layers"],
              f"the engine's cache is {got['cache_spec']}")
    ctx.say("replicas", ready_s=ready_s, n=n_rep,
            built_s=[s["built_s"] for s in
                     call_all(replicas, "bench_state")],
            param_bytes=rep0["param_bytes"],
            kv_pool_bytes=rep0["kv_pool_bytes"],
            latent_pool_bytes=rep0["latent_pool_bytes"],
            index_pool_bytes=rep0["index_pool_bytes"],
            compiled_steps=[r["compiled_steps"] for r in reports],
            cache_hits=[r["compile_cache_hits"] for r in reports],
            cache_misses=[r["compile_cache_misses"] for r in reports],
            cache_dir=rep0["compile_cache_dir"],
            attention_impl=rep0["attention_impl"],
            model=rep0["model"]["share"],
            cache_spec=rep0["model"]["cache_spec"],
            # this chip's share of a sample's assignments, a layer: as
            # the seed drew the experts, and as placed (replica_pangu.py)
            placement=call_all(replicas[:1], "bench_placement")[0])

    # ---- correctness sample, before: canaries on the idle engines, sent
    # together and then in turn (the module's text); the judged tokens
    # of those sent together against the plain reference on the engine's
    # own weights, teacher-forced with the engine's answer
    limit = int(cfg["max_position_embeddings"]) - CANARY_NEW
    canaries = canary_requests(ctx.seed, vocab, lengths=CANARY_LENGTHS,
                               limit=limit)
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
        prompts = [q["tokens"] for q in canaries]
        refs = call_all(replicas[:1], "bench_reference", prompts,
                        together[0], seconds=900)[0]
        held = compare(canaries, together[0], refs)
        held["moved_asked_alone"] = sum(
            a != b for a, b in zip(together[0], before[0]))
        problems.extend(held.pop("off")[:5])
        ctx.say("reference", **held, tolerance_ulps=LOGIT_TOL_ULPS,
                router_tau=ROUTER_TIE_TAU, select_tau=SELECT_TIE_TAU,
                max_off_share=MAX_OFF_SHARE,
                router_margins=sorted(
                    m for r in refs for m in r["margin"])[:8],
                select_margins=sorted(
                    m for r in refs for m in r["select_margin"])[:8])
        if ctx.sweep:
            # the second readings (a builder's run): what the reference
            # picks with its matrices in the nearest lower precision,
            # and with one mechanism wrong (MUTANTS), in
            # the engine's contexts, against the reference proper — on
            # the canaries to 9,000 tokens (96 positions): the two
            # longest are nine tenths of a reading's operations
            some, asked, said_by = canaries[:6], prompts[:6], together[0][:6]
            for how in [{"matrices": LOWER_PRECISION}] + [
                    {"variant": v} for v in MUTANTS]:
                wrong = call_all(replicas[:1], "bench_reference", asked,
                                 said_by, seconds=900, **how)[0]
                picks = [r["top_id"] for r in wrong]
                said = compare(some, picks, call_all(
                    replicas[:1], "bench_reference", asked, said_by,
                    picks=picks, seconds=900)[0])
                ctx.say("reference_mutant", **how,
                        **{**said, "off": said["off"][:1]})
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
