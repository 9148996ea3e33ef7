"""Deployment kind "serve_granite": `kinds/serve.py` for a model of the
hybrid state-space family (`ray_tpu/models/granite.py`) — the same entry
points

    ray_tpu.init -> Deployment(GraniteReplica, llm=True).bind(engine args)
    -> serve.run -> handle.stream from the open-loop client

with the engine's `model=` made of the configuration's published keys
(`model_type: granitemoehybrid` picks the family in
`ray_tpu.models.resolve`), the plain reference of `reference_granite.py`
(the recurrence token by token, float32), and `kinds/serve_laguna.py`'s
comparison in its FORM: the engine's tokens, teacher-forced through the
reference, each measured by how far under its own choice the reference
puts it (bfloat16 spacings at the size of its largest logit), and
`correct` decided by the SHARE of positions beyond `LOGIT_TOL_ULPS`, not
by the worst one.  Everything that is not the model's is imported from
`kinds/serve.py` and `kinds/serve_laguna.py`; this file restates `run`
and brings its own values.

**Why a share and not the worst position.**  Nothing routes here, so no
position is set aside (the reference returns a margin of 1 everywhere
and `tau` is 0) — but the model is 40 layers deep in bfloat16 with a
vocabulary of 100352, whose two largest logits lie a few spacings apart
at some positions of every run, and `kinds/serve.py`'s worst-position
limit fails about 3 % of seeds on a correct program at 12 layers
(PERF.md section 7).  A fault in the state, the scale or a slot moves
MOST positions far; a rounding moves a few by a little.

**What the canaries cover.**  The cell is for long answers over a state
that is carried, not re-read: a fault in the carry grows with every
token decoded.  So the canaries reach from less than one prefill chunk
to the mix's longest request, 2048 prompt tokens and 1024 decoded ones
through the state pool, 1440 judged positions in all, every prefill
width and decode table width the engine has.  They are asked TOGETHER
(several sequences a pass, lanes changing as the short ones end: what
the reference judges) and then IN TURN, each alone on the idle engine
(one packing whatever the clocks do): those tokens must come back the
same after the window, to the last id, from whatever state slots the
window's traffic left behind.

**Two limits**, each between two readings (PERF.md section 6, PR 40):
the largest share the program gave over its seeds on the chip, and what
the reference's other readings give against the reference proper
(`reference_granite.READINGS`: every matrix in float8_e4m3fn, the
nearest precision below the stated bfloat16; the attention scaled by
1/8; a chunk's padding left to decay the state; a slot's last owner's
state read by the next; the carry in bfloat16).  `MAX_OFF_SHARE` of the
positions may lie beyond `LOGIT_TOL_ULPS`: a fault everywhere (the
precision, the scale) moves most positions past it, a rounding a few.
`MAX_FAR_SHARE` may lie beyond `FAR_TOL_ULPS`: a fault in what a
sequence STARTS from (a stale slot, a decayed state) is forgotten as the
state decays, so it moves the first tokens behind a short prompt by tens
of spacings and the thousand behind a long one not at all — few
positions, each farther than any rounding goes.  A run is not `correct`
if it passes either.  `--sweep` runs print the readings as
`reference_reading` lines.
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
from benchmarks.kinds.serve_laguna import ask_in_turn, check_canaries
from benchmarks.replica_granite import GraniteReplica
from benchmarks.stats import percentile

# A tree without the model fails here, before any cluster starts.  (The
# check is of the file: importing `ray_tpu.models.granite` would import
# jax into this process, which must never hold the chip.)
_MODEL = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "ray_tpu", "models", "granite.py")
if not os.path.isfile(_MODEL):
    raise ImportError(f"this tree has no {_MODEL}: the program cannot "
                      f"run a model of the granitemoehybrid family")

# The share of positions that may lie beyond LOGIT_TOL_ULPS: between the
# program's largest over its seeds and the other readings' (the module's
# text; the numbers are in PERF.md section 6, PR 40).
MAX_OFF_SHARE = 0.05
# ... and the share that may lie beyond FAR_TOL_ULPS, where no rounding
# of the program reached on any seed
FAR_TOL_ULPS = 24.0
MAX_FAR_SHARE = 0.003
READINGS = ("float8_e4m3fn", "bfloat16_state", "scale_1_8", "decaying_pad",
            "stale_slot")
# (prompt tokens, tokens decoded): less than a chunk, one chunk, a few,
# past the 256- and 1024-column context buckets, and the mix's longest
# request; decode tables of 4, 16, 64 and 256 pages; answers from 16
# tokens to 1024 through the state pool.  1440 positions
CANARIES = ((24, 16), (64, 16), (150, 32), (330, 16), (700, 64),
            (1100, 16), (1500, 256), (2048, 1024))
# the configuration's keys the model is made of: every published key
# (`GraniteConfig.from_dict` reads what it knows and refuses, by name,
# the parts of the family it does not write)
MODEL_KEYS = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "shared_intermediate_size", "num_hidden_layers", "layer_types",
    "num_attention_heads", "num_key_value_heads", "attention_bias",
    "attention_multiplier", "embedding_multiplier", "residual_multiplier",
    "logits_scaling", "mamba_chunk_size", "mamba_conv_bias", "mamba_d_conv",
    "mamba_d_head", "mamba_d_state", "mamba_expand", "mamba_n_groups",
    "mamba_n_heads", "mamba_proj_bias", "max_position_embeddings",
    "normalization_function", "num_experts_per_tok", "num_local_experts",
    "position_embedding_type", "rms_norm_eps", "rope_scaling", "rope_theta",
    "tie_word_embeddings", "hidden_act")


def shares_beyond(canaries, answers, refs, tolerances=(2.0, 8.0, 32.0)):
    """The comparison's share at other tolerances than the two that
    decide, for the record of how far the readings lie apart."""
    return {f"{tol:g}": check_canaries(canaries, answers, refs, tau=0.0,
                                       tol_ulps=tol)["off_share"]
            for tol in tolerances}


def judge(canaries, answers, refs) -> Dict[str, Any]:
    """`check_canaries` under both limits (the module's text): its
    counts at `LOGIT_TOL_ULPS`, `far_share` beyond `FAR_TOL_ULPS`, the
    shares at other tolerances, and in `off` what either limit
    refuses."""
    held = check_canaries(canaries, answers, refs, tau=0.0,
                          max_off_share=MAX_OFF_SHARE)
    far = check_canaries(canaries, answers, refs, tau=0.0,
                         tol_ulps=FAR_TOL_ULPS, max_off_share=MAX_FAR_SHARE)
    held["far_share"] = far["off_share"]
    if far["off_share"] > MAX_FAR_SHARE:
        # in this file's words: the share is too small for whole per cents
        first = far["off"][0].partition("the first: ")[2]
        held["off"].append(
            f"{far['off_share']:.2%} of the judged positions (limit "
            f"{MAX_FAR_SHARE:.2%}) lie more than {FAR_TOL_ULPS:g} bfloat16 "
            f"spacings under the reference's choice, farther than a "
            f"rounding goes; the first: {first}")
    held["off_share_beyond"] = shares_beyond(canaries, answers, refs)
    return held


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """`LLMEngine(model=...)` for this configuration.  Refuses a file
    whose `layer_types` does not have one entry a layer."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError(f"layer_types has {len(cfg['layer_types'])} "
                         f"entries for {cfg['num_hidden_layers']} layers")
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
        GraniteReplica, NAME, num_replicas=n_rep,
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
        check(got["family"] == "granite",
              f"the engine runs {got['family']}")
        check(got["share"] is None, f"the engine holds {got['share']}: "
                                    f"this cell's model is whole")
        kinds = ["state" if t == "mamba" else "full"
                 for t in cfg["layer_types"]]
        check([layer[0] for layer in got["cache_spec"]] == kinds,
              f"the engine's cache is {got['cache_spec']}")
    ctx.say("replicas", ready_s=ready_s, n=n_rep,
            built_s=[s["built_s"] for s in
                     call_all(replicas, "bench_state")],
            param_bytes=rep0["param_bytes"],
            kv_pool_bytes=rep0["kv_pool_bytes"],
            state_pool_bytes=rep0["state_pool_bytes"],
            compiled_steps=[r["compiled_steps"] for r in reports],
            cache_hits=[r["compile_cache_hits"] for r in reports],
            cache_misses=[r["compile_cache_misses"] for r in reports],
            cache_dir=rep0["compile_cache_dir"],
            attention_impl=rep0["attention_impl"],
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
        # nothing routes: the margin is 1 at every position and none is
        # set aside
        held = judge(canaries, together[0], refs)
        held["moved_asked_alone"] = sum(
            a != b for a, b in zip(together[0], before[0]))
        problems.extend(held.pop("off")[:5])
        ctx.say("reference", **held, tolerance_ulps=LOGIT_TOL_ULPS,
                max_off_share=MAX_OFF_SHARE, far_tolerance_ulps=FAR_TOL_ULPS,
                max_far_share=MAX_FAR_SHARE)
        for reading in READINGS if ctx.sweep else ():
            # the other readings (a builder's run): what the reference
            # picks with that one thing changed, in the engine's
            # contexts, judged against the reference proper as a program
            # with that fault would be
            picks = [r["top_id"] for r in call_all(
                replicas[:1], "bench_reference", prompts, together[0],
                reading=reading, seconds=1500)[0]]
            proper = call_all(replicas[:1], "bench_reference", prompts,
                              together[0], picks=picks, seconds=1500)[0]
            said = judge(canaries, picks, proper)
            ctx.say("reference_reading", reading=reading,
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
