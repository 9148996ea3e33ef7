"""Deployment kind "serve_sdar": `kinds/serve.py` for SDAR-30B-A3B-Chat
(`model_type: sdar_moe`, the expert-layer family's third setting in
`ray_tpu/models/laguna.py`), which GENERATES BY DIFFUSION OVER BLOCKS —
the same entry points

    ray_tpu.init -> Deployment(SdarReplica, llm=True).bind(engine args)
    -> serve.run -> handle.stream from the open-loop client

with the engine's `model=` made of the configuration's published keys
and its `generation` group, the plain reference of `reference_sdar.py`,
and a comparison of PASSES, not of tokens.  Everything that is not the
model's — the poller, the tracer, the window, the sweep — is
`kinds/serve.py`'s own code, imported; `ask_in_turn`'s reason is
`kinds/serve_laguna.py`'s.

**What is compared.**  A canary is asked with its record
(`SdarReplica.generate_recorded`): for every block pass the block's
first position, the block as the pass read it and as it left it.  The
record is first held to the loop's SHAPE, exactly (`structure_faults`):
the first block opens at the prompt's last whole block with the prompt's
tail GIVEN and masks behind it; a pass changes masked positions only, at
least as many as the schedule asks, never to the mask's id; a block with
no mask left is read once more and left as it is (the commit) before the
next opens all masks, but for the answer's last block; the answer is
what the blocks hold, cut at `max_new`.  Then the reference is
teacher-forced with it (`reference_sdar.teacher_forced`): each
denoising pass's block state in the context of the program's own
committed blocks, a full forward under the block mask.  Of every
position a pass unmasked:

  - the TOKEN it put is the reference's argmax there, or lies within
    `LOGIT_TOL_ULPS` bfloat16 spacings of it (`kinds/serve.py`'s
    measure);
  - the POSITION is one the reference would have unmasked: its
    confidence is not more than `CONF_TIE_TAU` (in log c) below the best
    masked position the pass left masked; and a masked position whose
    confidence exceeds the threshold in the reference was unmasked;
  - positions whose router margin in the reference is at most
    `ROUTER_TIE_TAU` are set aside, as `serve_laguna.check_canaries`
    does and for its reason: there a correct bfloat16 program may route
    an expert differently.

A routing flip elsewhere in the block or in the context moves a logit by
an expert, not by a rounding, so the limit is on the SHARE of judged
positions that are off (`MAX_OFF_SHARE`), between its two readings: the
program's over its seeds, and the reference's own loop with its matrices
rounded to float8_e4m3fn.  `--sweep` runs with a number <= 0 print both
(`reference_mutant`: float8 and each of `reference_sdar.MUTANTS`, on the
eight shortest canaries of the run's seed and of -n further canary seeds),
and each has to come out not correct.  What the comparison cannot refuse
is in PERF.md section 7.
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
from benchmarks.kinds.serve import (LOGIT_TOL_ULPS, NAME, call_all,
                                    latency_ms, merge_traces, ms,
                                    one_window, sweep, ulps_below_top,
                                    wait_idle, window_polls)
from benchmarks.replica_sdar import SdarReplica
from benchmarks.stats import percentile

# A tree without the setting fails here, before any cluster starts.  (The
# check is of the FILE's text: importing `ray_tpu.models` would import
# jax into this process, which must never hold the chip.)
_FAMILIES = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "ray_tpu", "models", "__init__.py")
with open(_FAMILIES) as _f:
    if '"sdar_moe"' not in _f.read():
        raise ImportError(f"{_FAMILIES} names no family for model_type "
                          f"sdar_moe: the program cannot run this model")

# ISSUE 46's four (tails 1, 2, 1, 2; none, 2, 10 and 32 chunks), then
# twelve short ones (every tail, 0 to 3 chunks): 74 positions read a share
# of 0 to 0.17 on a sound program and 0.12 to 0.36 on the float8 loop, by
# the seed (PERF.md section 6, PR 46), and a short canary is a fourteenth
# of the long ones' reference time
CANARY_LENGTHS = (9, 130, 701, 2050,
                  5, 18, 27, 44, 62, 83, 99, 121, 147, 168, 203, 236)
CANARY_NEW = 18                        # cuts the last block
ROUTER_TIE_TAU = 0.003
CONF_TIE_TAU = 0.06
MAX_NEAR_TIE_SHARE = 0.5
MIN_JUDGED = 24
# between the program's largest reading over 42 seeds of canaries (0.076
# of 238 judged; mean 0.05) and the float8 loop's smallest over 16 (0.149
# of 114; mean 0.22): PERF.md section 6, PR 46
MAX_OFF_SHARE = 0.11
LOWER_PRECISION = "float8_e4m3fn"
MUTANT_CANARIES = 8    # the shortest: a mutant's loop is cache-free

# the configuration's keys the model is made of
MODEL_KEYS = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "max_position_embeddings",
              "rms_norm_eps", "num_experts", "num_experts_per_tok",
              "moe_intermediate_size", "norm_topk_prob", "rope_theta",
              "sliding_window", "generation")


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """`LLMEngine(model=...)` for this configuration.  Refuses a file
    that cuts an expert or gives layers a kind."""
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step") != 1:
        raise ValueError("every layer of this model is sparse")
    if cfg.get("use_sliding_window") or cfg.get("sliding_window"):
        raise ValueError("this model has no window")
    if "experts_held" in cfg:
        raise ValueError("this configuration holds every expert")
    return {k: cfg[k] for k in MODEL_KEYS}


def canary_requests(seed: int, vocab: int, mask: int,
                    lengths=CANARY_LENGTHS, limit: int = 0
                    ) -> List[Dict[str, Any]]:
    """Seeded prompts of these lengths with distinct first tokens (see
    generators/open_loop.py), none holding the mask's id; `limit` > 0
    cuts each to what a toy engine's context holds."""
    rnd = random.Random(f"canary-{seed}")
    if limit:
        lengths = [min(n, limit) for n in lengths]
    ids = [t for t in range(1, vocab) if t != mask] if vocab < 4096 else None

    def draw():
        if ids is not None:
            return rnd.choice(ids)
        while True:
            t = rnd.randrange(1, vocab)
            if t != mask:
                return t

    firsts: List[int] = []
    while len(firsts) < len(lengths):
        t = draw()
        if t not in firsts:
            firsts.append(t)
    return [{"tokens": [first] + [draw() for _ in range(n - 1)],
             "max_new_tokens": CANARY_NEW}
            for first, n in zip(firsts, lengths)]


def without_mask(plan: Dict[str, Any], mask: int, vocab: int) -> None:
    """The generator draws ids from the whole vocabulary: put another id
    where it drew the mask's (a first token stays distinct)."""
    firsts = {r["tokens"][0] for r in plan["requests"]}
    spare = next(t for t in range(vocab - 1, 0, -1)
                 if t != mask and t not in firsts)
    for r in plan["requests"]:
        toks = r["tokens"]
        if mask in toks:
            r["tokens"] = [spare if i == 0 else mask - 1 if t == mask else t
                           for i, t in enumerate(toks)] \
                if toks[0] == mask else \
                [mask - 1 if t == mask else t for t in toks]


def ask_recorded(replicas, requests, together: bool):
    """Every canary answered by every replica WITH its record, asked
    directly on an engine that serves nothing else: all at once (several
    sequences a pass, lanes out of phase), or in turn, the next sent when
    the last has ended (`serve_laguna.ask_in_turn`'s reason)."""
    if together:
        refs = [[r.handle_request.remote("generate_recorded", (q,), {})
                 for q in requests] for r in replicas]
        return [[get(ref, "a canary", 300) for ref in row] for row in refs]
    return [[get(r.handle_request.remote("generate_recorded", (q,), {}),
                 "a canary asked alone", 300) for q in requests]
            for r in replicas]


def structure_faults(request: Dict[str, Any], record: Dict[str, Any],
                     gen: Dict[str, Any]) -> List[str]:
    """What of the loop's shape (the module's text) a record breaks."""
    b, mask = int(gen["block_length"]), int(gen["mask_token_id"])
    steps = int(gen["denoising_steps"])
    sched = [b // steps + (k < b % steps) for k in range(steps)]
    prompt, new = list(request["tokens"]), int(request["max_new_tokens"])
    tokens, passes = list(record["tokens"]), record["passes"]
    total = len(prompt) + new
    faults: List[str] = []
    if len(tokens) != new or mask in tokens:
        faults.append(f"{len(tokens)} tokens for {new} asked, or a mask "
                      f"among them")
    p0 = len(prompt) // b * b
    tail = prompt[p0:]
    want, k, blocks = tail + [mask] * (b - len(tail)), 0, []
    for i, (at, before, after) in enumerate(passes):
        where = f"pass {i} (block at {at})"
        if at != p0 or list(before) != want:
            faults.append(f"{where} read {list(before)} at {at}; the loop "
                          f"reads {want} at {p0}")
            break
        masked = [t for t, x in enumerate(before) if x == mask]
        moved = [t for t in masked if after[t] != mask]
        if any(x != y for t, (x, y) in enumerate(zip(before, after))
               if t not in masked):
            faults.append(f"{where} changed a position that was not masked")
        if not masked:
            # the commit: the block's rows stand, the next block opens
            if list(after) != list(before):
                faults.append(f"{where}: a commit changed the block")
            p0, want, k = p0 + b, [mask] * b, 0
            continue
        need = min(sched[k] if k < len(sched) else b, len(masked))
        if len(moved) < need:
            faults.append(f"{where} unmasked {len(moved)} positions; the "
                          f"schedule asks {need}")
        want, k = list(after), k + 1
        if mask not in after:
            blocks.append((at, list(after)))
            if at + b >= total:
                # the answer's last block: no commit, nothing behind it
                if i != len(passes) - 1:
                    faults.append(f"passes behind the last block's end")
                break
    answer = [t for at, blk in blocks for t in blk][len(tail):][:new]
    if not faults and answer != tokens:
        faults.append("the answer is not what the record's blocks hold")
    return faults


def check_canaries(canaries, records, refs, gen,
                   tau: float = ROUTER_TIE_TAU, tau_c: float = CONF_TIE_TAU,
                   tol_ulps: float = LOGIT_TOL_ULPS,
                   max_off_share: float = MAX_OFF_SHARE,
                   min_judged: int = MIN_JUDGED) -> Dict[str, Any]:
    """The module's comparison.  Returns the counts (positions unmasked,
    judged, near ties of the router set aside, tokens that were not the
    reference's argmax, judged positions off by their token, by their
    position), the worst distance in bfloat16 spacings among the judged
    and among those set aside, the smallest confidence margin, every
    position's numbers (`rows`: distance, router margin, confidence
    margin), and what is wrong (`off`)."""
    off, beyond, rows = [], [], []
    n = judged = not_argmax = off_token = off_position = 0
    worst = worst_tie = 0.0
    least_conf = float("inf")
    for q, rec, ref in zip(canaries, records, refs):
        off.extend(f"canary of {len(q['tokens'])} tokens: {fault}"
                   for fault in structure_faults(q, rec, gen))
        denoising = [p for p in rec["passes"]
                     if int(gen["mask_token_id"]) in p[1]]
        for (p0, before, after), row in zip(denoising, ref):
            missed = [t for t in row["over"] if t not in row["moved"]]
            for j, t in enumerate(row["moved"]):
                n += 1
                tok = after[t]
                d = 0.0 if tok == row["top_id"][j] else \
                    ulps_below_top(row["top"][j], row["picked"][j])
                conf = row["conf_margin"][j]
                rows.append([d, row["margin"][j],
                             conf if conf != float("inf") else None])
                if row["margin"][j] <= tau:
                    worst_tie = max(worst_tie, d)
                    continue
                judged += 1
                not_argmax += tok != row["top_id"][j]
                worst = max(worst, d)
                least_conf = min(least_conf, conf)
                bad = []
                if d > tol_ulps:
                    off_token += 1
                    bad.append(f"put {tok}, which the reference puts "
                               f"{d:.1f} bfloat16 spacings under its own "
                               f"{row['top_id'][j]}")
                if conf < -tau_c or missed:
                    off_position += 1
                    bad.append(f"is {-conf:.3f} in log c under a position "
                               f"the pass left masked" if conf < -tau_c else
                               f"left {missed} masked, over the threshold")
                if bad:
                    beyond.append(f"canary of {len(q['tokens'])} tokens, "
                                  f"block at {p0}, position {t} (router "
                                  f"margin {row['margin'][j]:.4f}): "
                                  + "; ".join(bad))
    share = 1.0 - judged / n if n else 1.0
    off_share = len(beyond) / judged if judged else 1.0
    if off_share > max_off_share:
        off.append(f"{len(beyond)} of {judged} judged positions "
                   f"({off_share:.0%}, limit {max_off_share:.0%}) are off; "
                   f"the first: {beyond[0]}")
    if share > MAX_NEAR_TIE_SHARE:
        off.append(f"{share:.0%} of the positions are near ties of the "
                   f"router (margin <= {tau}): more than half set aside")
    if judged < min_judged:
        off.append(f"only {judged} positions judged; {min_judged} needed")
    return {"positions": n, "judged": judged, "near_tie_share": share,
            "not_argmax": not_argmax, "off_share": off_share,
            "off_token": off_token, "off_position": off_position,
            "worst_ulps": worst, "worst_ulps_near_ties": worst_tie,
            "least_conf_margin": least_conf if judged else None,
            "rows": rows, "off": off}


def mutant_readings(ctx, replicas, canaries, gen, canary_seed: int) -> None:
    """A builder's run: what the comparison says of the reference's own
    loop with its matrices in the nearest lower precision, and with each
    mechanism done wrong, on the shorter canaries (and of the loop
    proper: the control, which has to come out correct)."""
    short = sorted(canaries, key=lambda q: len(q["tokens"]))[
        :MUTANT_CANARIES]
    prompts = [q["tokens"] for q in short]
    # the names from the replica: the reference module imports jax, which
    # this process must never hold
    cases = [(None, None), (LOWER_PRECISION, None)] + [
        (None, m) for m in call_all(replicas[:1], "bench_mutants")[0]]
    for matrices, mutant in cases:
        records = call_all(replicas[:1], "bench_mutant", prompts,
                           CANARY_NEW, matrices=matrices, mutant=mutant,
                           seconds=900)[0]
        refs = call_all(replicas[:1], "bench_reference", prompts, records,
                        seconds=900)[0]
        said = check_canaries(short, records, refs, gen, min_judged=MIN_JUDGED)
        said.pop("rows")
        ctx.say("reference_mutant", canary_seed=canary_seed,
                matrices=matrices, mutant=mutant,
                **{**said, "off": said["off"][:2]})


def run(ctx) -> Dict[str, Any]:
    cfg, traffic = ctx.config, ctx.traffic
    dep = cfg["deployment"]
    n_rep = int(dep.get("replicas", 1))
    check(n_rep == ctx.cell["chips"],
          f"{n_rep} one-chip replica(s) in a cell of {ctx.cell['chips']} "
          f"chip(s)")
    model = model_kwargs(cfg)
    vocab, gen = int(cfg["vocab_size"]), cfg["generation"]
    mask = int(gen["mask_token_id"])
    engine_kwargs = dict(dep.get("engine", {}), model=model, seed=ctx.seed,
                         sizes=cfg)
    t_run = time.monotonic()
    app = Deployment(
        SdarReplica, NAME, num_replicas=n_rep,
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
        check(got["share"] == {"experts_held": [0, cfg["num_experts"]],
                               "num_experts": cfg["num_experts"],
                               "vocab_rows": vocab},
              f"the engine holds {got['share']}")
        check([layer[0] for layer in got["cache_spec"]]
              == ["full"] * cfg["num_hidden_layers"],
              f"the engine's cache is {got['cache_spec']}")
        check((got["qk_norm"], got["gated"], got["block_length"],
               got["denoising_steps"], got["mask_token_id"],
               got["confidence_threshold"],
               got["shared_expert_intermediate_size"]) == (
            True, False, gen["block_length"], gen["denoising_steps"], mask,
            gen["confidence_threshold"], 0),
            f"the engine's setting is {got}")
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
            cache_spec=rep0["model"]["cache_spec"],
            routers_balanced=call_all(replicas, "bench_balance")[0])

    # ---- correctness sample, before: canaries on the idle engines WITH
    # their records, sent together and then in turn; the passes of those
    # sent together against the plain reference on the engine's own
    # weights, teacher-forced with the engine's own blocks
    limit = int(cfg["max_position_embeddings"]) - CANARY_NEW - 4
    canaries = canary_requests(ctx.seed, vocab, mask, limit=limit)
    prompts = [q["tokens"] for q in canaries]
    together = ask_recorded(replicas, canaries, together=True)
    wait_idle(replicas)
    before = [[rec["tokens"] for rec in row]
              for row in ask_recorded(replicas, canaries, together=False)]
    expect(all(len(toks) == CANARY_NEW for toks in before[0]),
           f"a canary answered other than {CANARY_NEW} tokens")
    for row in before[1:]:
        expect(row == before[0], "replicas of one seed answer a canary "
                                 "differently")
    if not problems:
        refs = call_all(replicas[:1], "bench_reference", prompts,
                        together[0], seconds=900)[0]
        held = check_canaries(canaries, together[0], refs, gen)
        held["moved_asked_alone"] = sum(
            a["tokens"] != b for a, b in zip(together[0], before[0]))
        with open(os.path.join(ctx.out_dir, "reference_rows.json"),
                  "w") as f:
            json.dump(held.pop("rows"), f)
        problems.extend(held.pop("off")[:5])
        ctx.say("reference", **held, tolerance_ulps=LOGIT_TOL_ULPS,
                tau=ROUTER_TIE_TAU, tau_conf=CONF_TIE_TAU,
                max_off_share=MAX_OFF_SHARE)
        readings = [int(-x) for x in ctx.sweep if x <= 0]
        if readings:
            # a builder's run: the mutants' readings, and the program's
            # and the mutants' on further canary seeds of these weights
            mutant_readings(ctx, replicas, canaries, gen, ctx.seed)
            for extra in range(1, readings[0] + 1):
                more = canary_requests(ctx.seed + extra, vocab, mask,
                                       limit=limit)
                recs = ask_recorded(replicas[:1], more, together=True)[0]
                said = check_canaries(
                    more, recs, call_all(
                        replicas[:1], "bench_reference",
                        [q["tokens"] for q in more], recs,
                        seconds=900)[0], gen)
                with open(os.path.join(
                        ctx.out_dir, f"reference_rows_{extra}.json"),
                        "w") as f:
                    json.dump(said.pop("rows"), f)
                ctx.say("reference_more", canary_seed=ctx.seed + extra,
                        **{**said, "off": said["off"][:2]})
                mutant_readings(ctx, replicas, more, gen, ctx.seed + extra)
    compiles0 = [s["backend_compiles"]
                 for s in call_all(replicas, "bench_state")]

    generate = ctx.spec.generator(traffic["generator"])
    outcome: Dict[str, Any] = {}
    if ctx.sweep:
        ctx.sweep = [x for x in ctx.sweep if x > 0]

        def masked_out(*args):
            plan = generate(*args)
            without_mask(plan, mask, vocab)
            return plan

        sweep(ctx, handle, replicas, masked_out, traffic, vocab)
        outcome["sweep_only"] = True
    else:
        plan = generate(traffic, ctx.seed, ctx.seconds, vocab)
        without_mask(plan, mask, vocab)
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
    # tokens; nothing compiled since warm-up; every page back
    after = [[rec["tokens"] for rec in row]
             for row in ask_recorded(replicas, canaries, together=False)]
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
