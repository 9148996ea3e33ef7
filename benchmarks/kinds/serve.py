"""Deployment kind "serve": LLM replicas behind one Serve handle.

    ray_tpu.init -> Deployment(BenchReplica, llm=True).bind(engine args)
    -> serve.run -> handle.stream from the open-loop client

which is `serve.llm_deployment`'s own construction with the replica
target of `benchmarks/replica.py` in place of the one it subclasses.
Each replica leases one chip (`TPU: 1`) and is one process; this process
never imports jax.

The configuration's `deployment` group gives `replicas`,
`max_ongoing_requests` and the engine's arguments; the traffic mix gives
the generator, the rate, the lead-in and how the window ends.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import threading
import time
from typing import Any, Dict, List

from ray_tpu import serve
from ray_tpu.serve.api import Deployment

from benchmarks import client, model_math
from benchmarks.cluster import (bounded, check, get, wait_chips_free,
                                wait_gone)
from benchmarks.replica import BenchReplica
from benchmarks.stats import mean, percentile

NAME = "bench-llm"
# How far a token the engine picked may sit from the plain reference's
# argmax: the reference's logit of that token lies within this many
# bfloat16 spacings of the reference's largest (the lm_head's output is
# bfloat16; PERF.md, PR 22 run E measured at most 1 spacing between two
# correct bfloat16 programs and set 4; what PR 24's runs measured against
# the float32 reference is in PERF.md section 6).  Every token of every
# canary is held to it: the first comes from chunked prefill, the other
# 15 from the paged decode kernel reading the KV pages.
LOGIT_TOL_ULPS = 4.0
CANARY_LENGTHS = (24, 150, 80, 200)   # two past one 64-token prefill chunk
CANARY_NEW = 16
POLL_S = 0.5


def bf16_ulp(x: float) -> float:
    """The spacing of bfloat16 (8 significant bits) at the size of x."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -100))) - 7)


def ulps_below_top(top: float, picked: float) -> float:
    """How many bfloat16 spacings (at the size of the reference's largest
    logit) the reference puts the picked token under its own choice."""
    return (top - picked) / bf16_ulp(top)


def check_canaries(canaries, answers, refs) -> Dict[str, Any]:
    """Every generated token of every canary against the teacher-forced
    reference (`reference.teacher_forced`).  Returns the count of
    positions, how many picks were not the reference's argmax, the worst
    distance in spacings, and the positions beyond LOGIT_TOL_ULPS."""
    off, worst, not_argmax, n = [], 0.0, 0, 0
    for q, toks, ref in zip(canaries, answers, refs):
        for j, tok in enumerate(toks):
            n += 1
            if tok == ref["top_id"][j]:
                continue
            not_argmax += 1
            d = ulps_below_top(ref["top"][j], ref["picked"][j])
            worst = max(worst, d)
            if d > LOGIT_TOL_ULPS:
                off.append(f"canary of {len(q['tokens'])} tokens, token "
                           f"{j}: the engine picked {tok}, which the "
                           f"reference puts {d:.1f} bfloat16 spacings "
                           f"under its own {ref['top_id'][j]}")
    return {"positions": n, "not_argmax": not_argmax, "worst_ulps": worst,
            "off": off}


def call_all(replicas, method: str, *args, seconds: float = 300.0,
             **kwargs) -> List[Any]:
    refs = [r.handle_request.remote(method, args, kwargs) for r in replicas]
    return [get(ref, f"{NAME} {method}", seconds) for ref in refs]


def canary_requests(seed: int, vocab: int) -> List[Dict[str, Any]]:
    import random

    rnd = random.Random(f"canary-{seed}")
    # distinct first tokens: see generators/open_loop.py
    firsts = rnd.sample(range(1, vocab), len(CANARY_LENGTHS))
    return [{"tokens": [first] + [rnd.randrange(1, vocab)
                                  for _ in range(n - 1)],
             "max_new_tokens": CANARY_NEW}
            for first, n in zip(firsts, CANARY_LENGTHS)]


def ask_canaries(replicas, requests) -> List[List[List[int]]]:
    """Every canary answered by every replica, asked directly (not
    through the router) on an engine that serves nothing else."""
    refs = [[r.handle_request.remote("generate", (q,), {}) for q in requests]
            for r in replicas]
    return [[get(ref, "a canary", 300)["tokens"] for ref in row]
            for row in refs]


class Poller:
    """`bench_state` of every replica every POLL_S seconds, on a thread."""

    def __init__(self, replicas):
        self.replicas = replicas
        self.rows: List[List[Dict[str, Any]]] = [[] for _ in replicas]
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-poll")

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                states = call_all(self.replicas, "bench_state", seconds=30)
            except Exception:  # a poll lost is a sample lost
                states = []
            for rows, s in zip(self.rows, states):
                rows.append(s)
            self._stop.wait(POLL_S)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *_exc) -> None:
        self._stop.set()
        self._thread.join(60)


def tracer(ctx, replicas, traffic):
    """`on_window` of the client for a traced run: a few seconds of the
    window, a fifth of the way in."""
    trace_s = float(traffic.get("trace_s", 4.0))

    def on_window(w0: float, w1: float) -> None:
        span = min(trace_s, 0.5 * (w1 - w0))
        time.sleep(max(0.0, w0 + 0.2 * (w1 - w0) - time.monotonic()))
        refs = [r.handle_request.remote(
            "profile_start", (os.path.join(ctx.out_dir, f"trace-r{i}"),), {})
            for i, r in enumerate(replicas)]
        [get(ref, "profile_start", 60) for ref in refs]
        time.sleep(span)
        call_all(replicas, "profile_stop", seconds=120)

    return on_window


def merge_traces(parts: List[Dict[str, Any]]) -> Dict[str, Any]:
    """One reduction of the replicas' traces: a chip each, so means."""
    parts = [p for p in parts if p.get("busy_s")]
    if not parts:
        return {}
    n = len(parts)

    def merged(key):
        acc: Dict[str, float] = {}
        for p in parts:
            for name, secs in p[key]:
                acc[name] = acc.get(name, 0.0) + secs / n
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:10]]

    ops: Dict[str, float] = {}
    for p in parts:
        for k, v in p["op_seconds"].items():
            ops[k] = ops.get(k, 0.0) + v / n
    return {"devices": n, "busy_s": sum(p["busy_s"] for p in parts) / n,
            "window_s": sum(p["window_s"] for p in parts) / n,
            "device_ops": merged("device_ops"),
            "idle_gaps": merged("idle_gaps"), "op_seconds": ops,
            "trace_bytes": sum(p.get("trace_bytes", 0) for p in parts)}


def wait_idle(replicas, seconds: float = 120.0) -> None:
    deadline = time.monotonic() + seconds
    while True:
        states = call_all(replicas, "bench_state", seconds=30)
        if all(s["active"] == 0 and s["queued"] == 0 for s in states):
            return
        check(time.monotonic() < deadline,
              f"the engines did not go idle within {seconds:.0f}s")
        time.sleep(0.2)


def ms(x):
    return None if x is None else 1000.0 * x


def latency_ms(summary, qs=(50, 95)) -> Dict[str, Any]:
    """`ttft_p<q>_ms` and `tpot_p<q>_ms` of a window's samples."""
    return {f"{name}_p{q}_ms": ms(percentile(summary[f"{name}_s"], q))
            for name in ("ttft", "tpot") for q in qs}


def one_window(ctx, handle, replicas, plan, traffic, vocab, trace: bool):
    """The lead-in and the window of one plan: the client's records, the
    engine's polls, and the summary both are reduced to."""
    import ray_tpu

    def stream_fn(request):
        for ref in handle.stream(request):
            yield ray_tpu.get(ref, timeout=300)

    def cancel_fn(rids):
        return call_all(replicas, "bench_cancel", rids, seconds=60)

    end = traffic.get("end", "drain")
    with Poller(replicas) as poller:
        run = client.run_open_loop(
            plan, stream_fn, end=end,
            drain_s=float(traffic.get("drain_s", 10.0)), cancel_fn=cancel_fn,
            on_window=tracer(ctx, replicas, traffic) if trace else None)
        time.sleep(POLL_S)   # one poll past the end
    summary = client.summarize(run, vocab, end)
    return run, poller.rows, summary


def window_polls(rows: List[Dict[str, Any]], w0_epoch: float,
                 window_s: float) -> List[Dict[str, Any]]:
    return [s for s in rows if w0_epoch <= s["t"] <= w0_epoch + window_s]


def sweep(ctx, handle, replicas, generate, traffic, vocab) -> None:
    """`--sweep`: a lead-in and a window at each rate on one bring-up, a
    `bench sweep` line for each (how the knee was found: README.md)."""
    for rate in ctx.sweep:
        scale = rate / float(traffic["rate_rps"])
        plan = generate(traffic, ctx.seed, ctx.seconds, vocab, scale)
        for req in plan["requests"]:   # the engine replays a known id
            req["rid"] = f"{rate:g}rps-{req['rid']}"
        run, polls, s = one_window(ctx, handle, replicas, plan, traffic,
                                   vocab, trace=False)
        inside = [window_polls(r, run["w0_epoch"], s["window_s"])
                  for r in polls]
        queued = [sum(p["queued"] for p in at) for at in zip(*inside)]
        quarter = max(1, len(queued) // 4)
        ctx.say("sweep", rate_rps=rate, attempted=s["attempted"],
                failed=s["failed"], finished=s["finished"],
                open_at_end=s["open_at_end"], **latency_ms(s),
                tokens_per_s=s["tokens_in_window"] / s["window_s"],
                queued_first_quarter=mean(queued[:quarter]),
                queued_last_quarter=mean(queued[-quarter:]),
                queued_max=max(queued, default=0),
                active_mean=mean([sum(p["active"] for p in at)
                                  for at in zip(*inside)]))
        wait_idle(replicas)


def run(ctx) -> Dict[str, Any]:
    cfg, traffic = ctx.config, ctx.traffic
    dep = cfg["deployment"]
    n_rep = int(dep.get("replicas", 1))
    check(n_rep == ctx.cell["chips"],
          f"{n_rep} one-chip replica(s) in a cell of {ctx.cell['chips']} "
          f"chip(s)")
    widths = model_math.llama_kwargs(cfg)
    vocab = int(cfg["vocab_size"])
    engine_kwargs = dict(dep.get("engine", {}), model=widths, seed=ctx.seed)
    t_run = time.monotonic()
    app = Deployment(
        BenchReplica, NAME, num_replicas=n_rep,
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
        got = {k: rep["model"][k] for k in widths}
        check(got == widths, f"the engine runs {got}, not {widths}")
    ctx.say("replicas", ready_s=ready_s, n=n_rep,
            built_s=[s["built_s"] for s in
                     call_all(replicas, "bench_state")],
            param_bytes=rep0["param_bytes"],
            kv_pool_bytes=rep0["kv_pool_bytes"],
            compiled_steps=[r["compiled_steps"] for r in reports],
            cache_hits=[r["compile_cache_hits"] for r in reports],
            cache_misses=[r["compile_cache_misses"] for r in reports],
            cache_dir=rep0["compile_cache_dir"],
            attention_impl=rep0["attention_impl"])

    # ---- correctness sample, before: canaries on the idle engines, and
    # every token of them against the plain reference on the engine's own
    # weights, teacher-forced with the engine's answer
    canaries = canary_requests(ctx.seed, vocab)
    before = ask_canaries(replicas, canaries)
    expect(all(len(toks) == CANARY_NEW for toks in before[0]),
           f"a canary answered other than {CANARY_NEW} tokens")
    for row in before[1:]:
        expect(row == before[0], "replicas of one seed answer a canary "
                                 "differently")
    if not problems:
        refs = call_all(replicas[:1], "bench_reference",
                        [q["tokens"] for q in canaries], before[0])[0]
        held = check_canaries(canaries, before[0], refs)
        problems.extend(held.pop("off")[:5])
        ctx.say("reference", **held, tolerance_ulps=LOGIT_TOL_ULPS)
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
        traces = (merge_traces(call_all(
            replicas, "profile_reduce", seconds=300,
            unattributed="engine host, unattributed"))
            if ctx.trace else {})
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

    # ---- correctness sample, after: the same canaries, the same tokens;
    # and nothing compiled since warm-up
    after = ask_canaries(replicas, canaries)
    expect(after == before, "a canary's tokens changed over the window "
                            "(a recycled or mis-shared page)")
    states = call_all(replicas, "bench_state")
    reports1 = call_all(replicas, "device_report")
    for r0, r1, c0, s1 in zip(reports, reports1, compiles0, states):
        expect(r1["compiled_steps"] == r0["compiled_steps"]
               and s1["backend_compiles"] == c0,
               f"compiles after warm-up: compiled_steps "
               f"{r0['compiled_steps']} -> {r1['compiled_steps']}, backend "
               f"compiles {c0} -> {s1['backend_compiles']}")
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
