"""Deployment kind "train": one Train worker that holds the cell's chips.

    ray_tpu.init -> JaxTrainer(train_loop, ScalingConfig(num_workers=1,
    use_tpu=True)).fit() -> build_llama_train_state -> step_fn

`train_loop` runs in the worker, which is the only process that imports
jax; it is this file's, so it times its own steps, starts and stops the
profiler itself and wraps its phases in `TraceAnnotation`.  Every step
takes a fresh batch made on the host from the seed and placed inside the
timed loop: there is no Data -> device iterator yet, and a resident batch
would hide what a host-fed input costs.

The configuration's `deployment` group gives `mesh`, `learning_rate`,
`remat` and `loss_tol` — how far the first step's loss (bfloat16
activations, float32 parameters and loss) may lie from the plain
reference's float32 loss on the same weights and batch, as a share of the
reference; the configuration's file says where its number comes from.
The traffic mix gives the batch's shape and the file whose
`batch_for_step` makes the batches.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from typing import Any, Dict

from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

from benchmarks import model_math
from benchmarks.cluster import bounded, check, wait_chips_free, wait_gone

WARMUP_STEPS = 3
TRACE_PREFIX = "bench:"


def train_loop(config: Dict[str, Any]) -> Dict[str, Any]:
    """Runs in the Train worker.  jax is imported here, never in the
    parent."""
    first_line_epoch = time.time()
    import dataclasses

    import jax

    from benchmarks import reference, spec, trace_reduce
    from benchmarks.stallwatch import StallWatch
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.ops import count_compile_cache_events, device_report
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train.gspmd import build_llama_train_state, param_count

    compiles = [0]

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    count_compile_cache_events()
    watch = StallWatch()
    batch_for_step = spec._load_module(config["generator_file"],
                                       "batch_for_step")
    plan = config["plan"]
    cfg = dataclasses.replace(LlamaConfig(**config["widths"]),
                              remat=bool(config["remat"]))
    devices = jax.devices()[:config["chips"]]
    mesh = make_mesh(MeshSpec(**config["mesh"]), devices=devices)
    t0 = time.monotonic()
    params, opt, step_fn, _ = build_llama_train_state(
        cfg, mesh, rng_seed=config["seed"],
        learning_rate=config["learning_rate"],
        batch_size=plan["batch"], seq_len=plan["seq_len"])
    jax.block_until_ready((params, opt))
    init_s = time.monotonic() - t0
    n_params = param_count(params)

    # ---- correctness sample: the plain reference's loss on the initial
    # weights and the first batch (the step donates them, so before it)
    t0 = time.monotonic()
    ref_loss = reference.next_token_loss(
        params, batch_for_step(plan, 0), n_layers=cfg.n_layers,
        theta=cfg.rope_theta, eps=cfg.norm_eps)
    reference_s = time.monotonic() - t0
    warm_losses = []
    t0 = time.monotonic()
    for i in range(WARMUP_STEPS):
        params, opt, loss = step_fn(params, opt, batch_for_step(plan, i))
        warm_losses.append(float(loss))
    warmup_s = time.monotonic() - t0
    compiles0 = compiles[0]

    # ---- the window
    ann = jax.profiler.TraceAnnotation
    trace = config["trace"]
    trace_at = 0.2 * plan["window_s"]
    trace_for = min(float(config["trace_s"]), 0.5 * plan["window_s"])
    tracing, traced = False, False
    steps = []      # (seconds, True where the profiler started or stopped)
    losses = []
    w0_epoch = time.time()
    w0 = prev = time.monotonic()
    i = WARMUP_STEPS
    while True:
        flagged = False
        if trace and not traced and not tracing and prev - w0 >= trace_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            os.makedirs(trace["dir"], exist_ok=True)
            jax.profiler.start_trace(trace["dir"], profiler_options=opts)
            tracing, flagged, trace_t0 = True, True, time.monotonic()
        with ann(TRACE_PREFIX + "make_batch"):
            tokens = batch_for_step(plan, i)
        with ann(TRACE_PREFIX + "dispatch"):
            params, opt, loss = step_fn(params, opt, tokens)
        with ann(TRACE_PREFIX + "sync"):
            jax.block_until_ready(loss)
        if tracing and time.monotonic() - trace_t0 >= trace_for:
            jax.profiler.stop_trace()
            tracing, traced, flagged = False, True, True
        now = time.monotonic()
        steps.append((now - prev, flagged))
        losses.append(loss)
        prev = now
        i += 1
        if now - w0 >= plan["window_s"]:
            break
    window_s = prev - w0
    if tracing:
        jax.profiler.stop_trace()
    compiles1 = compiles[0]
    losses = [float(x) for x in losses]
    reduced = {}
    if trace:
        path = trace_reduce.find_xplane(trace["dir"])
        if path is not None:
            reduced = trace_reduce.reduce(
                path, prefer=TRACE_PREFIX,
                unattributed="train worker host, unattributed")
            reduced["trace_bytes"] = os.path.getsize(path)
    rep = device_report()
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    rep.update(first_line_epoch=first_line_epoch, w0_epoch=w0_epoch,
               window_s=window_s, steps=steps, n_params=n_params,
               init_s=init_s, reference_s=reference_s, warmup_s=warmup_s,
               ref_loss=ref_loss, warm_losses=warm_losses,
               first_loss=losses[0], last_loss=losses[-1],
               finite=all(math.isfinite(x) for x in warm_losses + losses),
               compiles_before=compiles0, compiles_after=compiles1,
               devices_used=len(devices), memory_peak_bytes=max(peaks),
               trace=reduced, stalls=watch.stop())
    return rep


def run(ctx) -> Dict[str, Any]:
    cfg, traffic = ctx.config, ctx.traffic
    dep = cfg["deployment"]
    chips = int(ctx.cell["chips"])
    widths = model_math.llama_kwargs(cfg)
    plan = ctx.spec.generator(traffic["generator"])(
        traffic, ctx.seed, ctx.seconds, int(cfg["vocab_size"]))
    tokens_per_step = plan["batch"] * plan["seq_len"]
    loop_config = {
        "widths": widths, "remat": dep.get("remat", True),
        "mesh": dep["mesh"], "learning_rate": dep["learning_rate"],
        "chips": chips, "seed": ctx.seed, "plan": plan,
        "generator_file": ctx.spec.generator_file(traffic["generator"]),
        "trace_s": float(traffic.get("trace_s", 4.0)),
        "trace": {"dir": os.path.join(ctx.out_dir, "trace-train")}
        if ctx.trace else None}
    trainer = JaxTrainer(
        train_loop, train_loop_config=loop_config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     resources_per_worker={"TPU": chips}),
        run_config=RunConfig(name="bench-train",
                             storage_path=os.path.join(ctx.out_dir,
                                                       "train")))
    t_fit = time.time()
    result = bounded(f"JaxTrainer.fit: a TPU:{chips} Train worker to be "
                     f"scheduled and run its window", 1100, trainer.fit)
    rep = result.per_worker_final[0]
    wait_chips_free(chips, f"the Train worker (pid {rep['pid']})")
    check(wait_gone([rep["pid"]]),
          f"Train worker {rep['pid']} outlived its lease")
    if not ctx.keep_trace:
        shutil.rmtree(os.path.join(ctx.out_dir, "trace-train"),
                      ignore_errors=True)
    ready_s = rep["first_line_epoch"] - t_fit
    problems = []
    if not ctx.rehearse:
        check(rep["platform"] == "tpu",
              f"the Train worker's jax runs on {rep['platform']!r}")
        check(rep["devices_used"] == chips,
              f"the step ran on {rep['devices_used']} device(s)")
        if rep["kernel_mode"] != "compiled":
            problems.append(f"Pallas kernels in {rep['kernel_mode']!r} mode")
    if not rep["finite"]:
        problems.append("a loss is not finite")
    loss_tol = float(dep["loss_tol"])
    off = abs(rep["warm_losses"][0] - rep["ref_loss"]) / rep["ref_loss"]
    if not off <= loss_tol:
        problems.append(
            f"first loss {rep['warm_losses'][0]!r} against the reference's "
            f"{rep['ref_loss']!r}: {off:.2e} apart (tolerance {loss_tol})")
    if rep["compiles_after"] != rep["compiles_before"]:
        problems.append(
            f"{rep['compiles_after'] - rep['compiles_before']} compile(s) "
            f"inside the window")
    clean = [s for s, flagged in rep["steps"] if not flagged]
    ctx.say("train", ready_s=ready_s, init_s=rep["init_s"],
            reference_s=rep["reference_s"], warmup_s=rep["warmup_s"],
            n_params=rep["n_params"], steps=len(rep["steps"]),
            window_s=rep["window_s"], ref_loss=rep["ref_loss"],
            warm_losses=rep["warm_losses"], loss_off_by=off,
            loss_tol=loss_tol,
            last_loss=rep["last_loss"],
            cache_hits=rep["compile_cache_hits"],
            cache_misses=rep["compile_cache_misses"],
            cache_dir=rep["compile_cache_dir"],
            compiles=rep["compiles_after"])
    ctx.say("worker_stalls", since_its_first_line=rep["stalls"])
    if problems:
        ctx.say("incorrect", problems=problems)
    return {
        "correct": not problems, "attempted": len(rep["steps"]),
        "failed": 0, "window_start_epoch": rep["w0_epoch"],
        "e2e": {"train_tokens_per_s":
                len(rep["steps"]) * tokens_per_step / rep["window_s"]},
        "obs": {"kind": "train", "ready_s": ready_s, "model": cfg,
                "train": {"clean_step_s": clean,
                          "tokens_per_step": tokens_per_step,
                          "seq_len": plan["seq_len"], "chips": chips},
                "trace": rep["trace"]},
        "device": {"platform": rep["platform"], "kind": rep["device_kind"],
                   "count": rep["devices_used"],
                   "memory_peak_bytes": rep["memory_peak_bytes"]}}
