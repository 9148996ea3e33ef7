"""Deployment kind "train_mellum": one Train worker that holds the cell's
chips and trains a model of the expert-layer family
(`ray_tpu/models/laguna.py`, `model_type: mellum`) on the normal path

    ray_tpu.init -> JaxTrainer(train_loop, ScalingConfig(num_workers=1,
    use_tpu=True)).fit() -> train.gspmd.build_train_state(model=...)
    -> step_fn / read

— `kinds/train.py` for another family: the same worker, mesh, fresh
host-made batch a step inside the timed loop, profiler started and
stopped by the loop, three warm-up steps.  What differs is the model's
(the step is built from `model=` through `models.resolve`; it hands its
counters out beside the loss, and `state.read` fetches both in one
transfer) and the correctness sample.

**The cell's weights** are the program's own initialisation from the
seed, with two things done to them here, where the cell is made, and
both said in the configuration (`embedding_scale_is`,
`expert_placement_is`): the embedding rows are scaled to unit variance,
and each layer's experts are PLACED on the deployment's chips by load —
the router's columns are ordered so that the chips' shares carry
near-equal loads of the first batch (`place_by_load`), and this chip is
the first.  A deployment places its experts so; random weights left as
drawn give this chip 23 to 29 % of the assignments by the seed, because
which experts the 70 heaviest ids choose is one draw.

**The correctness sample** — on the chip, at the published widths, on
the first timed-shape batch and the initial weights.  Before any step
(the step donates them): `state.grads_fn`, the jitted value_and_grad of
the very loss the step differentiates, gives the program's loss, its
gradients and the experts every layer chose.  `reference_mellum.py`
(plain float32, no kernel, no code of `ray_tpu/`) is handed those chosen
experts as GIVEN routing — which k experts a token takes is
discontinuous, and a bfloat16 program picks another where two
probabilities lie close (PERF.md section 6, PR 28); a swapped expert
moves gradients by far more than rounding does — and gives its loss and,
layer by layer, its gradients.  Compared: the loss (`loss_tol`, a share
of the reference's), and ||g - g_ref|| / ||g_ref|| of each parameter
group — embed, head, and by layer attention, router, w1, w3, w2 —
against `grad_tol[group]` (a group whose limit is null is reported and
not held: the configuration says why); the share of tokens whose
reference top-k set is not the program's is reported
(`routing_differs`) and bounded by `max_routing_differs`.  Then THE TIMED STEP ITSELF, the donated program
the window drives, on that same batch and those same weights (it is the
first warm-up step): its own loss against the reference's under the same
`loss_tol`, and the parameters it leaves behind against the reference's
gradients put through adamw's first update in plain arithmetic
(`reference_mellum.first_adamw_step`, the optimizer's constants from the
configuration): ||after - expected|| / ||expected - before|| by leaf,
the worst under `update_tol`.  A step that leaves a leaf unchanged reads
1 there; a wrong rate, a wrong update or a gradient of part of the
batch reads about 1 or more.  The configuration's file says where each
limit comes from.  Also required: finite losses, no compile in the
window, kernels compiled.

The comparison's seconds (the reference's programs, its compiles among
them) are no part of what a user waits for: `window_start_epoch` is
handed on moved back by them, so `setup_s` leaves them out.

`--sweep 1` (any value) runs the correctness sample once more for each
lower-precision mutant of the reference (`reference_mellum.MUTANTS`) in
the program's place — mutant against true reference, on the chip at the
real size — and prints what the comparison says of each, with the share
of tokens at which the true reference's own top-k is not the MUTANT's
own (what `routing_differs` would read with the mutant as the program):
the second readings of PERF.md.  A sweep prints no result.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from typing import Any, Dict, List

from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

from benchmarks.cluster import bounded, check, wait_chips_free, wait_gone
from benchmarks.kinds.train import WARMUP_STEPS

# A tree without the model fails here, before any cluster starts.  (The
# check is of the file's text: importing the model would import jax into
# this process, which must never hold the chip.  The family's file may
# exist on a tree that cannot train it, so the check is for the train
# side of the family interface.)
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_MODEL = os.path.join(_REPO, "ray_tpu", "models", "laguna.py")
_STEP = os.path.join(_REPO, "ray_tpu", "train", "gspmd.py")
for _path, _needs in ((_MODEL, "def train_build("),
                      (_STEP, "def build_train_state(")):
    try:
        with open(_path) as _f:
            _has = _needs in _f.read()
    except OSError:
        _has = False
    if not _has:
        raise ImportError(f"this tree's {_path} has no `{_needs}`: the "
                          f"program cannot train a model of the mellum "
                          f"family")

TRACE_PREFIX = "train.step"

# the configuration's keys the model is made of: published keys and the
# held share, none that decides a mechanism (`LagunaConfig.from_dict`
# takes a key the published config lacks for a mechanism it lacks;
# `num_experts` is the router's width there)
MODEL_KEYS = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "max_position_embeddings",
              "rms_norm_eps", "num_experts_per_tok", "moe_intermediate_size",
              "norm_topk_prob", "sliding_window", "layer_types",
              "mlp_layer_types", "rope_parameters", "experts_held")
GROUPS = ("embed", "head", "attention", "router", "w1", "w3", "w2")


def model_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """`build_train_state(model=...)` for this configuration, dtypes by
    name.  Refuses a file whose lists or held counts disagree."""
    n = cfg["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types"):
        if len(cfg[key]) != n:
            raise ValueError(f"{key} has {len(cfg[key])} entries for "
                             f"{n} layers")
    lo, hi = cfg["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError(f"experts_held {cfg['experts_held']} is not the "
                         f"{cfg['num_experts']} experts the file says "
                         f"are held")
    return {**{k: cfg[k] for k in MODEL_KEYS},
            "num_experts": cfg["num_experts_routed_over"],
            "dtype": cfg["deployment"]["dtype"], "param_dtype": "float32"}


def compare(program: Dict[str, Any], reference: Dict[str, Any],
            limits: Dict[str, Any]) -> List[str]:
    """What is wrong with `program` = {"loss", "errors": {group or
    layer_i.group: [relative error, the reference's norm]},
    "routing_differs": [share a layer]; of the timed step: "step_loss",
    "update_errors": {leaf: [relative error, norm]}} against `reference`
    = {"loss"} under `limits` = {"loss_tol", "grad_tol": {group: limit},
    "max_routing_differs", "update_tol"}; nothing where all is well.  A
    record without the step's two entries (a mutant of the reference
    takes no step) is held to the rest."""
    problems = []
    for what in ("loss", "step_loss"):
        if what not in program:
            continue
        off = abs(program[what] - reference["loss"]) / reference["loss"]
        if not off <= float(limits["loss_tol"]):
            problems.append(
                f"first {what.replace('_', ' ')} {program[what]!r} against "
                f"the reference's {reference['loss']!r}: {off:.2e} apart "
                f"(tolerance {limits['loss_tol']})")
    seen = set()
    for name, (err, norm) in sorted(program["errors"].items()):
        group = name.split(".")[-1]
        seen.add(group)
        if group not in limits["grad_tol"]:
            problems.append(f"no grad_tol for group {group!r}")
            continue
        limit = limits["grad_tol"][group]
        if limit is None:       # reported and not held: the file says why
            continue
        if not (math.isfinite(err) and err <= float(limit)):
            problems.append(
                f"gradient of {name}: ||g - g_ref|| / ||g_ref|| = "
                f"{err:.3e} (tolerance {limit}; ||g_ref|| = {norm:.3e})")
    for group in GROUPS:
        if group not in seen:
            problems.append(f"no gradient compared for group {group!r}")
    worst = max(program["routing_differs"], default=0.0)
    if not worst <= float(limits["max_routing_differs"]):
        problems.append(
            f"the reference's own top-k differs from the program's at "
            f"{worst:.3f} of a layer's tokens (limit "
            f"{limits['max_routing_differs']})")
    if "update_errors" in program:
        if not program["update_errors"]:
            problems.append("no parameter's change compared")
        for name, (err, norm) in sorted(program["update_errors"].items()):
            if not (math.isfinite(err)
                    and err <= float(limits["update_tol"])):
                problems.append(
                    f"the step's change of {name}: ||after - expected|| / "
                    f"||expected - before|| = {err:.3e} (tolerance "
                    f"{limits['update_tol']}; ||expected - before|| = "
                    f"{norm:.3e}; a leaf left unchanged reads 1)")
    return problems


def worst_by_group(errors: Dict[str, Any]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, (err, _) in errors.items():
        group = name.split(".")[-1]
        out[group] = max(out.get(group, 0.0), err)
    return out


def worst_by_leaf(errors: Dict[str, Any]) -> Dict[str, float]:
    """`update_errors` with the layer taken off a leaf's name, the worst
    layer's reading for each."""
    return worst_by_group({
        name.split(".", 1)[1].replace(".", "/")
        if name.startswith("layer_") else name.replace(".", "/"): value
        for name, value in errors.items()})


def place_by_load(loads, groups: int):
    """An order of the experts (a permutation of range(len(loads))) that
    puts them on `groups` chips of equal count and near-equal load, the
    first chip's first: the heaviest expert left goes to the chip with
    the least load that still has room (longest processing time first).
    Ties go to the lower index, so the same loads give the same order."""
    room = len(loads) // groups
    held = [[] for _ in range(groups)]
    total = [0] * groups
    for e in sorted(range(len(loads)), key=lambda e: (-int(loads[e]), e)):
        g = min((g for g in range(groups) if len(held[g]) < room),
                key=lambda g: (total[g], g))
        held[g].append(e)
        total[g] += int(loads[e])
    return [e for chip in held for e in sorted(chip)]


def _sample(reference_mellum, params, tokens, sizes, routing, got_grads,
            mutant=None, adamw=None):
    """The reference's (or a mutant's) parts against `got_grads` (a
    gradient tree shaped as the parameters): ({"loss", "routing_differs"},
    {group name: [relative error, norm]}).  A part is compared and
    dropped before the next is made.  With `adamw` = {"lr", "eps",
    "weight_decay"} a third value: (what adamw's first step makes of the
    parameters given the reference's gradients, each leaf's squared
    change), both shaped as the parameters and kept on the device."""
    said, errors, expected, moved = None, {}, {}, {}
    for part, tree in reference_mellum.grads_by_part(
            params, tokens, sizes, routing, mutant):
        if part == "loss":
            said = {"loss": tree["loss"],
                    "routing_differs": tree["routing_differs"]}
            continue
        head = part == "head"
        got = {k: got_grads[k] for k in tree} if head else got_grads[part]
        errors.update(reference_mellum.group_errors(part, got, tree))
        if adamw:
            held = {k: params[k] for k in tree} if head else params[part]
            new, change = reference_mellum.first_adamw_step(
                held, tree, **adamw)
            expected.update(new if head else {part: new})
            moved.update(change if head else {part: change})
    if adamw:
        return said, errors, (expected, moved)
    return said, errors


def train_loop(config: Dict[str, Any]) -> Dict[str, Any]:
    """Runs in the Train worker.  jax is imported here, never in the
    parent."""
    first_line_epoch = time.time()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_mellum, spec, trace_reduce
    from benchmarks.stallwatch import StallWatch
    from ray_tpu.ops import count_compile_cache_events, device_report
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train.gspmd import build_train_state, param_count

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
    model = dict(config["model"])
    for key in ("dtype", "param_dtype"):
        model[key] = jnp.dtype(model[key])
    devices = jax.devices()[:config["chips"]]
    mesh = make_mesh(MeshSpec(**config["mesh"]), devices=devices)
    t0 = time.monotonic()
    state = build_train_state(
        model, mesh, rng_seed=config["seed"],
        learning_rate=config["learning_rate"],
        batch_size=plan["batch"], seq_len=plan["seq_len"])
    params, opt = state.params, state.opt_state
    # the cell's weights (the configuration's `embedding_scale_is`)
    rows = jax.jit(lambda t: t * config["embedding_scale"],
                   donate_argnums=0)(params["embed"]["embedding"])
    params = {**params, "embed": {"embedding": rows}}
    # and the experts placed on the deployment's chips by load (the
    # configuration's `expert_placement_is`): layer by layer, because
    # what a layer's held experts add moves the routing of the next
    sizes = config["sizes"]
    tokens0 = batch_for_step(plan, 0)
    lo, hi = sizes["experts_held"]
    for i in range(len(sizes["layer_types"])):
        routing = state.grads_fn(params, tokens0)[0][1][1]
        router = params[f"layer_{i}"]["moe"]["moe_router"]
        loads = np.bincount(np.asarray(routing[i]).ravel(),
                            minlength=router.shape[1])
        order = place_by_load(loads, router.shape[1] // (hi - lo))
        moe = {**params[f"layer_{i}"]["moe"],
               "moe_router": router[:, np.asarray(order)]}
        params = {**params, f"layer_{i}": {**params[f"layer_{i}"],
                                           "moe": moe}}
    jax.block_until_ready((params, opt))
    init_s = time.monotonic() - t0
    n_params = param_count(params)

    # ---- correctness sample (the module's text), before any step
    t0 = time.monotonic()
    (loss0, (_, routing)), grads = state.grads_fn(params, tokens0)
    ref, errors, (expected, moved) = _sample(
        reference_mellum, params, tokens0, sizes, routing, grads,
        adamw={"lr": float(config["learning_rate"]), **config["adamw"]})
    sample = {"loss": float(loss0), "errors": errors,
              "routing_differs": ref["routing_differs"],
              "ref_loss": ref["loss"]}
    mutants = {}
    if config["mutants"]:
        # the true reference's gradients under the same given routing,
        # whole (a second 4 B a parameter beside the program's: dropped
        # with them before the first step)
        del grads
        _, true = reference_mellum.loss_and_grads(params, tokens0, sizes,
                                                  routing)
        for mutant in reference_mellum.MUTANTS:
            # roles swapped: the mutant's parts are `tree`, the true
            # reference's gradients `got`; the error is relative to the
            # mutant's norm, which differs from the true one by the error
            said, errs = _sample(reference_mellum, params, tokens0, sizes,
                                 routing, true, mutant)
            # with the mutant as the program: the experts IT chooses,
            # and where the true reference, given them, would choose others
            own = next(reference_mellum.grads_by_part(
                params, tokens0, sizes, None, mutant))[1]["ids"]
            apart = next(reference_mellum.grads_by_part(
                params, tokens0, sizes, own))[1]["routing_differs"]
            mutants[mutant] = {"loss": said["loss"], "errors": errs,
                               "routing_differs": apart}
        del true
    else:
        del grads
    reference_s = time.monotonic() - t0

    warm_losses = []
    t0 = time.monotonic()
    for i in range(WARMUP_STEPS):
        params, opt, loss, counters = state.step_fn(
            params, opt, batch_for_step(plan, i))
        warm_losses.append(state.read(loss, counters)[0])
        if i == 0:
            # the timed step on the sample's batch and weights: what it
            # left behind against the reference's first update
            t1 = time.monotonic()
            sample["step_loss"] = warm_losses[0]
            sample["update_errors"] = reference_mellum.update_errors(
                params, expected, moved)
            del expected, moved
            reference_s += time.monotonic() - t1
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
    totals = {n: 0 for n in state.counter_names}   # the window's
    span = {n: 0 for n in state.counter_names}     # the traced steps'
    span_steps = 0
    first_counted = last_counted = None
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
        with ann("bench:make_batch"):
            tokens = batch_for_step(plan, i)
        params, opt, loss, counters = state.step_fn(params, opt, tokens)
        loss, counted = state.read(loss, counters)
        first_counted = first_counted or counted
        last_counted = counted
        inside = tracing and not flagged
        if tracing and time.monotonic() - trace_t0 >= trace_for:
            jax.profiler.stop_trace()
            tracing, traced, flagged = False, True, True
        for name, value in counted.items():
            totals[name] += value
            if inside:      # a step wholly inside the traced span
                span[name] += value
        span_steps += inside
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
               sample=sample, mutants=mutants, warm_losses=warm_losses,
               first_loss=losses[0], last_loss=losses[-1],
               finite=all(math.isfinite(x) for x in warm_losses + losses),
               compiles_before=compiles0, compiles_after=compiles1,
               devices_used=len(devices), memory_peak_bytes=max(peaks),
               counters=totals, span_counters=span, span_steps=span_steps,
               first_step_counters=first_counted,
               last_step_counters=last_counted,
               trace=reduced, stalls=watch.stop())
    return rep


def run(ctx) -> Dict[str, Any]:
    cfg, traffic = ctx.config, ctx.traffic
    dep = cfg["deployment"]
    chips = int(ctx.cell["chips"])
    plan = ctx.spec.generator(traffic["generator"])(
        traffic, ctx.seed, ctx.seconds, int(cfg["vocab_size"]))
    tokens_per_step = plan["batch"] * plan["seq_len"]
    sizes = {k: cfg[k] for k in (
        "layer_types", "sliding_window", "rope_parameters",
        "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
        "experts_held")}
    loop_config = {
        "model": model_kwargs(cfg), "sizes": sizes, "mesh": dep["mesh"],
        "learning_rate": dep["learning_rate"], "adamw": dep["adamw"],
        "embedding_scale": float(dep["embedding_scale"]), "chips": chips,
        "seed": ctx.seed, "plan": plan, "mutants": bool(ctx.sweep),
        "generator_file": ctx.spec.generator_file(traffic["generator"]),
        "trace_s": float(traffic.get("trace_s", 4.0)),
        "trace": {"dir": os.path.join(ctx.out_dir, "trace-train")}
        if ctx.trace else None}
    trainer = JaxTrainer(
        train_loop, train_loop_config=loop_config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     resources_per_worker={"TPU": chips}),
        run_config=RunConfig(name="bench-train-mellum",
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
    sample = rep["sample"]
    problems += compare(sample, {"loss": sample["ref_loss"]}, dep)
    if rep["compiles_after"] != rep["compiles_before"]:
        problems.append(
            f"{rep['compiles_after'] - rep['compiles_before']} compile(s) "
            f"inside the window")
    clean = [s for s, flagged in rep["steps"] if not flagged]
    ctx.say("train", ready_s=ready_s, init_s=rep["init_s"],
            reference_s=rep["reference_s"], warmup_s=rep["warmup_s"],
            n_params=rep["n_params"], steps=len(rep["steps"]),
            window_s=rep["window_s"], warm_losses=rep["warm_losses"],
            last_loss=rep["last_loss"], counters=rep["counters"],
            first_step_counters=rep["first_step_counters"],
            last_step_counters=rep["last_step_counters"],
            cache_hits=rep["compile_cache_hits"],
            cache_misses=rep["compile_cache_misses"],
            cache_dir=rep["compile_cache_dir"],
            compiles=rep["compiles_after"],
            memory_peak_bytes=rep["memory_peak_bytes"])
    ctx.say("reference", loss=sample["loss"], ref_loss=sample["ref_loss"],
            loss_off_by=abs(sample["loss"] - sample["ref_loss"])
            / sample["ref_loss"], step_loss=sample["step_loss"],
            step_loss_off_by=abs(sample["step_loss"] - sample["ref_loss"])
            / sample["ref_loss"], loss_tol=dep["loss_tol"],
            grad_error_worst=worst_by_group(sample["errors"]),
            grad_tol=dep["grad_tol"], grad_errors=sample["errors"],
            routing_differs=sample["routing_differs"],
            max_routing_differs=dep["max_routing_differs"],
            update_error_worst=worst_by_leaf(sample["update_errors"]),
            update_tol=dep["update_tol"],
            update_errors=sample["update_errors"])
    for mutant, got in rep["mutants"].items():
        # a mutant stands in the program's place against the true
        # reference: it has to come out NOT correct
        found = compare(got, {"loss": sample["ref_loss"]}, dep)
        ctx.say("reference_lower_precision", mutant=mutant,
                refused=bool(found), problems=found[:4],
                loss_off_by=abs(got["loss"] - sample["ref_loss"])
                / sample["ref_loss"],
                grad_error_worst=worst_by_group(got["errors"]),
                routing_differs=got["routing_differs"])
    ctx.say("worker_stalls", since_its_first_line=rep["stalls"])
    if problems:
        ctx.say("incorrect", problems=problems)
    device = {"platform": rep["platform"], "kind": rep["device_kind"],
              "count": rep["devices_used"],
              "memory_peak_bytes": rep["memory_peak_bytes"]}
    if ctx.sweep:
        return {"sweep_only": True, "device": device}
    return {
        "correct": not problems, "attempted": len(rep["steps"]),
        "failed": 0,
        # less the comparison's seconds (the module's text)
        "window_start_epoch": rep["w0_epoch"] - rep["reference_s"],
        "e2e": {"train_tokens_per_s":
                len(rep["steps"]) * tokens_per_step / rep["window_s"]},
        "obs": {"kind": "train", "ready_s": ready_s, "model": cfg,
                "train": {"clean_step_s": clean,
                          "tokens_per_step": tokens_per_step,
                          "seq_len": plan["seq_len"], "chips": chips,
                          "steps": len(rep["steps"]),
                          # the family's training module recomputes every
                          # block in the backward: the forward kernels
                          # are asked twice a step (the roofline readers)
                          "remat": True,
                          "counters": rep["counters"],
                          "span_counters": rep["span_counters"],
                          "span_steps": rep["span_steps"]},
                "trace": rep["trace"]},
        "device": device}
