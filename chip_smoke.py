#!/usr/bin/env python3
"""The quickest proof that ray_tpu still starts on the chip.

Drives the two TPU paths once, through the entry points a user calls, at
full width with random weights from a seed:

  serve   ray_tpu.init() -> serve.llm_deployment(LlamaConfig at
          Llama-3-8B widths, depth cut to one 16 GB chip) -> serve.run ->
          streamed requests, decoded through the paged kernel.
  train   ray_tpu.init() -> JaxTrainer(ScalingConfig(use_tpu=True)) ->
          one Train worker actor -> build_llama_train_state -> steps.

This process never imports jax.  Each phase's jax work runs in ONE worker
process that holds the chip, leased through the ordinary resource path
(`TPU: 1`), and that process is gone before the next phase starts.

    python chip_smoke.py              one chip (what the driver runs)
    python chip_smoke.py --chips 4    only the cross-chip paths: a 2x2
                                      GSPMD train step against one
                                      device, and four one-chip replicas
                                      behind the Serve router

One JSON object per phase on stdout — observations of a smoke run, not
benchmark numbers — and as the LAST line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Any phase that fails, or a device that is not a TPU, exits non-zero and
prints no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import math
import os
import random
import sys
import tempfile
import threading
import time

import ray_tpu
from ray_tpu import serve
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

REPO = os.path.dirname(os.path.abspath(__file__))

SERVE_MAX_BATCH = 8
SERVE_MAX_NEW = 12
TRAIN_STEPS = 5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized for; every phase takes one as `sz`."""

    rehearsal: bool
    serve_model: str
    serve_layers: int
    long_prompts: tuple
    train_model: str
    train_batch: int
    train_seq: int
    big_model: str
    big_layers: int
    big_batch: int
    big_seq: int


REAL = Sizes(
    rehearsal=False,
    # Serve: Llama-3-8B widths, none changed.  Depth from the rehearsal
    # compile's memory_analysis() of the days the engine stored float32
    # weights (0.81 GiB a layer plus 3.9 GiB of embedding and head; with
    # a bf16 KV pool for max_batch 8 x 8192 tokens, 0.25 GiB a layer, the
    # decode step totalled 13.4 GiB with 8 layers).  It stores bfloat16
    # since PR 29, half of that; the depth is kept (a smoke, not a cell).
    serve_model="llama3_8b", serve_layers=8, long_prompts=(100, 150),
    # Train: the repo's one-chip training config (bench.py).
    train_model="bench_1b", train_batch=8, train_seq=1024,
    # Four chips: the depth at 8B widths whose adamw state (16 bytes a
    # parameter with gradients) no single chip can hold: 2.8e9 parameters.
    big_model="llama3_8b", big_layers=8, big_batch=4, big_seq=1024)
# --rehearse: the same control flow at a toy size on whatever jax finds,
# to find wrong paths here before a chip call.  Never prints the result.
TOY = Sizes(
    rehearsal=True, serve_model="tiny", serve_layers=2,
    long_prompts=(70, 100),  # tiny has 128 positions
    train_model="tiny", train_batch=4, train_seq=128,
    big_model="tiny", big_layers=2, big_batch=4, big_seq=128)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    """One line per phase: on stdout, and kept under chiprun_out/ (which
    `chiprun` brings back) in case worker logs crowd it out of the tail."""
    line = json.dumps({"phase": phase, **fields})
    print(line, flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke.jsonl"), "a") as f:
        f.write(line + "\n")


def bounded(what: str, seconds: float, fn, *args, **kwargs):
    """Run `fn` with a bound on the wait; name the wait when it expires."""
    box = {}

    def run():
        try:
            box["value"] = fn(*args, **kwargs)
        except BaseException as e:  # re-raised in the caller
            box["error"] = e

    t = threading.Thread(target=run, daemon=True, name=f"wait:{what}")
    t.start()
    t.join(seconds)
    if t.is_alive():
        raise SmokeFailure(f"timed out after {seconds:.0f}s waiting for {what}")
    if "error" in box:
        raise box["error"]
    return box["value"]


def get(ref, what: str, seconds: float):
    try:
        return ray_tpu.get(ref, timeout=seconds)
    except ray_tpu.GetTimeoutError:
        raise SmokeFailure(
            f"timed out after {seconds:.0f}s waiting for {what}") from None


def wait_chips_free(n: int, what: str, seconds: float = 60.0) -> None:
    """The agent hands a TPU lease's resource back only when the process
    that held the chips has exited, so this is also the wait for that
    process to be gone."""
    deadline = time.monotonic() + seconds
    while ray_tpu.available_resources().get("TPU", 0) < n:
        if time.monotonic() > deadline:
            raise SmokeFailure(
                f"timed out after {seconds:.0f}s waiting for {what} to exit "
                f"and give its chip back")
        time.sleep(0.2)


def pid_gone(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    return False


def check_device(sz: Sizes, rep: dict, chips: int, where: str,
                 also=()) -> None:
    """Everything that can hold only on the chip is checked here and
    nowhere else; a rehearsal skips exactly this.  `also`: further
    (holds, what-is-wrong) pairs of that kind."""
    if sz.rehearsal:
        return
    check(rep["platform"] == "tpu",
          f"{where}: jax runs on {rep['platform']!r}, not on a TPU")
    check(rep["kernel_mode"] == "compiled",
          f"{where}: Pallas kernels are in {rep['kernel_mode']!r} mode")
    check(rep["device_count"] == chips,
          f"{where}: the lease holds {chips} chip(s) but jax sees "
          f"{rep['device_count']} device(s)")
    for holds, wrong in also:
        check(holds, f"{where}: {wrong}")


def device_task() -> dict:
    from ray_tpu.ops import device_report

    return device_report()  # imports jax: the probe is that it finds the TPU


def probe_phase(sz: Sizes, chips: int) -> None:
    """A task that leases every chip of the host, before anything large
    is built: the agent's chip count must be what jax sees in a worker,
    and a run that cannot reach the TPU ends here, in seconds."""
    t0 = time.monotonic()
    task = ray_tpu.remote(resources={"TPU": chips}, max_retries=0)(device_task)
    rep = get(task.remote(), f"a TPU:{chips} task to be scheduled and start "
                             f"jax", 300)
    check_device(sz, rep, chips, "probe")
    wait_chips_free(chips, f"the probe's worker (pid {rep['pid']})")
    emit("probe", wall_s=time.monotonic() - t0, **{k: rep[k] for k in (
        "platform", "device_kind", "device_count", "kernel_mode",
        "visible_chips", "compile_cache_dir")})


# ------------------------------------------------------------------ serve


def serve_requests(sz: Sizes, seed: int, vocab: int):
    """Prompts of different lengths, some past one 64-token prefill
    chunk; the second wave shares a 48-token (3-page) prefix."""
    rnd = random.Random(seed)

    def toks(n):
        return [rnd.randrange(1, vocab) for _ in range(n)]

    shared = toks(48)
    first = [toks(5), toks(40)] + [toks(n) for n in sz.long_prompts]
    second = [shared + toks(9), shared + toks(17), shared + toks(30)]
    return first, second


class Streams:
    """Requests streamed concurrently through the handle path, each under
    a request id of its own: the key of its rows in the engine's trace."""

    def __init__(self, handle, what: str):
        self.handle, self.what = handle, what
        self.tokens, self.ttft, self.want = {}, {}, {}
        self._threads, self._first = [], {}

    def start(self, rid: str, prompt, max_new: int = SERVE_MAX_NEW) -> None:
        self.tokens[rid] = self.ttft[rid] = None
        self.want[rid] = max_new
        self._first[rid] = threading.Event()
        t = threading.Thread(target=self._run, args=(rid, prompt, max_new),
                             daemon=True)
        self._threads.append(t)
        t.start()

    def _run(self, rid: str, prompt, max_new: int) -> None:
        t0 = time.monotonic()
        toks = []
        for ref in self.handle.stream({"tokens": prompt, "request_id": rid,
                                       "max_new_tokens": max_new}):
            item = ray_tpu.get(ref, timeout=300)
            if self.ttft[rid] is None:
                self.ttft[rid] = time.monotonic() - t0
                self._first[rid].set()
            toks.extend(item["tokens"])
        self.tokens[rid] = toks

    def wait_first_token(self, rid: str, seconds: float = 300.0) -> None:
        check(self._first[rid].wait(seconds),
              f"timed out after {seconds:.0f}s waiting for the first token "
              f"of {self.what} request {rid}")

    def join(self, seconds: float = 600.0) -> dict:
        deadline = time.monotonic() + seconds
        for t in self._threads:
            t.join(max(0.0, deadline - time.monotonic()))
        late = [rid for rid, t in self.tokens.items()
                if t is None or len(t) != self.want[rid]]
        check(not late, f"{self.what}: requests {late} did not complete "
                        f"with their tokens within {seconds:.0f}s")
        return self.tokens


def stream_requests(handle, first, second, what: str) -> list:
    """The first wave all at once.  Then the first prompt of the second
    wave, decoding long enough to still be alive (pages are shared among
    live sequences) when the others, which share its prefix, arrive.
    Request ids are "0", "1", ... in the order of `first + second`;
    returns their seconds to the first token, every request having come
    back with the tokens it asked for (`Streams.join`)."""
    streams = Streams(handle, what)
    for i, p in enumerate(first):
        streams.start(str(i), p)
    streams.join()
    holder = str(len(first))
    streams.start(holder, second[0], max_new=4 * SERVE_MAX_NEW)
    streams.wait_first_token(holder)
    for i, p in enumerate(second[1:], len(first) + 1):
        streams.start(str(i), p)
    streams.join()
    return [streams.ttft[str(i)] for i in range(len(first) + len(second))]


@ray_tpu.remote
def published_config(name: str) -> dict:
    """A task with no chip in its lease, on the machine that has one:
    what its jax comes up on, and the model file's own widths (the parent
    cannot import the model file, which imports jax)."""
    import jax

    from ray_tpu.models.llama import LlamaConfig

    cfg = getattr(LlamaConfig, name)()
    return {"platform": jax.devices()[0].platform,
            "model": {f.name: getattr(cfg, f.name)
                      for f in dataclasses.fields(cfg)
                      if "dtype" not in f.name}}


def engine_kwargs(sz: Sizes, seed: int, widths: dict) -> dict:
    return dict(model={**widths, "n_layers": sz.serve_layers}, seed=seed,
                max_batch=SERVE_MAX_BATCH)


def check_engine(sz: Sizes, rep: dict, widths: dict, where: str) -> None:
    check_device(sz, rep, 1, where, also=[
        (rep["decode_has_tpu_custom_call"],
         "the lowered decode step holds no tpu_custom_call")])
    check({k: rep["model"][k] for k in widths} == widths
          and rep["model"]["n_layers"] == sz.serve_layers
          and rep["page_size"] == 16 and rep["dtype"] == "bfloat16",
          f"{where}: the engine runs {rep['model']}, page "
          f"{rep['page_size']}, {rep['dtype']} — not {sz.serve_model}'s "
          f"widths at {sz.serve_layers} layers")


def replica_call(replica, method: str, what: str, seconds: float = 300.0):
    return get(replica.handle_request.remote(method, (), {}), what, seconds)


def serve_phase(sz: Sizes, seed: int, widths: dict) -> None:
    """One replica of the engine, the requests, its checks; gone when
    this returns."""
    name = "llm"
    t0 = time.monotonic()
    app = serve.llm_deployment(
        name, ray_actor_options={"resources": {"TPU": 1}},
        **engine_kwargs(sz, seed, widths))
    handle = bounded(f"serve.run({name}): a TPU:1 replica to be scheduled, "
                     f"build its engine and warm up", 900, serve.run, app)
    ready_s = time.monotonic() - t0
    replica = handle._replicas[0]
    rep0 = replica_call(replica, "device_report", f"{name} device_report")
    check_engine(sz, rep0, widths, name)
    stats0 = replica_call(replica, "stats", f"{name} stats")
    first, second = serve_requests(sz, seed, widths["vocab_size"])
    t1 = time.monotonic()
    ttft = stream_requests(handle, first, second, name)
    requests_s = time.monotonic() - t1
    stats = replica_call(replica, "stats", f"{name} stats")
    rep1 = replica_call(replica, "device_report", f"{name} device_report")
    check((stats["platform"], stats["kernel_mode"])
          == (rep0["platform"], rep0["kernel_mode"]),
          f"{name}: stats() reports {stats['platform']!r}, kernels "
          f"{stats['kernel_mode']!r}; device_report() {rep0['platform']!r}, "
          f"{rep0['kernel_mode']!r}")
    check(stats["prefix_hits"] >= len(second) - 1,
          f"{name}: {stats['prefix_hits']} prefix hits for {len(second) - 1} "
          f"requests that share a live sequence's prefix")
    check(rep1["compiled_steps"] == rep0["compiled_steps"],
          f"{name}: {rep1['compiled_steps'] - rep0['compiled_steps']} "
          f"compile(s) after warm-up")
    pid = rep1["pid"]
    serve.delete(name)
    wait_chips_free(1, f"the {name} replica (pid {pid})")
    check(pid_gone(pid), f"{name}: replica process {pid} outlived its lease")
    emit("serve", model=sz.serve_model, layers=sz.serve_layers,
         max_batch=SERVE_MAX_BATCH,
         requests=len(first) + len(second),
         prompt_lens=[len(p) for p in first + second],
         param_bytes=rep1["param_bytes"], kv_pool_bytes=rep1["kv_pool_bytes"],
         peak_bytes_in_use=rep1["peak_bytes_in_use"],
         compiled_steps=rep1["compiled_steps"],
         decode_has_tpu_custom_call=rep1["decode_has_tpu_custom_call"],
         prefix_hits=stats["prefix_hits"],
         # the requests' decode steps only: warm-up's first one compiles
         decode_steps=stats["decode_steps"] - stats0["decode_steps"],
         mean_decode_step_s=(stats["decode_secs"] - stats0["decode_secs"])
         / max(1, stats["decode_steps"] - stats0["decode_steps"]),
         first_token_s=ttft,
         compile_cache_hits=rep1["compile_cache_hits"],
         compile_cache_misses=rep1["compile_cache_misses"],
         compile_cache_dir=rep1["compile_cache_dir"],
         ready_s=ready_s, requests_s=requests_s,
         wall_s=time.monotonic() - t0,
         device={"platform": rep1["platform"], "kind": rep1["device_kind"],
                 "count": rep1["device_count"]})
# ------------------------------------------------------------------ train


def train_loop(config: dict) -> dict:
    """Runs in the Train worker actor.  jax is imported here, never in
    the parent."""
    import time

    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.ops import count_compile_cache_events, device_report
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train.gspmd import build_llama_train_state, param_count

    count_compile_cache_events()  # before this loop's compiles
    cfg = dataclasses.replace(
        getattr(LlamaConfig, config["model"])(), remat=True,
        **config.get("overrides", {}))
    devices = jax.devices()[:config["devices"]]
    mesh = make_mesh(MeshSpec(**config["mesh"]), devices=devices)
    batch, seq = config["batch"], config["seq"]
    t0 = time.monotonic()
    params, opt, step_fn, _ = build_llama_train_state(
        cfg, mesh, rng_seed=config["seed"], batch_size=batch, seq_len=seq)
    jax.block_until_ready((params, opt))
    init_s = time.monotonic() - t0
    state_bytes = sum(int(x.nbytes)
                      for x in jax.tree_util.tree_leaves((params, opt)))
    # after init, before any step: what each device holds — by the
    # allocator's count, and by the shards of the state placed on it
    held = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in devices]
    shard_bytes = {d.id: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves((params, opt)):
        for shard in leaf.addressable_shards:
            shard_bytes[shard.device.id] += int(shard.data.nbytes)
    # one fixed batch: a model that learns at all drives its loss down
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(config["seed"] + 1), (batch, seq), 0,
        cfg.vocab_size, dtype="int32"))
    losses, step_s = [], []
    for _ in range(config["steps"]):
        t = time.monotonic()
        params, opt, loss = step_fn(params, opt, tokens)
        jax.block_until_ready(loss)
        step_s.append(time.monotonic() - t)
        losses.append(float(loss))
    rep = device_report()
    rep.update(n_params=param_count(params), state_bytes=state_bytes,
               bytes_in_use_after_init=held,
               state_shard_bytes=list(shard_bytes.values()), init_s=init_s,
               step_s=step_s, losses=losses, mesh=config["mesh"],
               peak_bytes_per_device=[
                   int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices])
    return rep


def run_train(tag: str, chips: int, config: dict, seconds: float) -> list:
    """One JaxTrainer run per config in `config["runs"]`, all in ONE
    worker process (one fit, one actor): returns the loop's reports."""
    def loop(cfg):
        return [train_loop({**cfg, **run}) for run in cfg["runs"]]

    trainer = JaxTrainer(
        loop, train_loop_config=config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(
            name=tag, storage_path=os.path.join(REPO, "chiprun_out", "train")))
    result = bounded(
        f"JaxTrainer.fit({tag}): a TPU:{chips} Train worker to be scheduled "
        f"and run its steps", seconds, trainer.fit)
    reports = result.per_worker_final[0]
    wait_chips_free(chips, f"the {tag} Train worker (pid {reports[0]['pid']})")
    check(pid_gone(reports[0]["pid"]),
          f"{tag}: Train worker {reports[0]['pid']} outlived its lease")
    return reports


def check_losses(rep: dict, where: str) -> None:
    losses = rep["losses"]
    check(all(math.isfinite(x) for x in losses),
          f"{where}: loss is not finite: {losses}")
    check(losses[-1] < losses[0], f"{where}: loss does not fall: {losses}")


def train_phase(sz: Sizes, seed: int) -> dict:
    t0 = time.monotonic()
    (rep,) = run_train("train", 1, {
        "seed": seed, "runs": [{
            "model": sz.train_model, "devices": 1, "mesh": {"dp": -1},
            "batch": sz.train_batch, "seq": sz.train_seq,
            "steps": TRAIN_STEPS}]},
        seconds=900)
    check_device(sz, rep, 1, "train")
    check_losses(rep, "train")
    emit("train", model=f"{sz.train_model}+remat", batch=sz.train_batch,
         seq=sz.train_seq,
         wall_s=time.monotonic() - t0,
         **{k: rep[k] for k in (
             "n_params", "state_bytes", "losses", "init_s", "step_s",
             "peak_bytes_in_use", "kernel_mode", "compile_cache_hits",
             "compile_cache_misses")})
    return rep


# ------------------------------------------------------------- four chips


def four_chip_train(sz: Sizes, seed: int) -> dict:
    """(a) one worker that holds all four chips: the 2x2 GSPMD step
    against one device of the same process, then 8B widths at a depth
    one chip cannot initialise."""
    t0 = time.monotonic()
    small = {"model": sz.train_model, "batch": sz.train_batch,
             "seq": sz.train_seq, "steps": 3}
    four, one, big = run_train("train4", 4, {
        "seed": seed, "runs": [
            {**small, "devices": 4, "mesh": {"fsdp": 2, "tp": 2}},
            {**small, "devices": 1, "mesh": {"dp": -1}},
            {"model": sz.big_model, "overrides": {"n_layers": sz.big_layers},
             "devices": 4, "mesh": {"fsdp": 2, "tp": 2},
             "batch": sz.big_batch, "seq": sz.big_seq, "steps": 2}]},
        seconds=1500)
    state = four["state_bytes"]
    held = four["bytes_in_use_after_init"]
    hbm = 16 * 2**30
    check_device(sz, four, 4, "train4", also=[
        # the CPU backend keeps no memory_stats
        (all(0.2 * state <= h <= 0.4 * state for h in held),
         f"by memory_stats the devices hold {held} bytes of a {state}-byte "
         f"state after init; expected about a quarter each"),
        (big["state_bytes"] > hbm,
         f"the {sz.big_layers}-layer state is {big['state_bytes']} bytes, "
         f"which one chip could hold")])
    for a, b in zip(four["losses"], one["losses"]):
        check(abs(a - b) <= 2e-2 * abs(b),
              f"train4: losses on 2x2 {four['losses']} and on one device "
              f"{one['losses']} disagree")
    check_losses(four, "train4 2x2")
    check(all(0.2 * state <= h <= 0.4 * state
              for h in four["state_shard_bytes"]),
          f"train4: by their shards the devices hold "
          f"{four['state_shard_bytes']} bytes of a {state}-byte state after "
          f"init; expected about a quarter each")
    check_losses(big, f"train4 {sz.big_model}")
    check(all(p < hbm for p in big["peak_bytes_per_device"]),
          f"train4: per-device peaks {big['peak_bytes_per_device']}")
    emit("train4", wall_s=time.monotonic() - t0,
         losses_2x2=four["losses"], losses_one_device=one["losses"],
         step_s_2x2=four["step_s"], step_s_one_device=one["step_s"],
         state_bytes=state, bytes_in_use_after_init=held,
         state_shard_bytes=four["state_shard_bytes"],
         big={k: big[k] for k in (
             "n_params", "state_bytes", "bytes_in_use_after_init",
             "state_shard_bytes", "peak_bytes_per_device", "losses",
             "init_s", "step_s")},
         compile_cache_hits=big["compile_cache_hits"],
         compile_cache_misses=big["compile_cache_misses"])
    return four


def four_chip_serve(sz: Sizes, seed: int, widths: dict) -> dict:
    """(b) four one-chip replicas behind the router, each in its own
    worker with its own chip from the lease."""
    name = "llm-x4"
    t0 = time.monotonic()
    app = serve.llm_deployment(
        name, num_replicas=4,
        ray_actor_options={"resources": {"TPU": 1}},
        **engine_kwargs(sz, seed, widths))
    handle = bounded(f"serve.run({name}): four TPU:1 replicas to be "
                     f"scheduled, build their engines and warm up",
                     600, serve.run, app)
    ready_s = time.monotonic() - t0
    check(len(handle._replicas) == 4,
          f"{name}: {len(handle._replicas)} replicas")
    reps = [replica_call(r, "device_report", f"{name} device_report")
            for r in handle._replicas]
    for rep in reps:
        check_engine(sz, rep, widths, name)
    chips = [rep["visible_chips"] for rep in reps]
    check(len(set(chips)) == 4 and len({rep["pid"] for rep in reps}) == 4,
          f"{name}: replicas hold chips {chips}")
    first, second = serve_requests(sz, seed, widths["vocab_size"])
    prompts = first + second
    # what one replica answers, asked directly
    before = [replica_call(r, "stats", "stats")["admitted_total"]
              for r in handle._replicas]
    reference = [get(handle._replicas[0].handle_request.remote(
        "generate", ({"tokens": p, "max_new_tokens": SERVE_MAX_NEW},), {}),
        f"{name} replica 0 generate", 300)["tokens"] for p in prompts]
    # the same prompts, three times over, through the router
    streams = Streams(handle, name)
    for i, p in enumerate(prompts * 3):
        streams.start(str(i), p)
    tokens = streams.join()
    routed = [tokens[str(i)] for i in range(3 * len(prompts))]
    after = [replica_call(r, "stats", "stats")["admitted_total"]
             for r in handle._replicas]
    served = [a > b for a, b in zip(after[1:], before[1:])]
    check(all(served), f"{name}: the router left replicas idle: requests "
                       f"admitted {before} -> {after}")
    wrong = [i for i, t in enumerate(routed) if t != reference[i % len(prompts)]]
    check(not wrong, f"{name}: routed requests {wrong} differ from replica "
                     f"0's tokens for the same prompt")
    pids = [rep["pid"] for rep in reps]
    serve.delete(name)
    wait_chips_free(4, f"the {name} replicas (pids {pids})")
    emit("serve4", replicas=4, chips=chips, ready_s=ready_s,
         bounds=[rep["chips_per_process_bounds"] for rep in reps],
         requests_routed=len(routed), requests_admitted=after,
         identical_to_one_replica=True, wall_s=time.monotonic() - t0)
    return reps[0]


# ------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever jax finds, with a pretended "
                         "TPU resource; exits 3 and never prints the result")
    args = ap.parse_args()
    sz = TOY if args.rehearse else REAL
    t0 = time.monotonic()
    # session files under this run's TMPDIR, not the shared /tmp/ray_tpu
    os.environ.setdefault(
        "RT_TMPDIR", os.path.join(tempfile.gettempdir(), "ray_tpu"))
    ray_tpu.init(
        resources={"TPU": args.chips} if sz.rehearsal else None,
        # the replica constructor initialises ~10 GiB of weights and
        # compiles six programs under the deploy health gate
        _system_config={"serve_replica_health_timeout_s": 900.0})
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        check(have == args.chips,
              f"the node agent counted {have:g} TPU chip(s); this run needs "
              f"{args.chips}")
        from ray_tpu import _native

        cpu_task = get(published_config.remote(sz.serve_model),
                       "a task with no TPU in its lease", 120)
        check(cpu_task["platform"] == "cpu",
              f"a task with no chip in its lease came up on "
              f"{cpu_task['platform']!r}")
        widths = {k: v for k, v in cpu_task["model"].items()
                  if k not in ("n_layers", "remat")}
        emit("cluster", tpu=have, native_built=_native.available(wait=True),
             device_nodes=sorted(glob.glob("/dev/accel*")
                                 + glob.glob("/dev/vfio/*")),
             no_chip_task_platform=cpu_task["platform"],
             jax_compilation_cache_dir=os.environ.get(
                 "JAX_COMPILATION_CACHE_DIR"))
        probe_phase(sz, args.chips)
        if args.chips == 1:
            serve_phase(sz, args.seed, widths)
            rep = train_phase(sz, args.seed)
        else:
            rep = four_chip_train(sz, args.seed)
            four_chip_serve(sz, args.seed, widths)
        device = {"platform": rep["platform"], "kind": rep["device_kind"],
                  "count": rep["device_count"]}
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
    check("jax" not in sys.modules, "the parent process imported jax")
    emit("total", wall_s=time.monotonic() - t0)
    if sz.rehearsal:
        print(f"chip_smoke: rehearsal on {device} ran to its end; "
              f"this is not a chip run", file=sys.stderr, flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
