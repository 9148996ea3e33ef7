#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
                              --trace <0|1>

One process per run.  It never imports jax (a chip belongs to one
process): it starts the cluster with `ray_tpu.init`, brings the cell's
deployment up through the cluster's own entry points with `TPU` leases,
warms up, measures for `--seconds`, tears down (worker gone, chips
back), and prints the result as ONE JSON object on the last line of its
standard output.  Every earlier line is free-form, `bench <what> {...}`.

No chip, fewer chips than the cell needs, or jax on the CPU in a worker
is a failure: exit code 1 and no result line, never a fallback.

Beyond the driver's four arguments (for the builder of a benchmark PR):
    --rehearse       toy sizes from the configuration's `rehearsal`
                     group on whatever jax finds, with a pretended TPU
                     resource; walks every path, exits 3, prints no result
    --sweep a,b,c    serving cells: one bring-up, then a lead-in and a
                     window at each of these request rates; prints a
                     `bench sweep` line for each and no result (exit 3)
    --keep-trace     leave the profiler's files under chiprun_out/bench/
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()   # process start, for setup_s

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


class Ctx:
    """What a deployment kind's `run(ctx)` is given."""

    def __init__(self, spec, cell, config, traffic, args, out_dir, watch):
        self.spec, self.cell = spec, cell
        self.watch = watch   # this process's StallWatch, from its start
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.sweep = ([float(x) for x in args.sweep.split(",")]
                      if args.sweep else [])
        self.keep_trace = bool(args.keep_trace)
        self.out_dir = out_dir

    @staticmethod
    def say(what: str, **fields) -> None:
        print(f"bench {what} {json.dumps(fields)}", flush=True)


def rehearsal_config(config: dict) -> dict:
    """The configuration with its `rehearsal` group laid over it: the
    same keys at toy values, so the same code runs at a size the CPU
    finishes."""
    toy = config.get("rehearsal")
    if toy is None:
        raise SystemExit("this configuration has no `rehearsal` group")
    merged = {**config, **{k: v for k, v in toy.items()
                           if k != "deployment"}}
    merged["deployment"] = {**config["deployment"],
                            **toy.get("deployment", {})}
    return merged


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args()

    from benchmarks.spec import Spec
    from benchmarks.stallwatch import StallWatch

    watch = StallWatch()
    spec = Spec(REPO)
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    if args.rehearse:
        config = rehearsal_config(config)
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    out_dir = os.path.join(
        REPO, "chiprun_out", "bench",
        f"{cell['name']}-seed{args.seed}-trace{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    ctx = Ctx(spec, cell, config, traffic, args, out_dir, watch)
    run_kind = spec.kind(config["deployment"]["kind"])

    # only now the program: a directory that holds the benchmark alone
    # fails here, before anything is started
    from benchmarks import cluster

    try:
        try:
            cluster.start(int(cell["chips"]), args.rehearse)
            outcome = run_kind(ctx)
        finally:
            cluster.stop()   # also where the start found no chip
            # when this process did not run, set-up and teardown included:
            # a run that failed says so too
            ctx.say("stalls", harness=watch.stop())
    except cluster.BenchFailure as e:
        return fail(str(e))
    if "jax" in sys.modules:
        return fail("the parent process imported jax")
    if outcome.get("sweep_only"):
        ctx.say("no-result", why="a sweep is not a run",
                device=outcome["device"])
        return 3

    device = dict(outcome["device"])
    setup_s = outcome["window_start_epoch"] - T_START
    result = {"correct": bool(outcome["correct"]),
              "attempted": int(outcome["attempted"]),
              "failed": int(outcome["failed"])}
    if args.trace:
        obs = {**outcome["obs"], "device": device}
        if args.rehearse:
            # the readers' arithmetic walked with a chip's peaks; the
            # numbers mean nothing and are not printed as a result
            obs["device"] = {**device, "kind": "TPU v5 lite"}
        trace = obs.get("trace") or {}
        if not trace.get("busy_s"):
            return fail("the traced window holds no device operation")
        result["metrics"] = spec.read_layer_metrics(cell["name"], obs)
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
    else:
        values = {**outcome["e2e"], "setup_s": setup_s}
        result["metrics"] = {}
        for m in spec.metrics_of("end_to_end", cell["name"]):
            if values.get(m["name"]) is None:
                return fail(f"no value for {m['name']}")
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    ctx.say("also", setup_s=setup_s, **outcome["e2e"])
    result["device"] = device
    if args.rehearse:
        # every path walked, at a toy size on whatever jax found: what
        # would have been the result is shown and is not one
        ctx.say("rehearsal", would_print=result)
        return 3
    print(json.dumps(result), flush=True)
    return 0


def fail(why: str) -> int:
    print(f"benchmarks/run.py: FAILED: {why}", file=sys.stderr, flush=True)
    return 1


if __name__ == "__main__":
    sys.exit(main())
