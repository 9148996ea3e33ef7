"""How close a training step's expert kernels run to the least time the
chip could take for them, in percent.

The least time: the larger of (bytes / peak HBM bandwidth) and
(operations / peak bfloat16 rate) that `model_math_mellum.
expert_train_cost` reckons for the (token, expert) assignments and the
(expert, layer pass) pairs of the steps that ran WHOLLY inside the
traced span (the step's own counters, read back with each loss): one
backward and, where every block is rematerialised, two forwards — the
recomputation is a pass the kernels are really asked for; padding rows
and predicated-off tiles are not.  Over the device seconds of the
operations labelled `moe_experts` (the forward and backward
`pallas_call`s' names) in the same span.  The counted steps cover a
little less than the trace (the steps cut by its two ends), never more:
a reading over 100 is a fault of the count.  Nothing where the program
hands out no such counters or the trace has no such operation."""

from benchmarks import model_math_mellum, peaks, trace_reduce


def read(obs, params):
    trace = obs.get("trace") or {}
    t = obs.get("train") or {}
    span = t.get("span_counters") or {}
    if not trace.get("busy_s"):
        return None
    secs = trace_reduce.seconds_matching(trace, params["pattern"])
    assignments = span.get("train_moe_assignments_total")
    calls = span.get("train_moe_expert_calls_total")
    if not secs or not assignments or not calls:
        return None
    cost = model_math_mellum.expert_train_cost(
        obs["model"], assignments, calls,
        forward_passes=2.0 if t.get("remat") else 1.0)
    kind = obs["device"]["kind"]
    floor_s = max(cost["bytes"] / peaks.peak(kind, "hbm_bytes_per_s"),
                  cost["flops"] / peaks.peak(kind, "bf16_flops_per_s"))
    return 100.0 * floor_s / (secs * trace.get("devices", 1))
