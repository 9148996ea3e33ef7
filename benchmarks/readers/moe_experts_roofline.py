"""How close the routed experts' matmuls run to the least time the chip
could take for them, in percent.

The least time: the larger of (bytes / peak HBM bandwidth) and
(operations / peak bfloat16 rate) that `model_math_laguna.
expert_matmul_cost` reckons for the (token, expert) assignments and the
(expert, layer pass) pairs touched INSIDE the traced span — the engine's
`stats()` read just after the profiler started and just before it
stopped (`replica_laguna.py`), decode and prefill passes together.  Over
the device seconds of the operations labelled `moe_experts` (the
`pallas_call`s' name) in the same span.  The counters cover a little
less than the trace (the passes in flight at its two ends), never more:
a reading over 100 is a fault of the count.  Nothing where the program
has no such counters or the trace no such operation."""

from benchmarks import model_math_laguna, peaks, trace_reduce
from benchmarks.readers.stats_ratio import lookup

PASSES = ("decode", "prefill")


def span_change(trace, key):
    """The change of one grouped counter over the traced span, all
    passes and replicas together; None where a replica has none."""
    total = 0.0
    for pair in trace.get("span_stats") or [None]:
        if not pair:
            return None
        for which in PASSES:
            first = lookup(pair[0], f"{key}.{which}")
            last = lookup(pair[1], f"{key}.{which}")
            if first is None or last is None:
                return None
            total += last - first
    return total


def read(obs, params):
    trace = obs.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    secs = trace_reduce.seconds_matching(trace, params["pattern"])
    assignments = span_change(trace, "moe_assignments_total")
    touched = span_change(trace, "moe_expert_calls_total")
    if not secs or not assignments or not touched:
        return None
    cost = model_math_laguna.expert_matmul_cost(
        obs["model"], assignments, touched)
    kind = obs["device"]["kind"]
    floor_s = max(cost["bytes"] / peaks.peak(kind, "hbm_bytes_per_s"),
                  cost["flops"] / peaks.peak(kind, "bf16_flops_per_s"))
    return 100.0 * floor_s / (secs * trace.get("devices", 1))
