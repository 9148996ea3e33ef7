"""How close the block kernel runs to the least time the chip could take
for it, in percent.

The least time: the larger of (bytes / peak HBM bandwidth) and
(operations / peak bfloat16 rate) that `model_math_sdar.
block_kernel_cost` reckons for the cached rows the block passes read
INSIDE the traced span — the change of `block_rows_read_total` between
the engine's `stats()` just after the profiler started and just before
it stopped (`replica_laguna.py`).  Over the device seconds of the
operations labelled `paged_attention_block` (the `pallas_call`'s name)
in the same span.  The counter moves when a pass is dispatched, the
trace when it runs, a step later: the two differ by the passes in flight
at the span's ends, one in some hundreds.  Nothing where the program has
no such counter or the trace no such operation."""

from benchmarks import model_math_sdar, peaks, trace_reduce
from benchmarks.readers.stats_ratio import lookup

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def span_rows(trace):
    total = 0.0
    for pair in trace.get("span_stats") or [None]:
        if not pair:
            return None
        first = lookup(pair[0], "block_rows_read_total")
        last = lookup(pair[1], "block_rows_read_total")
        if first is None or last is None:
            return None
        total += last - first
    return total


def read(obs, params):
    trace = obs.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    secs = trace_reduce.seconds_matching(trace, params["pattern"])
    rows = span_rows(trace)
    if not secs or not rows:
        return None
    cost = model_math_sdar.block_kernel_cost(
        obs["model"], rows, ITEMSIZE[obs["engine"]["dtype"]])
    kind = obs["device"]["kind"]
    floor_s = max(cost["bytes"] / peaks.peak(kind, "hbm_bytes_per_s"),
                  cost["flops"] / peaks.peak(kind, "bf16_flops_per_s"))
    return 100.0 * floor_s / (secs * trace.get("devices", 1))
