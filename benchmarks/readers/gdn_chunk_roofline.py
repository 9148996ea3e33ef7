"""How close the chunked gated-delta-rule prefill kernel runs to the
least time the chip could take for its REQUIRED work, in percent.

The least time (`model_math_olmo.chunk_cost`): the chunk form's
products at chunks of 64 for the (token, linear layer) pairs the prefill
passes carried INSIDE the traced span at the chip's peak bf16 rate, or
the bytes of those tokens' q, k, v, o and gates plus each visited
(lane, pass, layer) state read once and written once at its peak HBM
bandwidth, whichever is longer — over the device seconds of the
operations labelled `gated_delta_chunk` (the `pallas_call`'s name) in
the same span.  The tokens are the change of
`delta_prefill_tokens_total.prefill`, the visits that of
`state_prefill_rows_total`, between the engine's `stats()` just after
the profiler started and just before it stopped (`replica_laguna.py`).
Required work, whatever implements it: how the kernel builds the
triangle's inverse, that it computes in float32 at the highest
precision, and that it works on a pair of heads at once are not counted.
Nothing where the program has no such counter or the trace no such
operation."""

from benchmarks import model_math_olmo, peaks, trace_reduce
from benchmarks.readers.stats_ratio import lookup


def span_change(trace, key):
    total = 0.0
    for pair in trace.get("span_stats") or [None]:
        if not pair:
            return None
        first, last = lookup(pair[0], key), lookup(pair[1], key)
        if first is None or last is None:
            return None
        total += last - first
    return total


def read(obs, params):
    trace = obs.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    secs = trace_reduce.seconds_matching(trace, params["pattern"])
    tokens = span_change(trace, "delta_prefill_tokens_total.prefill")
    visits = span_change(trace, "state_prefill_rows_total")
    if not secs or not tokens or visits is None:
        return None
    cost = model_math_olmo.chunk_cost(obs["model"], tokens, visits)
    kind = obs["device"]["kind"]
    floor_s = max(cost["flops"] / peaks.peak(kind, "bf16_flops_per_s"),
                  cost["bytes"] / peaks.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * floor_s / (secs * trace.get("devices", 1))
