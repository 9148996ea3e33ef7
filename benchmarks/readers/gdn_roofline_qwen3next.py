"""How close the gated-delta-rule kernels run to the least time the chip
could take for their REQUIRED work on a model of fewer key heads than
value heads, in percent: `readers/gdn_chunk_roofline.py` and
`readers/gdn_decode_roofline.py` with `model_math_qwen3next`'s costs,
which count a key head's `K K^T` and `Q K^T` once and not once a value
head it serves.

`params["form"]`: "chunk" — the prefill kernel: the chunk form's products
for the (token, linear layer) pairs of the traced span at the chip's peak
bf16 rate, or those tokens' bytes plus each visited state read and
written once at its peak HBM bandwidth, whichever is longer — or "decode"
— the update kernel: the rows the span's decode passes updated, each read
once and written once in float32.  Over the device seconds of the
operations labelled `params["pattern"]` in the same span.  Nothing where
the program has no such counter or the trace no such operation."""

from benchmarks import model_math_qwen3next, peaks, trace_reduce
from benchmarks.readers.gdn_chunk_roofline import span_change


def read(obs, params):
    trace = obs.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    secs = trace_reduce.seconds_matching(trace, params["pattern"])
    if not secs:
        return None
    kind = obs["device"]["kind"]
    hbm = peaks.peak(kind, "hbm_bytes_per_s")
    if params["form"] == "decode":
        rows = span_change(trace, "state_decode_rows_total")
        if not rows:
            return None
        floor_s = model_math_qwen3next.state_update_cost(
            obs["model"], rows)["bytes"] / hbm
    else:
        tokens = span_change(trace, "delta_prefill_tokens_total.prefill")
        visits = span_change(trace, "state_prefill_rows_total")
        if not tokens or visits is None:
            return None
        cost = model_math_qwen3next.chunk_cost(obs["model"], tokens, visits)
        floor_s = max(cost["flops"] / peaks.peak(kind, "bf16_flops_per_s"),
                      cost["bytes"] / hbm)
    return 100.0 * floor_s / (secs * trace.get("devices", 1))
