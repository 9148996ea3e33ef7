"""How close a kernel of the sparse-attention family runs to the least
time the chip could take for the work the traced span REQUIRED of it, in
percent.  `params["work"]`:

  "index"      the (query, visible key) pairs the indexer scored — the
               change of `sparse_index_pairs_total` inside the span, by
               pass kind.  A prefill pass is bound by its products
               (`model_math_glm.index_score_cost`: 64 queries share a
               key's read); a decode pass by the larger of its products
               and its keys' bytes (one query a key).
  "attention"  the (query, SELECTED row) pairs of the prefill passes —
               the change of `sparse_rows_selected_total.prefill` — at
               `model_math_glm.selected_attention_flops`.

Over the device seconds of the operations whose label matches
`params["pattern"]` in the same span (`replica_laguna.py` reads
`stats()` just inside its two ends).  Nothing where the program has no
such counter or the trace no such operation."""

from benchmarks import model_math_glm, peaks, trace_reduce
from benchmarks.readers.stats_ratio import lookup

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


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
    if not secs:
        return None
    kind, m = obs["device"]["kind"], obs["model"]
    flops_s = peaks.peak(kind, "bf16_flops_per_s")
    if params["work"] == "attention":
        pairs = span_change(trace, "sparse_rows_selected_total.prefill")
        if not pairs:
            return None
        floor_s = model_math_glm.selected_attention_flops(m, pairs) / flops_s
    else:
        prefill = span_change(trace, "sparse_index_pairs_total.prefill")
        decode = span_change(trace, "sparse_index_pairs_total.decode")
        if prefill is None or decode is None or not prefill + decode:
            return None
        size = ITEMSIZE[obs["engine"]["dtype"]]
        lone = model_math_glm.index_score_cost(m, decode, decode, size)
        floor_s = model_math_glm.index_score_cost(
            m, prefill, 0, size)["flops"] / flops_s + max(
            lone["flops"] / flops_s,
            lone["bytes"] / peaks.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * floor_s / (secs * trace.get("devices", 1))
