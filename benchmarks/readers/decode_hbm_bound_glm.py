"""`decode_hbm_bound` for the latent-attention family's sparse setting:
the time the decode pass's bytes need at the chip's peak memory
bandwidth over the time the pass takes, in percent.

Bytes (`model_math_glm.decode_step_bytes`): the weights outside the
routed experts as stored, the matrices of the experts a decode pass
TOUCHED (change of `moe_expert_calls_total.decode` over the change of
`decode_steps`), the index keys the lanes' indexers scanned (change of
`sparse_index_pairs_total.decode`: one query a lane, so a pair is a key)
and the latent rows their attention then read (change of
`sparse_rows_selected_total.decode`).  Nothing where the program has no
such counters."""

from benchmarks import model_math_glm, peaks
from benchmarks.readers import engine_decode_step
from benchmarks.readers.stats_ratio import change

KV_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(obs, params):
    step_ms = engine_decode_step.read(obs, {})
    polls = [rows for rows in obs.get("polls") or [] if len(rows) >= 2]
    if not step_ms or not polls:
        return None
    calls = change(polls, ["moe_expert_calls_total.decode"])
    scanned = change(polls, ["sparse_index_pairs_total.decode"])
    read_rows = change(polls, ["sparse_rows_selected_total.decode"])
    steps = change(polls, ["decode_steps"])
    if calls is None or scanned is None or read_rows is None or not steps:
        return None
    m, engine = obs["model"], obs["engine"]
    n_bytes = model_math_glm.decode_step_bytes(
        m, weight_itemsize=engine["param_bytes"]
        / model_math_glm.total_params(m),
        kv_itemsize=KV_ITEMSIZE[engine["dtype"]],
        index_rows=scanned / steps, latent_rows=read_rows / steps,
        experts_touched=calls / steps)
    floor_s = n_bytes / peaks.peak(obs["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / (step_ms / 1000.0)
