"""`decode_hbm_bound` for the SDAR configuration: the time a block
pass's bytes need at the chip's peak memory bandwidth over the time the
pass takes, in percent.

Bytes (`model_math_sdar.block_pass_bytes`): the weights outside the
experts as stored, the matrices of the experts a block pass TOUCHED
(change of `moe_expert_calls_total.decode` over the change of
`decode_steps`: a block pass is the engine's decode pass), and the
cached rows the pass's lanes read (change of `block_rows_read_total`
over the same, the engine's own count: no mean context is assumed).
Nothing where the program has no such counters."""

from benchmarks import model_math_sdar, peaks
from benchmarks.readers import engine_decode_step
from benchmarks.readers.stats_ratio import change

KV_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(obs, params):
    step_ms = engine_decode_step.read(obs, {})
    polls = [rows for rows in obs.get("polls") or [] if len(rows) >= 2]
    if not step_ms or not polls:
        return None
    calls = change(polls, ["moe_expert_calls_total.decode"])
    rows = change(polls, ["block_rows_read_total"])
    steps = change(polls, ["decode_steps"])
    if calls is None or rows is None or not steps:
        return None
    m, engine = obs["model"], obs["engine"]
    n_bytes = model_math_sdar.block_pass_bytes(
        m, weight_itemsize=engine["param_bytes"]
        / model_math_sdar.total_params(m),
        kv_itemsize=KV_ITEMSIZE[engine["dtype"]],
        rows_read=rows / steps, experts_touched=calls / steps)
    floor_s = n_bytes / peaks.peak(obs["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / (step_ms / 1000.0)
