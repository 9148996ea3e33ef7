"""`decode_hbm_bound` for the latent-attention family: the time the
decode pass's bytes need at the chip's peak memory bandwidth over the
time the pass takes, in percent.

Bytes (`model_math_pangu.decode_step_bytes`): the weights outside the
routed experts as stored, the matrices of the experts a decode pass
TOUCHED (change of `moe_expert_calls_total.decode` over the change of
`decode_steps`), and every layer's latent rows of the live contexts —
the rows the decode passes read (change of `latent_decode_rows_total`
over the change of `decode_steps`) at the bytes a row needs.  Nothing
where the program has no such counters."""

from benchmarks import model_math_pangu, peaks
from benchmarks.readers import engine_decode_step
from benchmarks.readers.stats_ratio import change

KV_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(obs, params):
    step_ms = engine_decode_step.read(obs, {})
    polls = [rows for rows in obs.get("polls") or [] if len(rows) >= 2]
    if not step_ms or not polls:
        return None
    calls = change(polls, ["moe_expert_calls_total.decode"])
    rows = change(polls, ["latent_decode_rows_total"])
    steps = change(polls, ["decode_steps"])
    if calls is None or rows is None or not steps:
        return None
    m, engine = obs["model"], obs["engine"]
    # rows a pass are (token, layer) pairs: one context of that many
    # tokens on one layer is the same bytes
    n_bytes = model_math_pangu.decode_step_bytes(
        m, weight_itemsize=engine["param_bytes"]
        / model_math_pangu.total_params(m),
        kv_itemsize=KV_ITEMSIZE[engine["dtype"]],
        contexts=[rows / steps / m["num_hidden_layers"]],
        experts_touched=calls / steps)
    floor_s = n_bytes / peaks.peak(obs["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / (step_ms / 1000.0)
