"""How close the decode pass runs to the time its bytes need at the
chip's peak memory bandwidth, in percent.

Bytes of a decode step, from shapes (`model_math.decode_step_bytes`):
the blocks' and the head's weights as the engine stores them
(`param_bytes / parameters` bytes each) plus the keys and values of the
live contexts — mean occupied lanes (polled) times the mean context a
request holds half-way through its answer (prompt + output / 2, from the
mix's requests).  Over the peak of the table keyed by `device_kind`, over
`engine_decode_step`'s time.  Reads 100 only if the pass did nothing but
stream those bytes at the peak rate."""

from benchmarks import model_math, peaks
from benchmarks.readers import engine_decode_step, engine_occupancy

KV_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(obs, params):
    step_ms = engine_decode_step.read(obs, {})
    occupancy = engine_occupancy.read(obs, {})
    summary = obs.get("summary") or {}
    if not step_ms or occupancy is None or not summary.get("mean_context"):
        return None
    m, engine = obs["model"], obs["engine"]
    lanes = occupancy / 100.0 * obs["polls"][0][0]["max_batch"]
    n_bytes = model_math.decode_step_bytes(
        m, weight_itemsize=engine["param_bytes"] / model_math.total_params(m),
        kv_itemsize=KV_ITEMSIZE[engine["dtype"]],
        context_tokens=lanes * summary["mean_context"])
    floor_s = n_bytes / peaks.peak(obs["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / (step_ms / 1000.0)
