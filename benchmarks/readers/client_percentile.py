"""A percentile of one of the client's sample lists (`ttft_s`, `tpot_s`,
`late_s`: seconds, over the requests due in the window that did not
fail), times `scale`.  Parameters: `samples`, `q`, `scale`."""

from benchmarks.stats import percentile


def read(obs, params):
    samples = (obs.get("summary") or {}).get(params["samples"])
    if not samples:
        return None
    return percentile(samples, params["q"]) * params.get("scale", 1.0)
