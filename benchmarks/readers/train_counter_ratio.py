"""A ratio of a training step's own counters, summed over the window's
steps (`obs["train"]["counters"]`, what `TrainState.read` gave with each
loss): `scale` x (`num` / `num_over`) / (`den` / `den_over`), each a
counter's name; `num_over` and `den_over` may be left out (1).  Nothing
where the program hands out no such counter or a divisor is zero."""


def read(obs, params):
    counters = (obs.get("train") or {}).get("counters") or {}

    def value(key):
        name = params.get(key)
        return 1.0 if name is None else counters.get(name)

    num, num_over, den, den_over = (
        value(k) for k in ("num", "num_over", "den", "den_over"))
    if None in (num, num_over, den, den_over) or not num_over * den:
        return None
    return (float(params.get("scale", 1.0))
            * (num / num_over) / (den / den_over))
