"""Mean milliseconds of an engine decode step over the window: the change
of `LLMEngine.stats()`'s `decode_secs` over that of `decode_steps`
between the first and the last poll inside the window, all replicas
together.  The engine's clock runs from before it builds the block
tables to after `np.asarray(next_tok)`, a device sync: host build +
dispatch + device + sync of the decode pass, without the prefill pass
that may share the step."""


def read(obs, params):
    secs = steps = 0.0
    for rows in obs.get("polls") or []:
        if len(rows) >= 2:
            secs += rows[-1]["decode_secs"] - rows[0]["decode_secs"]
            steps += rows[-1]["decode_steps"] - rows[0]["decode_steps"]
    return 1000.0 * secs / steps if steps else None
