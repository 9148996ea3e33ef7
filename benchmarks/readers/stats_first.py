"""One key of `LLMEngine.stats()` as the first poll inside the window
has it, for what is fixed once a replica is up (`startup_secs.warm`); a
dot reaches into a nested group.  Of several replicas the largest: a
deployment is ready when its slowest replica is.  Nothing when the key
is not in the polls."""

from benchmarks.readers.stats_ratio import lookup


def read(obs, params):
    values = [lookup(rows[0], params["key"])
              for rows in obs.get("polls") or [] if rows]
    values = [v for v in values if v is not None]
    return max(values) if values else None
