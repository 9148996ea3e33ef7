"""The change of one sum of `LLMEngine.stats()` counters over the change
of another, between the first and the last poll inside the window, all
replicas together, times `scale`: `num` and `den` are lists of keys, a
dot reaching into a nested group (`phase_secs.admit`).  Without `den`,
the plain change of `num`.  The counters are cumulative, so the change
is what the window did.  Nothing when a key is not in the polls (a
program that has no such counter), or the denominator did not move."""


def lookup(row, key):
    for part in key.split("."):
        if not isinstance(row, dict) or part not in row:
            return None
        row = row[part]
    return row


def change(polls, keys):
    total = 0.0
    for rows in polls:
        for key in keys:
            first, last = lookup(rows[0], key), lookup(rows[-1], key)
            if first is None or last is None:
                return None
            total += last - first
    return total


def read(obs, params):
    polls = [rows for rows in obs.get("polls") or [] if len(rows) >= 2]
    num = change(polls, params["num"]) if polls else None
    if num is None:
        return None
    scale = float(params.get("scale", 1.0))
    if "den" not in params:
        return scale * num
    den = change(polls, params["den"])
    return scale * num / den if den else None
