"""The context a decoding lane reads in a pass: latent rows the decode
passes read (change of `latent_decode_rows_total` over the window's
polls) over the lanes they stepped (change of `decode_lane_steps_total`)
and the model's layers — what the latent decode kernel's time should
follow.  Nothing where the program has no such counter."""

from benchmarks.readers.stats_ratio import change


def read(obs, params):
    polls = [rows for rows in obs.get("polls") or [] if len(rows) >= 2]
    if not polls:
        return None
    rows = change(polls, ["latent_decode_rows_total"])
    lanes = change(polls, ["decode_lane_steps_total"])
    if rows is None or not lanes:
        return None
    return rows / lanes / obs["model"]["num_hidden_layers"]
