"""The latent rows a decoding lane's pass GATHERS on one layer: the
change of `sparse_decode_rows_total.decode` over the window's polls,
over the lanes the decode passes stepped (change of
`decode_lane_steps_total`) and the model's layers — at most
`index_topk`, and 0 for the lanes of a pass whose table is no wider than
that (they read their whole context in place).  Nothing where the
program has no such counter."""

from benchmarks.readers.stats_ratio import change


def read(obs, params):
    polls = [rows for rows in obs.get("polls") or [] if len(rows) >= 2]
    if not polls:
        return None
    rows = change(polls, ["sparse_decode_rows_total.decode"])
    lanes = change(polls, ["decode_lane_steps_total"])
    if rows is None or not lanes:
        return None
    return rows / lanes / obs["model"]["num_hidden_layers"]
