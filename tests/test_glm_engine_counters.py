"""What the engine counts of the sparse setting's selection, by pass
kind (`stats()`: the model's SPARSE_COUNTERS, summed on the device and
read back behind the step's tokens)."""

from test_glm_engine import _engine
from test_glm_model import CFG, PAGE, TOKENS


def test_the_engine_counts_what_was_scored_seen_and_read():
    """One request of 100 tokens and 5 new: by pass kind, (query, key)
    pairs scored, rows seen and read a query and a layer, rows gathered
    a decode lane, queries that saw no more than `index_topk` rows,
    pages of index keys the score kernel copied."""
    eng = _engine(prefix_sharing=False)
    eng.generate_batch([{"tokens": [int(t) for t in TOKENS[:100]],
                         "max_new_tokens": 5}])
    st = eng.stats()
    layers, k = CFG.num_hidden_layers, CFG.index_topk
    warm_seen = {"prefill": 0, "decode": 0}   # (no warm-up was run)
    seen = sum(range(1, 101))
    assert st["sparse_rows_visible_total"]["prefill"] \
        == layers * seen + warm_seen["prefill"]
    assert st["sparse_rows_selected_total"]["prefill"] \
        == layers * sum(min(t, k) for t in range(1, 101)) \
        + warm_seen["prefill"]
    # the chunks whose context is past 64 columns are scored: positions
    # 64..99 (the chunk that ends at 64 still fits the dense pass)
    assert st["sparse_index_pairs_total"]["prefill"] \
        == layers * sum(range(65, 101))
    assert st["sparse_dense_queries_total"]["prefill"] \
        == layers * k + warm_seen["prefill"]
    # four decode steps at contexts 101..104 (the fifth token needs no
    # pass), each lane gathering `index_topk` rows a layer
    ctx = sum(range(101, 105))
    assert st["sparse_index_pairs_total"]["decode"] == layers * ctx
    assert st["sparse_rows_visible_total"]["decode"] \
        == layers * ctx + warm_seen["decode"]
    assert st["sparse_decode_rows_total"] == {"prefill": 0,
                                              "decode": layers * 4 * k}
    assert st["sparse_rows_selected_total"]["decode"] \
        == layers * 4 * k + warm_seen["decode"]
    assert st["sparse_dense_queries_total"]["decode"] == 0
    # the pages that hold a position the lane's queries see, in the
    # passes that score: the chunks that end at 80, 96 and 100 rows, and
    # four decode steps of seven pages (the idle lanes have none)
    assert st["sparse_index_pages_read_total"] == {
        "prefill": layers * sum(-(-hi // PAGE) for hi in (80, 96, 100)),
        "decode": layers * 4 * 7}
