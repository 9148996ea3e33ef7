"""The engine and its cache groups (serve/cache_groups.py), over the five
tiny configurations: what `stats()` and `device_report()` are keyed by —
literals taken from the commit before the groups left `LLMEngine` (PR 44),
which every reader of the benchmark goes by — and that a warm-up's
garbage pass and a real pass are told of the cache by `groups` of one
tree structure, so a shape compiles once."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.granite import GraniteConfig
from ray_tpu.models.laguna import LagunaConfig
from ray_tpu.models.pangu import PanguConfig
from ray_tpu.serve.llm import LLMEngine

STATS = frozenset("""
    active admit_blocked_steps admit_blocked_steps_total admitted_total
    between_secs cancelled chained_dispatches_dry_total
    chained_dispatches_total compile_secs_total
    compiles_total cow_splits deadline_expired decode_lane_steps_total
    decode_lane_steps_wasted_total decode_secs decode_steps
    dry_in_dispatch_total finished_total
    first_tokens_total free_pages host_cpu_secs host_off_cpu_secs
    host_secs host_wall_secs kernel_mode kv_pages_in_use
    kv_pages_shipped_in kv_pages_shipped_out
    kv_window_pages_released_total last_batch loop_running loop_secs
    off_cpu_secs paged_grid_steps_live_total paged_grid_steps_total
    park_secs phase_secs platform prefill_ctx_cols_total
    prefill_ctx_rows_total prefill_deep_passes_total
    prefill_narrow_passes_total
    prefill_passes_by_width prefill_secs prefill_slots_total prefill_steps
    prefill_tokens_total prefill_wait_secs_total prefix_hits
    prefix_sharing prefix_sharing_refused prefix_tokens_shared
    queue_wait_secs_total queued runahead_decode_steps_total shared_pages
    startup_secs starved_before_secs starved_before_secs_total
    starved_secs starved_secs_total
    step_secs steps submitted_total turnaround_secs turnarounds_total
    used_pages
""".split())
REPORT = frozenset("""
    attention_impl bytes_in_use chips_per_process_bounds compile_cache_dir
    compile_cache_hits compile_cache_misses compile_secs_total
    compiled_steps compiles_total decode_has_tpu_custom_call device_count
    device_ids device_kind dtype kernel_mode kv_pool_bytes model page_size
    param_bytes peak_bytes_in_use pid platform recent_compiles
    state_pool_bytes visible_chips
""".split())
MOE = {f"moe_{name}_total" for name in (
    "assignments", "expert_calls", "expert_slots", "layer_passes",
    "max_load", "row_tiles_active")}
LATENT = {"latent_decode_calls_total", "latent_decode_rows_total",
          "latent_prefill_rows_total", "latent_pages_in_use",
          "latent_pool_bytes"}
SPARSE = {f"sparse_{name}_total" for name in (
    "decode_rows", "dense_queries", "index_pages_read", "index_pairs",
    "rows_selected", "rows_visible")}
STATE = {"state_decode_calls_total", "state_decode_rows_total",
         "state_prefill_rows_total", "state_slots_in_use",
         "state_pool_bytes"}


def _llama():
    return dict(model="tiny", seed=1, page_size=8)


def _laguna():
    return dict(cfg=dataclasses.replace(
        LagunaConfig.tiny(), dtype=jnp.float32,
        max_position_embeddings=128), seed=3, page_size=8)


def _pangu():
    return dict(cfg=dataclasses.replace(
        PanguConfig.tiny(), dtype=jnp.float32, max_position_embeddings=128),
        seed=5, page_size=16)


def _pangu_sparse():
    from test_glm_model import CFG, PAGE

    return dict(cfg=dataclasses.replace(CFG, max_position_embeddings=128),
                seed=5, page_size=PAGE, prefill_lanes=2)


def _granite():
    return dict(cfg=dataclasses.replace(
        GraniteConfig.tiny(), dtype=jnp.float32, param_dtype=jnp.float32,
        max_position_embeddings=128), seed=5, page_size=16)


# the configuration -> (its cache kinds, what `stats()` has beyond STATS,
# what `device_report()` has beyond REPORT)
CASES = {
    _llama: (["full"], set(), set()),
    _laguna: (["full", "window"], MOE, set()),
    _pangu: (["full"], MOE | LATENT, {"latent_pool_bytes"}),
    _pangu_sparse: (["full"], MOE | LATENT | SPARSE | {"index_pool_bytes"},
                    {"latent_pool_bytes", "index_pool_bytes"}),
    _granite: (["full", "state"], STATE, set()),
}


def _shapes(tree):
    return (jax.tree_util.tree_structure(tree),
            [(np.shape(leaf), np.asarray(leaf).dtype)
             for leaf in jax.tree_util.tree_leaves(tree)])


@pytest.mark.parametrize("make", list(CASES), ids=lambda f: f.__name__[1:])
def test_the_keys_are_the_parents_and_a_shape_compiles_once(make):
    kinds, more_stats, more_report = CASES[make]
    eng = LLMEngine(**make(), max_batch=4, prefill_chunk=16)
    assert list(eng._groups) == kinds
    eng.warm_up()
    compiled = eng.device_report()["compiled_steps"]
    passes, forward = [], eng._forward

    def spy(tokens, q_pos, last_idx, groups, **kw):
        passes.append((tokens.shape, groups))
        return forward(tokens, q_pos, last_idx, groups, **kw)

    eng._forward = spy
    rs = np.random.RandomState(7)
    outs = eng.generate_batch(
        [{"tokens": [int(t) for t in rs.randint(1, 200, n)],
          "max_new_tokens": 3} for n in (40, 5, 21)])
    assert [len(o) for o in outs] == [3, 3, 3]
    # (the prompt of 40 has a deep chunk to go where there is a deep pass)
    deep = {eng._deep_prefill[1]} if eng._deep_prefill else set()
    assert deep == (set() if make is _pangu_sparse else {32})
    assert {cols for (_l, cols), _g in passes} == {16, 1} | deep
    assert eng.stats()["prefill_deep_passes_total"] == len(deep)
    for (lanes, cols), groups in passes:
        assert list(groups) == kinds
        full, decode = groups["full"], cols == 1
        width = full["block_tables" if decode else "ctx"].shape[1]
        # what the warm-up ran at this shape: no row, every lane garbage
        garbage = eng._pass_groups([], lanes, cols, width, decode=decode)
        assert _shapes(garbage) == _shapes(groups)
        assert not any(np.asarray(leaf).any()
                       for leaf in jax.tree_util.tree_leaves(garbage))
    report = eng.device_report()
    assert report["compiled_steps"] == compiled
    assert set(eng.stats()) == STATS | more_stats
    assert set(report) == REPORT | more_report


@pytest.mark.parametrize("chunk", [16, 32, 64, 256])
def test_window_pages_are_sized_by_the_widest_chunk(chunk):
    """`WindowPages` is built for the widest chunk a pass of the engine
    may carry (the deep pass's): every one of `max_batch` sequences
    advancing by whole chunks at once finds its pages, holds no more
    than `per_seq`, and a pass's gathered context is as wide as ITS OWN
    chunk asks — the narrow pass's columns are not the deep one's."""
    from ray_tpu.serve.cache_groups import WindowPages

    window, page, lanes, total = 32, 8, 4, 1024
    group = WindowPages("window", window, page, chunk, lanes, total // page)
    assert group.per_seq == (window + chunk) // page + 2
    assert group.num_pages == 1 + lanes * group.per_seq
    for cols in (1, 16, chunk):
        assert group.ctx_width(cols) == -(-(window + cols) // page) * page
    held = [group.admit(total)[0] for _ in range(lanes)]
    rows = []
    for lo in range(3, total - chunk, chunk):   # chunks that start mid-page
        for lane, st in enumerate(held):
            group.advance(st, lo, lo + chunk)
            assert st.next - st.first <= group.per_seq
            rows.append((lane, {"window": st}, lo, lo + chunk))
        arrays = group.prefill_arrays(rows[-lanes:], lanes, chunk, 4096)
        width = group.ctx_width(chunk)
        assert arrays["ctx"].shape == (lanes, width)
        assert arrays["slots"].shape == (lanes, chunk)
        seen = min(lo + chunk, width)
        assert arrays["ctx_mask"].sum(axis=1).tolist() == [seen] * lanes
        # every row a query of the chunk sees is on a page the lane holds
        assert (arrays["ctx"][arrays["ctx_mask"]][-(window + chunk - 1):]
                >= page).all()
        assert group.used() <= lanes * group.per_seq
    for st in held:
        group.release(st)
    assert group.used() == 0
    assert sorted(group.free) == list(range(1, group.num_pages))


@pytest.mark.parametrize("seed", range(4))
def test_the_shared_page_count_is_kept_where_a_reference_changes(seed):
    """`FullPages.shared` (the `shared` gauge and `shared_pages` of
    `stats()`) is kept on transitions — a page's references going 1 -> 2
    adds one, 2 -> 1 takes one away — and equals a scan of the reference
    counts after any run of admissions that share whole pages, split one
    mid-page (copy-on-write) and release."""
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.cache_groups import FullPages

    ps, total = 4, 48
    cfg = LlamaConfig(vocab_size=64, dim=16, n_layers=1, n_heads=2,
                      n_kv_heads=1, hidden_dim=32, max_seq_len=total)
    full = FullPages(cfg.cache_spec(), ps, 1 + 6 * (total // ps), total,
                     True, "")
    rs = np.random.RandomState(seed)
    stems = [[int(t) for t in rs.randint(1, 60, total)] for _ in range(3)]
    live, most, shared_admissions, splits = [], 0, 0, 0
    for _ in range(400):
        scan = sum(r > 1 for r in full.refs)
        assert full.shared == scan == full.gauges()["shared"] \
            == full.stats({})["shared_pages"]
        most = max(most, scan)
        if live and (rs.rand() < 0.45 or len(live) == 6):
            full.release(live.pop(rs.randint(len(live)))[0])
            continue
        # a stem's first `keep` tokens (whole pages or mid-page), then its own
        keep = int(rs.randint(0, total - 8))
        n = int(rs.randint(keep + 2, total - 4))
        tokens = stems[rs.randint(3)][:keep] + [
            int(t) for t in rs.randint(60, 64, n - keep)]
        plan = full.fit(n + 4, tokens)
        assert plan is not None
        held, there, copy = full.admit(plan)
        shared_admissions += there > 0
        splits += copy is not None
        full.written(held, tokens, n)   # its prompt's whole pages: shareable
        live.append((held, tokens))
    assert most > 5 and shared_admissions > 50 and splits > 10
    for held, _tokens in live:
        full.release(held)
    assert full.shared == 0 == sum(full.refs)
    assert sorted(full.free) == list(range(1, full.num_pages))
