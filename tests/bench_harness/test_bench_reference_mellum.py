"""The Mellum training configuration, its arithmetic, its traffic, and
the comparison that decides `correct` in its cell — at a small size on
the CPU, on parameter trees made here (no code of `ray_tpu/`)."""

import importlib.util
import os
import shutil
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import model_math_mellum as mm  # noqa: E402
from benchmarks.generators import zipf_batches  # noqa: E402
from benchmarks.kinds import train_mellum  # noqa: E402
from benchmarks.spec import Spec  # noqa: E402

SPEC = Spec(REPO)
CFG = SPEC.config("mellum2-12b-a2.5b-train")

# https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/
# config.json, the numbers and switches of the catalog row
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


def test_the_configuration_keeps_every_published_width():
    changed = {k for k, v in PUBLISHED.items() if CFG[k] != v}
    assert changed == set(CFG["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_attention_heads",
        "num_key_value_heads", "vocab_size", "max_position_embeddings"}
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    assert set(CFG["why_reduced"]) == set(CFG["reduced"])
    n = CFG["num_hidden_layers"]
    assert CFG["layer_types"] == (PERIOD * 7)[:n] == PERIOD
    assert CFG["mlp_layer_types"] == ["sparse"] * n
    assert CFG["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    # one chip's share of four that divide each layer
    assert CFG["experts_held"] == [0, 16]
    assert CFG["num_experts_routed_over"] == PUBLISHED["num_experts"]
    assert 4 * CFG["num_experts"] == PUBLISHED["num_experts"]
    assert 4 * CFG["num_attention_heads"] == PUBLISHED["num_attention_heads"]
    assert 4 * CFG["num_key_value_heads"] == PUBLISHED["num_key_value_heads"]
    assert 4 * CFG["vocab_size"] == PUBLISHED["vocab_size"]
    dep = CFG["deployment"]
    assert dep["kind"] == "train_mellum"
    for word in ("4 chips", "share each layer", "same", "heads", "experts",
                 "vocabulary"):
        assert word in dep["stands_for"], word
    assert set(CFG["assumed"]) >= {"qk_norm_and_gate", "router_scores",
                                   "loss", "mtp_head"}
    assert set(dep["grad_tol"]) == set(train_mellum.GROUPS)
    assert set(dep["grad_tol_is"]) >= set(train_mellum.GROUPS)
    entry = next(c for c in SPEC.benchmark["configs"]
                 if c["name"] == CFG["name"])
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    cell = SPEC.cell("train-mellum2-4l-8k")
    assert cell["chips"] == 1 and cell["traffic"] == "zipf-batches-1x8192"


def _lines_of_benchmark_json():
    """Every string BENCHMARK.json holds to 200 characters on one line
    (the driver refuses the file before any run otherwise: this cell's
    `why` was 203 at the first check)."""
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bm = json.load(fh)
    for entry in bm["configs"]:
        yield f"configs.{entry['name']}.why", entry["why"]
        yield f"configs.{entry['name']}.source", entry["source"]
    for entry in bm["workloads"]:
        yield f"workloads.{entry['name']}.why", entry["why"]
    for layer in sorted({m["layer"] for m in bm["per_layer"]}):
        yield f"per_layer.layer.{layer}", layer


@pytest.mark.parametrize("where, line", list(_lines_of_benchmark_json()),
                         ids=[w for w, _ in _lines_of_benchmark_json()])
def test_a_line_of_benchmark_json_is_1_to_200_printable_characters(
        where, line):
    assert 1 <= len(line) <= 200, (where, len(line))
    assert line.isprintable() and "\t" not in line, where


def test_the_program_is_given_the_router_whole_and_the_held_share():
    model = train_mellum.model_kwargs(CFG)
    assert model["model_type"] == "mellum"
    assert model["num_experts"] == 64 and model["experts_held"] == [0, 16]
    assert model["num_attention_heads"] == 8
    assert model["num_key_value_heads"] == 1
    assert model["param_dtype"] == "float32" and model["dtype"] == "bfloat16"
    # published keys and the held share, nothing that decides a
    # mechanism for the model: its loader reads the keys' absence
    assert not {"gating", "gated", "remat",
                "shared_expert_intermediate_size"} & set(model)
    with pytest.raises(ValueError):
        train_mellum.model_kwargs({**CFG, "num_experts": 64})
    with pytest.raises(ValueError):
        train_mellum.model_kwargs({**CFG, "layer_types": PERIOD * 2})


def test_the_arithmetic_against_hand_counts():
    # a layer held: q and o 2 x 2304 x 8 x 128, k and v 2 x 2304 x 128
    assert mm.attention_params(CFG) == 5_308_416
    assert mm.router_params(CFG) == 147_456
    assert mm.expert_params(CFG) == 6_193_152
    assert mm.layer_params(CFG) == 104_550_912
    assert mm.total_params(CFG) == 531_452_160
    assert mm.total_params(CFG) == CFG["whole_model"]["held_here"]
    assert mm.whole_model_params(CFG) == 12_149_915_904
    assert mm.whole_model_params(CFG) == CFG["whole_model"]["parameters"]
    assert mm.state_bytes(CFG) == 12 * 531_452_160
    # mean positions a query sees: the band of a sliding layer
    assert mm.mean_context(8192, 1024) == pytest.approx(960.0625)
    assert mm.mean_context(8192, 0) == 4096.5
    assert mm.mean_context(512, 1024) == 256.5     # never filled
    assert mm.expected_held_assignments_per_token(CFG) == 2.0
    parts = mm.forward_flops_per_token(CFG, 8192, 2.0)
    assert parts["projections"] == 4 * 2 * 5_308_416
    assert parts["attention"] == pytest.approx(
        4 * 8 * 128 * (3 * 960.0625 + 4096.5))
    assert parts["experts"] == 4 * 2 * 2.0 * 6_193_152
    assert parts["router"] == 4 * 2 * 147_456
    assert parts["head"] == 2 * 24576 * 2304
    total = sum(parts.values())
    assert total == pytest.approx(284.56e6, rel=1e-4)
    assert mm.train_flops_per_token(CFG, 8192, 2.0) == pytest.approx(
        853.68e6, rel=1e-4)
    assert parts["experts"] / total == pytest.approx(0.348, abs=2e-3)
    assert parts["head"] / total == pytest.approx(0.398, abs=2e-3)
    # a window layer does 23 % of a full layer's pairs
    assert mm.attention_pairs(CFG, 8192, "window") / 3 \
        / mm.attention_pairs(CFG, 8192, "full") == pytest.approx(0.2344,
                                                                 abs=1e-3)
    back = mm.flash_cost(CFG, 8192, 1, which="full", backward=True)
    fwd = mm.flash_cost(CFG, 8192, 1, which="full", backward=False)
    assert back["flops"] == 2 * fwd["flops"] == 8192 * 4096.5 * 8 * 8 * 128
    assert fwd["bytes"] == 2 * (2 * 8192 * 8 * 128 + 2 * 8192 * 128)
    one = mm.expert_train_cost(CFG, 1000, 10, forward_passes=1)
    two = mm.expert_train_cost(CFG, 1000, 10, forward_passes=2)
    assert one["flops"] == 6 * 1000 * 6_193_152
    assert two["flops"] == 8 * 1000 * 6_193_152
    assert two["bytes"] > one["bytes"] > 10 * 6_193_152 * (2 + 2 + 4)


def test_the_traffic_is_zipf_over_the_rows_held_and_made_from_the_seed():
    mix = SPEC.traffic("zipf-batches-1x8192")
    assert (mix["batch"], mix["seq_len"], mix["zipf_exponent"]) == (1, 8192,
                                                                    1.0)
    plan = SPEC.generator(mix["generator"])(mix, 3000000019, 50.0, 24576)
    a = zipf_batches.batch_for_step(plan, 5)
    assert a.shape == (1, 8192) and a.dtype == np.int32
    assert 0 <= a.min() and a.max() < 24576
    assert np.array_equal(a, zipf_batches.batch_for_step(plan, 5))
    assert not np.array_equal(a, zipf_batches.batch_for_step(plan, 6))
    other = zipf_batches.batch_for_step({**plan, "seed": 7}, 5)
    assert not np.array_equal(a, other)
    # exponent 1.0 over 24576 ranks: the first rank is 9.3 % of the mass
    # (1 / (ln 24576 + 0.577)), the first ten 27 %
    counts = np.sort(np.bincount(a[0], minlength=24576))[::-1]
    assert 0.07 < counts[0] / 8192 < 0.12
    assert 0.22 < counts[:10].sum() / 8192 < 0.33
    assert (counts > 0).sum() > 2000          # and a long tail
    # one permutation a seed: the heavy ids stay heavy for the run, and
    # another seed's are others
    def heavy(plan, step):
        return set(np.argsort(np.bincount(
            zipf_batches.batch_for_step(plan, step)[0],
            minlength=24576))[-3:].tolist())

    assert all(heavy(plan, s) == heavy(plan, 0) for s in range(1, 4))
    assert not heavy({**plan, "seed": 7}, 0) & heavy(plan, 0)


@pytest.mark.parametrize("seed, experts, groups", [(0, 64, 4), (1, 64, 4),
                                                   (2, 8, 2)])
def test_experts_are_placed_on_chips_of_equal_count_and_near_equal_load(
        seed, experts, groups):
    """Loads as a Zipf batch and random routers give them (max over mean
    2 to 5): every chip gets its count, the first chip's load is within
    2 % of its share (8 experts: within a fifth), and the same loads
    give the same order."""
    rng = np.random.default_rng(seed)
    loads = rng.gamma(1.5, 1.0, experts)
    loads = (loads / loads.sum() * 8192 * 8).astype(int)
    order = train_mellum.place_by_load(loads, groups)
    assert sorted(order) == list(range(experts))
    assert order == train_mellum.place_by_load(list(loads), groups)
    room = experts // groups
    by_chip = [sum(loads[e] for e in order[g * room:(g + 1) * room])
               for g in range(groups)]
    share = loads.sum() / groups
    assert abs(by_chip[0] - share) <= (0.02 if experts == 64 else 0.2) * share
    assert max(by_chip) - min(by_chip) <= max(loads)


def _record(loss=10.5, ref_loss=10.5, err=1e-3, differs=0.02, **groups):
    errors = {"embed": [err, 5.0], "head": [groups.get("head", err), 5.0]}
    for layer in range(4):
        for g in ("attention", "router", "w1", "w3", "w2"):
            errors[f"layer_{layer}.{g}"] = [groups.get(g, err), 1.0]
    return ({"loss": loss, "errors": errors,
             "routing_differs": [differs] * 4,
             "step_loss": groups.get("step_loss", ref_loss),
             "update_errors": {
                 "embed.embedding": [groups.get("moved", 0.2), 1e-3],
                 "layer_3.moe.moe_router": [0.3, 1e-4]}},
            {"loss": ref_loss})


def test_the_comparison_on_hand_made_records():
    dep = CFG["deployment"]
    assert train_mellum.compare(*_record(), dep) == []
    off = train_mellum.compare(*_record(loss=10.5 * (1 + 3e-4)), dep)
    assert len(off) == 1 and "first loss" in off[0]
    for group in ("attention", "router", "w1", "w3", "w2"):
        found = train_mellum.compare(
            *_record(**{group: 1.5 * dep["grad_tol"][group]}), dep)
        assert len(found) == 4 and all(group in p for p in found)
    # the head's own group is reported and not held (no lower-precision
    # reading separates it: the file's `grad_tol_is`), yet has to be there
    assert dep["grad_tol"]["head"] is None
    assert train_mellum.compare(*_record(head=0.5), dep) == []
    assert train_mellum.compare(*_record(w2=float("nan")), dep)
    assert train_mellum.compare(*_record(w2=float("inf")), dep)
    flips = train_mellum.compare(
        *_record(differs=1.5 * dep["max_routing_differs"]), dep)
    assert len(flips) == 1 and "top-k" in flips[0]
    # the timed step's own loss, and the state it leaves behind
    off = train_mellum.compare(*_record(step_loss=10.5 * (1 - 3e-4)), dep)
    assert len(off) == 1 and "first step loss" in off[0]
    for moved in (1.0, 2.0, float("nan")):   # unchanged; the wrong way
        left = train_mellum.compare(*_record(moved=moved), dep)
        assert len(left) == 1 and "embed.embedding" in left[0]
    program, reference = _record()
    program["update_errors"] = {}
    assert train_mellum.compare(program, reference, dep) == [
        "no parameter's change compared"]
    assert train_mellum.worst_by_leaf(_record()[0]["update_errors"]) == {
        "embed/embedding": 0.2, "moe/moe_router": 0.3}
    program, reference = _record()
    del program["errors"]["embed"]
    assert any("embed" in p for p in train_mellum.compare(
        program, reference, dep))
    assert train_mellum.worst_by_group(
        _record(router=0.5)[0]["errors"])["router"] == 0.5


# ------------------------------------------ lower-precision mutants, toy


def _toy():
    """(params, tokens, sizes): a tree of the program's layout at toy
    widths, made with numpy."""
    rng = np.random.default_rng(5)
    d, heads, hd, f, experts, held, vocab, n = 64, 8, 16, 32, 8, 4, 256, 4

    def w(*shape):
        return (rng.standard_normal(shape) * shape[0] ** -0.5
                ).astype(np.float32)

    params = {"embed": {"embedding": rng.standard_normal(
        (vocab, d)).astype(np.float32)},
        "final_norm": {"scale": np.ones(d, np.float32)},
        "lm_head": {"kernel": w(d, vocab)}}
    for i in range(n):
        params[f"layer_{i}"] = {
            "attn_norm": {"scale": np.ones(d, np.float32)},
            "mlp_norm": {"scale": np.ones(d, np.float32)},
            "attn": {"wq": {"kernel": w(d, heads, hd)},
                     "wk": {"kernel": w(d, 1, hd)},
                     "wv": {"kernel": w(d, 1, hd)},
                     "wo": {"kernel": w(heads * hd, d).reshape(heads, hd,
                                                               d)}},
            "moe": {"moe_router": w(d, experts),
                    "moe_experts_w1": np.stack([w(d, f)] * held)
                    + w(held, d, f) * 0.5,
                    "moe_experts_w3": w(held, d, f) * d ** 0.5 * f ** -0.5,
                    "moe_experts_w2": np.stack([w(f, d) for _ in
                                                range(held)])}}
    sizes = {**{k: CFG[k] for k in ("layer_types", "rope_parameters",
                                    "norm_topk_prob", "rms_norm_eps")},
             "sliding_window": 32, "num_experts_per_tok": 2,
             "experts_held": [0, held]}
    tokens = rng.integers(0, vocab, (1, 128)).astype(np.int32)
    return params, tokens, sizes


@pytest.fixture(scope="module")
def toy():
    import jax

    from benchmarks import reference_mellum as ref

    params, tokens, sizes = _toy()
    said, true = ref.loss_and_grads(params, tokens, sizes)
    jax.block_until_ready(true)
    return ref, params, tokens, sizes, said, true


def _against(toy, mutant):
    """What the cell's comparison says of a mutant standing in the
    program's place against the true reference."""
    ref, params, tokens, sizes, said, true = toy
    got, errors = train_mellum._sample(ref, params, tokens, sizes,
                                       said["ids"], true, mutant)
    record = {"loss": got["loss"], "errors": errors,
              "routing_differs": got["routing_differs"]}
    return record, train_mellum.compare(record, {"loss": said["loss"]},
                                        CFG["deployment"])


def test_the_reference_against_itself_is_correct(toy):
    record, problems = _against(toy, None)
    assert problems == []
    assert max(train_mellum.worst_by_group(record["errors"]).values()) < 1e-6


def test_bfloat16_logits_and_loss_are_refused_by_the_loss(toy):
    record, problems = _against(toy, "logits_bfloat16")
    assert any("first loss" in p for p in problems), record["loss"]


def test_float8_expert_products_are_refused_by_the_gradients(toy):
    record, problems = _against(toy, "experts_float8_e4m3fn")
    refused = {p.split(":")[0].split(".")[-1] for p in problems
               if p.startswith("gradient of")}
    assert {"w1", "w3", "w2", "router"} <= refused, problems


def test_float8_head_operands_are_refused_by_what_flows_back_from_it(toy):
    """Not by the head's own group, which nothing separates (the file's
    `grad_tol_is`): by the groups its cotangent reaches."""
    record, problems = _against(toy, "head_float8_e4m3fn")
    refused = {p.split(":")[0].split(".")[-1].split()[-1] for p in problems
               if p.startswith("gradient of")}
    assert {"embed", "attention"} <= refused, problems


STEP = {"eps": 1e-8, "weight_decay": 1e-4}


@pytest.mark.parametrize("lr", [1e-6, 3e-4])
def test_the_first_update_in_plain_arithmetic_is_optax_adamws(toy, lr):
    """`first_adamw_step` against the optimizer the program fixes, on the
    toy tree's true gradients: the state optax leaves reads rounding; a
    state left unchanged reads 1 on every leaf, a step at twice the rate
    1, a step the wrong way 2 — whatever the rate."""
    import jax
    import optax

    ref, params, _, _, _, true = toy
    assert CFG["deployment"]["adamw"] == STEP
    expected, moved = ref.first_adamw_step(params, true, lr=lr, **STEP)

    def after(rate, grads=true):
        tx = optax.adamw(rate)
        updates, _ = tx.update(grads, tx.init(params), params)
        return ref.update_errors(optax.apply_updates(params, updates),
                                 expected, moved)

    leaves = len(jax.tree_util.tree_leaves(params))
    sound = after(lr)
    assert len(sound) == leaves
    assert "layer_3.moe.moe_experts_w2" in sound
    assert max(e for e, _ in sound.values()) < 0.02
    tol = CFG["deployment"]["update_tol"]
    unchanged = ref.update_errors(params, expected, moved)
    assert all(e == pytest.approx(1.0, abs=1e-3)
               for e, _ in unchanged.values())
    assert min(e for e, _ in after(2 * lr).values()) > tol
    away = after(lr, jax.tree_util.tree_map(lambda g: -g, true))
    assert all(e == pytest.approx(2.0, abs=0.1) for e, _ in away.values())
    program, reference = _record()
    program["update_errors"] = unchanged
    assert len(train_mellum.compare(program, reference,
                                    CFG["deployment"])) == leaves


def test_a_bfloat16_router_is_finer_than_the_programs_own_noise(toy):
    """What the comparison CANNOT refuse, as a tested fact (PERF.md
    section 7): a router computed in bfloat16 moves every gradient group
    by less than the bfloat16 activations of a correct program do (chip
    readings in the configuration's `grad_tol_is`), so no limit that
    admits the program refuses it."""
    record, problems = _against(toy, "router_bfloat16")
    worst = train_mellum.worst_by_group(record["errors"])
    assert 0 < worst["router"] < CFG["deployment"]["grad_tol"]["router"]
    assert not any(p.startswith("gradient of") for p in problems)


def test_given_routing_is_taken_and_its_difference_reported(toy):
    ref, params, tokens, sizes, said, true = toy
    swapped = [np.asarray(ids).copy() for ids in said["ids"]]
    # the first 32 tokens of layer 2 take experts 6 and 7 instead
    swapped[2][:32] = [6, 7]
    got, grads = ref.loss_and_grads(params, tokens, sizes, routing=swapped)
    assert got["routing_differs"][2] == pytest.approx(32 / 128, abs=0.02)
    assert got["routing_differs"][0] == 0.0
    assert np.array_equal(np.asarray(got["ids"][2]), swapped[2])
    assert got["loss"] != said["loss"]


def test_the_kind_fails_at_import_on_a_tree_that_cannot_train_the_family(
        tmp_path):
    """The parent commit with this PR's benchmark files laid over it:
    `models/laguna.py` is there (it serves) but has no train side."""
    kinds = tmp_path / "benchmarks" / "kinds"
    kinds.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "benchmarks", "kinds",
                             "train_mellum.py"), kinds)
    models = tmp_path / "ray_tpu" / "models"
    models.mkdir(parents=True)
    (models / "laguna.py").write_text("def build(cfg, page_size): ...\n")
    found = importlib.util.spec_from_file_location(
        "train_mellum_on_parent", kinds / "train_mellum.py")
    with pytest.raises(ImportError, match="train_build"):
        found.loader.exec_module(importlib.util.module_from_spec(found))
