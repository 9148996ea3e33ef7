"""`model_type: mellum` — the ungated, all-sparse setting of the expert
family — trained through `train/gspmd.build_train_state`: the loss and
every gradient group against the plain reference of the benchmark
(`benchmarks/reference_mellum.py`, which shares no code with the
program), and the step's counters.  Tiny
widths, two periods of sliding x3 + full, a sequence four times the
window, float32 activations (a bfloat16 toy flips experts), the flash
kernels routed in by a lowered threshold (interpreter)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import reference_mellum as ref  # noqa: E402
from ray_tpu.models import laguna, llama  # noqa: E402
from ray_tpu.parallel.mesh import MeshSpec, make_mesh  # noqa: E402
from ray_tpu.train.gspmd import build_train_state  # noqa: E402

SEQ = 128


def _sizes(cfg):
    return dict(
        layer_types=list(cfg.layer_types),
        sliding_window=cfg.sliding_window,
        rope_parameters={k: dict(v)
                         for k, v in dict(cfg.rope_parameters).items()},
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob, rms_norm_eps=cfg.rms_norm_eps,
        experts_held=list(cfg.experts_held))


@pytest.fixture(scope="module")
def trained(request):
    """(config, TrainState, tokens, the program's loss / counters /
    routing / gradients at the initial weights)."""
    was = llama.FLASH_PREFILL_MIN_SEQ
    llama.FLASH_PREFILL_MIN_SEQ = SEQ
    request.addfinalizer(
        lambda: setattr(llama, "FLASH_PREFILL_MIN_SEQ", was))
    cfg = dataclasses.replace(laguna.LagunaConfig.tiny_ungated(),
                              dtype=jnp.float32)
    mesh = make_mesh(MeshSpec(dp=-1), devices=jax.devices()[:1])
    state = build_train_state(cfg, mesh, rng_seed=3, batch_size=1,
                              seq_len=SEQ)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, SEQ),
                                               dtype=np.int32)
    (loss, (counters, routing)), grads = state.grads_fn(state.params,
                                                        tokens)
    # the reference's gradients under the program's routing, made once
    _, want = ref.loss_and_grads(state.params, tokens, _sizes(cfg),
                                 routing=routing)
    return cfg, state, tokens, (float(loss), counters, routing, grads,
                                want)


def test_loss_is_the_references(trained):
    cfg, state, tokens, (loss, _, routing, _, _) = trained
    said, _ = ref.loss_and_grads(state.params, tokens, _sizes(cfg))
    assert abs(loss - said["loss"]) / said["loss"] < 1e-5
    # float32 on both sides: the reference's own top-k is the program's
    for own, taken in zip(said["ids"], routing):
        assert np.array_equal(np.sort(own, -1), np.sort(taken, -1))


@pytest.mark.parametrize("group", ["embed", "head", "attention", "router",
                                   "w1", "w3", "w2"])
def test_every_gradient_group_is_the_references(trained, group):
    grads, want = trained[3][3:]
    seen = 0
    for part in ["embed", "head"] + [f"layer_{i}" for i in range(8)]:
        got_part = ({k: grads[k] for k in ("final_norm", "lm_head")}
                    if part == "head" else grads[part])
        want_part = ({k: want[k] for k in ("final_norm", "lm_head")}
                     if part == "head" else want[part])
        for name, (err, norm) in ref.group_errors(
                part, got_part, want_part).items():
            if name.split(".")[-1] == group:
                seen += 1
                assert norm > 0 and err < 2e-5, (name, err)
    assert seen == (1 if group in ("embed", "head") else 8)


def test_the_step_hands_out_its_counters_beside_the_loss(trained):
    cfg, state, tokens, (loss, counters, routing, _, _) = trained
    assert state.counter_names == (
        "train_moe_assignments_total", "train_moe_expert_calls_total",
        "train_moe_max_load_total", "train_moe_row_tiles_active_total",
        "train_moe_row_tiles_total", "train_moe_layer_passes_total")
    # donated: a copy of the state goes in
    p, o = jax.tree_util.tree_map(jnp.copy, (state.params, state.opt_state))
    p, o, step_loss, step_counters = state.step_fn(p, o, tokens)
    value, counted = state.read(step_loss, step_counters)
    assert abs(value - loss) < 1e-5
    assert counted == dict(zip(state.counter_names,
                               (int(c) for c in counters)))
    assert counted["train_moe_layer_passes_total"] == 8
    held = sum(int(jnp.sum((ids >= 0) & (ids < 4))) for ids in routing)
    assert counted["train_moe_assignments_total"] == held
    assert (0 < counted["train_moe_row_tiles_active_total"]
            <= counted["train_moe_row_tiles_total"])
    assert len(routing) == 8 and routing[0].shape == (SEQ, 2)
    # the process's metrics carry them too
    from ray_tpu._private.metrics import train_step_counters

    rendered = "\n".join(
        train_step_counters("train_moe_assignments_total").render())
    assert "ray_tpu_train_moe_assignments_total" in rendered
    second = state.step_fn(p, o, tokens)
    assert float(second[2]) < value          # the step trains
