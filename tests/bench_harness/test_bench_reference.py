"""The plain reference against the program's model at tiny size on the
CPU (on the chip the same comparisons run at the published widths,
outside the timed window, in every run), and the trace reduction on a
small recorded trace."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    # float32 activations: the reference's own precision, so the
    # comparison is tight
    cfg = LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                      rope_theta=1e6, norm_eps=1e-5, dtype=jnp.float32)
    model = LlamaModel(cfg)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 48),
                                               dtype=np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    sizes = dict(n_layers=2, theta=1e6, eps=1e-5)
    return cfg, model, params, tokens, sizes


def test_reference_logits_match_the_model(tiny):
    from benchmarks import reference

    cfg, model, params, tokens, sizes = tiny
    want = np.asarray(model.apply({"params": params}, tokens))
    got = np.asarray(reference.logits(params, tokens, **sizes))
    # both float32; the orders of summation differ
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    at = [[47, 3], [0, 20]]
    some = np.asarray(reference.logits(params, tokens, at=at, **sizes))
    for b in range(2):
        np.testing.assert_allclose(some[b], want[b, at[b]], rtol=2e-4,
                                   atol=2e-5)


def test_reference_loss_matches_the_programs_loss(tiny):
    from benchmarks import reference
    from ray_tpu.models.llama import causal_lm_loss

    cfg, model, params, tokens, sizes = tiny
    want = float(causal_lm_loss(model.apply({"params": params}, tokens),
                                tokens))
    got = reference.next_token_loss(params, tokens, **sizes)
    assert got == pytest.approx(want, rel=1e-5)


def test_teacher_forced_reference_follows_the_answer_it_is_given(tiny):
    """Row b of the batch is prompt + answer, padded: every position's
    verdict is the model's own on that row alone, whatever the padding
    and whatever the answer held before it."""
    from benchmarks import reference

    cfg, model, params, tokens, sizes = tiny
    prompts = [list(map(int, tokens[0, :30])), list(map(int, tokens[1, :9]))]
    answers = [[5, 9, 200, 17], [250, 1, 1, 3]]   # not the model's picks
    got = reference.teacher_forced(params, prompts, answers, **sizes)
    for prompt, answer, ref in zip(prompts, answers, got):
        row = np.asarray([prompt + answer[:-1]], np.int32)
        logits = np.asarray(model.apply({"params": params}, row))[0]
        for j, tok in enumerate(answer):
            at = logits[len(prompt) - 1 + j]
            assert ref["top_id"][j] == int(at.argmax())
            assert ref["top"][j] == pytest.approx(float(at.max()), rel=1e-4)
            assert ref["picked"][j] == pytest.approx(float(at[tok]),
                                                     rel=1e-4, abs=1e-5)


def test_every_canary_token_is_held_to_four_bfloat16_spacings():
    from benchmarks.kinds import serve as serve_kind

    assert serve_kind.bf16_ulp(8.0) == 2.0 ** -4
    assert serve_kind.ulps_below_top(8.0, 7.9) == pytest.approx(1.6)
    canaries = [{"tokens": [1] * 24}]
    # token 0 is the argmax; token 1 lies 1.6 spacings under it (a pick
    # two correct bfloat16 programs may differ in); token 2 lies 8 under
    ref = {"top": [8.0, 8.0, 8.0], "top_id": [5, 6, 7],
           "picked": [8.0, 7.9, 7.5]}
    held = serve_kind.check_canaries(canaries, [[5, 9, 3]], [ref])
    assert held["positions"] == 3 and held["not_argmax"] == 2
    assert held["worst_ulps"] == pytest.approx(8.0)
    assert len(held["off"]) == 1 and "token 2" in held["off"][0]
    assert serve_kind.check_canaries(canaries, [[5, 9]], [ref])["off"] == []


def test_a_wrong_model_fails_the_reference(tiny):
    """A reference that any model passes checks nothing: the model with
    another rotary base parts from it by far more than the tolerance."""
    import dataclasses

    from benchmarks import reference
    from ray_tpu.models.llama import LlamaModel

    cfg, _model, params, tokens, sizes = tiny
    other = LlamaModel(dataclasses.replace(cfg, rope_theta=1e4))
    got = np.asarray(other.apply({"params": params}, tokens))
    want = np.asarray(reference.logits(params, tokens, **sizes))
    assert np.abs(got - want).max() > 1e-2


# ---------------------------------------------------------- trace_reduce


def test_trace_reduce_on_hand_made_intervals():
    from benchmarks import trace_reduce as tr

    ops = [(0.000, 0.010, "fusion f32[4]"), (0.005, 0.012, "copy"),
           (0.020, 0.030, "fusion f32[4]"),
           (0.0305, 0.040, "attn tpu_custom_call bf16[16,8,4,128]")]
    host = [(0.011, 0.021, "bench:make_batch"), (0.000, 0.050, "outer"),
            (0.012, 0.019, "inner")]
    r = tr.reduce_events({"/device:TPU:0": ops}, host, min_gap_s=1e-3)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.040)
    # union: [0, 0.012] + [0.020, 0.030] + [0.0305, 0.040]
    assert r["busy_s"] == pytest.approx(0.012 + 0.010 + 0.0095)
    # one gap of 8 ms (the 0.5 ms one is under min_gap_s); the
    # benchmark's own span wins over the shorter inner one
    assert r["idle_gaps"] == [["bench:make_batch", pytest.approx(0.008)]]
    assert r["device_ops"][0] == ["fusion f32[4]", pytest.approx(0.020)]
    assert tr.seconds_matching(r, "tpu_custom_call") == pytest.approx(0.0095)
    assert tr.seconds_matching(r, r"^fusion") == pytest.approx(0.020)
    assert tr.seconds_matching(r, "no such op") == 0
    none = tr.reduce_events({"/device:TPU:0": ops}, [], min_gap_s=1e-3,
                            unattributed="nobody")
    assert none["idle_gaps"][0][0] == "nobody"
    two = tr.reduce_events({"/device:TPU:0": ops,
                            "/device:TPU:1": ops[:1]}, host)
    assert two["devices"] == 2
    assert two["busy_s"] == pytest.approx((0.0315 + 0.010) / 2)
    empty = tr.reduce_events({}, host)
    assert empty["busy_s"] is None and empty["device_ops"] == []


def test_labels_of_real_tpu_operation_names():
    """Names as a v5e trace of this repo's steps holds them (PR 24): the
    whole HLO text of the instruction."""
    from benchmarks.trace_reduce import label

    mlp = ('%fusion.294 = (f32[16]{0:T(128)S(1)}, bf16[16,4096]{1,0:T(8,128)'
           '(2,1)S(1)}) fusion(bf16[16,4096]{1,0:T(8,128)(2,1)S(1)} '
           '%get-tuple-element.55, f32[14336,4096]{1,0:T(8,128)} '
           '%params__layer_1____mlp____w2____kernel__.1, f32[4096,14336]'
           '{1,0:T(8,128)} %params__layer_1____mlp____w3____kernel__.1, '
           'f32[4096]{0:T(1024)S(1)} %copy-done.24), kind=kOutput, '
           'calls=%fused_computation.365')
    assert label(mlp) == ("fusion f32[16] [layer_*.mlp.w2.kernel, "
                          "layer_*.mlp.w3.kernel]")
    assert label(mlp.replace("layer_1", "layer_11")
                 .replace("fusion.294", "fusion.300")) == label(mlp)
    kernel = ('%attn.12 = bf16[16,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} '
              'custom-call(s32[16,64]{1,0:T(8,128)} %block_tables), '
              'custom_call_target="tpu_custom_call", operand_layout')
    assert label(kernel) == "attn tpu_custom_call bf16[16,8,4,128]"
    opt = ('%fusion.61 = (f32[4096,32768]{1,0:T(8,128)}, f32[4096,32768]'
           '{1,0:T(8,128)}) fusion(f32[4096,32768]{1,0:T(8,128)} '
           '%p__lm_head____kernel__.1, f32[]{:T(128)S(6)} %sub.47, '
           'f32[4096,32768]{1,0:T(8,128)} %o_0__nu__lm_head____kernel__.1)')
    assert label(opt) == ("fusion f32[4096,32768] [lm_head.kernel, "
                          "o_0.nu.lm_head.kernel]")
    assert label("%copy-done.36 = f32[4096]{0} copy-done(%x)") \
        == "copy-done f32[4096]"
    assert label("dot_general.1") == "dot_general.1"   # not HLO text


def test_trace_reduce_on_a_recorded_trace():
    """`data/small_cpu.xplane.pb`: four rounds of a jitted matmul-and-sum
    on the CPU backend between `bench:` annotations, the last of which
    sleeps 4 ms (recorded with python_tracer_level 0)."""
    from jax.profiler import ProfileData

    from benchmarks import trace_reduce as tr

    path = os.path.join(DATA, "small_cpu.xplane.pb")
    r = tr.reduce(path)
    assert r["devices"] == 1 and 0 < r["busy_s"] < r["window_s"]
    # the operations, summed straight from the file
    want = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if any(k == "hlo_op" for k, _v in e.stats):
                    want[e.name] = want.get(e.name, 0.0) + e.duration_ns * 1e-9
    assert set(want) == {"dot_general.1", "wrapped_reduce",
                         "wrapped_reduce-window"}
    for name, secs in want.items():
        assert r["op_seconds"][name] == pytest.approx(secs)
    assert r["device_ops"][0][0] == "dot_general.1"
    assert tr.seconds_matching(r, "dot_general") \
        == pytest.approx(want["dot_general.1"])
    # three sleeps of 4 ms lie between the rounds' operations
    gaps = dict(r["idle_gaps"])
    assert gaps["bench:make_batch"] == pytest.approx(0.012, abs=0.003)
    assert r["busy_s"] + sum(gaps.values()) <= r["window_s"] + 1e-9


def test_find_xplane(tmp_path):
    from benchmarks import trace_reduce as tr

    assert tr.find_xplane(str(tmp_path)) is None
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert tr.find_xplane(str(tmp_path)).endswith("host.xplane.pb")
