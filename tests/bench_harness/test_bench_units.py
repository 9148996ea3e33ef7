"""The benchmark's arithmetic and data plumbing, with no cluster and no
jax: traffic generation, percentiles and TPOT, the open-loop client
against a fake stream, the FLOP and byte counts against hand counts, and
a toy cell added from a temporary directory without editing a file."""

import json
import os
import statistics
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import client, model_math, peaks, stats  # noqa: E402
from benchmarks.spec import Spec, SpecError  # noqa: E402

SPEC = Spec(REPO)


# ------------------------------------------------------------- the files


def test_benchmark_json_names_files_that_exist():
    bm = SPEC.benchmark
    assert bm["command"] == ["python3", "benchmarks/run.py"]
    for cfg in bm["configs"]:
        data = SPEC.config(cfg["name"])
        assert data["source"] == cfg["source"]
        assert data["reduced"] == cfg["reduced"]
        assert SPEC.kind(data["deployment"]["kind"])
    for cell in bm["workloads"]:
        traffic = SPEC.traffic(cell["traffic"])
        assert SPEC.generator(traffic["generator"])
        assert SPEC.config(cell["config"])
        reported = {m["name"] for m in SPEC.metrics_of("end_to_end",
                                                       cell["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        layer = SPEC.metrics_of("per_layer", cell["name"])
        assert layer
        for m in layer:   # what a layer metric moves is reported there
            assert m["moves"] in reported


@pytest.mark.parametrize("name", ["mistral-7b-v0.3-serve",
                                  "mistral-7b-v0.3-train"])
def test_configurations_keep_the_published_widths(name):
    cfg = SPEC.config(name)
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "head_dim": 128, "vocab_size": 32768,
                 "rope_theta": 1000000.0, "rms_norm_eps": 1e-05,
                 "sliding_window": None, "tie_word_embeddings": False,
                 "num_hidden_layers": 32, "max_position_embeddings": 32768}
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"])
    assert not changed & {"hidden_size", "intermediate_size", "head_dim",
                          "num_attention_heads", "num_key_value_heads"}
    kw = model_math.llama_kwargs(cfg)
    assert kw["dim"] == 4096 and kw["hidden_dim"] == 14336
    assert kw["n_layers"] == cfg["num_hidden_layers"]


def test_a_model_llama_py_cannot_express_is_refused():
    cfg = SPEC.config("mistral-7b-v0.3-serve")
    with pytest.raises(ValueError):
        model_math.llama_kwargs({**cfg, "head_dim": 64})
    with pytest.raises(ValueError):
        model_math.llama_kwargs({**cfg, "sliding_window": 4096})


# --------------------------------------------------------------- traffic


@pytest.mark.parametrize("mix", ["chat-steady", "chat-overload"])
def test_open_loop_is_deterministic_and_hits_its_medians(mix):
    gen = SPEC.generator("open_loop")
    params = SPEC.traffic(mix)
    a = gen(params, 2147483659, 45.0, 32768)
    b = gen(params, 2147483659, 45.0, 32768)
    c = gen(params, 7, 45.0, 32768)
    assert a == b
    assert [r["tokens"] for r in a["requests"]] \
        != [r["tokens"] for r in c["requests"]]
    # every seed's window holds the same cycle, entered at another point
    def window(plan, key):
        return [key(r) for r in plan["requests"] if r["counted"]]

    for key in (lambda r: len(r["tokens"]), lambda r: r["max_new_tokens"]):
        cycle_a, cycle_c = window(a, key), window(c, key)
        assert (cycle_a == cycle_c) == ("start_at" in params)
        assert any(cycle_a[k:] + cycle_a[:k] == cycle_c
                   for k in range(len(cycle_a)))
    n = len(window(a, id))
    assert n == round(params["rate_rps"] * 45.0)
    lead_in = [r for r in a["requests"] if not r["counted"]]
    assert lead_in and all(r["due_s"] < params["lead_in_s"] for r in lead_in)
    assert abs(len(lead_in) - params["rate_rps"] * params["lead_in_s"]) \
        <= 0.5 * params["rate_rps"] * params["lead_in_s"]
    # the lead-in is the stretch of the cycle just before the window
    sizes = [len(r["tokens"]) for r in a["requests"]]
    k = len(lead_in)
    assert sizes[:k] == sizes[-k:]
    prompts = [len(r["tokens"]) for r in a["requests"] if r["counted"]]
    outs = [r["max_new_tokens"] for r in a["requests"] if r["counted"]]
    pl, ol = params["prompt_len"], params["output_len"]
    assert abs(statistics.median(prompts) - pl["median"]) <= 0.05 * pl["median"]
    assert abs(statistics.median(outs) - ol["median"]) <= 0.05 * ol["median"]
    assert pl["min"] <= min(prompts) and max(prompts) <= pl["max"]
    assert ol["min"] <= min(outs) and max(outs) <= ol["max"]
    assert max(prompts) == pl["max"]     # the clip is reached
    total = params["lead_in_s"] + 45.0
    due = [r["due_s"] for r in a["requests"]]
    assert due == sorted(due) and 0 <= due[0] and due[-1] <= total
    assert all(r["counted"] == (r["due_s"] >= params["lead_in_s"])
               for r in a["requests"])
    assert all(0 < t < 32768 for r in a["requests"] for t in r["tokens"])
    assert len({r["rid"] for r in a["requests"]}) == len(a["requests"])
    # no two prompts start alike (the engine would share their pages)
    assert len({r["tokens"][0] for r in a["requests"]}) == len(a["requests"])


def test_a_mix_above_capacity_fixes_where_its_window_starts():
    gen = SPEC.generator("open_loop")
    params = SPEC.traffic("chat-overload")
    assert "start_at" in params and "start_at" not in SPEC.traffic(
        "chat-steady")
    a = gen(params, 1, 50.0, 32768)["requests"]
    b = gen(params, 2, 50.0, 32768)["requests"]
    assert [(r["due_s"], len(r["tokens"]), r["max_new_tokens"]) for r in a] \
        == [(r["due_s"], len(r["tokens"]), r["max_new_tokens"]) for r in b]
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]


def test_open_loop_gaps_look_exponential():
    from benchmarks.generators.open_loop import exponential_gaps

    gaps = exponential_gaps(1000, 100.0)
    assert abs(sum(gaps) - 100.0) < 1e-9
    mean = statistics.mean(gaps)
    # an exponential's standard deviation equals its mean, its median
    # is ln 2 of it
    assert abs(statistics.pstdev(gaps) / mean - 1.0) < 0.05
    assert abs(statistics.median(gaps) / mean - 0.693) < 0.01


def test_stallwatch_sees_a_held_interpreter_and_its_cpu_time():
    import time

    from benchmarks.stallwatch import StallWatch

    watch = StallWatch()
    time.sleep(0.2)
    sum(range(40_000_000))   # ONE C call: the GIL is not let go inside it
    time.sleep(0.2)
    gaps = watch.stop()
    assert gaps, "a thread held the interpreter and no gap was seen"
    worst = max(gaps, key=lambda g: g["gap_s"])
    assert set(worst) == {"at", "gap_s", "cpu_s"}
    # this process was running all through the gap (a quarter: the box
    # that runs the tests is shared)
    assert worst["cpu_s"] > 0.25 * worst["gap_s"]


def test_token_batches_are_the_seeds():
    from benchmarks.generators import token_batches as tb

    plan = tb.generate(SPEC.traffic("batches-4x2048"), 2147483659, 45.0,
                       32768)
    a, b = tb.batch_for_step(plan, 3), tb.batch_for_step(plan, 3)
    assert a.shape == (4, 2048) and a.dtype.name == "int32"
    assert (a == b).all() and (a != tb.batch_for_step(plan, 4)).any()
    assert a.min() >= 0 and a.max() < 32768


# ------------------------------------------------------------ arithmetic


def test_percentile_on_hand_made_samples():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(list(reversed(xs)), 25) == 20.0
    assert stats.percentile([], 95) is None
    assert stats.percentile([7.0], 95) == 7.0
    hundred = list(range(1, 101))
    assert stats.percentile(hundred, 95) == pytest.approx(95.05)


def test_tpot():
    # first item at 1.0 s, last at 1.9 s, 10 tokens: 9 gaps of 0.1 s
    assert stats.tpot_s(1.0, 1.9, 10) == pytest.approx(0.1)
    assert stats.tpot_s(1.0, 1.0, 1) is None
    assert stats.mean([1.0, 2.0, 6.0]) == 3.0 and stats.mean([]) is None


def test_flops_and_bytes_against_hand_counts():
    serve = SPEC.config("mistral-7b-v0.3-serve")
    train = SPEC.config("mistral-7b-v0.3-train")
    # one block: q and o 4096 x 4096 each, k and v 4096 x 1024 each,
    # three 4096 x 14336 matrices
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert model_math.layer_matmul_params(serve) == layer
    assert model_math.layer_params(serve) == layer + 2 * 4096
    head = 32768 * 4096
    assert model_math.total_params(train) == 2 * (layer + 8192) + 4096 \
        + 2 * head == 704_663_552
    assert model_math.total_params(serve) == 12 * (layer + 8192) + 4096 \
        + 2 * head
    assert model_math.matmul_params(train) == 2 * layer + head
    # training: 6 a matmul parameter; attention 12 * layers * heads *
    # head_dim * (seq / 2)
    attn = 12 * 2 * 32 * 128 * 1024
    assert model_math.train_flops_per_token(train, 2048) \
        == 6 * (2 * layer + head) + attn
    assert model_math.train_flops_per_token(train, 2048) \
        == pytest.approx(3.523e9, rel=1e-3)
    # serving: 4 KiB of keys and values a token a layer in bfloat16
    assert model_math.kv_bytes_per_token(serve, 2) == 12 * 4096
    weights = (12 * (layer + 8192) + 4096 + head) * 4
    assert model_math.decode_step_bytes(serve, 4, 2, 0) == weights
    assert model_math.decode_step_bytes(serve, 4, 2, 1000) \
        == weights + 1000 * 12 * 4096
    assert weights == pytest.approx(11.0e9, rel=0.01)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu", "bf16_flops_per_s")


# ------------------------------------------------------------ the client


def _plan(n=6, gap=0.05, lead=0.1, window=0.25):
    reqs = [{"rid": f"r{i}", "due_s": i * gap, "tokens": [1, 2, 3],
             "max_new_tokens": 5, "counted": i * gap >= lead}
            for i in range(n)]
    return {"lead_in_s": lead, "window_s": window, "requests": reqs}


def test_open_loop_client_times_from_the_due_time():
    def stream(request):
        time.sleep(0.02)
        yield {"tokens": [4]}
        time.sleep(0.04)
        yield {"tokens": [5, 6, 7, 8]}

    run = client.run_open_loop(_plan(), stream, end="drain", drain_s=2.0)
    s = client.summarize(run, vocab_size=100, end="drain")
    assert s["attempted"] == 4 and s["failed"] == 0 and s["finished"] == 4
    assert all(0.02 <= t < 0.2 for t in s["ttft_s"])
    assert all(0.008 <= t < 0.05 for t in s["tpot_s"])   # 0.04 s / 4
    assert all(0 <= t < 0.05 for t in s["late_s"])
    assert s["mean_context"] == 3 + 2.5
    assert 0 < s["tokens_in_window"] <= 6 * 5


def test_open_loop_client_counts_failures():
    def stream(request):
        if request["request_id"] == "r3":
            raise RuntimeError("refused")
        yield {"tokens": [4, 5, 6, 7, 8] if request["request_id"] != "r4"
               else [4, 5]}                     # r4: too few tokens
        if request["request_id"] == "r5":
            yield {"tokens": [999]}             # outside the vocabulary

    run = client.run_open_loop(_plan(), stream, end="drain", drain_s=2.0)
    s = client.summarize(run, vocab_size=100, end="drain")
    assert s["attempted"] == 4 and s["failed"] == 3
    assert sorted(s["failed_rids"]) == ["r3", "r4", "r5"]


@pytest.mark.parametrize("end,failed", [("cancel", 0), ("drain", 4)])
def test_open_loop_client_at_the_windows_end(end, failed):
    cancelled = []

    def stream(request):
        for i in range(100):       # never done inside the window
            time.sleep(0.01)
            yield {"tokens": [i % 50]}

    run = client.run_open_loop(_plan(), stream, end=end, drain_s=0.1,
                               cancel_fn=cancelled.extend)
    s = client.summarize(run, vocab_size=100, end=end)
    assert len(cancelled) == 6 and not s["hung"]
    assert s["attempted"] == 4 and s["failed"] == failed
    assert s["finished"] == 0 and s["tokens_in_window"] > 0


def test_a_stream_the_engine_ends_early_is_dropped_not_done():
    """`cancel_fn` ends the sequences in the engine, so their streams end
    normally with fewer tokens than asked: dropped, neither done nor
    failed, in a cell that cancels."""
    import threading

    ended = threading.Event()

    def stream(request):
        yield {"tokens": [1]}
        ended.wait(5)            # decoding until the engine is told to stop

    run = client.run_open_loop(_plan(), stream, end="cancel", drain_s=0.0,
                               cancel_fn=lambda rids: ended.set())
    s = client.summarize(run, vocab_size=100, end="cancel")
    assert s["failed"] == 0 and s["finished"] == 0 and not s["hung"]
    assert all(r.cancelled and not r.done for r in run["records"])


# ------------------------------------------- a cell added as files alone


def test_a_cell_is_added_by_files_and_an_entry(tmp_path):
    """A later PR's cell: a configuration, a traffic mix with a
    generator of its own, a layer metric with a reader of its own —
    new files and new entries, nothing that exists edited."""
    root = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "generators", "layer_metrics",
                "readers"):
        (root / sub).mkdir(parents=True)
    bm = json.loads(json.dumps(SPEC.benchmark))
    bm["configs"].append({"name": "toy", "source": "a test", "reduced": [],
                          "file": "benchmarks/configs/toy.json",
                          "why": "a toy"})
    bm["workloads"].append({"name": "toy.bursts", "config": "toy",
                            "traffic": "bursts", "chips": 1, "why": "toy"})
    bm["per_layer"].append({"name": "burst_size", "unit": "requests",
                            "better": "higher", "source": "program_counter",
                            "layer": "load generator",
                            "moves": "setup_s", "workloads": ["toy.bursts"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    (root / "configs" / "toy.json").write_text(json.dumps(
        {"vocab_size": 50, "deployment": {"kind": "serve"}}))
    (root / "traffic" / "bursts.json").write_text(json.dumps(
        {"generator": "bursts", "burst": 3, "every_s": 0.1}))
    (root / "generators" / "bursts.py").write_text(
        "def generate(params, seed, seconds, vocab_size, rate_scale=1.0):\n"
        "    reqs = [{'rid': f'b{i}', 'due_s': params['every_s'] * (i // params['burst']),\n"
        "             'tokens': [seed % vocab_size], 'max_new_tokens': 2,\n"
        "             'counted': True}\n"
        "            for i in range(round(seconds / params['every_s']) * params['burst'])]\n"
        "    return {'lead_in_s': 0.0, 'window_s': seconds, 'requests': reqs}\n")
    (root / "layer_metrics" / "burst_size.json").write_text(json.dumps(
        {"unit": "requests", "layer": "load generator", "moves": "setup_s",
         "reader": "burst_size", "params": {"every_s": 0.1}}))
    (root / "readers" / "burst_size.py").write_text(
        "def read(obs, params):\n"
        "    n = obs['summary']['attempted']\n"
        "    return n / round(obs['summary']['window_s'] / params['every_s']) if n else None\n")

    spec = Spec(str(tmp_path), root=str(root))
    cell = spec.cell("toy.bursts")
    assert spec.config(cell["config"])["deployment"]["kind"] == "serve"
    assert spec.kind("serve")            # the old driver serves the new cell
    traffic = spec.traffic(cell["traffic"])
    plan = spec.generator(traffic["generator"])(traffic, 7, 0.3, 50)
    assert len(plan["requests"]) == 9

    def stream(request):
        yield {"tokens": [1, 2]}

    run = client.run_open_loop(plan, stream, end="drain", drain_s=1.0)
    summary = client.summarize(run, 50, "drain")
    metrics = spec.read_layer_metrics("toy.bursts", {"summary": summary,
                                                     "ready_s": 1.5})
    assert metrics["burst_size"] == {"value": 3.0, "unit": "requests"}
    assert metrics["ready_s"]["value"] == 1.5     # an old metric, all cells
    assert "ttft_p50_ms" not in metrics           # not this cell's
    # the old cells are untouched and still resolve from the new root
    assert spec.traffic("chat-steady")["generator"] == "open_loop"
    with pytest.raises(SpecError):
        spec.cell("no-such-cell")
    with pytest.raises(SpecError):
        spec.traffic("../configs/toy")


def test_a_reader_that_finds_nothing_is_left_out():
    out = SPEC.read_layer_metrics("serve-chat-steady",
                                  {"summary": {}, "polls": [], "trace": {}})
    assert out == {}
