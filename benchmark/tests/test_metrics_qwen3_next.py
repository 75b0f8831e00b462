"""The arithmetic of the readers and operation counts that came with the
``qwen3-next-80b-a3b-ep4-l12`` configuration, on synthetic stamps and a
synthetic trace; the traffic file's limits against the chip's own readings
and the controls; the configuration file against the catalog's published
numbers."""

import json
import os
import types

import pytest

from benchmark import flops_qwen3_next as fl, reduce_trace
from benchmark.drivers import model_serve_closed, model_serve_closed_runs
from benchmark.tests.test_metrics import reader
from benchmark.tests.test_metrics_granite_hybrid import as_served

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "qwen3-next-80b-a3b-ep4-l12"
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")))
TRAFFIC = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "rag-closed-16-longprompt.json")))
M = CONFIG["model"]["config"]
PEAKS = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
EXPECTED = os.path.join(ROOT, "benchmark", "reference", "expected", NAME + ".")
CELL = "qwen3next-serve-rag-16"


def request(prompt_len, t_first, arrivals, asked=8):
    return dict(t_send=t_first - 0.2, t_first=t_first, t_done=arrivals[-1][0], arrivals=arrivals,
                asked=asked, prompt_len=prompt_len)


def ctx(requests=None, split=None, trace=None, around=None, slots=16):
    first, last = split or ({}, {})
    return types.SimpleNamespace(
        stamps=dict(t_open=100.0, t_close=110.0, requests=requests if requests is not None else [],
                    phase_split_open=first, healthz=dict(phase_split=last, decode_chunk=8, slots=slots),
                    phase_split_trace=around),
        trace=trace, peaks=PEAKS, config=CONFIG, traffic={}, run=types.SimpleNamespace(chips=1),
        device=dict(memory_peak_bytes=13 * 2 ** 30))


def test_qwen3_next_operations_and_bytes_from_shapes():
    assert fl.delta_layers(M) == 9 and fl.attention_layers(M) == 3 and fl.expert_layers(M) == 12
    assert fl.experts_here(M) == 128 and fl.mean_assignments_here(M) == 2.5
    assert fl.conv_channels(M) == 8192 and fl.state_elements(M) == 32 * 128 * 128 == 524_288
    # ISSUE 42's arithmetic, less the vectors: in_proj_qkvz, in_proj_ba, out_proj; q (twice as wide), k, v, o
    assert fl.delta_matmul_params(M) == 2048 * 12288 + 2048 * 64 + 4096 * 2048 == 33_685_504
    assert fl.attention_matmul_params(M) == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 == 27_262_976
    assert fl.expert_matrix_elements(M) == 3_145_728
    assert fl.shared_matmul_params(M) == 1_048_576 + 3_145_728 + 2_048
    head = 37984 * 2048
    want = 9 * 33_685_504 + 3 * 27_262_976 + 12 * (4_196_352 + 2.5 * 3_145_728) + head
    assert fl.active_matmul_params(M, 2.5) == want == 607_477_760.0
    assert fl.active_matmul_params(M, 2.5, head=False) == want - head
    assert fl.step_flops(M) == 9 * (7 * 524_288 + 2 * 4 * 8192)
    assert fl.decode_flops(M, 700, 2.5) == 2.0 * want + fl.step_flops(M) + 3 * 4 * 700 * 16 * 256
    # a 150-wide prompt: three chunks of 64; a 40-wide one: one chunk of 40 (its inverse takes 2 x 5 products)
    per_chunk = 16 * 4 * 64 * 64 * 128 + 32 * (10 * 2 * 64 ** 3 + 2 * 64 * 64 * 256 + 6 * 64 * 128 * 128 + 2 * 64 * 64 * 128)
    assert fl.chunk_flops(M, 150) == 9 * (3 * per_chunk + 150 * 2 * 4 * 8192)
    assert fl.chunk_flops(M, 40) == 9 * (16 * 4 * 40 * 40 * 128 + 32 * (10 * 2 * 40 ** 3 + 2 * 40 * 40 * 256
                                                                       + 6 * 40 * 128 * 128 + 2 * 40 * 40 * 128)
                                         + 40 * 2 * 4 * 8192)
    assert fl.prefill_flops(M, 150, 2.5) == (2.0 * 150 * (want - head) + fl.chunk_flops(M, 150)
                                             + 3 * 4 * (150 * 150 / 2) * 16 * 256 + 2.0 * head)
    # a slot and a step (the configuration file's `deployment`)
    assert fl.state_bytes_per_slot(M) == 18_874_368 + 442_368 and fl.kv_bytes_per_slot(M, 2560) == 15_728_640
    fixed = 2 * (9 * 33_685_504 + 3 * 27_262_976 + 12 * 3_145_728 + head) + 4 * 12 * (1_048_576 + 2048)
    assert fl.fixed_param_bytes(M) == fixed == 1_051_426_816
    assert fl.decode_step_bytes(M, 16, 414.0) == (fixed + 2 * 414 * 3_145_728 + 16 * 2 * 19_316_736
                                                  + 16 * 15_728_640) == 4_525_883_392
    assert fl.moe_gmm_flops(M, 480) == 480 * 2 * 3_145_728
    assert fl.moe_gmm_bytes(M, 414, 480) == 2 * (414 * 3_145_728 + 480 * (2 * 2048 + 4 * 512))


def test_qwen3_next_window_counts_what_arrived_inside_with_the_counted_share():
    inside = request(900, 101.0, [(101.0, 1), (102.0, 4), (103.0, 3)])
    straddles = request(200, 99.0, [(99.0, 2), (100.5, 6)])  # prefilled before the window
    got = fl.window_flops(M, [inside, straddles, dict(inside, prompt_len=None)], 100.0, 110.0, 2.4)
    want = (fl.prefill_flops(M, 900, 2.4) + fl.decode_flops(M, 900, 2.4) + 4 * fl.decode_flops(M, 900 + 1 + 1.5, 2.4)
            + 3 * fl.decode_flops(M, 900 + 5 + 1, 2.4) + 6 * fl.decode_flops(M, 200 + 2 + 2.5, 2.4))
    assert got == pytest.approx(want)
    # the chunk's counters over the window: 2.4 assignments a row landed here a layer-step, not the even 2.5
    split = ({"moe.assignments_here_n": 1000, "moe.layer_steps_n": 120},
             {"moe.assignments_here_n": 1000 + 2.4 * 16 * 1200, "moe.layer_steps_n": 120 + 1200})
    share = reader("layer_metrics", "serve_mfu_share_gdn")(ctx([inside, straddles], split=split))
    assert share == pytest.approx(100 * want / (10.0 * 197e12)) and 0 < share < 100
    # without the counters: the even-routing mean
    even = reader("layer_metrics", "serve_mfu_share_gdn")(ctx([inside, straddles]))
    assert even == pytest.approx(100 * fl.window_flops(M, [inside, straddles], 100.0, 110.0, 2.5) / (10.0 * 197e12))


def test_qwen3_next_here_share_is_the_counters_quotient():
    first = {"moe.assignments_here_n": 500, "moe.assignments_absent_n": 1500}
    last = {"moe.assignments_here_n": 500 + 24_800, "moe.assignments_absent_n": 1500 + 75_200}
    assert reader("layer_metrics", "moe_decode_assignments_here_share")(ctx(split=(first, last))) == pytest.approx(24.8)


def synthetic_trace():
    """Three decode chunks of 56 ms (8 steps of 7 ms) with 36 grouped
    products of 0.9 ms in each, a prefill (with its own) between them."""
    ms = 1_000_000
    modules = [(0, 56 * ms, "jit_chunk(123)"), (56 * ms, 90 * ms, "jit_prefill_row(7)"),
               (90 * ms, 146 * ms, "jit_chunk(123)"), (146 * ms, 202 * ms, "jit_chunk(123)")]
    ops = []
    for s, e, name in modules:
        ops.append((s, e, "%while.3 = (s32[], f32[16,32,128,128]{3,2,1,0}) while(...)"))
        for i in range(36 if "chunk" in name else 30):
            at = s + i * ms
            ops.append((at, at + 900_000, f"%gmm.{i} = bf16[48,512]{{1,0}} custom-call(...)"))
    marks = {reduce_trace.MARK_START: [(0, 1)], reduce_trace.MARK_STOP: [(202 * ms - 1, 202 * ms)]}
    return reduce_trace.Trace({"/device:TPU:0": dict(ops=ops, modules=modules)}, marks)


AROUND = [{"moe.layer_steps_n": 1200, "moe.assignments_here_n": 48_000, "moe.experts_touched_n": 41_400},
          {"moe.layer_steps_n": 1200 + 480, "moe.assignments_here_n": 48_000 + 480 * 40,
           "moe.experts_touched_n": 41_400 + 480 * 34.5}]


def test_qwen3_next_step_roofline_takes_the_counted_experts_and_the_chunks_median_step():
    c = ctx(trace=synthetic_trace(), around=AROUND)
    assert reader("layer_metrics", "serve_decode_step_device_s")(c) == pytest.approx(0.007)
    whole = reader("layer_metrics", "gdn_decode_step_roofline")(c)
    assert whole == pytest.approx(100 * (fl.decode_step_bytes(M, 16, 12 * 34.5) / 819e9) / 0.007) and 70 < whole < 100
    # fewer slots: less state and fewer keys to move, the same matrices and experts
    fewer = reader("layer_metrics", "gdn_decode_step_roofline")(ctx(trace=synthetic_trace(), around=AROUND, slots=8))
    assert fewer == pytest.approx(100 * (fl.decode_step_bytes(M, 8, 12 * 34.5) / 819e9) / 0.007) and fewer < whole


def test_qwen3_next_gmm_roofline_counts_the_assignments_here_inside_the_chunks():
    c = ctx(trace=synthetic_trace(), around=AROUND)
    layer_steps = 3 * 8 * 12  # three chunk executions of 8 steps over 12 expert layers
    need_flops = fl.moe_gmm_flops(M, 40 * layer_steps)
    need_bytes = fl.moe_gmm_bytes(M, 34.5 * layer_steps, 40 * layer_steps)
    assert need_bytes / 819e9 > need_flops / 197e12  # bound by the touched experts' bytes
    got = reader("layer_metrics", "moe_decode_roofline_ep")(c)
    assert got == pytest.approx(100 * (need_bytes / 819e9) / (3 * 36 * 0.0009)) and 50 < got < 100


@pytest.mark.parametrize("name", ["serve_mfu_share_gdn", "gdn_decode_step_roofline", "moe_decode_roofline_ep",
                                  "moe_decode_assignments_here_share"])
def test_qwen3_next_readers_with_nothing_to_read_give_none(name):
    """Another driver's stamps, another configuration, a program without the
    counters (the parent), or no trace: the metric is left out, nothing raises."""
    bare = ctx()
    bare.stamps = dict(cycles=[], t_open=100.0)
    assert reader("layer_metrics", name)(bare) is None
    other = ctx(requests=[request(10, 101.0, [(101.0, 1)])], split=({}, {"chunks_n": 5}), trace=synthetic_trace(),
                around=[{"chunks_n": 1}, {"chunks_n": 4}])
    other.config = dict(model=dict(config=dict(num_experts_per_tok=4)),
                        trace_names=dict(decode_chunk="^jit_chunk", moe_gmm="^%?gmm"))
    assert reader("layer_metrics", name)(other) is None


def test_qwen3_next_cell_and_metrics_are_in_the_benchmark():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "rag-closed-16-longprompt", 1)
    mine = {m["name"]: m for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert sorted(mine) == ["gdn_decode_step_roofline", "moe_decode_assignments_here_share",
                            "moe_decode_roofline_ep", "serve_mfu_share_gdn"]
    assert all(m["moves"] == "serve_tokens_per_s" for m in mine.values())
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"] if CELL in m.get("workloads", [])}
    assert {"serve_tokens_per_s", "device_idle_share_serve", "hbm_peak_gib_serve", "serve_decode_step_device_s",
            "serve_prefill_pad_share", "moe_experts_touched_per_step", "moe_decode_load_max_over_mean",
            "compiles_in_window", "serve_host_frac"} <= listed
    # the readers that import another model's counts are left alone
    assert not {"moe_decode_roofline", "serve_decode_step_roofline", "serve_mfu_share", "serve_mfu_share_hybrid"} & listed


def test_qwen3_next_configuration_keeps_the_published_widths():
    """Every number of the catalog's entry is in the file under its key but
    the three that are reduced, which stand beside their published counts;
    the model as run has the widths too, and the router its published width."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"Qwen3-Next-80B-A3B-Instruct"' in line)
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert CONFIG["source"] == row["source_url"] and CONFIG["reduced"] == reduced
    for key, value in row["config"].items():
        if key in reduced:
            assert CONFIG["published"][key] == value and CONFIG[key] < value, key
            continue
        assert CONFIG[key] == value, key
        if key in M:
            assert M[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"], CONFIG["vocab_size"]) == (12, 128, 37984)
    assert M["num_experts"] == 512 and M["experts_held"] == 128 and M["num_hidden_layers"] == 12
    assert M["vocab_size"] == 37984 == 151936 // 4 and "5,423,084,736" in CONFIG["deployment"]
    p = TRAFFIC["params"]
    assert p["prompt_width"] + p["max_new_tokens"] <= M["max_seq_len"] == 2560 and p["batch_size"] == p["clients"] == 16
    assert (p["prompt_len"], p["max_tokens"], p["n_requests"]) == (dict(lo=128, hi=2048), dict(lo=64, hi=512), 64)
    assert (p["warmup_seconds"], p["trace_seconds"], p["temperature"]) == (12, 1.5, 0.0)
    runs = p["canary"]["runs"]
    assert runs["count"] >= 16 and runs["tokens"] == 24 and runs["limits"]["past"] == 8  # the default chunk


def test_the_limits_admit_the_served_precision_and_refuse_the_controls():
    """The traffic file's limits on the chip's own readings (the engine's
    answers as served and under its three controls,
    ``expected/qwen3-next-80b-a3b-ep4-l12.readings.json``, written by
    ``make_expected_qwen3_next.py --engine``) and on the reference computed
    in fewer bits: the served bf16 passes both comparisons with room; a
    server that zeroes a row's state at admission fails on the runs and on
    the first tokens' successors while its prefill is whole; matrices at 3
    mantissa bits fail, in the engine and in the reference. The state
    rounded to bf16 after every step is recorded beside them: it can be told
    from the float32 state and it passes."""
    expected = json.load(open(EXPECTED + "serve_canary.json"))
    readings = json.load(open(EXPECTED + "readings.json"))
    teacher_limits = TRAFFIC["params"]["canary"]["teacher"]["limits"]
    runs_limits = TRAFFIC["params"]["canary"]["runs"]["limits"]
    medians = (("teacher_logprob_median_abs_diff", teacher_limits["median_logprob_tolerance"]),
               ("teacher_second_logprob_median_abs_diff", teacher_limits["second_median_logprob_tolerance"]),
               ("runs_logprob_median_abs_diff", runs_limits["median_logprob_tolerance"]))

    def judged(got):
        first, runs = got
        first, runs = [tuple(x) for x in first], [tuple(x) for x in runs]
        ok1, n1 = model_serve_closed.judge_teacher(first, expected["teacher"], teacher_limits)
        ok2, n2 = model_serve_closed_runs.judge_runs(runs, expected["runs"], runs_limits)
        return ok1, ok2, dict(n1, **n2)

    ok1, ok2, served = judged((readings["served"]["teacher"], readings["served"]["runs"]))
    assert ok1 and ok2 and served["runs"] == 24
    for key, limit in medians:
        assert 1.5 * served[key] < limit, key
    late_all, second_all = served["runs_positions_past_first_chunk"], served["teacher_second_positions"]
    assert served["runs_positions_past_first_chunk_compared"] > 1.2 * runs_limits["past_min_compared"] * late_all
    assert served["teacher_second_positions_compared"] > 1.1 * teacher_limits["second_min_compared"] * second_all

    ok1, ok2, zeroed = judged((readings["state-zeroed"]["teacher"], readings["state-zeroed"]["runs"]))
    assert not ok1 and not ok2
    assert zeroed["teacher_logprob_median_abs_diff"] == served["teacher_logprob_median_abs_diff"]  # the prefill is whole
    assert zeroed["runs_positions_past_first_chunk_compared"] < 0.8 * runs_limits["past_min_compared"] * late_all
    assert zeroed["runs_mismatch"] and zeroed["runs_mismatch"][0]["gap"] > runs_limits["gap_tolerance"]

    ok1, ok2, three_bits = judged((readings["mantissa3"]["teacher"], readings["mantissa3"]["runs"]))
    assert not ok1 and not ok2
    ok1, ok2, control = judged(as_served(json.load(open(EXPECTED + "mantissa3.serve_canary.json")), expected))
    assert not ok1 and not ok2
    for key, limit in (medians[0], medians[2]):
        assert three_bits[key] > 1.5 * limit and control[key] > 1.5 * limit, key

    # recorded, not required: what rounding the state to bf16 after every step and every prefill reads
    # (the engine's own; the reference with such a state was not computed at this size)
    ok1, ok2, state = judged((readings["state-bfloat16"]["teacher"], readings["state-bfloat16"]["runs"]))
    print("state rounded to bf16 every step:", state["teacher_second_logprob_median_abs_diff"],
          state["runs_logprob_median_abs_diff"], "against", served["teacher_second_logprob_median_abs_diff"],
          served["runs_logprob_median_abs_diff"])
    assert ok1 and ok2  # the limits do not guard it
    assert state["teacher_second_logprob_median_abs_diff"] > served["teacher_second_logprob_median_abs_diff"]
