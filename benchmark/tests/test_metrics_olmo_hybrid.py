"""The arithmetic of the readers and operation counts that came with the
``olmo-hybrid-7b-pp4-l8`` configuration, on synthetic stamps and a synthetic
trace; the traffic file's limits against the chip's own readings and the
controls; the configuration file against the catalog's published numbers;
a CPU rehearsal of the cell, traced and untraced."""

import json
import os
import types

import pytest

from benchmark import flops_olmo_hybrid as fl, reduce_trace
from benchmark.drivers import model_serve_closed, model_serve_closed_runs
from benchmark.tests.test_metrics import reader
from benchmark.tests.test_metrics_granite_hybrid import as_served
from benchmark.tests.test_rehearsal import bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "olmo-hybrid-7b-pp4-l8"
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")))
TRAFFIC = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "longdoc-closed-4.json")))
M = CONFIG["model"]["config"]
PEAKS = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
EXPECTED = os.path.join(ROOT, "benchmark", "reference", "expected", NAME + ".")
CELL = "olmo-hybrid-serve-longdoc-4"
MINE = ["gdn_prefill_scan_roofline", "olmo_decode_step_roofline", "prefill_flash_roofline_serve",
        "serve_kv_valid_share", "serve_mfu_share_olmo", "serve_prefill_share_of_busy"]


def request(prompt_len, t_first, arrivals, asked=8):
    return dict(t_send=t_first - 0.2, t_first=t_first, t_done=arrivals[-1][0], arrivals=arrivals,
                asked=asked, prompt_len=prompt_len)


def ctx(requests=None, split=None, trace=None, around=None, slots=4):
    first, last = split or ({}, {})
    return types.SimpleNamespace(
        stamps=dict(t_open=100.0, t_close=110.0, requests=requests if requests is not None else [],
                    phase_split_open=first, healthz=dict(phase_split=last, decode_chunk=8, slots=slots),
                    phase_split_trace=around),
        trace=trace, peaks=PEAKS, config=CONFIG, traffic={}, run=types.SimpleNamespace(chips=1),
        device=dict(memory_peak_bytes=12 * 2 ** 30))


def test_olmo_hybrid_operations_and_bytes_from_shapes():
    assert fl.delta_layers(M) == 6 and fl.attention_layers(M) == 2 and fl.head_dim(M) == 128
    assert fl.conv_channels(M) == 11_520 and fl.state_elements(M) == 30 * 96 * 192 == 552_960
    # ISSUE 56's arithmetic, less the vectors: [q ; k ; v ; z], [b ; a], W_o; q, k, v, o; the SwiGLU
    assert fl.delta_matmul_params(M) == 3840 * 17_280 + 3840 * 60 + 5760 * 3840 == 88_704_000
    assert fl.attention_matmul_params(M) == 4 * 3840 * 3840 == 58_982_400
    assert fl.mlp_matmul_params(M) == 126_812_160
    assert fl.layers_matmul_params(M) == 6 * 88_704_000 + 2 * 58_982_400 + 8 * 126_812_160 == 1_664_686_080
    assert fl.head_params(M) == 385_351_680
    assert fl.matrix_bytes(M) == 2 * (1_664_686_080 + 385_351_680) == 4_100_075_520  # "4.10 GB of matrices"
    assert fl.step_flops(M) == 6 * (7 * 552_960 + 2 * 4 * 11_520)
    assert fl.decode_flops(M, 3560) == 2.0 * 2_050_037_760 + fl.step_flops(M) + 2 * 4 * 3560 * 30 * 128
    # a 150-wide prompt: three chunks of 64; a 40-wide one: one chunk of 40
    carry = 30 * (6 * 64 * 96 * 192 + 2 * 64 * 64 * 192 + 96 * 192)
    assert fl.scan_carry_flops(M, 150) == 6 * 3 * carry
    per_chunk = 30 * 4 * 64 * 64 * 96 + 30 * (64 ** 3 / 3 + 2 * 64 * 64 * (192 + 96))
    assert fl.scan_flops(M, 150) == 6 * (3 * per_chunk + 150 * 2 * 4 * 11_520) + 6 * 3 * carry
    assert fl.scan_flops(M, 40) == (6 * (30 * 4 * 40 * 40 * 96 + 30 * (40 ** 3 / 3 + 2 * 40 * 40 * 288) + 40 * 8 * 11_520)
                                    + 6 * 30 * (6 * 40 * 96 * 192 + 2 * 40 * 40 * 192 + 96 * 192))
    # the loop's operands as they are carried: three Q x dk and the Q x Q block in bf16, U and the output in float32
    assert fl.scan_carry_bytes(M, 128) == 6 * 2 * 30 * (2 * (3 * 64 * 96 + 64 * 64) + 4 * 2 * 64 * 192)
    # the tiled walk: the causal half of 8,192 squared in two layers, a third of a percent... of nothing: 3.6% of a prefill
    assert fl.flash_causal_flops(M, 8192) == 2 * 4 * (8192 * 8192 / 2) * 30 * 128 == 1.030792151040e12
    assert fl.flash_bytes(M, 8192) == 2 * 2 * 8192 * 128 * 2 * 60
    assert fl.prefill_flops(M, 700) == (2.0 * 700 * 1_664_686_080 + fl.scan_flops(M, 700)
                                        + fl.flash_causal_flops(M, 700) + 2.0 * 385_351_680)
    assert 0.034 < fl.flash_causal_flops(M, 8192) / fl.prefill_flops(M, 8192) < 0.038
    assert 0.009 < fl.scan_flops(M, 8192) / fl.prefill_flops(M, 8192) < 0.012
    # a decode step: a position of two ungrouped layers is 30,720 bytes, a row of 8,704 is 267 MB, a state 13.7 MB
    assert fl.kv_bytes(M, 1) == 2 * 2 * 30 * 128 * 2 == 30_720 and fl.kv_bytes(M, 8704) == 267_386_880
    assert fl.state_bytes_per_slot(M) == 6 * (4 * 552_960 + 2 * 3 * 11_520) == 13_685_760
    assert fl.decode_step_bytes(M, 4, 4 * 3560) == 4_100_075_520 + 4 * 3560 * 30_720 + 4 * 2 * 13_685_760
    assert fl.decode_step_bytes(M, 4, 4 * 8704) / 819e9 == pytest.approx(6.446e-3, rel=1e-3)  # the rows read whole


def test_olmo_hybrid_window_counts_what_arrived_inside():
    reqs = [request(2000, 101.0, [(101.0, 1), (101.5, 8)]),  # the prompt and 9 tokens inside
            request(5000, 99.5, [(99.5, 1), (100.5, 8)]),  # its prompt before the window: 8 tokens inside
            request(100, 111.0, [(111.0, 1)])]  # after it
    want = (fl.prefill_flops(M, 2000) + fl.decode_flops(M, 2000) + 8 * fl.decode_flops(M, 2000 + 1 + 3.5)
            + 8 * fl.decode_flops(M, 5000 + 1 + 3.5))
    assert fl.window_flops(M, reqs, 100.0, 110.0) == pytest.approx(want)
    got = reader("layer_metrics", "serve_mfu_share_olmo")(ctx(requests=reqs))
    assert got == pytest.approx(100 * want / (10.0 * 197e12)) and 0 < got < 100


def test_olmo_hybrid_kv_valid_share_is_the_counters_quotient():
    first = {"kv_positions_valid_n": 1_000, "kv_positions_held_n": 10_000}
    last = {"kv_positions_valid_n": 1_000 + 14_240 * 800, "kv_positions_held_n": 10_000 + 4 * 8704 * 800}
    got = reader("layer_metrics", "serve_kv_valid_share")(ctx(split=(first, last)))
    assert got == pytest.approx(100 * 14_240 / (4 * 8704)) and 40 < got < 42


MS = 1_000_000
FLASH = ('%branch_1_fun.{i} = (bf16[30,{w},128]{{2,1,0:T(8,128)(2,1)}}, f32[30,8,{w}]{{2,1,0:T(8,128)}}) custom-call(bf16[30,{w},128]{{2,1,0}} %a, '
         'bf16[30,{w},128]{{2,1,0}} %b, bf16[30,{w},128]{{2,1,0}} %c), custom_call_target="tpu_custom_call"')
LOOP = ('%while.{i} = (s32[], f32[1,30,1,96,192]{{4,3,2,1,0}}, f32[{c},1,30,1,64,192]{{5,4,3,2,1,0}}, '
        'f32[{c},1,30,1,64,96]{{5,4,3,2,1,0}}) while(%tuple.{i}), condition=%cond, body=%body')


def synthetic_trace():
    """Two decode chunks of 72 ms (8 steps of 9 ms) around a 4,096-wide
    prefill of 200 ms (two flash calls of 6 ms, six scan loops of 5 ms) and
    a gap of 28 ms in which the device does nothing."""
    modules = [(0, 72 * MS, "jit_chunk(123)"), (72 * MS, 272 * MS, "jit_prefill_row(7)"),
               (300 * MS, 372 * MS, "jit_chunk(123)")]
    ops = [(s, e, "%fusion.1 = bf16[4,3840]{1,0} fusion(...)") for s, e, _ in modules]
    for i in range(2):
        at = (80 + 50 * i) * MS
        ops.append((at, at + 6 * MS, FLASH.format(i=i, w=4096)))
    for i in range(6):
        at = (100 + 20 * i) * MS
        ops.append((at, at + 5 * MS, LOOP.format(i=i, c=64)))
    marks = {reduce_trace.MARK_START: [(0, 1)], reduce_trace.MARK_STOP: [(372 * MS - 1, 372 * MS)]}
    return reduce_trace.Trace({"/device:TPU:0": dict(ops=ops, modules=modules)}, marks)


AROUND = [{"kv_positions_valid_n": 5_000_000, "row_steps_n": 3_200},
          {"kv_positions_valid_n": 5_000_000 + 16 * 14_000, "row_steps_n": 3_200 + 64}]


def test_olmo_hybrid_step_roofline_takes_the_rows_real_lengths_and_the_chunks_median_step():
    c = ctx(trace=synthetic_trace(), around=AROUND)
    assert reader("layer_metrics", "serve_decode_step_device_s")(c) == pytest.approx(0.009)
    got = reader("layer_metrics", "olmo_decode_step_roofline")(c)  # 16 steps read 14,000 valid positions each
    assert got == pytest.approx(100 * (fl.decode_step_bytes(M, 4, 14_000) / 819e9) / 0.009) and 55 < got < 70
    # the same step charged for whole rows would read higher: that is what the metric must not do
    assert got < 100 * (fl.decode_step_bytes(M, 4, 4 * 8704) / 819e9) / 0.009


def test_olmo_hybrid_prefill_readers_take_each_calls_own_width():
    c = ctx(trace=synthetic_trace(), around=AROUND, requests=[request(3000, 101.0, [(101.0, 1)])])
    one_attention = dict(M, num_hidden_layers=1, layer_types=["full_attention"])
    least = fl.flash_causal_flops(one_attention, 4096) / 197e12
    assert least > fl.flash_bytes(one_attention, 4096) / 819e9  # bound by its operations
    assert reader("layer_metrics", "prefill_flash_roofline_serve")(c) == pytest.approx(100 * least / 0.006)
    one_delta = dict(M, num_hidden_layers=1, layer_types=["linear_attention"])
    by_flops, by_bytes = fl.scan_carry_flops(one_delta, 4096) / 197e12, fl.scan_carry_bytes(one_delta, 4096) / 819e9
    got = reader("layer_metrics", "gdn_prefill_scan_roofline")(c)
    assert got == pytest.approx(100 * max(by_flops, by_bytes) / 0.005) and 0 < got < 100
    # 200 ms of prefill in 344 ms of busy device
    assert reader("layer_metrics", "serve_prefill_share_of_busy")(c) == pytest.approx(100 * 200 / 344)


@pytest.mark.parametrize("name", MINE)
def test_olmo_hybrid_readers_with_nothing_to_read_give_none(name):
    """Another driver's stamps, another configuration, a program without the
    counters or the scopes (the parent), or no trace: the metric is left out,
    nothing raises."""
    bare = ctx()
    bare.stamps = dict(cycles=[], t_open=100.0)
    assert reader("layer_metrics", name)(bare) is None
    other = ctx(requests=[request(10, 101.0, [(101.0, 1)])], split=({}, {"chunks_n": 5}), trace=synthetic_trace(),
                around=[{"chunks_n": 1}, {"chunks_n": 4}])
    other.config = dict(model=dict(config=dict(num_experts_per_tok=4)), trace_names=dict(decode_chunk="^jit_chunk"))
    assert reader("layer_metrics", name)(other) is None


def test_olmo_hybrid_cell_and_metrics_are_in_the_benchmark():
    bench_file = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench_file["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "longdoc-closed-4", 1)
    assert bench_file["workloads"][-1] is cell and bench_file["configs"][-1]["name"] == NAME  # new entries at the end
    mine = {m["name"]: m for m in bench_file["per_layer"] if m.get("workloads") == [CELL]}
    assert sorted(mine) == MINE and [m["name"] for m in bench_file["per_layer"][-6:]] == [
        "serve_mfu_share_olmo", "olmo_decode_step_roofline", "gdn_prefill_scan_roofline",
        "prefill_flash_roofline_serve", "serve_prefill_share_of_busy", "serve_kv_valid_share"]
    assert all(m["moves"] == "serve_tokens_per_s" for m in mine.values())
    listed = {m["name"] for m in bench_file["per_layer"] + bench_file["end_to_end"] if CELL in m.get("workloads", [])}
    assert {"serve_tokens_per_s", "serve_ttft_p50_s", "serve_ttft_p90_s", "serve_host_frac",
            "device_idle_share_serve", "hbm_peak_gib_serve", "serve_inbox_wait_s", "serve_queue_wait_s",
            "serve_admit_to_first_token_s", "serve_slot_occupancy", "serve_decode_step_device_s",
            "serve_prefill_pad_share", "compiles_in_window", "setup_programs"} <= listed
    # the readers that import another model's counts are left alone
    assert not {"gdn_decode_step_roofline", "serve_decode_step_roofline", "serve_mfu_share_gdn",
                "serve_mfu_share_hybrid", "moe_experts_touched_per_step"} & listed


def test_olmo_hybrid_configuration_keeps_the_published_widths():
    """Every key of the catalog's entry is in the file under its name and
    with its value but the depth, which stands beside its published count;
    the model as run has every width, the first eight layers' kinds and the
    whole vocabulary."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"Olmo-Hybrid-7B"' in line)
    assert CONFIG["source"] == row["source_url"] and CONFIG["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert CONFIG["published"][key] == value == 32 and CONFIG[key] == M[key] == 8
            continue
        assert CONFIG[key] == value, key
        if key in M and key != "layer_types":
            assert M[key] == value, key
    assert M["layer_types"] == row["config"]["layer_types"][:8] == (["linear_attention"] * 3 + ["full_attention"]) * 2
    assert "2,435,748,072" in CONFIG["cut"]["arithmetic"] and "four pipeline stages" in CONFIG["deployment"]
    assert {"block_order", "qk_norm", "no_rope", "head_dim", "init", "max_seq_len", "chunked_form"} <= set(CONFIG["assumed"])
    p = TRAFFIC["params"]
    assert p["prompt_width"] + p["max_new_tokens"] == M["max_seq_len"] == 8704 and p["batch_size"] == p["clients"] == 4
    assert (p["prompt_len"], p["max_tokens"], p["n_requests"]) == (dict(lo=1024, hi=8192), dict(lo=64, hi=384), 32)
    assert (p["warmup_seconds"], p["temperature"]) == (12, 0.0) and 1.0 <= p["trace_seconds"] <= 2.0
    assert TRAFFIC["driver"] == "model_serve_closed_runs"
    runs, teacher = p["canary"]["runs"], p["canary"]["teacher"]
    assert runs["tokens"] == 24 and runs["limits"]["past"] == 8 and teacher["length"] == p["prompt_width"]
    # the traffic as the cell's ``why`` states it: one fixed list, every bucket about a third of it
    from benchmark.drivers.serve_closed import make_requests
    pairs = [(len(prompt), n) for prompt, n in make_requests(p, CONFIG["vocab_size"], 11)]
    lengths = sorted(n for n, _ in pairs)
    assert (lengths[0], lengths[-1], sum(lengths) / 32) == (1061, 7699, 3464.75)
    assert [sum(lo < n <= hi for n in lengths) for lo, hi in ((0, 2048), (2048, 4096), (4096, 8192))] == [11, 10, 11]
    assert sum(n for _, n in pairs) / 32 == 223.65625 and sorted(pairs) == sorted(
        (len(prompt), n) for prompt, n in make_requests(p, CONFIG["vocab_size"], 2 ** 31 + 5))  # a seed only reorders


def test_olmo_hybrid_limits_admit_the_served_precision_and_refuse_the_controls():
    """The traffic file's limits on the chip's own readings (the engine's
    answers as served and under its four controls,
    ``expected/olmo-hybrid-7b-pp4-l8.readings.json``, written by
    ``make_expected_olmo_hybrid.py --engine``) and on the reference computed
    in fewer bits: the served bf16 passes both comparisons with room; a
    server that zeroes a row's state at admission fails on the runs and on
    the first tokens' successors while its prefill is whole; matrices at 3
    mantissa bits fail, in the engine and in the reference; ``beta`` without
    its factor 2 fails everywhere; a tiled prefill that does not mask the
    left pad fails on the first tokens."""
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

    def of(control):
        return judged((readings[control]["teacher"], readings[control]["runs"]))

    ok1, ok2, served = of("served")
    assert ok1 and ok2 and served["runs"] == TRAFFIC["params"]["canary"]["runs"]["count"]
    for key, limit in medians:
        assert 1.5 * served[key] < limit, key
    late_all, second_all = served["runs_positions_past_first_chunk"], served["teacher_second_positions"]
    assert served["runs_positions_past_first_chunk_compared"] > 1.2 * runs_limits["past_min_compared"] * late_all
    assert served["teacher_second_positions_compared"] > 1.1 * teacher_limits["second_min_compared"] * second_all

    ok1, ok2, zeroed = of("state-zeroed")
    assert not ok1 and not ok2
    assert zeroed["teacher_logprob_median_abs_diff"] == served["teacher_logprob_median_abs_diff"]  # the prefill is whole
    assert zeroed["runs_positions_past_first_chunk_compared"] < 0.8 * runs_limits["past_min_compared"] * late_all

    ok1, ok2, three_bits = of("mantissa3")
    assert not ok1 and not ok2
    ok1, ok2, control = judged(as_served(json.load(open(EXPECTED + "mantissa3.serve_canary.json")), expected))
    assert not ok1 and not ok2
    for key, limit in (medians[0], medians[2]):
        assert three_bits[key] > 1.5 * limit and control[key] > 1.5 * limit, key

    ok1, ok2, halved = of("beta-halved")
    assert not ok1 and not ok2

    ok1, _, unmasked = of("pad-unmasked")  # the rows' keys and values are whole: only the prefill's own answer is not
    assert not ok1
    assert (unmasked["teacher_mismatch"] and unmasked["teacher_mismatch"][0]["gap"] > teacher_limits["gap_tolerance"]
            or unmasked["teacher_logprob_median_abs_diff"] > 1.5 * teacher_limits["median_logprob_tolerance"]
            or 2 * unmasked["teacher_positions_compared"] < unmasked["teacher_positions"])


@pytest.mark.parametrize("trace", [0, 1])
def test_olmo_hybrid_cell_rehearses_with_its_counters(trace):
    """The cell's own driver once on the host, traced and untraced: ``correct``
    by the three comparisons, and traced the counter-fed metric is in the
    line (the device's are not: the host has no device trace)."""
    got = bench("--workload", CELL, "--seed", "3000000017", "--seconds", "5", "--trace", str(trace), "--rehearse")
    assert got.returncode == 0, got.stderr[-2000:]
    body = json.loads(got.stdout.strip().splitlines()[-1])["cpu_rehearsal"]
    assert body["correct"] is True and body["failed"] == 0 and body["attempted"] > 0, body["checks"]
    assert body["checks"]["runs_ok"] and body["checks"]["teacher_ok"] and body["checks"]["programs_compiled_in_window"] == 0
    if trace:
        assert 0 < body["metrics"]["serve_kv_valid_share"]["value"] < 100
        assert "serve_prefill_pad_share" in body["metrics"] and "olmo_decode_step_roofline" not in body["metrics"]
    else:
        assert set(body["metrics"]) == {"serve_tokens_per_s", "setup_s"}
