"""The arithmetic of the readers and operation counts that came with the
``granite-4.0-h-micro`` configuration, on synthetic stamps and a synthetic
trace; the judgement of the runs on made-up answers; the traffic file's
limits against the chip's own readings and the controls; the configuration
file against the catalog's published numbers."""

import json
import os
import types

import pytest

from benchmark import decode_chunks, flops_granite_hybrid as fl, reduce_trace
from benchmark.drivers import model_serve_closed, model_serve_closed_runs
from benchmark.tests.test_metrics import reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs", "granite-4.0-h-micro.json")))
TRAFFIC = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "chat-closed-short.json")))
M = CONFIG["model"]["config"]
PEAKS = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
EXPECTED = os.path.join(ROOT, "benchmark", "reference", "expected", "granite-4.0-h-micro.")


def request(prompt_len, t_first, arrivals, asked=8):
    return dict(t_send=t_first - 0.2, t_first=t_first, t_done=arrivals[-1][0], arrivals=arrivals,
                asked=asked, prompt_len=prompt_len)


def ctx(requests=None, split=None, trace=None, slots=32):
    first, last = split or ({}, {})
    return types.SimpleNamespace(
        stamps=dict(t_open=100.0, t_close=110.0, requests=requests if requests is not None else [],
                    phase_split_open=first, healthz=dict(phase_split=last, decode_chunk=8, slots=slots),
                    phase_split_trace=None),
        trace=trace, peaks=PEAKS, config=CONFIG, traffic={}, run=types.SimpleNamespace(chips=1),
        device=dict(memory_peak_bytes=14 * 2 ** 30))


def test_granite_operations_and_bytes_from_shapes():
    assert fl.mamba_layers(M) == 36 and fl.attention_layers(M) == 4
    assert fl.mamba_inner(M) == 4096 and fl.conv_channels(M) == 4352 and fl.state_elements(M) == 524_288
    assert fl.mamba_matmul_params(M) == 2048 * 8512 + 4096 * 2048 == 25_821_184
    assert fl.attention_matmul_params(M) == 10_485_760
    mlp = 3 * 2048 * 8192
    want = 36 * 25_821_184 + 4 * 10_485_760 + 40 * mlp + 100352 * 2048
    assert fl.active_matmul_params(M) == want == 3_190_292_480  # all but the vectors of 3,191,396,096
    assert fl.active_matmul_params(M, head=False) == want - 100352 * 2048
    assert fl.step_flops(M) == 36 * (5 * 524_288 + 2 * 4 * 4352)
    assert fl.decode_flops(M, 300) == 2.0 * want + fl.step_flops(M) + 4 * 4 * 300 * 32 * 64
    # a 512-wide prompt: two chunks of 256; a 100-wide one: one chunk of 100
    per_chunk = 2 * 256 * 256 * 128 + 2 * 256 * 256 * 64 * 64 + 4 * 256 * 64 * 128 * 64
    assert fl.scan_flops(M, 512) == 36 * (2 * per_chunk + 512 * 2 * 4 * 4352)
    assert fl.scan_flops(M, 100) == 36 * (2 * 100 * 100 * 128 + 2 * 100 * 100 * 4096 + 4 * 100 * 4096 * 128
                                          + 100 * 2 * 4 * 4352)
    assert fl.prefill_flops(M, 100) == (2.0 * 100 * (want - 100352 * 2048) + fl.scan_flops(M, 100)
                                         + 4 * 4 * (100 * 100 / 2) * 32 * 64 + 2.0 * 100352 * 2048)
    # the issue's arithmetic for a slot and a step
    assert fl.state_bytes_per_slot(M) == 75_497_472 + 940_032 and fl.kv_bytes_per_slot(M, 768) == 6_291_456
    assert fl.held_param_bytes(M) == 2 * want
    assert fl.decode_step_bytes(M, 32) == 2 * want + 32 * 2 * 76_437_504 + 32 * 6_291_456 == 11_473_911_808


def test_granite_window_counts_what_arrived_inside_at_its_own_context():
    inside = request(300, 101.0, [(101.0, 1), (102.0, 4), (103.0, 3)])
    straddles = request(200, 99.0, [(99.0, 2), (100.5, 6)])  # prefilled before the window
    no_length = dict(inside, prompt_len=None)
    got = fl.window_flops(M, [inside, straddles, no_length], 100.0, 110.0)
    want = (fl.prefill_flops(M, 300) + fl.decode_flops(M, 300) + 4 * fl.decode_flops(M, 300 + 1 + 1.5)
            + 3 * fl.decode_flops(M, 300 + 5 + 1) + 6 * fl.decode_flops(M, 200 + 2 + 2.5))
    assert got == pytest.approx(want)
    share = reader("layer_metrics", "serve_mfu_share_hybrid")(ctx([inside, straddles]))
    assert share == pytest.approx(100 * want / (10.0 * 197e12)) and 0 < share < 100


def test_granite_pad_share_is_the_counters_quotient():
    first = {"prefill_tokens_real_n": 1000, "prefill_tokens_padded_n": 1500}
    last = {"prefill_tokens_real_n": 1000 + 17400, "prefill_tokens_padded_n": 1500 + 25800}
    got = reader("layer_metrics", "serve_prefill_pad_share")(ctx(split=(first, last)))
    assert got == pytest.approx(100 * (1 - 17400 / 25800))


def synthetic_trace():
    """Three decode chunks of 144 ms (8 steps of 18 ms), a prefill between them."""
    ms = 1_000_000
    modules = [(0, 144 * ms, "jit_chunk(123)"), (144 * ms, 160 * ms, "jit_prefill_row(7)"),
               (160 * ms, 304 * ms, "jit_chunk(123)"), (304 * ms, 448 * ms, "jit_chunk(123)")]
    ops = [(s, e, "%while.3 = (s32[], f32[32,64,64,128]{3,2,1,0}) while(...)") for s, e, _ in modules]
    marks = {reduce_trace.MARK_START: [(0, 1)], reduce_trace.MARK_STOP: [(448 * ms - 1, 448 * ms)]}
    return reduce_trace.Trace({"/device:TPU:0": dict(ops=ops, modules=modules)}, marks)


def test_granite_decode_step_roofline_takes_the_chunks_median_step():
    c = ctx(trace=synthetic_trace())
    assert len(decode_chunks.executions(c)) == 3
    assert reader("layer_metrics", "serve_decode_step_device_s")(c) == pytest.approx(0.018)
    whole = reader("layer_metrics", "serve_decode_step_roofline")(c)
    assert whole == pytest.approx(100 * (11_473_911_808 / 819e9) / 0.018) and 70 < whole < 100
    # another slot count moves the bytes: 16 slots have 8.93 GB to move a step
    assert reader("layer_metrics", "serve_decode_step_roofline")(ctx(trace=synthetic_trace(), slots=16)) == pytest.approx(
        100 * (fl.decode_step_bytes(M, 16) / 819e9) / 0.018)


@pytest.mark.parametrize("name", ["serve_mfu_share_hybrid", "serve_decode_step_roofline", "serve_prefill_pad_share"])
def test_granite_readers_with_nothing_to_read_give_none(name):
    """Another driver's stamps, another configuration, a program without the
    counters (the parent), or no trace: the metric is left out, nothing raises."""
    bare = ctx()
    bare.stamps = dict(cycles=[], t_open=100.0)
    assert reader("layer_metrics", name)(bare) is None
    other = ctx(requests=[request(10, 101.0, [(101.0, 1)])], split=({}, {"chunks_n": 5}), trace=synthetic_trace())
    other.config = dict(model=dict(config=dict(num_experts_per_tok=4)), trace_names=dict(decode_chunk="^jit_chunk"))
    assert reader("layer_metrics", name)(other) is None


def test_judge_runs_on_made_up_answers():
    judge = model_serve_closed_runs.judge_runs
    n = 24
    runs = [dict(prompt=[1] * 40, tokens=list(range(100, 100 + n)), top2_gap=[0.3] * n, logprobs=[-9.0] * n),
            dict(prompt=[1] * 300, tokens=list(range(200, 200 + n)), top2_gap=[0.3] * 20 + [0.001] + [0.3] * 3,
                 logprobs=[-9.0] * n)]
    limits = dict(gap_tolerance=0.05, median_logprob_tolerance=0.02, past=8, past_min_compared=0.75)
    near = [(r["tokens"], [-9.01] * n) for r in runs]
    ok, numbers = judge(near, runs, limits)
    assert ok and numbers["runs_whole"] == 2 and numbers["runs_positions_compared"] == 48
    assert numbers["runs_positions_past_first_chunk"] == numbers["runs_positions_past_first_chunk_compared"] == 32
    assert numbers["runs_logprob_median_abs_diff"] == pytest.approx(0.01)
    # a late differing token where the reference stands at a near-tie ends that run and is no fault ...
    tie = [near[0], (runs[1]["tokens"][:20] + [7] * 4, [-9.01] * n)]
    ok, numbers = judge(tie, runs, limits)
    assert ok and numbers["runs_positions_past_first_chunk_compared"] == 16 + 12 and numbers["runs_whole"] == 1
    # ... at a wide gap it is one
    late = [near[0], (runs[1]["tokens"][:15] + [7] * 9, [-9.01] * n)]
    ok, numbers = judge(late, runs, limits)
    assert not ok and numbers["runs_mismatch"][0]["position"] == 15 and numbers["runs_mismatch"][0]["prompt_len"] == 300
    # too few positions past the 8th compared is no comparison of the second chunk: both runs
    # leave the reference's at near-ties in the first chunk
    for r in runs:
        r["top2_gap"][9] = 0.001
    early = [(r["tokens"][:9] + [7] * 15, [-9.01] * n) for r in runs]
    ok, numbers = judge(early, runs, limits)
    assert not ok and not numbers["runs_mismatch"] and numbers["runs_positions_past_first_chunk_compared"] == 2
    # every log-probability a little further off
    assert not judge([(t, [-9.03] * n) for t, _ in near], runs, limits)[0]
    # a server that lost the state answers other tokens from the first decode step on
    assert not judge([(r["tokens"][:1] + [7] * 23, [-9.0] * n) for r in runs], runs, limits)[0]
    with pytest.raises(ValueError):
        judge(near[:1], runs, limits)
    with pytest.raises(ValueError):
        judge([(near[0][0][:5], near[0][1][:5]), near[1]], runs, limits)


def test_the_runs_driver_puts_both_comparisons_in_the_one_place_and_takes_them_out_again(monkeypatch):
    seen = []
    monkeypatch.setattr(model_serve_closed, "check_teacher", lambda port, run, checks: seen.append("first") or True)
    monkeypatch.setattr(model_serve_closed_runs, "check_runs", lambda port, run, checks: seen.append("runs") or False)
    before = model_serve_closed.check_teacher
    monkeypatch.setattr(model_serve_closed, "run", lambda run: model_serve_closed.check_teacher(1, run, {}))
    assert model_serve_closed_runs.run(types.SimpleNamespace()) is False and seen == ["first", "runs"]
    assert model_serve_closed.check_teacher is before


def test_granite_configuration_keeps_the_published_widths():
    """Every number of the catalog's entry is in the file under its key;
    nothing is reduced; the model as run has them too."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"granite-4.0-h-micro"' in line)
    assert CONFIG["source"] == row["source_url"] and CONFIG["reduced"] == []
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key
        if key in M:
            assert M[key] == value, key
    assert M["layer_types"].count("mamba") == 36 and M["max_seq_len"] == 768
    p = TRAFFIC["params"]
    assert p["prompt_width"] + p["max_new_tokens"] <= M["max_seq_len"] and p["batch_size"] == p["clients"] == 16
    runs = p["canary"]["runs"]
    assert runs["count"] >= 16 and runs["tokens"] == 24 and runs["limits"]["past"] == 8  # the default chunk


def as_served(control: dict, expected: dict):
    """A reference control's file (what the reference in fewer bits says at
    the expected file's positions) in the form the judges take."""
    first = []
    for said, want in zip(control["teacher"], expected["teacher"]):
        second = dict(zip(want["second_at"], zip(said["second_tokens"], said["second_logprobs"])))
        for j, (token, logprob) in enumerate(zip(said["tokens"], said["logprobs"])):
            first.append(([token, second[j][0]], [logprob, second[j][1]]) if j in second else ([token], [logprob]))
    return first, [(r["tokens"], r["logprobs"]) for r in control["runs"]]


def test_the_limits_admit_the_served_precision_and_refuse_the_controls():
    """The traffic file's limits on the chip's own readings (recorded by the
    builder, ``expected/granite-4.0-h-micro.readings.json``: what the engine
    wrote as served and under three controls) and on the reference computed
    in fewer bits: the served bf16 passes both comparisons with room; a
    server that zeroes a row's recurrent state at admission fails on the
    runs *and* on the first tokens' successors while its prefill is whole;
    matrices at 3 mantissa bits fail by the medians, in the engine and in the
    reference. The state rounded to bf16 after every step is recorded beside
    them, whatever it reads."""
    expected = json.load(open(EXPECTED + "serve_canary.json"))
    readings = json.load(open(EXPECTED + "readings.json"))
    teacher_limits = TRAFFIC["params"]["canary"]["teacher"]["limits"]
    runs_limits = TRAFFIC["params"]["canary"]["runs"]["limits"]

    def judged(got):
        first, runs = got
        ok1, n1 = model_serve_closed.judge_teacher([tuple(x) for x in first], expected["teacher"], teacher_limits)
        ok2, n2 = model_serve_closed_runs.judge_runs([tuple(x) for x in runs], expected["runs"], runs_limits)
        return ok1, ok2, dict(n1, **n2)

    ok1, ok2, served = judged((readings["served"]["teacher"], readings["served"]["runs"]))
    assert ok1 and ok2 and served["teacher_positions"] == 58 and served["runs"] == 24
    for key, limit in (("teacher_logprob_median_abs_diff", teacher_limits["median_logprob_tolerance"]),
                       ("teacher_second_logprob_median_abs_diff", teacher_limits["second_median_logprob_tolerance"]),
                       ("runs_logprob_median_abs_diff", runs_limits["median_logprob_tolerance"])):
        assert 2.5 * served[key] < limit, key
    assert served["runs_positions_past_first_chunk_compared"] == 384 > 1.3 * runs_limits["past_min_compared"] * 384
    assert served["teacher_second_positions_compared"] == 28 > 1.15 * teacher_limits["second_min_compared"] * 30

    lost = readings["state_zeroed_at_admission"]
    ok1, ok2, zeroed = judged((lost["teacher"], lost["runs"]))
    assert not ok1 and not ok2
    assert zeroed["teacher_logprob_median_abs_diff"] == served["teacher_logprob_median_abs_diff"]  # the prefill is whole
    # 20 of 30 second tokens and 167 of 384 late positions survive a lost state (wide gaps), far off
    assert zeroed["teacher_second_positions_compared"] == 20 < 0.85 * teacher_limits["second_min_compared"] * 30
    assert zeroed["runs_positions_past_first_chunk_compared"] == 167 < 0.6 * runs_limits["past_min_compared"] * 384
    assert zeroed["teacher_second_logprob_median_abs_diff"] > 10 * teacher_limits["second_median_logprob_tolerance"]
    assert zeroed["runs_logprob_median_abs_diff"] > 10 * runs_limits["median_logprob_tolerance"]
    assert zeroed["runs_mismatch"] and zeroed["runs_mismatch"][0]["gap"] > 10 * runs_limits["gap_tolerance"]

    coarse = readings["matrices_3_mantissa_bits"]
    ok1, ok2, three_bits = judged((coarse["teacher"], coarse["runs"]))
    assert not ok1 and not ok2
    assert three_bits["teacher_logprob_median_abs_diff"] > 2.5 * teacher_limits["median_logprob_tolerance"]
    assert three_bits["runs_logprob_median_abs_diff"] > 2.5 * runs_limits["median_logprob_tolerance"]
    ok1, ok2, control = judged(as_served(json.load(open(EXPECTED + "mantissa3.serve_canary.json")), expected))
    assert not ok1 and not ok2
    assert control["teacher_logprob_median_abs_diff"] > 2.5 * teacher_limits["median_logprob_tolerance"]
    assert control["runs_logprob_median_abs_diff"] > 2.5 * runs_limits["median_logprob_tolerance"]

    # recorded, not required: what rounding the state to bf16 after every step reads
    rounded = readings["state_bf16_every_step"]
    _, _, state = judged((rounded["teacher"], rounded["runs"]))
    _, _, state_control = judged(as_served(json.load(open(EXPECTED + "state-bfloat16.serve_canary.json")), expected))
    print("state rounded to bf16 every step:", state["teacher_second_logprob_median_abs_diff"],
          state["runs_logprob_median_abs_diff"], state_control["runs_logprob_median_abs_diff"])
    assert state["runs_logprob_median_abs_diff"] < runs_limits["median_logprob_tolerance"]  # the limits do not guard it
    assert state["runs_logprob_median_abs_diff"] > served["runs_logprob_median_abs_diff"]
