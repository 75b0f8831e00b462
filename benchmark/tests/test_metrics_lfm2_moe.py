"""The arithmetic of the readers and operation counts that came with the
``lfm2-24b-a2b-l10`` configuration, on synthetic stamps and a synthetic
trace; the configuration file against the catalog's published numbers; and
the driver's refusal of a program that lacks the family."""

import difflib
import hashlib
import inspect
import json
import os
import types

import pytest

from benchmark import decode_chunks, flops_lfm2_moe, reduce_trace
from benchmark.drivers import model_serve_closed, serve_closed
from benchmark.harness import RunFailed
from benchmark.tests.test_metrics import reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b-l10.json")))
M = CONFIG["model"]["config"]
PEAKS = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
EXPERT = 3 * 2048 * 1536  # one expert's SwiGLU, elements


def request(prompt_len, t_first, arrivals, asked=8):
    return dict(t_send=t_first - 0.2, t_first=t_first, t_done=arrivals[-1][0], arrivals=arrivals,
                asked=asked, prompt_len=prompt_len)


def ctx(requests=None, split=None, around_trace=None, trace=None):
    first, last = split or ({}, {})
    return types.SimpleNamespace(
        stamps=dict(t_open=100.0, t_close=110.0, requests=requests if requests is not None else [],
                    phase_split_open=first, healthz=dict(phase_split=last, decode_chunk=8),
                    phase_split_trace=around_trace),
        trace=trace, peaks=PEAKS, config=CONFIG, traffic={}, run=types.SimpleNamespace(chips=1),
        device=dict(memory_peak_bytes=11 * 2 ** 30))


def test_lfm2_operations_from_shapes():
    assert flops_lfm2_moe.layer_kinds(M) == ["conv", "conv", "full_attention", "conv", "conv",
                                             "conv", "full_attention", "conv", "conv", "conv"]
    assert flops_lfm2_moe.attention_layers(M) == 2 and flops_lfm2_moe.expert_layers(M) == 8
    assert flops_lfm2_moe.expert_matrix_elements(M) == EXPERT == 9_437_184
    # 8 convolutions of 4 d^2, 2 attentions (q and o of d^2, k and v of d x 512), 2 dense
    # SwiGLUs of 11776, 8 x (router + 4 experts), the tied head
    want = (8 * 4 * 2048 * 2048 + 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 2 * 3 * 2048 * 11776
            + 8 * (2048 * 64 + 4 * EXPERT) + 65536 * 2048)
    assert flops_lfm2_moe.active_matmul_params(M) == want == 737_148_928
    assert flops_lfm2_moe.active_matmul_params(M, head=False) == want - 65536 * 2048
    assert flops_lfm2_moe.decode_flops(M, 500) == 2.0 * want + 2 * 4 * 500 * 32 * 64
    assert flops_lfm2_moe.prefill_flops(M, 100) == (
        2.0 * 100 * (want - 65536 * 2048) + 2 * 4 * (100 * 100 / 2) * 32 * 64 + 2.0 * 65536 * 2048)
    assert flops_lfm2_moe.moe_gmm_flops(M, 64) == 64 * 2 * EXPERT
    assert flops_lfm2_moe.moe_gmm_bytes(M, 41, 64) == 2 * (41 * EXPERT + 64 * (2 * 2048 + 4 * 1536))


def test_window_counts_what_arrived_inside_at_its_own_context():
    inside = request(300, 101.0, [(101.0, 1), (102.0, 4), (103.0, 3)])
    straddles = request(200, 99.0, [(99.0, 2), (100.5, 6)])  # prefilled before the window
    cut = request(50, 109.5, [(109.5, 1), (111.0, 7)])  # its tail arrives after the close
    no_length = dict(inside, prompt_len=None)
    got = flops_lfm2_moe.window_flops(M, [inside, straddles, cut, no_length], 100.0, 110.0)
    want = (flops_lfm2_moe.prefill_flops(M, 300) + flops_lfm2_moe.decode_flops(M, 300)
            + 4 * flops_lfm2_moe.decode_flops(M, 300 + 1 + 1.5) + 3 * flops_lfm2_moe.decode_flops(M, 300 + 5 + 1)
            + 6 * flops_lfm2_moe.decode_flops(M, 200 + 2 + 2.5)
            + flops_lfm2_moe.prefill_flops(M, 50) + flops_lfm2_moe.decode_flops(M, 50))
    assert got == pytest.approx(want)
    share = reader("layer_metrics", "serve_mfu_share")(ctx([inside, straddles, cut]))
    assert share == pytest.approx(100 * want / (10.0 * 197e12)) and 0 < share < 100


def test_experts_touched_per_step_is_the_counters_quotient():
    first = {"moe.experts_touched_n": 1000, "moe.layer_steps_n": 40}
    last = {"moe.experts_touched_n": 1000 + 41 * 800, "moe.layer_steps_n": 840}
    assert reader("layer_metrics", "moe_experts_touched_per_step")(ctx(split=(first, last))) == 41.0


def test_decode_load_max_over_mean_is_the_counters_quotient():
    first = {"moe.load_max_over_mean_n": 100.0, "moe.layer_steps_n": 40}
    last = {"moe.load_max_over_mean_n": 100.0 + 3.5 * 800, "moe.layer_steps_n": 840}
    assert reader("layer_metrics", "moe_decode_load_max_over_mean")(ctx(split=(first, last))) == 3.5


def synthetic_trace():
    """Three decode chunks of 80 ms (8 steps each), a prefill between them;
    24 grouped products of 1 ms in a chunk, and the prefill's own."""
    ms = 1_000_000
    modules = [(0, 80 * ms, "jit_chunk(123)"), (80 * ms, 130 * ms, "jit_prefill_row(7)"),
               (130 * ms, 210 * ms, "jit_chunk(123)"), (210 * ms, 290 * ms, "jit_chunk(123)")]
    ops = []
    for s, e, name in modules:
        n = 24 if name.startswith("jit_chunk") else 8
        for i in range(n):
            at = s + i * 2 * ms
            ops.append((at, at + ms, "%gmm.5 = bf16[64,1536]{1,0} custom-call(...)"))
            ops.append((at + ms, at + 2 * ms, "%fusion.9 = bf16[16,2048]{1,0} fusion(...)"))
    marks = {reduce_trace.MARK_START: [(0, 1)], reduce_trace.MARK_STOP: [(290 * ms - 1, 290 * ms)]}
    trace = reduce_trace.Trace({"/device:TPU:0": dict(ops=ops, modules=modules)}, marks)
    assert trace.window == (0, 290 * ms)
    return trace


def test_decode_readers_take_the_chunks_and_what_ran_inside_them():
    # the traced seconds' own routing: 41 experts touched and 64 assignments a layer-step
    around = [{"moe.layer_steps_n": 1000, "moe.assignments_n": 64000, "moe.experts_touched_n": 41000},
              {"moe.layer_steps_n": 1200, "moe.assignments_n": 76800, "moe.experts_touched_n": 49200}]
    c = ctx(around_trace=around, trace=synthetic_trace())
    found = decode_chunks.executions(c)
    assert len(found) == 3
    assert decode_chunks.op_seconds_inside(c, found, CONFIG["trace_names"]["moe_gmm"]) == pytest.approx(0.072)
    assert reader("layer_metrics", "serve_decode_step_device_s")(c) == pytest.approx(0.010)
    layer_steps = 3 * 8 * 8
    least = max(flops_lfm2_moe.moe_gmm_flops(M, 64 * layer_steps) / 197e12,
                flops_lfm2_moe.moe_gmm_bytes(M, 41 * layer_steps, 64 * layer_steps) / 819e9)
    got = reader("layer_metrics", "moe_decode_roofline")(c)
    assert got == pytest.approx(100 * least / 0.072) and 0 < got
    # the patterns the configuration carries find the program's names
    assert decode_chunks.executions(ctx(trace=synthetic_trace()))
    assert reader("layer_metrics", "moe_decode_roofline")(ctx(trace=synthetic_trace())) is None  # no counters


@pytest.mark.parametrize("name", ["serve_mfu_share", "moe_decode_roofline",
                                  "moe_experts_touched_per_step", "serve_decode_step_device_s",
                                  "moe_decode_load_max_over_mean"])
def test_lfm2_readers_with_nothing_to_read_give_none(name):
    """Another driver's stamps, another configuration, a program without the
    counters, or no trace: the metric is left out and nothing raises."""
    bare = ctx()
    bare.stamps = dict(cycles=[], t_open=100.0)
    assert reader("layer_metrics", name)(bare) is None
    gpt = ctx(requests=[dict(request(10, 101.0, [(101.0, 1)]), prompt_len=None)], split=({}, {"chunks_n": 5}))
    gpt.config = dict(gpt_config={})
    assert reader("layer_metrics", name)(gpt) is None


def test_the_driver_refuses_a_program_without_the_family(monkeypatch, tmp_path):
    run = types.SimpleNamespace(config=CONFIG, traffic=dict(params={}), platform="cpu")
    monkeypatch.setattr(model_serve_closed, "families", lambda: {"gpt": (), "llama": ()})
    with pytest.raises(RunFailed, match="no model family 'lfm2_moe'"):
        model_serve_closed.start_server(run, 1, str(tmp_path), str(tmp_path / "log"))
    monkeypatch.undo()
    assert "lfm2_moe" in model_serve_closed.families()


def test_judge_teacher_on_made_up_positions():
    judge = model_serve_closed.judge_teacher
    teacher = [dict(tokens=[5, 6, 7, 8], top2_gap=[0.3, 0.01, 0.3, 0.3], logprobs=[-7.0] * 4,
                    second_at=[0, 2], second_tokens=[15, 17], second_logprobs=[-6.0, -6.0]),
               dict(tokens=[9, 10], top2_gap=[0.3, 0.3], logprobs=[-7.0] * 2,
                    second_at=[1], second_tokens=[20], second_logprobs=[-6.0])]
    limits = dict(gap_tolerance=0.1, median_logprob_tolerance=0.02, second_min_compared=0.6,
                  second_median_logprob_tolerance=0.2)
    near = [([5, 15], [-7.01, -6.1]), ([6], [-7.01]), ([7, 17], [-6.99, -6.05]), ([8], [-7.3]),
            ([9], [-7.015]), ([10, 21], [-7.0, -3.0])]
    ok, numbers = judge(near, teacher, limits)
    # one first token far off does not move the median; two of three second tokens are the reference's
    assert ok and numbers["teacher_positions_compared"] == 6
    assert numbers["teacher_logprob_median_abs_diff"] == pytest.approx(0.01)
    assert numbers["teacher_second_positions"] == 3 and numbers["teacher_second_positions_compared"] == 2
    assert numbers["teacher_second_logprob_median_abs_diff"] == pytest.approx(0.075)
    # every first token a little further off does
    ok, numbers = judge([(t, [lp[0] - 0.03] + lp[1:]) for t, lp in near], teacher, limits)
    assert not ok and numbers["teacher_logprob_median_abs_diff"] > 0.02
    # another first token where the reference's two best are a near-tie is left out; where they are not, a fault
    tie = [near[0], ([66], [-7.01])] + near[2:]
    assert judge(tie, teacher, limits)[0] and judge(tie, teacher, limits)[1]["teacher_positions_compared"] == 5
    ok, numbers = judge([([55, 15], [-7.01, -6.1])] + near[1:], teacher, limits)
    assert not ok and numbers["teacher_mismatch"][0]["position"] == 0
    # a server whose first decode step reads a lost state: the second tokens are others (or far off)
    lost = [([5, 99], [-7.01, -6.1])] + near[1:]
    assert not judge(lost, teacher, limits)[0]
    far = [([5, 15], [-7.01, -6.5]), near[1], ([7, 17], [-6.99, -5.5])] + near[3:]
    assert not judge(far, teacher, limits)[0]
    # fewer than half of the positions compared is no comparison
    teacher[0]["top2_gap"] = [0.01] * 4
    assert not judge([([0], [-7.0])] * 4 + near[4:], teacher, dict(limits, second_min_compared=0))[0]
    with pytest.raises(ValueError):
        judge(near[:3], teacher, limits)


def test_the_limits_admit_the_served_precision_and_refuse_the_next_lower():
    """The traffic file's limits on the chip's own readings (recorded by the
    builder, ``expected/lfm2-24b-a2b-l10.readings.json``) and on the control
    (the float32 reference with its experts in 8 bits, ``make_expected_lfm2_moe.py
    --expert-dtype float8_e4m3fn``): the served bf16 passes with room, the
    served path with 8-bit experts and the control fail by the median and by
    tokens at wide gaps, a server that loses a row's state by its second tokens."""
    expected = os.path.join(ROOT, "benchmark", "reference", "expected", "lfm2-24b-a2b-l10.")
    teacher = json.load(open(expected + "serve_canary.json"))["teacher"]
    control = json.load(open(expected + "float8_e4m3fn.serve_canary.json"))["teacher"]
    readings = json.load(open(expected + "readings.json"))
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "rollout-closed-16-long.json")))
    limits = traffic["params"]["canary"]["teacher"]["limits"]
    judge = model_serve_closed.judge_teacher
    assert [s["sequence"] for s in control] == [s["sequence"] for s in teacher]

    ok, served = judge(readings["served_bf16"], teacher, limits)
    assert ok and served["teacher_positions"] == 247 and served["teacher_second_positions"] == 64
    assert 1.5 * served["teacher_logprob_median_abs_diff"] < limits["median_logprob_tolerance"]
    assert 1.5 * served["teacher_second_logprob_median_abs_diff"] < limits["second_median_logprob_tolerance"]
    assert served["teacher_second_positions_compared"] > 1.5 * limits["second_min_compared"] * 64

    ok, eight_bit = judge(readings["served_fp8_experts"], teacher, limits)
    assert not ok and eight_bit["teacher_mismatch"]
    assert eight_bit["teacher_logprob_median_abs_diff"] > 1.5 * limits["median_logprob_tolerance"]
    # the control has no second tokens: judged on the first
    ok, of_control = judge([([t], [lp]) for s in control for t, lp in zip(s["tokens"], s["logprobs"])],
                           teacher, dict(limits, second_min_compared=0))
    assert not ok and of_control["teacher_mismatch"]
    assert of_control["teacher_logprob_median_abs_diff"] > 1.5 * limits["median_logprob_tolerance"]

    ok, lost = judge(readings["served_state_lost_at_admission"], teacher, limits)
    assert not ok and lost["teacher_second_positions_compared"] == 0
    assert lost["teacher_logprob_median_abs_diff"] == served["teacher_logprob_median_abs_diff"]  # the prefill is whole


def test_the_two_serving_windows_have_not_drifted_apart():
    """``model_serve_closed.run`` is ``serve_closed.run`` with another server
    command, ``prompt_len`` on the records, two ``/healthz`` reads around the
    trace, the teacher-forced comparison in the greedy canary's place and two
    more checks (no accepted benchmark file may be edited to take them as
    hooks). Every other line is the same, in the same order: the 34 lines of
    ``serve_closed.run`` that are not in the copy are the ones known here. When this fails, a change to
    one window has not reached the other: bring it over, then set the count
    and the digest again."""
    a = inspect.getsource(serve_closed.run).splitlines()
    b = inspect.getsource(model_serve_closed.run).splitlines()
    only_a = [line for tag, i1, i2, _, _ in difflib.SequenceMatcher(None, a, b, autojunk=False).get_opcodes()
              if tag in ("replace", "delete") for line in a[i1:i2]]
    digest = hashlib.sha1("\n".join(only_a).encode()).hexdigest()[:16]
    assert (len(only_a), digest) == (34, "d2bbe8ee7418a795"), "\n".join(only_a)


def test_lfm2_configuration_keeps_the_published_widths():
    """Every number of the catalog's entry is in the file under its key, the
    nested groups whole; only ``num_hidden_layers`` differs; the model as
    run has them too."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    published = next(json.loads(l) for l in open(catalog) if '"LFM2-24B-A2B"' in l)["config"]
    assert CONFIG["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    for key, value in published.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
        if key in M and key != "num_hidden_layers":
            assert M[key] == value, key
    assert M["num_hidden_layers"] == CONFIG["num_hidden_layers"] == 10
    assert M["rope_theta"] == published["rope_parameters"]["rope_theta"]
    assert M["layer_types"][:10].count("full_attention") == 2
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "rollout-closed-16-long.json")))
    p = traffic["params"]
    assert p["prompt_width"] + p["max_new_tokens"] <= M["max_seq_len"] and p["batch_size"] == p["clients"] == 16
