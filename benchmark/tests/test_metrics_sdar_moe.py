"""The arithmetic of the readers and operation counts that came with the
``sdar-30b-a3b-pp8-l6`` configuration, on synthetic stamps, a synthetic
trace and the recorded fixture; ``judge_blocks`` on decisions made by hand
and, under the traffic file's limits, on the chip's own readings (the
served path passes, each control fails a limit); the configuration file
against the catalog's published numbers. (The cell's CPU rehearsal, traced
and untraced, is ``test_rehearsal.py``'s: it reads its cells from
``BENCHMARK.json``.)"""

import json
import os
import types

import pytest

from benchmark import flops_sdar_moe as fl, reduce_trace
from benchmark.drivers.model_serve_closed_blocks import decisions_of, judge_blocks
from benchmark.tests.test_metrics import reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "sdar-30b-a3b-pp8-l6"
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")))
TRAFFIC = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "blockgen-closed-16.json")))
M = CONFIG["model"]["config"]
PEAKS = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
EXPECTED = os.path.join(ROOT, "benchmark", "reference", "expected", NAME + ".")
CELL = "sdar-moe-serve-blockgen-16"
MINE = ["moe_block_gmm_roofline", "sdar_block_pass_roofline", "serve_block_commit_pass_share",
        "serve_block_tokens_per_row_pass", "serve_mfu_share_sdar"]
MS = 1_000_000


def sdar_ctx(requests=None, split=None, trace=None, around=None, slots=16):
    first, last = split or ({}, {})
    return types.SimpleNamespace(
        stamps=dict(t_open=100.0, t_close=110.0, requests=requests if requests is not None else [],
                    phase_split_open=first, healthz=dict(phase_split=last, decode_chunk=9, slots=slots),
                    phase_split_trace=around),
        trace=trace, peaks=PEAKS, config=CONFIG, traffic={}, run=types.SimpleNamespace(chips=1),
        device=dict(memory_peak_bytes=10 * 2 ** 30))


def test_sdar_moe_operations_and_bytes_from_shapes():
    # ISSUE 59's arithmetic: q 2,048 x 4,096, k and v 2,048 x 512 each, o 4,096 x 2,048; one expert 3 x 2,048 x 768
    assert fl.attention_params(M) == 18_874_368 and fl.expert_matrix_elements(M) == 4_718_592
    assert fl.head_params(M) == 151_936 * 2048 == 311_164_928 and fl.layers(M) == 6
    per_layer = 18_874_368 + 262_144 + 8 * 4_718_592
    assert fl.active_matmul_params(M, head=False) == 6 * per_layer == 341_311_488
    assert fl.active_matmul_params(M) == 341_311_488 + 311_164_928
    # a pass of 16 live rows at 600 real positions each: 64 positions through everything, scores over the rows
    scores = 6 * 4.0 * 4 * (16 * 600) * 32 * 128
    assert fl.pass_flops(M, 16, 16 * 600) == 2.0 * 64 * 652_476_416 + scores
    assert fl.pass_flops(M, 16, 16 * 600) / 197e12 < 0.5e-3  # "0.08 TFLOP": far under the bytes' time
    assert fl.prefill_flops(M, 400) == 2.0 * 400 * 341_311_488 + 6 * 4.0 * 400 * 202.0 * 32 * 128
    # keys and values: 12,288 bytes a position; a pass that touches 125 of 128 experts a layer moves ~8.1 GB
    assert fl.kv_bytes(M, 1) == 12_288
    fixed = 6 * (18_874_368 * 2 + 262_144 * 4) + 2 * 311_164_928
    assert fl.pass_bytes(M, 6 * 125, 16 * 600) == fixed + 2 * 750 * 4_718_592 + 16 * 600 * 12_288
    assert fl.pass_bytes(M, 6 * 125, 16 * 600) / 819e9 == pytest.approx(9.85e-3, rel=0.02)  # "~10 ms a pass"
    assert fl.moe_gmm_flops(M, 64 * 8) == 512 * 2.0 * 4_718_592
    assert fl.moe_gmm_bytes(M, 125, 512) == 2 * (125 * 4_718_592 + 512 * (2 * 2048 + 4 * 768))


def test_sdar_moe_window_counts_the_prefills_inside_and_the_passes():
    def request(prompt_len, t_first):
        return dict(t_send=t_first - 0.2, t_first=t_first, t_done=t_first + 1, arrivals=[(t_first, 4)], asked=256,
                    prompt_len=prompt_len)

    reqs = [request(403, 101.0), request(900, 99.5), request(64, 111.0)]  # one prefill inside: its 400 whole-block tokens
    split = ({"block.row_passes_n": 100, "kv_positions_valid_n": 50_000},
             {"block.row_passes_n": 100 + 16 * 1000, "kv_positions_valid_n": 50_000 + 16 * 1000 * 700})
    want = fl.prefill_flops(M, 400) + fl.pass_flops(M, 16_000, 16_000 * 700)
    assert fl.window_flops(M, reqs, 100.0, 110.0, 16_000, 16_000 * 700) == pytest.approx(want)
    got = reader("layer_metrics", "serve_mfu_share_sdar")(sdar_ctx(requests=reqs, split=split))
    assert got == pytest.approx(100 * want / (10.0 * 197e12)) and 0 < got < 100


def test_sdar_moe_block_counters_give_tokens_a_pass_and_the_commit_share():
    first = {"block.tokens_fixed_n": 40, "block.row_passes_n": 30, "block.commit_row_passes_n": 10}
    last = {"block.tokens_fixed_n": 40 + 4_000, "block.row_passes_n": 30 + 3_000, "block.commit_row_passes_n": 10 + 1_000}
    c = sdar_ctx(split=(first, last))
    assert reader("layer_metrics", "serve_block_tokens_per_row_pass")(c) == pytest.approx(4 / 3)
    assert reader("layer_metrics", "serve_block_commit_pass_share")(c) == pytest.approx(100 / 3)


GMM = "%gmm.{i} = bf16[512,768]{{1,0:T(8,128)(2,1)}} custom-call(bf16[512,2048]{{1,0}} %a), custom_call_target=\"tpu_custom_call\""


def sdar_trace():
    """Two block chunks of 108 ms (9 passes of 12 ms) around a prefill of 60 ms;
    in each chunk 9 x 6 x 3 grouped products of 0.4 ms; one more inside the
    prefill, which is another program's."""
    modules = [(0, 108 * MS, "jit_chunk(55)"), (108 * MS, 168 * MS, "jit_prefill_block_row(9)"),
               (170 * MS, 278 * MS, "jit_chunk(55)")]
    ops = [(s, e, "%fusion.1 = bf16[16,4,2048]{2,1,0} fusion(...)") for s, e, _ in modules]
    for start in (0, 170 * MS):
        for i in range(9 * 6 * 3):
            at = start + i * 600_000
            ops.append((at, at + 400_000, GMM.format(i=i)))
    ops.append((120 * MS, 125 * MS, GMM.format(i=999)))
    marks = {reduce_trace.MARK_START: [(0, 1)], reduce_trace.MARK_STOP: [(278 * MS - 1, 278 * MS)]}
    return reduce_trace.Trace({"/device:TPU:0": dict(ops=ops, modules=modules)}, marks)


# the traced seconds' counters: 18 passes of 16 rows; 90 experts touched a layer-pass; rows ~650 long
AROUND = [{"kv_positions_valid_n": 7_000, "row_steps_n": 1_600, "moe.experts_touched_n": 900,
           "moe.layer_steps_n": 60, "moe.assignments_n": 5_000},
          {"kv_positions_valid_n": 7_000 + 18 * 16 * 650, "row_steps_n": 1_600 + 18 * 16,
           "moe.experts_touched_n": 900 + 108 * 90, "moe.layer_steps_n": 60 + 108,
           "moe.assignments_n": 5_000 + 108 * 512}]


def test_sdar_moe_pass_roofline_takes_the_experts_touched_and_the_rows_real_lengths():
    c = sdar_ctx(trace=sdar_trace(), around=AROUND)
    assert reader("layer_metrics", "serve_decode_step_device_s")(c) == pytest.approx(0.012)
    got = reader("layer_metrics", "sdar_block_pass_roofline")(c)
    assert got == pytest.approx(100 * (fl.pass_bytes(M, 6 * 90, 16 * 650) / 819e9) / 0.012) and 50 < got < 70
    # charged for every expert and whole rows it would read higher: that is what the metric must not do
    assert got < 100 * (fl.pass_bytes(M, 6 * 128, 16 * 1536) / 819e9) / 0.012


def test_sdar_moe_gmm_roofline_counts_the_products_inside_the_chunks_alone():
    c = sdar_ctx(trace=sdar_trace(), around=AROUND)
    kernel_s = 2 * 9 * 6 * 3 * 0.0004  # the prefill's 5 ms are not in it
    layer_steps = 2 * 9 * 6
    least = max(fl.moe_gmm_bytes(M, 90 * layer_steps, 512 * layer_steps) / 819e9,
                fl.moe_gmm_flops(M, 512 * layer_steps) / 197e12)
    got = reader("layer_metrics", "moe_block_gmm_roofline")(c)
    assert got == pytest.approx(100 * least / kernel_s) and 0 < got < 100


@pytest.mark.parametrize("name", MINE)
def test_sdar_moe_readers_with_nothing_to_read_give_none(name):
    """Another driver's stamps, another configuration, a program without the
    counters (the parent), the recorded fixture's trace (another program's) or
    no trace: the metric is left out, nothing raises."""
    bare = sdar_ctx()
    bare.stamps = dict(cycles=[], t_open=100.0)
    assert reader("layer_metrics", name)(bare) is None
    parent = sdar_ctx(requests=[], split=({}, {"chunks_n": 5}), trace=sdar_trace(), around=[{"chunks_n": 1}, {"chunks_n": 4}])
    assert reader("layer_metrics", name)(parent) is None
    other = sdar_ctx(split=({}, dict(AROUND[1], **{"block.row_passes_n": 9, "block.tokens_fixed_n": 9,
                                                      "block.commit_row_passes_n": 3})),
                     trace=sdar_trace(), around=AROUND)
    other.config = dict(model=dict(config=dict(num_experts_per_tok=4)), trace_names=dict(decode_chunk="^jit_chunk"))
    if name not in ("serve_block_tokens_per_row_pass", "serve_block_commit_pass_share"):  # counters say it themselves
        assert reader("layer_metrics", name)(other) is None
    recorded = sdar_ctx(trace=reduce_trace.load(None, os.path.join(os.path.dirname(__file__), "fixture.xplane.pb")),
                        around=AROUND)
    if name in ("sdar_block_pass_roofline", "moe_block_gmm_roofline"):
        assert reader("layer_metrics", name)(recorded) is None


def test_sdar_moe_cell_and_metrics_are_in_the_benchmark():
    bench_file = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench_file["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "blockgen-closed-16", 1)
    # new entries went behind the nine cells, eight configurations and 62 readers there were (found by name: the
    # next PR's entries go behind these)
    conf = next(c for c in bench_file["configs"] if c["name"] == NAME)
    assert bench_file["workloads"].index(cell) == 9 and bench_file["configs"].index(conf) == 8
    assert conf["reduced"] == ["num_hidden_layers"] and len(cell["why"]) <= 200
    # (its own readers list it first: a later PR's cell goes behind it in their lists)
    mine = {m["name"]: m for m in bench_file["per_layer"] if m.get("workloads", [None])[0] == CELL}
    assert sorted(mine) == MINE and [m["name"] for m in bench_file["per_layer"][62:67]] == [
        "serve_block_tokens_per_row_pass", "serve_block_commit_pass_share", "sdar_block_pass_roofline",
        "moe_block_gmm_roofline", "serve_mfu_share_sdar"]
    assert all(m["moves"] == "serve_tokens_per_s" for m in mine.values())
    listed = {m["name"] for m in bench_file["per_layer"] + bench_file["end_to_end"] if CELL in m.get("workloads", [])}
    assert {"serve_tokens_per_s", "serve_ttft_p50_s", "serve_ttft_p90_s", "serve_host_frac", "device_idle_share_serve",
            "hbm_peak_gib_serve", "serve_inbox_wait_s", "serve_queue_wait_s", "serve_admit_to_first_token_s",
            "serve_decode_step_device_s", "serve_prefill_pad_share", "serve_kv_valid_share",
            "moe_experts_touched_per_step", "moe_decode_load_max_over_mean", "compiles_in_window",
            "setup_programs"} <= listed
    # tokens over row-passes is no share here, and the readers that import another model's counts are left alone
    assert not {"serve_slot_occupancy", "moe_decode_roofline", "serve_mfu_share", "serve_decode_step_roofline"} & listed


def test_sdar_moe_configuration_keeps_the_published_widths():
    """Every key of the catalog's entry is in the file under its name and
    with its value but the depth, which stands beside its published count;
    the model as run has every width, every expert and the whole vocabulary."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"SDAR-30B-A3B-Chat"' in line)
    assert CONFIG["source"] == row["source_url"] and CONFIG["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert CONFIG["published"][key] == value == 48 and CONFIG[key] == M[key] == 6
            continue
        assert CONFIG[key] == value and M[key] == value, key
    assert (M["block_length"], M["denoising_steps"], M["mask_token_id"], M["max_seq_len"]) == (4, 2, 151_669, 1536)
    assert {"block_length", "denoising_steps", "schedule", "mask_token_id", "qk_norm", "no_shift", "init"} <= set(CONFIG["assumed"])
    assert "eight stages" in CONFIG["deployment"] and CONFIG["counts"]["parameters"] == 4_361_055_744
    p = TRAFFIC["params"]
    assert p["prompt_width"] + p["max_new_tokens"] + 2 * (M["block_length"] - 1) <= M["max_seq_len"]
    assert p["batch_size"] == p["clients"] == 16 and p["max_tokens"] == dict(lo=256, hi=256)
    assert (p["prompt_len"], p["n_requests"], p["ids_below"]) == (dict(lo=64, hi=1022), 256, 151_643)
    assert (p["warmup_seconds"], p["temperature"]) == (12, 0.0) and 1.0 <= p["trace_seconds"] <= 2.0
    assert TRAFFIC["driver"] == "model_serve_closed_blocks" and p["canary"]["blocks"]["tokens"] == 12
    # the traffic as the cell's ``why`` states it: one fixed list that a seed only reorders
    from benchmark.drivers.serve_closed import make_requests
    pairs = [(len(prompt), n) for prompt, n in make_requests(p, p["ids_below"], 11)]
    lengths = sorted(n for n, _ in pairs)
    assert (lengths[0], lengths[-1], round(sum(lengths) / 256)) == (64, 1021, 338) and {n for _, n in pairs} == {256}
    assert sorted(pairs) == sorted((len(prompt), n) for prompt, n in make_requests(p, p["ids_below"], 2 ** 31 + 5))
    assert max(t for prompt, _ in make_requests(p, p["ids_below"], 2 ** 31 + 5) for t in prompt) < 151_643


def a_run(prompt_len=9):
    """A reference run made by hand: 12 tokens after ``prompt_len``, every
    block's pass 0 fixing the first half of what is undecided."""
    tokens = list(range(100, 112))
    passes = []
    for i in range(12):
        block_at = (prompt_len + i) % 4
        first = prompt_len % 4 if (prompt_len + i) // 4 == prompt_len // 4 else 0
        undecided = 4 - first
        passes.append(0 if block_at - first < -(-undecided // 2) else 1)
    return dict(prompt=list(range(prompt_len)), tokens=tokens, logprobs=[-5.0 - 0.1 * i for i in range(12)], passes=passes,
                top2_gap=[0.5] * 12, select_gap=[0.3] * 12)


def test_sdar_moe_decisions_are_one_a_block_and_pass():
    want = a_run(9)  # the first block holds 9, 10, 11: pass 0 fixes two of the three
    assert want["passes"] == [0, 0, 1, 0, 0, 1, 1, 0, 0, 1, 1, 0]
    got = decisions_of(9, want["tokens"], want["passes"], 4)
    assert got[0] == (2, 0, {9: 100, 10: 101}) and got[1] == (2, 1, {11: 102})
    assert [d[:2] for d in got] == [(2, 0), (2, 1), (3, 0), (3, 1), (4, 0), (4, 1), (5, 0)]


def test_sdar_moe_judge_blocks_tells_a_fault_from_a_near_tie():
    limits = dict(gap_tolerance=0.1, median_logprob_tolerance=0.02, later_min_compared=0.5)
    want = a_run(9)
    same = (want["tokens"], [x + 0.01 for x in want["logprobs"]], want["passes"])
    ok, n = judge_blocks([same], [want], limits, 4)
    assert ok and n["blocks_runs_whole"] == 1 and n["blocks_tokens_compared"] == 12 and n["blocks_first_decisions_same"] == 1
    assert n["blocks_tokens_past_first_block"] == n["blocks_tokens_past_first_block_compared"] == 9
    assert n["blocks_logprob_median_abs_diff"] == pytest.approx(0.01)
    # another token where the reference's runner-up was far: a fault; where it was near: the run ends there, no fault
    other = list(want["tokens"])
    other[4] = 999  # position 13, block 3, pass 0
    ok, n = judge_blocks([(other, same[1], same[2])], [want], limits, 4)
    assert not ok and n["blocks_mismatch"][0]["block"] == 3 and n["blocks_tokens_compared"] == 3
    near = dict(want, top2_gap=[0.5] * 4 + [0.05] + [0.5] * 7)
    ok, n = judge_blocks([(other, same[1], same[2])], [near], dict(limits, later_min_compared=0.0), 4)
    assert ok and not n["blocks_mismatch"] and n["blocks_tokens_past_first_block_compared"] == 0
    ok, _ = judge_blocks([(other, same[1], same[2])], [near], limits, 4)
    assert not ok  # too little compared past the first block
    # other positions fixed at a pass: judged by the confidence's margin
    moved = list(want["passes"])
    moved[3], moved[5] = 1, 0  # block 3 fixes 12 and 14 first, where the reference fixed 12 and 13
    ok, n = judge_blocks([(want["tokens"], same[1], moved)], [want], limits, 4)
    assert not ok and n["blocks_mismatch"][0]["gap"] == 0.3 and n["blocks_mismatch"][0]["at_pass"] == 0
    # log-probabilities further than the limit at the median
    ok, _ = judge_blocks([(want["tokens"], [x + 0.05 for x in want["logprobs"]], want["passes"])], [want], limits, 4)
    assert not ok
    with pytest.raises(ValueError):
        judge_blocks([same, same], [want], limits, 4)


def test_sdar_moe_judge_blocks_calls_no_fault_what_the_file_cannot_show():
    """A run's last block, cut by the cap: the reference's pass 0 fixed 21 and
    22, which no answer shows, and position 20 at the forced pass 1 (nothing
    else was left: an infinite gap). A server that took 20 and 22 at pass 0
    differs at a near-tie the file does not hold: the run ends there, without
    a fault. The same difference in a whole block is a fault."""
    limits = dict(gap_tolerance=0.1, median_logprob_tolerance=0.02, later_min_compared=0.5)
    want = a_run(9)
    want["passes"][11], want["select_gap"][11] = 1, float("inf")
    early = list(want["passes"])
    early[11] = 0
    ok, n = judge_blocks([(want["tokens"], want["logprobs"], early)], [want], limits, 4)
    assert ok and not n["blocks_mismatch"] and n["blocks_runs_whole"] == 0 and n["blocks_tokens_compared"] == 11
    # another token at that pass of the cut block is no fault either: its context may differ unseen
    other = list(want["tokens"])
    other[11] = 999
    ok, n = judge_blocks([(other, want["logprobs"], want["passes"])], [want], limits, 4)
    assert ok and not n["blocks_mismatch"] and n["blocks_tokens_compared"] == 11
    # at the cut block's pass 0 what was fixed is in the file, and a far runner-up is a fault
    want0 = a_run(9)  # position 20 at pass 0
    ok, n = judge_blocks([(other, want0["logprobs"], want0["passes"])], [want0], limits, 4)
    assert not ok and n["blocks_mismatch"][0]["block"] == 5 and n["blocks_mismatch"][0]["gap"] == 0.5
    # a whole block: every position is in the file, and a forced pass that differs is a fault
    whole = a_run(8)
    assert whole["passes"][8:] == [0, 0, 1, 1]
    whole["select_gap"][10:] = [float("inf")] * 2
    late = whole["passes"][:11] + [2]
    ok, n = judge_blocks([(whole["tokens"], whole["logprobs"], late)], [whole], limits, 4)
    assert not ok and n["blocks_mismatch"][0]["at_pass"] == 1 and n["blocks_mismatch"][0]["gap"] == float("inf")


def test_sdar_moe_limits_admit_the_served_precision_and_refuse_the_controls():
    """The traffic file's limits on the chip's own readings
    (``expected/sdar-30b-a3b-pp8-l6.readings.json``, written by
    ``make_expected_sdar_moe.py``): the engine's answers as served pass with
    room; matrices at 3 mantissa bits fail, in the engine and in the
    reference; a block attended causally fails; a denoise pass's keys and
    values left in the cache fail past the first block, whose decisions read
    the prefill alone and stay the reference's."""
    expected = json.load(open(EXPECTED + "serve_canary.json"))
    readings = json.load(open(EXPECTED + "readings.json"))
    limits = TRAFFIC["params"]["canary"]["blocks"]["limits"]

    def of(control):
        return judge_blocks([tuple(r) for r in readings[control]["runs"]], expected["runs"], limits, M["block_length"])

    ok, served = of("served")
    later_all = served["blocks_tokens_past_first_block"]
    assert ok and served["blocks_runs"] == len(expected["runs"]) >= 12 and not served["blocks_mismatch"]
    assert 1.5 * served["blocks_logprob_median_abs_diff"] < limits["median_logprob_tolerance"]
    assert served["blocks_tokens_past_first_block_compared"] > 1.2 * limits["later_min_compared"] * later_all
    for control in ("mantissa3", "reference-mantissa3"):
        ok, bits = of(control)
        assert not ok and bits["blocks_logprob_median_abs_diff"] > 1.5 * limits["median_logprob_tolerance"], control
    ok, causal = of("mask-causal")
    assert not ok and (causal["blocks_mismatch"]
                       or causal["blocks_logprob_median_abs_diff"] > 1.5 * limits["median_logprob_tolerance"])
    ok, kept = of("scratch-kept")
    assert not ok and kept["blocks_first_decisions_same"] == served["blocks_first_decisions_same"]
    assert kept["blocks_tokens_past_first_block_compared"] < 0.8 * limits["later_min_compared"] * later_all
