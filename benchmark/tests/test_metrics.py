"""The metric arithmetic on synthetic stamps: operations from shapes,
whole-cycle windowing, the wrapper's stall, the request mix."""

import importlib.util
import os
import types

import pytest

from benchmark import flops
from benchmark.drivers import serve_closed, train_cycles
from benchmark.harness import percentile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(kind, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cycle(seg_start, seg, stall, ok=True):
    return dict(seg_start=seg_start, t_ready=seg_start + seg, t_ret=seg_start + seg + stall, ok=ok)


def train_ctx(cycles, t_open=100.0):
    return types.SimpleNamespace(
        stamps=dict(cycles=cycles, t_open=t_open, steps_per_cycle=10, tokens_per_step=32768, saves=True),
        setup_s=55.0, trace=None, peaks=dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9),
        config=dict(gpt_config=dict(num_layers=12, embed_dim=768, vocab_size=50304, num_heads=12, head_dim=64)),
        traffic=dict(params=dict(seq=1024, batch=32)), run=types.SimpleNamespace(chips=1),
        device=dict(memory_peak_bytes=15 * 2 ** 30))


def test_flops_from_shapes():
    assert flops.gpt_matmul_params(12, 768, 50304) == 123_568_128
    assert flops.train_flops_per_token(12, 768, 50304, 1024) == 854_654_976
    # causal forward: 4 B H T^2 d / 2; the backward costs 2.5 forwards
    fwd = flops.flash_attention_flops(32, 12, 1024, 64, backward=False)
    assert fwd == 4 * 32 * 12 * 1024 * 1024 * 64 / 2
    assert flops.flash_attention_flops(32, 12, 1024, 64) == 3.5 * fwd
    tensor = 32 * 12 * 1024 * 64 * 2
    assert flops.flash_attention_bytes(32, 12, 1024, 64) == 12 * tensor
    least, limit = flops.roofline_seconds(197e12, 1.0, dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9))
    assert least == 1.0 and limit == "flops"
    assert flops.roofline_seconds(1.0, 819e9, dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)) == (1.0, "bytes")


def test_step_rate_leaves_the_saves_out():
    # three cycles of 10 steps: segments of 2 s; stalls of 0.5, 7, 0.5 s
    cycles = [cycle(100.0, 2.0, 0.5), cycle(102.5, 2.0, 7.0), cycle(111.5, 2.0, 0.5)]
    ctx = train_ctx(cycles)
    assert reader("end_to_end", "train_tokens_per_s")(ctx) == pytest.approx(3 * 10 * 32768 / 6.0)
    assert reader("end_to_end", "save_stall_s")(ctx) == pytest.approx(0.5)  # the faster half: one of three
    assert reader("layer_metrics", "save_stall_max_s")(ctx) == pytest.approx(7.0)
    assert reader("layer_metrics", "save_share_of_window")(ctx) == pytest.approx(100 * 8.0 / 14.0)
    assert reader("layer_metrics", "train_mfu")(ctx) == pytest.approx(
        100 * 854_654_976 * (3 * 10 * 32768 / 6.0) / 197e12)
    assert reader("end_to_end", "setup_s")(ctx) == 55.0
    assert reader("layer_metrics", "hbm_peak_gib_train")(ctx) == 15.0


def test_save_stall_is_the_mean_of_the_faster_half():
    # ten saves as the chip gives them: two slow, one after each, one of 0.62
    got = [6.1, 1.07, 0.52, 0.54, 0.54, 0.50, 0.62, 0.52, 6.2, 1.08]
    assert train_cycles.faster_half_mean(got) == pytest.approx((0.50 + 0.52 + 0.52 + 0.54 + 0.54) / 5)
    assert train_cycles.faster_half_mean(got[:9]) == pytest.approx((0.50 + 0.52 + 0.52 + 0.54) / 4)
    assert train_cycles.faster_half_mean([0.7]) == 0.7
    # a checkpoint twice as slow moves it one for one, however many slow saves the window held
    assert train_cycles.faster_half_mean([2 * x for x in got]) == pytest.approx(2 * train_cycles.faster_half_mean(got))


def test_a_reader_with_nothing_to_read_returns_none():
    serve_only = types.SimpleNamespace(stamps=dict(requests=[], t_open=0.0, t_close=1.0), trace=None,
                                       peaks=None, device=dict(memory_peak_bytes=0))
    for kind, name in [("end_to_end", "train_tokens_per_s"), ("end_to_end", "save_stall_s"),
                       ("layer_metrics", "step_device_s"), ("layer_metrics", "flash_roofline"),
                       ("layer_metrics", "hbm_peak_gib_train"), ("layer_metrics", "save_stall_max_s")]:
        assert reader(kind, name)(serve_only) is None
    assert reader("end_to_end", "serve_tokens_per_s")(train_ctx([cycle(0, 1, 1)])) is None


def test_whole_cycles_only():
    window = dict(warmup_cycles=2, t_open=100.0, cycles=[
        dict(t_ret=90.0), dict(t_ret=100.0),  # warm-up: the second one opens the window
        dict(t_ret=104.0), dict(t_ret=108.0), dict(t_ret=112.5)])
    assert [c["t_ret"] for c in train_cycles.whole_cycles(window, 12.0)] == [104.0, 108.0]
    assert [c["t_ret"] for c in train_cycles.whole_cycles(window, 12.5)] == [104.0, 108.0, 112.5]
    assert train_cycles.whole_cycles(window, 3.0) == []


def test_serve_counts_the_tokens_that_arrived_inside_the_window():
    reqs = [dict(t_send=9.0, t_first=9.5, t_done=10.5, asked=40,       # straddles the opening: 30 inside
                 arrivals=[(9.5, 10), (10.1, 20), (10.5, 10)]),
            dict(t_send=10.0, t_first=10.2, t_done=12.0, asked=60, arrivals=[(10.2, 20), (12.0, 40)]),
            dict(t_send=19.0, t_first=19.4, t_done=21.0, asked=80,     # straddles the close: 10 inside
                 arrivals=[(19.4, 10), (20.5, 70)]),
            dict(t_send=19.8, t_first=20.3, t_done=None, asked=16, arrivals=[(20.3, 8)])]  # cut off after
    stamps = dict(requests=reqs, t_open=10.0, t_close=20.0)
    ctx = types.SimpleNamespace(stamps=stamps, trace=None)
    assert serve_closed.tokens_arrived(stamps) == 100
    assert reader("end_to_end", "serve_tokens_per_s")(ctx) == pytest.approx(10.0)
    # TTFT over the requests sent *and* first answered inside the window: 0.2 and 0.4 s;
    # the last one's first token came after the close, where a traced run has the profiler on
    assert reader("layer_metrics", "serve_ttft_p50_s")(ctx) == pytest.approx(0.2)
    assert reader("layer_metrics", "serve_ttft_p90_s")(ctx) == pytest.approx(0.4)


def test_serve_host_frac_is_the_windows_own():
    # /healthz totals at the window's two ends: the warm-up's 900 ms of admission are not in it
    first = dict(serving_host_frac=0.9, rounds=10, admission_ms=900.0, prefill_ms=50.0, host_sync_ms=50.0)
    last = dict(serving_host_frac=0.5, rounds=90, admission_ms=1000.0, prefill_ms=250.0, host_sync_ms=650.0,
                decode_dispatch_ms=60.0, retirement_ms=40.0, overlap_hidden_ms=100.0)
    ctx = types.SimpleNamespace(stamps=dict(phase_split_open=first, healthz=dict(phase_split=last)))
    assert reader("layer_metrics", "serve_host_frac")(ctx) == pytest.approx(100 * 200.0 / 1100.0)
    ctx.stamps["phase_split_open"] = None
    assert reader("layer_metrics", "serve_host_frac")(ctx) is None


def test_every_seed_offers_the_same_sizes():
    p = dict(requests_key=11, n_requests=64, prompt_len=dict(lo=32, hi=512), max_tokens=dict(lo=16, hi=128))
    a = serve_closed.make_requests(p, 50304, 1)
    b = serve_closed.make_requests(p, 50304, 3_000_000_011)
    sizes = lambda reqs: sorted((len(t), m) for t, m in reqs)  # noqa: E731
    assert sizes(a) == sizes(b) and [len(t) for t, _ in a] != [len(t) for t, _ in b]
    assert all(32 <= len(t) <= 512 and 16 <= m <= 128 for t, m in a)
    assert a == serve_closed.make_requests(p, 50304, 1)


def test_percentile_is_nearest_rank():
    values = list(range(1, 11))
    assert percentile(values, 50) == 5 and percentile(values, 90) == 9 and percentile(values, 100) == 10
    assert percentile([3.0], 95) == 3.0
