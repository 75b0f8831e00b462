"""The trace reduction on a small trace recorded on the v5e
(``record_fixture.py``): three executions of one small program, each
followed by a 20 ms ``save_call`` span with the device idle."""

import os

import pytest

from benchmark import reduce_trace as rt

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return rt.load(None, FIXTURE)


def test_interval_arithmetic():
    assert rt.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert rt.total(rt.clip([(0, 3), (5, 7)], 2, 6)) == 2
    assert rt.overlap([(0, 3), (5, 7)], [(2, 6)]) == 2
    assert rt.gaps([(0, 3), (5, 7)], 0, 10) == [(3, 5), (7, 10)]
    assert rt.gaps([], 0, 4) == [(0, 4)]


def test_clock_offset_rule():
    modules = {4: (100.0, 110.0), 5: (300.0, 310.0)}
    # run 4 met an idle device (enqueue 7 after its device stamp), run 5 queued
    assert rt.clock_offset_ns(modules, {4: 107.0, 5: 150.0}, {4: 130.0, 5: 330.0}) == 7.0
    assert rt.clock_offset_ns({}, {}, {}) == 0.0


def test_device_clock_is_brought_onto_the_hosts(trace):
    offset = trace.clock_offsets_ns["/device:TPU:0"]
    assert 7.0e6 < offset < 9.0e6  # the device stamps lagged the host by 7.8 ms
    # after the shift every execution starts inside its step_dispatch..save_call pair
    for (s, e, _), (d0, _), (_, c1) in zip(
            trace.devices["/device:TPU:0"]["modules"], trace.host["step_dispatch"],
            trace.host["save_call"]):
        assert d0 < s < e < c1


def test_busy_and_idle(trace):
    got = trace.busy_and_window(1)
    assert got["window_s"] == pytest.approx(0.065261019, rel=1e-6)
    assert got["busy_s"] == pytest.approx(0.00027064, rel=1e-3)
    name, durations = trace.main_module()
    assert name == "jit__lambda" and len(durations) == 3
    assert durations[1] == pytest.approx(9.0218e-05, rel=1e-3)


def test_one_kernels_time(trace):
    ops = {rt.short_name(n): v for n, v in trace.op_seconds().items()}
    seconds, count = ops["fusion bf16[]"]
    assert count == 3 and seconds == pytest.approx(3 * 9.0197e-05, rel=1e-3)


def test_gap_attribution(trace):
    got = trace.idle_gaps_by_span(["save_call", "step_dispatch"], rest="between_steps")
    busy = trace.busy_and_window(1)
    assert sum(got.values()) == pytest.approx(busy["window_s"] - busy["busy_s"], rel=1e-6)
    # the device sat idle through each 20 ms save_call, and through nothing else for long
    assert got["save_call"] == pytest.approx(0.0622, rel=0.02)
    assert got["save_call"] / sum(got.values()) > 0.95
    top = trace.breakdown(["save_call", "step_dispatch"], "between_steps")
    assert top["idle_gaps"][0][0] == "save_call"
    assert top["device_ops"][0][0] == "fusion bf16[]"


def test_short_name():
    assert rt.short_name(
        "%fusion.5 = f32[32,1024,50304]{2,1,0:T(8,128)} fusion(bf16[32]{0} %x), kind=kLoop"
    ) == "fusion.5 f32[32,1024,50304]"
    assert rt.short_name(
        "%CausalSelfAttention_0.76 = (bf16[384,1024,64]{2,1,0}, bf16[384,1024,64]{2,1,0}) custom-call(...)"
    ) == "CausalSelfAttention_0.76 bf16[384,1024,64]"
    assert rt.short_name("jit_step_fn") == "jit_step_fn"
