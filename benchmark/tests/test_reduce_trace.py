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


def test_stop_trace_writes_the_one_file_load_opens(tmp_path):
    """``stop_trace`` on the host: the ``xplane.pb`` where ``find_xplane``
    looks, both markers in it, and no ``trace.json.gz`` (JAX's own stop
    converts every event to one that nothing here reads: 154 of 229 s at
    GPT-2 XL, ``PERF.md`` section 7 (14)); the profiler can start again."""
    import glob

    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    said = []
    rt.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("step_dispatch"):
        step(x).block_until_ready()
    got = rt.stop_trace(log=said.append)
    files = [p for p in glob.glob(str(tmp_path / "**" / "*"), recursive=True) if os.path.isfile(p)]
    assert len(files) == 1 and files[0] == rt.find_xplane(str(tmp_path))
    assert os.path.relpath(files[0], tmp_path).split(os.sep)[:2] == ["plugins", "profile"]
    assert got["wrote"] == "xplane.pb" and got["xplane_bytes"] == os.path.getsize(files[0]) > 0
    assert got["collect_s"] >= 0 and got["export_s"] >= 0
    assert len(said) == 2 and "collect_s=" in said[0] and "export_s=" in said[1]
    trace = rt.load(str(tmp_path))
    assert len(trace.host[rt.MARK_START]) == len(trace.host[rt.MARK_STOP]) == len(trace.host["step_dispatch"]) == 1
    assert trace.window[0] < trace.host["step_dispatch"][0][0] < trace.window[1]
    assert trace.events["host"] > 3 and trace.events["file_bytes"] == got["xplane_bytes"]
    rt.start_trace(str(tmp_path / "again"))  # the session was given back
    assert rt.stop_trace()["wrote"] == "xplane.pb"


def test_stop_trace_falls_back_on_jaxs_own_where_the_session_is_not_to_be_had(tmp_path, monkeypatch):
    import glob

    monkeypatch.setattr(rt, "_held_session", lambda: None)
    said = []
    rt.start_trace(str(tmp_path))
    got = rt.stop_trace(log=said.append)
    assert got["wrote"] == "jax.profiler.stop_trace" and got["collect_s"] is None and "no session handle" in said[0]
    assert rt.load(str(tmp_path)) is not None
    assert glob.glob(str(tmp_path / "**" / "*.trace.json.gz"), recursive=True)  # what the other way leaves out


def test_the_fixture_counts_what_it_read_of_its_file(trace):
    """The two device lines ``load`` reads (12 of the file's 15 device events) and every host line."""
    assert trace.events == dict(device=12, host=98, file_bytes=os.path.getsize(FIXTURE),
                                head_cut_s=0.0, tail_cut_s=0.0)


def _served(live: bool):
    """A window cut out of a running server (``PERF.md`` section 6, PR 55):
    the chunk in flight at the start ends 2 ms before the opening marker and
    the next launch stalls (the first under a profiler just started), so the
    next chunk begins 73 ms in; then chunks of 50 ms with 1 ms between them,
    the last cut at 20 ms by the stop, whose marker comes 31 ms later."""
    ms = 1_000_000
    modules = [(-30 * ms, -2 * ms, "jit_chunk")] + [(s * ms, (s + 50) * ms, "jit_chunk") for s in range(73, 400, 51)]
    modules.append((430 * ms, 450 * ms, "jit_chunk"))
    ops = [(e - (i + 1) * 10 * ms, e - i * 10 * ms, "fusion.1")
           for s, e, _ in modules for i in range((e - s) // (10 * ms))]
    host = {rt.MARK_START: [(0, 1)], rt.MARK_STOP: [(481 * ms - 1, 481 * ms)]}
    if live:
        host[rt.MARK_LIVE] = [(481 * ms, 481 * ms + 1)]
    return rt.Trace({"/device:TPU:0": dict(ops=ops, modules=modules)}, host)


def test_a_live_window_holds_whole_programs_of_a_settled_profiler_and_the_devices_record_of_them():
    """Of the program in flight at the stop the profiler keeps what had
    finished: up to a whole chunk of apparent idleness before the marker;
    and the first launches after the profiler's start stall. A server's
    window (``stop_trace(live=True)``) starts at the first program begun
    ``LIVE_SETTLE_S`` in and ends with the device's record; any other
    window keeps its ends (a trainer has synced: its tail is real)."""
    whole, live = _served(False), _served(True)
    assert whole.window == (0, 481_000_000) and (whole.head_cut_s, whole.tail_cut_s) == (0.0, 0.0)
    assert whole.busy_and_window(1) == dict(busy_s=0.370, window_s=0.481)  # 7 chunks and 20 ms of an eighth
    assert rt.LIVE_SETTLE_S == 0.25 and live.window == (277_000_000, 450_000_000)  # chunks begin at 73, 124, .. 277
    assert (live.head_cut_s, live.tail_cut_s) == (pytest.approx(0.277), pytest.approx(0.031))
    assert live.busy_and_window(1) == dict(busy_s=0.170, window_s=0.173)  # the real gaps of 1 ms stay
    assert dict(live.breakdown([])["idle_gaps"]) == {"host_other": pytest.approx(0.003)}
    assert dict(whole.breakdown([])["idle_gaps"]) == {"host_other": pytest.approx(0.111)}
    assert len(live.module_durations()["jit_chunk"]) == 4 and len(whole.module_durations()["jit_chunk"]) == 8


def test_a_live_stop_writes_its_marker_and_a_trace_with_no_device_keeps_its_window(tmp_path):
    rt.start_trace(str(tmp_path))
    rt.stop_trace(live=True)
    trace = rt.load(str(tmp_path))
    assert len(trace.host[rt.MARK_STOP]) == len(trace.host[rt.MARK_LIVE]) == 1
    assert trace.host[rt.MARK_LIVE][0][0] >= trace.host[rt.MARK_STOP][0][1] == trace.window[1]
    assert trace.head_cut_s == trace.tail_cut_s == 0.0 and not trace.used_planes()


def test_the_file_written_reads_as_jaxs_own_export():
    """``compare_exports.py`` (run by hand on the chip at PR 55: ``same:
    True``) on the host, at a few executions: ``session.export`` of the same
    bytes, and a session stopped by JAX itself, read the same through ``load``."""
    from benchmark.tests import compare_exports

    assert compare_exports.main(steps=20) == 0
