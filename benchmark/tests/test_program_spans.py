"""The readers of the program's own spans and counters
(``benchmark/program_spans.py`` and the nine readers that use it): each on
a synthetic ``reduce_trace.Trace`` or synthetic stamps, the ``None`` each
gives on what the parent of the PR that added them produces, and one CPU
rehearsal of each driver, traced, that still ends ``correct`` and prints
the new metrics in the cells that list them."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import program_spans
from benchmark.reduce_trace import MARK_START, MARK_STOP, Trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
MS = 1_000_000  # ns

SAVE = ["save_d2h_s", "save_memcpy_s", "save_host_other_s"]
LOOP = ["step_dispatch_s", "loop_overhead_s"]
SERVE = ["serve_inbox_wait_s", "serve_queue_wait_s", "serve_admit_to_first_token_s",
         "serve_slot_occupancy"]


def reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def trace_of(host, lo=0, hi=10_000 * MS):
    host = dict(host)
    host[MARK_START], host[MARK_STOP] = [(lo, lo)], [(hi, hi)]
    return Trace({}, {k: sorted(v) for k, v in host.items()})


def ctx_of(trace=None, **stamps):
    return types.SimpleNamespace(trace=trace, stamps=stamps)


def save_spans(start, leaves, d2h_ms, memcpy_ms, lead_ms=20, tail_ms=5):
    """One ``ckpt.save``: ``lead_ms`` of lock, plan and ensure, then per
    leaf a wait and a copy, then ``tail_ms``."""
    at = start + lead_ms * MS
    d2h, memcpy = [], []
    for _ in range(leaves):
        d2h.append((at, at + d2h_ms * MS))
        at += d2h_ms * MS
        memcpy.append((at, at + memcpy_ms * MS))
        at += memcpy_ms * MS
    return (start, at + tail_ms * MS), d2h, memcpy


def test_the_saves_split_sums_to_the_faster_saves_whole_span():
    # a slow save (leaves wait 30 ms each) and a fast one (2 ms): two are traced, the faster one counts
    slow, slow_d2h, slow_cp = save_spans(100 * MS, 10, 30, 1)
    fast, fast_d2h, fast_cp = save_spans(5000 * MS, 10, 2, 1)
    # the staging thread's spans of the same names, outside any ckpt.save, count for nothing
    stray = [(9000 * MS, 9100 * MS)]
    trace = trace_of({"ckpt.save": [slow, fast], "ckpt.save.d2h": slow_d2h + fast_d2h + stray,
                      "ckpt.save.memcpy": slow_cp + fast_cp, "save_call": [slow, fast]})
    ctx = ctx_of(trace, cycles=[{}])
    d2h, memcpy, other = (reader(n)(ctx) for n in SAVE)
    assert d2h == pytest.approx(10 * 0.002)
    assert memcpy == pytest.approx(10 * 0.001)
    assert other == pytest.approx(0.025)
    assert d2h + memcpy + other == pytest.approx((fast[1] - fast[0]) / 1e9)
    found = program_spans.saves(trace)
    assert [round(f["whole_s"], 3) for f in found] == [0.335, 0.055]
    # three traced saves: still the faster half, one of three
    third, d3, c3 = save_spans(7000 * MS, 10, 4, 1)
    trace = trace_of({"ckpt.save": [slow, fast, third], "ckpt.save.d2h": slow_d2h + fast_d2h + d3,
                      "ckpt.save.memcpy": slow_cp + fast_cp + c3})
    assert reader("save_d2h_s")(ctx_of(trace)) == pytest.approx(0.020)
    # four: the mean of the two fastest
    fourth, d4, c4 = save_spans(8000 * MS, 10, 6, 1)
    trace = trace_of({"ckpt.save": [slow, fast, third, fourth],
                      "ckpt.save.d2h": slow_d2h + fast_d2h + d3 + d4,
                      "ckpt.save.memcpy": slow_cp + fast_cp + c3 + c4})
    assert reader("save_d2h_s")(ctx_of(trace)) == pytest.approx((0.020 + 0.040) / 2)


def test_a_save_cut_by_the_windows_edge_is_left_out():
    whole, d2h, memcpy = save_spans(100 * MS, 4, 2, 1)
    cut, cut_d2h, cut_cp = save_spans(9990 * MS, 4, 2, 1)  # ends after the window's close
    trace = trace_of({"ckpt.save": [whole, cut], "ckpt.save.d2h": d2h + cut_d2h,
                      "ckpt.save.memcpy": memcpy + cut_cp})
    assert len(program_spans.saves(trace)) == 1


def loop_spans(n, start=0, wait_ms=1, dispatch_ms=3, report_ms=2, gap_ms=280):
    waits, dispatches, reports = [], [], []
    at = start
    for _ in range(n):
        waits.append((at, at + wait_ms * MS))
        at += wait_ms * MS
        dispatches.append((at, at + dispatch_ms * MS))
        at += dispatch_ms * MS + gap_ms * MS  # a save, or nothing, in between
        reports.append((at, at + report_ms * MS))
        at += report_ms * MS
    return {"train.data_wait": waits, "train.step_dispatch": dispatches, "train.report": reports}


def test_the_loops_medians():
    host = loop_spans(9)
    # one step in nine syncs inside its report (on_step at a cycle's end): the median does not see it
    s, e = host["train.report"][4]
    host["train.report"][4] = (s, s + 200 * MS)
    host["train.data_wait"][5:] = [(a + 198 * MS, b + 198 * MS) for a, b in host["train.data_wait"][5:]]
    host["train.step_dispatch"][5:] = [(a + 198 * MS, b + 198 * MS) for a, b in host["train.step_dispatch"][5:]]
    host["train.report"][5:] = [(a + 198 * MS, b + 198 * MS) for a, b in host["train.report"][5:]]
    ctx = ctx_of(trace_of(host), cycles=[{}])
    assert reader("step_dispatch_s")(ctx) == pytest.approx(0.003)
    assert reader("loop_overhead_s")(ctx) == pytest.approx(0.003)
    assert len(program_spans.steps(ctx.trace)) == 9


def test_a_step_cut_by_the_windows_edge_is_left_out():
    host = loop_spans(5)
    # the window opens after the first step's data_wait and closes before the last one's report
    lo = host["train.data_wait"][0][1] + 1
    hi = host["train.report"][-1][0] + 1
    found = program_spans.steps(trace_of(host, lo, hi))
    assert len(found) == 3
    assert all(f["data_wait_s"] == pytest.approx(0.001) and f["report_s"] == pytest.approx(0.002)
               for f in found)


def phase_split(admitted, inbox, queue, first, emitted, row_steps, **ms):
    return dict(serving_host_frac=0.3, rounds=10, requests_admitted_n=admitted,
                inbox_wait_s_sum=inbox, queue_wait_s_sum=queue, admit_to_first_token_s_sum=first,
                tokens_emitted_n=emitted, row_steps_n=row_steps, chunks_n=row_steps // 32, **ms)


def test_the_servers_waits_are_window_differences_per_admitted_request():
    opened = phase_split(40, 0.8, 2.0, 6.0, 2000, 6400, admission_ms=10.0, host_sync_ms=90.0)
    closed = phase_split(192, 2.32, 17.2, 36.4, 12640, 25600, admission_ms=40.0, host_sync_ms=360.0)
    ctx = ctx_of(None, phase_split_open=opened, healthz=dict(phase_split=closed), requests=[])
    assert reader("serve_inbox_wait_s")(ctx) == pytest.approx(0.010)
    assert reader("serve_queue_wait_s")(ctx) == pytest.approx(0.100)
    assert reader("serve_admit_to_first_token_s")(ctx) == pytest.approx(0.200)
    assert reader("serve_slot_occupancy")(ctx) == pytest.approx(100 * 10640 / 19200)
    # the counters do not end in _ms: the accepted serve_host_frac sums what it summed
    assert reader("serve_host_frac")(ctx) == pytest.approx(100 * 30.0 / 300.0)


@pytest.mark.parametrize("name", SAVE + LOOP + SERVE)
def test_the_parents_shape_of_input_reads_none(name):
    """What the parent commit gives: a trace with the benchmark's own
    wrapper spans only, and a ``phase_split`` with no counter in it."""
    read = reader(name)
    parent_trace = trace_of({"save_call": [(100 * MS, 600 * MS)],
                             "step_dispatch": [(700 * MS, 703 * MS)]})
    parent_split = dict(serving_host_frac=0.3, rounds=9, admission_ms=1.0, host_sync_ms=9.0)
    assert read(ctx_of(parent_trace, cycles=[{}])) is None
    assert read(ctx_of(None, cycles=[{}])) is None  # an untraced run
    assert read(ctx_of(None, phase_split_open=parent_split, requests=[],
                       healthz=dict(phase_split=parent_split))) is None
    assert read(ctx_of(None, requests=[], healthz=dict(phase_split=None))) is None
    assert read(ctx_of(trace_of({}))) is None  # the other driver's cell


def test_no_admission_in_the_window_reads_none_not_a_division():
    split = phase_split(40, 0.8, 2.0, 6.0, 2000, 6400)
    ctx = ctx_of(None, phase_split_open=split, healthz=dict(phase_split=dict(split)), requests=[])
    for name in SERVE:
        assert reader(name)(ctx) is None


@pytest.mark.parametrize("cell,names", [("gpt2s-train-save", SAVE + LOOP), ("gpt2xl-serve-closed", SERVE)])
def test_rehearsal_of_each_driver_prints_the_program_metrics(cell, names):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "2147483659",
         "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-2000:]
    body = json.loads(got.stdout.strip().splitlines()[-1])["cpu_rehearsal"]
    assert body["correct"] is True and body["failed"] == 0, body["checks"]
    for name in names:
        assert name in body["metrics"], (name, sorted(body["metrics"]))
    other = set(SAVE + LOOP + SERVE) - set(names)
    assert not other & set(body["metrics"])
    if cell == "gpt2s-train-save":
        m = {k: v["value"] for k, v in body["metrics"].items()}
        # on the CPU the save stages behind a snapshot: the per-leaf spans are the staging thread's,
        # and count only where they overlap the trainer's ckpt.save
        assert m["save_d2h_s"] >= 0 and m["save_memcpy_s"] >= 0 and m["save_host_other_s"] > 0
    else:
        assert 0 < body["metrics"]["serve_slot_occupancy"]["value"] <= 100
