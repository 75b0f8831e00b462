"""The readers of the program's start-up record (``setup_*``,
``compiles_in_window``) on hand-made records and stamps: a warm start, a
cold one, a parent that writes no record, a program built inside the
window; then one rehearsal of a training and of a serving cell, so that
the real path is known to produce what the readers take."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import startup_records

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
ALL = ["setup_backend_s", "setup_build_s", "setup_trace_lower_s", "setup_backend_compile_s",
       "setup_cache_read_s", "setup_programs", "setup_cache_misses", "setup_named_share",
       "compiles_in_window"]
TRAIN_ONLY = ["setup_agent_s", "setup_first_step_s"]
T0 = 1_000_000.0  # benchmark/run.py's start, seconds on the wall clock


def reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def phases(start_s, *named):
    """[{name, unix_ns, s}] laid end to end from ``start_s``."""
    out, at = [], start_s
    for name, s in named:
        out.append(dict(name=f"startup.{name}", unix_ns=int(at * 1e9), s=s))
        at += s
    return out


def write(directory, kind, pid, at_s, record):
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, f"{kind}_{pid}_{int(at_s * 1e9)}.json"), "w") as f:
        json.dump(record, f)


def program(name, at_s, cache, backend_s, pid=20, trace_s=0.5, lower_s=0.25):
    return dict(fun_name=name, unix_ns=int(at_s * 1e9), trace_s=trace_s, lower_s=lower_s,
                backend_s=backend_s, cache=cache, thread="MainThread", pid=pid)


def train_ctx(work, t_open=T0 + 60.0, setup_s=60.0):
    cycles = [dict(seg_start=t_open, t_ready=t_open + 3.0, t_ret=t_open + 3.5, ok=True),
              dict(seg_start=t_open + 3.5, t_ready=t_open + 6.5, t_ret=t_open + 7.0, ok=True)]
    return types.SimpleNamespace(
        run=types.SimpleNamespace(work=str(work), t_start=T0),
        stamps=dict(cycles=cycles, t_open=t_open), setup_s=setup_s)


def a_training_start(work, compile_totals, late=()):
    """The agent's and the worker's records of one start: the agent is up
    after 3 s, the rendezvous takes 1, the spawn 2 and returns 1.5 s after
    the worker's process began (an overlap); the worker's phases fill 30 s
    with 4 s of its script's own time between them."""
    startup = os.path.join(str(work), "logs", "startup")
    write(startup, "rdzv", 10, T0 + 6.0, dict(
        rdzv_s=1.0, round=0, restart=0, worker_pid=20, pid=10,
        process_start_unix_ns=int((T0 + 0.2) * 1e9),
        phases=phases(T0 + 0.2, ("agent_up", 2.8), ("rdzv", 1.0), ("spawn", 2.0))))
    worker = phases(T0 + 4.5, ("imports", 4.0), ("backend", 7.0), ("script", 3.0), ("init_state", 9.0),
                    ("build_step", 0.5), ("script", 1.0), ("restore", 0.5), ("first_step", 5.0))
    write(startup, "worker", 20, T0 + 34.5, dict(
        resumed=False, restart=0, restore_s=0.5, first_step_s=5.0, compile_s=4.0, pid=20,
        process_start_unix_ns=int((T0 + 4.5) * 1e9), phases=worker, compile=compile_totals, compiles=[]))
    for i, c in enumerate(late):
        write(startup, "compile", 20, c["unix_ns"] / 1e9 + i * 1e-6, c)


WARM = dict(trace_s=2.0, lower_s=6.0, backend_s=0.25, cache_read_s=1.5, programs=9, cache_hits=2, cache_misses=7)
COLD = dict(trace_s=2.0, lower_s=6.0, backend_s=41.0, cache_read_s=0.0, programs=9, cache_hits=0, cache_misses=9)


def test_a_warm_training_start(tmp_path):
    a_training_start(tmp_path, WARM, late=[program("jit(copy)", T0 + 40.0, "miss", 0.25)])
    ctx = train_ctx(tmp_path)
    assert reader("setup_backend_s")(ctx) == pytest.approx(11.0)
    assert reader("setup_build_s")(ctx) == pytest.approx(9.5)
    assert reader("setup_first_step_s")(ctx) == pytest.approx(5.0)
    assert reader("setup_agent_s")(ctx) == pytest.approx(4.5)
    # the program built after the record, before the window, is set-up too
    assert reader("setup_trace_lower_s")(ctx) == pytest.approx(8.75)
    assert reader("setup_backend_compile_s")(ctx) == pytest.approx(0.5)
    assert reader("setup_cache_read_s")(ctx) == pytest.approx(1.5)
    assert reader("setup_programs")(ctx) == 10
    assert reader("setup_cache_misses")(ctx) == 8
    assert reader("compiles_in_window")(ctx) == 0
    # named: the agent's 5.8 s and the worker's 26 s (its script's 4 s are
    # nobody's), less the 1.5 s the spawn overlaps the imports, plus the
    # late program's 1 s: 31.3 of 60
    assert reader("setup_named_share")(ctx) == pytest.approx(100 * 31.3 / 60.0)


def test_a_cold_training_start_says_so(tmp_path):
    a_training_start(tmp_path, COLD)
    ctx = train_ctx(tmp_path)
    assert reader("setup_cache_misses")(ctx) == 9
    assert reader("setup_backend_compile_s")(ctx) == pytest.approx(41.0)
    assert reader("setup_cache_read_s")(ctx) == 0.0


def test_a_program_built_inside_the_window_is_counted(tmp_path):
    t_open = T0 + 60.0
    a_training_start(tmp_path, WARM, late=[
        program("jit(copy)", T0 + 40.0, "miss", 0.25),
        program("jit(step_fn)", t_open + 4.0, "miss", 12.0),
        program("jit(concatenate)", t_open + 9.0, "miss", 0.01),  # after the last whole cycle
        program("jit(other)", t_open + 4.0, "miss", 1.0, pid=99),  # another process's
    ])
    ctx = train_ctx(tmp_path, t_open=t_open)
    assert reader("compiles_in_window")(ctx) == 1
    assert reader("setup_programs")(ctx) == 10  # the window's own is not set-up


@pytest.mark.parametrize("name", ALL + TRAIN_ONLY)
def test_a_parent_without_the_record_reads_none_training(tmp_path, name):
    os.makedirs(tmp_path / "logs")  # worker logs, no startup directory
    assert reader(name)(train_ctx(tmp_path)) is None
    # the parent's own recovery record (four keys, no phases) is no start-up record
    write(str(tmp_path / "logs" / "startup"), "worker", 20, T0 + 30.0,
          dict(resumed=False, restart=0, restore_s=0.5, first_step_s=5.0, compile_s=3.5))
    assert reader(name)(train_ctx(tmp_path)) is None


def split(programs, **startup):
    out = {"serving_host_frac": 0.1, "rounds": 10, "admission_ms": 5.0, "host_sync_ms": 95.0,
           "compile.programs_n": programs}
    out.update({f"startup.{k}_s_sum": v for k, v in startup.items()})
    return out


def serve_ctx(opened, closed, setup_s=100.0):
    return types.SimpleNamespace(
        run=types.SimpleNamespace(work="/nonexistent", t_start=T0), setup_s=setup_s,
        stamps=dict(requests=[], t_open=T0 + setup_s, phase_split_open=opened,
                    healthz=dict(phase_split=closed)))


def test_a_warm_serving_start():
    opened = split(30, imports=6.0, backend=5.0, build_model=0.5, params=8.0, engine=1.5, listen=0.25)
    opened.update({"compile.trace_s_sum": 9.0, "compile.lower_s_sum": 6.0, "compile.backend_s_sum": 0.5,
                   "compile.cache_read_s_sum": 20.0, "compile.cache_hits_n": 28, "compile.cache_misses_n": 2,
                   "compile.startup_s_sum": 7.0})
    closed = dict(opened, host_sync_ms=4000.0)
    ctx = serve_ctx(opened, closed)
    assert reader("setup_backend_s")(ctx) == pytest.approx(11.0)
    assert reader("setup_build_s")(ctx) == pytest.approx(10.0)
    assert reader("setup_trace_lower_s")(ctx) == pytest.approx(15.0)
    assert reader("setup_backend_compile_s")(ctx) == pytest.approx(0.5)
    assert reader("setup_cache_read_s")(ctx) == pytest.approx(20.0)
    assert reader("setup_programs")(ctx) == 30
    assert reader("setup_cache_misses")(ctx) == 2
    assert reader("compiles_in_window")(ctx) == 0
    # the phases' 21.25 s and the 35.5 - 7 s of programs built after them
    assert reader("setup_named_share")(ctx) == pytest.approx(49.75)
    for name in TRAIN_ONLY:
        assert reader(name)(ctx) is None


def test_a_program_built_under_load_is_counted():
    opened = split(30, imports=6.0, backend=5.0)
    closed = dict(opened, **{"compile.programs_n": 31})
    assert reader("compiles_in_window")(serve_ctx(opened, closed)) == 1


@pytest.mark.parametrize("name", ALL + TRAIN_ONLY)
def test_a_parent_without_the_record_reads_none_serving(name):
    parent = {"serving_host_frac": 0.1, "rounds": 10, "admission_ms": 5.0, "requests_admitted_n": 7}
    assert reader(name)(serve_ctx(parent, dict(parent))) is None
    assert reader(name)(serve_ctx(None, None)) is None


def test_overlaps_count_once():
    assert startup_records.union_seconds([(0.0, 4.0), (3.0, 6.0), (10.0, 11.0)], 1.0, 10.5) == pytest.approx(5.5)


@pytest.mark.parametrize("cell,names", [
    ("gpt2s-train-save", ALL + TRAIN_ONLY),
    ("gpt2xl-serve-closed", ALL),
])
def test_rehearsal_prints_the_startup_metrics(cell, names):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "3900000007",
         "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-2000:]
    body = json.loads(got.stdout.strip().splitlines()[-1])["cpu_rehearsal"]
    metrics = body["metrics"]
    for name in names:
        assert name in metrics, (name, sorted(metrics))
    assert metrics["setup_backend_s"]["value"] > 0 and metrics["setup_build_s"]["value"] > 0
    assert metrics["setup_programs"]["value"] >= 2
    # a rehearsal's short warm-up may leave an admission size to the window
    # (``admit_sizes_not_met_in_warmup``): the reader then counts what the
    # launcher counted there, less what fell between the launcher's reads and
    # /healthz's (the driver takes the launcher's first and last)
    checks = body["checks"]
    assert 0 <= metrics["compiles_in_window"]["value"] <= checks.get("programs_compiled_in_window", 0) + checks.get(
        "programs_read_from_cache_in_window", 0)
    assert 0 < metrics["setup_named_share"]["value"] <= 100.0
