"""Every start names its own time (attribution/recovery.py,
observability/spans.py: startup_span, common/compile_cache.py:
watch_compiles): the phases of a start partition its wall time, compiles
are measured by JAX's own events and told apart from cache reads, one
record a start goes to the spool, and ``tpurun --log_dir`` gives the spool
a default place."""

import json
import logging
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.attribution import recovery
from dlrover_tpu.attribution.phases import PHASES, PhaseAccumulator
from dlrover_tpu.common import compile_cache
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability.spans import (
    SpanAccumulator,
    process_accumulator,
    startup_phase,
    startup_span,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh_start(monkeypatch):
    """This test is a process that has just begun: no phases kept, the
    start-up not yet closed, the clock at now."""
    acc = process_accumulator()
    acc.reset()
    monkeypatch.setattr(recovery, "_process_start_ns", time.time_ns())
    monkeypatch.setattr(recovery, "_adopted_warm", False)
    yield acc
    acc.reset()


# ---------------------------------------------------------------------------
# the compile listener, over a cache directory of its own (a child process:
# the test process's cache is the suite's)
# ---------------------------------------------------------------------------

_LISTENER_CHILD = textwrap.dedent(
    """
    import json, sys
    import jax, jax.numpy as jnp
    from dlrover_tpu.common import compile_cache as cc

    cc.watch_compiles()
    cc.watch_compiles()  # idempotent: one set of listeners

    def poly(x):
        for _ in range(8):
            x = jnp.sin(x) * 2.0 + 1.0
        return x

    def mine():
        return [r for r in cc.compile_records() if r["fun_name"] == "jit(poly)"]

    x, x5, x6 = jnp.ones((4, 4)), jnp.ones((5, 5)), jnp.ones((6, 6))
    jax.jit(poly)(x)                      # the cache has nothing: a miss
    jax.clear_caches()                    # the in-memory caches only
    jax.jit(poly)(x)                      # the same program: a hit
    after_two = dict(cc.compile_totals())
    jax.jit(poly).lower(x5).as_text()     # lowered, never compiled
    after_lower = dict(cc.compile_totals())
    jax.config.update("jax_enable_compilation_cache", False)
    from jax._src import compilation_cache as jax_cache
    jax_cache.reset_cache()               # JAX decides once whether it caches
    jax.clear_caches()
    jax.jit(poly)(x6)                     # no cache asked: off
    print(json.dumps(dict(records=mine(), after_two=after_two,
                          after_lower=after_lower, final=cc.compile_totals())))
    """
)


@pytest.fixture(scope="module")
def listener_run(tmp_path_factory):
    cache = tmp_path_factory.mktemp("compile_cache")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
        PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    got = subprocess.run(
        [sys.executable, "-c", _LISTENER_CHILD], env=env, cwd=_REPO,
        capture_output=True, text=True, timeout=180,
    )
    assert got.returncode == 0, got.stderr[-3000:]
    return json.loads(got.stdout.strip().splitlines()[-1])


class TestCompileListener:
    @pytest.mark.parametrize(
        "index,cache", [(0, "miss"), (1, "hit"), (2, "off")]
    )
    def test_classifies_each_program(self, listener_run, index, cache):
        records = listener_run["records"]
        assert [r["cache"] for r in records] == ["miss", "hit", "off"]
        rec = records[index]
        assert rec["cache"] == cache
        assert rec["backend_s"] > 0 and rec["lower_s"] > 0
        assert rec["thread"] == "MainThread" and rec["unix_ns"] > 0

    def test_a_hit_books_under_the_read_not_the_compile(self, listener_run):
        miss, hit, _off = listener_run["records"]
        totals = listener_run["after_two"]
        # every program of the child so far, the eager ones too: the hit's
        # backend duration is in cache_read_s and in no other sum
        assert totals["cache_read_s"] == pytest.approx(
            hit["backend_s"], abs=1e-5
        )
        assert totals["cache_hits"] == 1
        assert totals["backend_s"] >= miss["backend_s"]
        assert totals["cache_misses"] >= 1
        assert totals["programs"] == (
            totals["cache_hits"] + totals["cache_misses"]
        )

    def test_lowered_and_never_compiled_counts_for_nothing(self, listener_run):
        # the 5x5 input was lowered for its text: tracing and lowering
        # events, no backend event, so no program and not a second booked
        assert listener_run["after_lower"] == listener_run["after_two"]
        assert len(listener_run["records"]) == 3

    def test_off_counts_as_a_compile_and_as_no_miss(self, listener_run):
        before, final = listener_run["after_lower"], listener_run["final"]
        assert final["cache_misses"] == before["cache_misses"]
        assert final["cache_hits"] == before["cache_hits"]
        assert final["programs"] > before["programs"]
        assert final["backend_s"] > before["backend_s"]


class TestAfterStartupWarning:
    def test_warns_once_for_a_new_shape_not_for_a_cached_call(
        self, fresh_start, monkeypatch
    ):
        compile_cache.watch_compiles()
        monkeypatch.setattr(compile_cache, "_quiet", False)
        seen = []

        class Keep(logging.Handler):
            def emit(self, record):
                seen.append((record.levelno, record.getMessage()))

        handler = Keep()
        logger.addHandler(handler)
        try:
            @jax.jit
            def warned_fn(x):
                return jnp.cos(x) + 3.0

            x7, x9 = jnp.ones((7,)), jnp.ones((9,))
            warned_fn(x7)  # start-up still open: no warning
            fresh_start.take_startup_phases(close=True)
            warned_fn(x7)  # cached: no program, no line
            warned_fn(x9)  # a new shape: one program, one line
            warned_fn(x9)
        finally:
            logger.removeHandler(handler)
        mine = [
            (level, msg) for level, msg in seen
            if "compiled after start-up: jit(warned_fn)" in msg
        ]
        assert len(mine) == 1, seen
        assert mine[0][0] == logging.WARNING
        assert "backend" in mine[0][1] and mine[0][1].rstrip().endswith(")")

    def test_a_server_logs_it_at_info(self, fresh_start, monkeypatch):
        compile_cache.watch_compiles()
        monkeypatch.setattr(compile_cache, "_quiet", True)
        levels = []

        class Keep(logging.Handler):
            def emit(self, record):
                if "jit(served_fn)" in record.getMessage():
                    levels.append(record.levelno)

        handler = Keep()
        logger.addHandler(handler)
        try:
            fresh_start.take_startup_phases(close=True)

            @jax.jit
            def served_fn(x):
                return jnp.tanh(x) * 5.0

            served_fn(jnp.ones((11,)))
        finally:
            logger.removeHandler(handler)
        assert levels == [logging.INFO]


# ---------------------------------------------------------------------------
# the phases of a start
# ---------------------------------------------------------------------------


class TestStartupPhases:
    def test_a_fake_start_is_contiguous_and_sums_to_wall_time(
        self, fresh_start, tmp_path, monkeypatch
    ):
        monkeypatch.setenv(recovery.RECOVERY_DIR_ENV, str(tmp_path))
        t0_ns = recovery.process_start_unix_ns()
        time.sleep(0.03)
        recovery.startup_from_process_start("imports")
        with startup_span("backend"):
            time.sleep(0.05)
        time.sleep(0.02)  # the script's own code: nobody's phase
        with startup_span("init_state"):
            time.sleep(0.04)
            with startup_span("params"):  # nested: annotates, keeps nothing
                time.sleep(0.01)

        @startup_phase("build_step")
        def build():
            time.sleep(0.01)
            return 7

        assert build() == 7
        began = time.time_ns()
        time.sleep(0.03)
        fresh_start.add_startup_phase("first_step", began, 0.03)
        record = recovery.write_startup_record(
            "worker", {"resumed": False, "restart": 0, "first_step_s": 0.03}
        )
        wall_s = (time.time_ns() - t0_ns) / 1e9
        phases = record["phases"]
        names = [p["name"] for p in phases]
        assert names == [
            "startup.imports", "startup.backend", recovery.UNNAMED_PHASE,
            "startup.init_state", "startup.build_step", "startup.first_step",
        ]
        assert phases[0]["unix_ns"] == t0_ns == record["process_start_unix_ns"]
        for before, after in zip(phases, phases[1:]):
            end = before["unix_ns"] + before["s"] * 1e9
            assert abs(after["unix_ns"] - end) < 2e6, (before, after)
        assert sum(p["s"] for p in phases) == pytest.approx(wall_s, rel=0.05)
        # and the same record is what the spool holds, old keys beside new
        (on_disk,) = [
            r for r in recovery.read_records(str(tmp_path))
            if r["_kind"] == "worker"
        ]
        assert on_disk["phases"] == phases and on_disk["first_step_s"] == 0.03
        assert on_disk["pid"] == os.getpid()

    def test_after_the_record_a_phase_keeps_and_books_nothing(
        self, fresh_start
    ):
        with startup_span("backend"):
            pass
        recovery.write_startup_record("worker", {})
        booked = dict(fresh_start.totals())
        with startup_span("init_state"):  # a reload, a re-plan: steady state
            time.sleep(0.005)
        recovery.startup_from_process_start("imports")
        assert fresh_start.totals() == booked
        assert fresh_start.take_startup_phases() == []

    def test_an_agent_writes_one_record_a_worker_start(self, fresh_start):
        recovery.startup_from_process_start("agent_up")
        with startup_span("rdzv"):
            pass
        first = recovery.write_startup_record(
            "rdzv", {"rdzv_s": 0.0, "round": 0}, close=False
        )
        assert [p["name"] for p in first["phases"]][:1] == ["startup.agent_up"]
        time.sleep(0.02)  # the worker trains; then it dies
        with startup_span("respawn_decide"):
            time.sleep(0.005)
        with startup_span("rdzv"):
            pass
        second = recovery.write_startup_record(
            "rdzv", {"rdzv_s": 0.0, "round": 1, "restart": 1}, close=False
        )
        # the restart's record begins at the death seen, not at the
        # process's start: no filler over the hours in between
        assert [p["name"] for p in second["phases"]] == [
            "startup.respawn_decide", "startup.rdzv",
        ]
        assert not fresh_start.startup_closed

    def test_an_accumulator_of_its_own_keeps_its_own_phases(self):
        acc = SpanAccumulator()
        with acc.startup_span("backend"):
            pass
        (kept,) = acc.take_startup_phases(close=True)
        assert kept["name"] == "startup.backend" and kept["unix_ns"] > 0
        assert "startup.backend" in acc.totals()
        assert process_accumulator() is not acc


# ---------------------------------------------------------------------------
# the spool: old keys over old and new records, and where it lives
# ---------------------------------------------------------------------------


class TestSpool:
    def test_aggregate_reads_old_and_new_records_alike(
        self, fresh_start, tmp_path, monkeypatch
    ):
        root = str(tmp_path)
        monkeypatch.setenv(recovery.RECOVERY_DIR_ENV, root)
        # as the parent wrote them: four keys
        recovery.record_phase_file("rdzv", {"rdzv_s": 2.0, "round": 1})
        recovery.record_phase_file("worker", {
            "resumed": True, "restore_s": 1.0, "compile_s": 6.0,
            "first_step_s": 8.0,
        })
        # as this tree writes them: the same keys, the whole start beside
        with startup_span("rdzv"):
            pass
        recovery.write_startup_record(
            "rdzv", {"rdzv_s": 4.0, "round": 2, "restart": 1}, close=False
        )
        with startup_span("restore"):
            pass
        recovery.write_startup_record("worker", {
            "resumed": True, "restart": 1, "restore_s": 3.0,
            "compile_s": 2.0, "first_step_s": 4.0,
        })
        # a compile after start-up and a handed-in world's record (no
        # rdzv_s) are not recoveries' phases
        recovery.record_phase_file("compile", {"fun_name": "jit(f)"})
        recovery.record_phase_file("rdzv", {"round": 3, "phases": []})
        agg = recovery.aggregate(root)
        assert agg == {
            "rdzv_s": 3.0, "recovery_samples": 2, "restore_s": 2.0,
            "compile_s": 4.0, "first_step_s": 6.0,
        }

    def test_log_dir_gives_the_spool_a_default_place(self, monkeypatch, tmp_path):
        monkeypatch.delenv(recovery.RECOVERY_DIR_ENV, raising=False)
        recovery.default_recovery_dir(None)
        assert recovery.recovery_dir() is None
        recovery.default_recovery_dir(str(tmp_path / "logs"))
        assert recovery.recovery_dir() == str(tmp_path / "logs" / "startup")

    def test_an_explicit_spool_wins_over_log_dir(self, monkeypatch, tmp_path):
        monkeypatch.setenv(recovery.RECOVERY_DIR_ENV, str(tmp_path / "mine"))
        recovery.default_recovery_dir(str(tmp_path / "logs"))
        assert recovery.recovery_dir() == str(tmp_path / "mine")

    def test_with_neither_nothing_is_written(
        self, fresh_start, monkeypatch, tmp_path
    ):
        monkeypatch.delenv(recovery.RECOVERY_DIR_ENV, raising=False)
        monkeypatch.chdir(tmp_path)
        with startup_span("backend"):
            pass
        record = recovery.write_startup_record("worker", {"restart": 0})
        assert record["phases"]  # composed, logged, not spooled
        assert os.listdir(tmp_path) == []

    def test_process_start_is_the_kernels_and_not_later_than_ours(self):
        import dlrover_tpu

        monkey = recovery._process_start_ns
        recovery._process_start_ns = None
        try:
            start = recovery.process_start_unix_ns()
        finally:
            recovery._process_start_ns = monkey
        assert 0 < start <= dlrover_tpu.FIRST_LINE_UNIX_NS


# ---------------------------------------------------------------------------
# what /healthz carries of it
# ---------------------------------------------------------------------------


class TestPhaseSplitKeys:
    def test_summary_of_startup_has_no_ms_key_and_a_fresh_one_is_unchanged(
        self, fresh_start
    ):
        fresh = PhaseAccumulator()
        fresh.add_round([(p, 0.001) for p in PHASES])
        assert set(fresh.split().summary()) == {
            "serving_host_frac", "rounds", "overlap_hidden_s",
            *[f"{p}_ms" for p in PHASES],
        }
        assert recovery.startup_summary() == {}
        recovery.startup_from_process_start("imports")
        with startup_span("backend"):
            pass
        fresh_start.count("compile.trace_s", 0.25)
        fresh_start.count("compile.programs")
        fresh_start.count("requests_admitted")  # not start-up's: left out
        summary = recovery.startup_summary()
        assert set(summary) == {
            "startup.imports_s_sum", "startup.backend_s_sum",
            "compile.trace_s_sum", "compile.programs_n",
        }
        assert not any(key.endswith("_ms") for key in summary)
        assert len(json.dumps(summary)) < 200

    def test_serve_host_frac_reads_the_same_with_and_without(self):
        import importlib.util
        import types

        path = os.path.join(
            _REPO, "benchmark", "layer_metrics", "serve_host_frac.py"
        )
        spec = importlib.util.spec_from_file_location("serve_host_frac", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        opened = {"admission_ms": 10.0, "host_sync_ms": 90.0, "rounds": 5}
        closed = {"admission_ms": 30.0, "host_sync_ms": 170.0, "rounds": 9}
        extra_open = {
            "startup.imports_s_sum": 6.0, "startup.backend_s_sum": 5.0,
            "compile.backend_s_sum": 40.0, "compile.programs_n": 30,
        }
        extra_closed = dict(extra_open, **{"compile.programs_n": 31})

        def read(first, last):
            return module.read(types.SimpleNamespace(stamps=dict(
                phase_split_open=first, healthz=dict(phase_split=last)
            )))

        assert read(opened, closed) == pytest.approx(20.0)
        assert read(
            dict(opened, **extra_open), dict(closed, **extra_closed)
        ) == read(opened, closed)


# ---------------------------------------------------------------------------
# tpurun --standalone --log_dir, once (a process tree: a limit of its own)
# ---------------------------------------------------------------------------

_TPURUN_WORKER = textwrap.dedent(
    """
    import jax, jax.numpy as jnp
    from dlrover_tpu.common.platform import force_virtual_cpu
    force_virtual_cpu(1)
    from dlrover_tpu.trainer.elastic import elastic_context
    from dlrover_tpu.trainer.loop import ElasticTrainLoop

    ctx = elastic_context()

    class NoSave:
        def load_consistent(self, template): return -1, None
        def save_to_memory(self, step, pytree, **_): return True
        def wait_staged_all(self, timeout=0.0): return True
        def wait_staged(self, timeout=0.0): return True
        def wait_saving(self, timeout=0.0): return True
        def close(self): pass

    @jax.jit
    def step(state, x):
        return {"v": state["v"] + x.sum()}, state["v"].sum()

    loop = ElasticTrainLoop(NoSave(), step, ctx=ctx, max_steps=3,
                            memory_every=100, storage_every=0)
    loop.run({"v": jnp.zeros(3)}, ((jnp.ones(2),) for _ in range(5)))
    """
)


def test_tpurun_log_dir_leaves_an_agent_and_a_worker_record(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(_TPURUN_WORKER)
    log_dir = tmp_path / "logs"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        DLROVER_JOB_NAME=f"startup_rec_{os.getpid()}",
    )
    env.pop(recovery.RECOVERY_DIR_ENV, None)
    got = subprocess.run(
        [
            sys.executable, "-m", "dlrover_tpu.launcher.elastic_run",
            "--standalone", "--nnodes", "1", "--max_restarts", "0",
            "--log_dir", str(log_dir), str(script),
        ],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=240,
    )
    assert got.returncode == 0, got.stderr[-3000:]
    records = recovery.read_records(str(log_dir / "startup"))
    agents = [r for r in records if r["_kind"] == "rdzv"]
    workers = [r for r in records if r["_kind"] == "worker"]
    assert len(agents) == 1 and len(workers) == 1, os.listdir(log_dir)
    agent, worker = agents[0], workers[0]
    assert [p["name"] for p in agent["phases"] if p["name"] != recovery.UNNAMED_PHASE] == [
        "startup.agent_up", "startup.rdzv", "startup.spawn",
    ]
    assert agent["worker_pid"] == worker["pid"] and "rdzv_s" in agent
    named = [p["name"] for p in worker["phases"]]
    for phase in ("imports", "backend", "restore", "first_step"):
        assert f"startup.{phase}" in named, named
    # the four old keys, and compile_s a measured duration of the step's program
    for key in ("restore_s", "first_step_s", "compile_s", "resumed"):
        assert key in worker
    step_programs = [
        c for c in worker["compiles"] if c["fun_name"] == "jit(step)"
    ]
    assert len(step_programs) == 1
    assert worker["compile_s"] >= round(step_programs[0]["backend_s"], 3) > 0
    assert worker["compile_s"] <= worker["first_step_s"] + 0.05
    assert worker["compile"]["programs"] >= 1
    # both records lie on one clock, the worker's start inside the agent's spawn
    spawn = [p for p in agent["phases"] if p["name"] == "startup.spawn"][0]
    assert spawn["unix_ns"] <= worker["process_start_unix_ns"] or worker.get("warm")
