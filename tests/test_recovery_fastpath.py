"""Warm-restart fast path (docs/recovery.md): compile-ahead remesh,
overlapped restore, double-buffered input, and MTTR phase attribution.

Everything here is deliberately cheap — tiny jitted steps, no model
compiles — because tier-1 is a time-boxed run and the production-shaped
proof (warm-vs-cold A/B at equal fault plans) lives in the bench's
``recovery_ab`` section and the storm harness.
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.attribution import recovery
from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler
from dlrover_tpu.trainer.dataloader import PrefetchIterator
from dlrover_tpu.trainer.loop import (
    ElasticTrainLoop,
    gradient_accumulation_steps,
)
from dlrover_tpu.trainer.precompile import (
    CompileAheadService,
    anticipated_worlds,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_saver(tmp_ipc_dir, monkeypatch):
    job = f"recfp_{os.getpid()}_{id(tmp_ipc_dir)}"
    monkeypatch.setenv("DLROVER_JOB_NAME", job)
    AsyncCheckpointSaver.reset()
    yield
    AsyncCheckpointSaver.reset()
    for name in os.listdir("/dev/shm"):
        if name.startswith(f"dlrover_{job}_"):
            SharedMemoryHandler(
                0, name=name.split(f"dlrover_{job}_", 1)[1]
            ).unlink()


# ---------------------------------------------------------------------------
# PrefetchIterator: the double-buffered input pipeline
# ---------------------------------------------------------------------------


class TestPrefetchIterator:
    def test_order_and_values_identical_to_source(self):
        src = [np.full((2, 2), i, np.int32) for i in range(20)]
        got = list(PrefetchIterator(iter(src)))
        assert len(got) == 20
        for want, have in zip(src, got):
            np.testing.assert_array_equal(want, have)

    def test_stage_fn_applied_in_order(self):
        got = list(PrefetchIterator(iter(range(10)), stage_fn=lambda x: x * 2))
        assert got == [i * 2 for i in range(10)]

    def test_producer_error_reraises_on_consumer(self):
        def src():
            yield 1
            raise RuntimeError("boom in producer")

        it = PrefetchIterator(src())
        assert next(it) == 1
        with pytest.raises(RuntimeError, match="boom in producer"):
            for _ in range(5):
                next(it)

    def test_stage_fn_error_reraises(self):
        def bad_stage(x):
            raise ValueError("stage failed")

        it = PrefetchIterator(iter([1, 2]), stage_fn=bad_stage)
        with pytest.raises(ValueError, match="stage failed"):
            next(it)

    def test_lazy_start_consumes_nothing_before_first_draw(self):
        drawn = []

        def src():
            for i in range(5):
                drawn.append(i)
                yield i

        it = PrefetchIterator(src())
        time.sleep(0.1)
        assert drawn == []  # no thread until the first __next__
        assert next(it) == 0
        it.close()

    def test_exhaustion_raises_stop_iteration_then_stays_stopped(self):
        it = PrefetchIterator(iter([7]))
        assert next(it) == 7
        with pytest.raises(StopIteration):
            next(it)
        with pytest.raises(StopIteration):
            next(it)

    def test_close_is_idempotent_and_unblocks_producer(self):
        def endless():
            i = 0
            while True:
                yield i
                i += 1

        it = PrefetchIterator(endless())
        assert next(it) == 0
        it.close()
        it.close()
        # the producer thread exited (did not wedge on a full queue)
        assert it._thread is None or not it._thread.is_alive()


class TestLoopPrefetchBitExact:
    """The acceptance contract: the prefetch loop is bit-exact with the
    synchronous loop under JAX_PLATFORMS=cpu — same steps, same final
    state bits."""

    def _run(self, tmp_path, tag, prefetch):
        @jax.jit
        def step(state, x, y):
            w = state["w"] * 0.99 + jnp.asarray(x).sum() * 1e-3
            b = state["b"] + jnp.asarray(y).mean()
            return {"w": w, "b": b}, w.sum()

        r = np.random.default_rng(7)

        def data():
            # host numpy: the prefetch producer thread must not
            # dispatch jax computations
            while True:
                x = r.integers(0, 100, (4, 8)).astype(np.int32)
                yield x, np.roll(x, 1, axis=1)

        engine = CheckpointEngine(
            str(tmp_path / f"ckpt_{tag}"), standalone=True, replicate=False
        )
        state = {
            "w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
            "b": jnp.float32(0.0),
        }
        loop = ElasticTrainLoop(
            engine,
            step,
            max_steps=6,
            storage_every=100,
            prefetch_input=prefetch,
        )
        try:
            final = loop.run(state, data())
        finally:
            engine.shm.unlink()
            engine.close()
        return final

    def test_prefetch_loop_bit_exact_with_sync_loop(self, tmp_path):
        sync = self._run(tmp_path, "sync", prefetch=False)
        pre = self._run(tmp_path, "pre", prefetch=True)
        for a, b in zip(jax.tree.leaves(sync), jax.tree.leaves(pre)):
            # bitwise, not allclose: staging a draw early must not
            # change the bytes
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_sync_escape_hatch_still_applies_stage_fn(self, tmp_path):
        staged = []

        def stage(batch):
            staged.append(1)
            return batch

        @jax.jit
        def step(state, x):
            return {"v": state["v"] + jnp.asarray(x).sum()}, state["v"].sum()

        engine = CheckpointEngine(
            str(tmp_path / "ckpt_hatch"), standalone=True, replicate=False
        )
        try:
            loop = ElasticTrainLoop(
                engine,
                step,
                max_steps=3,
                storage_every=100,
                prefetch_input=False,
                input_stage_fn=stage,
            )
            loop.run(
                {"v": jnp.zeros(2)},
                ((np.ones((2, 2), np.float32),) for _ in range(10)),
            )
        finally:
            engine.shm.unlink()
            engine.close()
        assert len(staged) == 3


# ---------------------------------------------------------------------------
# Fixed-global-batch accumulation rounding (trainer/loop.py)
# ---------------------------------------------------------------------------


class TestGradAccumRounding:
    def test_divisible_worlds(self):
        assert gradient_accumulation_steps(8, 8) == 1
        assert gradient_accumulation_steps(8, 4) == 2
        assert gradient_accumulation_steps(8, 2) == 4
        assert gradient_accumulation_steps(8, 1) == 8

    def test_non_divisible_rounds_up(self):
        # round UP: the global batch grows slightly rather than
        # silently shrinking (documented in trainer/loop.py)
        assert gradient_accumulation_steps(8, 3) == 3  # ceil(8/3)
        assert gradient_accumulation_steps(8, 5) == 2  # ceil(8/5)
        assert gradient_accumulation_steps(7, 2) == 4  # ceil(7/2)
        assert gradient_accumulation_steps(10, 4) == 3  # ceil(10/4)

    def test_grown_or_degenerate_worlds(self):
        assert gradient_accumulation_steps(4, 8) == 1  # grown past max
        assert gradient_accumulation_steps(4, 4) == 1
        assert gradient_accumulation_steps(4, 0) == 1  # guard
        assert gradient_accumulation_steps(0, 4) == 1


# ---------------------------------------------------------------------------
# Compile-ahead remesh (trainer/precompile.py)
# ---------------------------------------------------------------------------


class TestAnticipatedWorlds:
    def test_adjacent_worlds_first(self):
        worlds = anticipated_worlds(4, max_workers=8, node_unit=1)
        assert worlds[0] in (3, 5) and worlds[1] in (3, 5)
        assert 4 not in worlds

    def test_shrink_ladder_covers_distinct_accum_factors(self):
        worlds = anticipated_worlds(8, max_workers=8, node_unit=1)
        # every distinct accumulation factor below 8 compiles a
        # distinct program; each must appear exactly once
        factors = {gradient_accumulation_steps(8, w) for w in worlds}
        assert {2, 3, 4} <= factors
        assert len(worlds) == len(set(worlds))

    def test_node_unit_granularity(self):
        worlds = anticipated_worlds(4, max_workers=8, node_unit=2)
        assert all(w % 2 == 0 for w in worlds)
        assert 6 in worlds and 2 in worlds

    def test_bounds_and_degenerate(self):
        assert anticipated_worlds(0) == []
        assert anticipated_worlds(1, max_workers=1) == []
        worlds = anticipated_worlds(8, max_workers=8)
        assert all(1 <= w <= 8 for w in worlds)


class TestCompileAheadService:
    def test_compiles_anticipated_set_and_records_timing(self):
        built = []
        svc = CompileAheadService(
            lambda w: built.append(w), current_world=4, max_workers=8
        )
        svc.start()
        assert svc.wait(timeout=10)
        svc.stop()
        stats = svc.stats()
        assert set(built) == set(stats["compiled"])
        assert set(built) == set(anticipated_worlds(4, 8))
        assert all(t >= 0 for t in stats["compiled"].values())
        assert stats["errors"] == {}

    def test_build_errors_recorded_not_raised(self):
        def build(w):
            if w == 3:
                raise RuntimeError("mesh mismatch")

        svc = CompileAheadService(build, current_world=4, max_workers=8)
        svc.start()
        assert svc.wait(timeout=10)
        svc.stop()
        stats = svc.stats()
        assert "mesh mismatch" in stats["errors"][3]
        assert 3 not in stats["compiled"]

    def test_reanticipate_dedups_compiled_worlds(self):
        built = []
        svc = CompileAheadService(
            lambda w: built.append(w), current_world=4, max_workers=8
        )
        svc.start()
        assert svc.wait(timeout=10)
        first = list(built)
        fresh = svc.anticipate(5)
        assert svc.wait(timeout=10)
        svc.stop()
        # worlds already compiled for current=4 are not re-built
        assert not (set(first) & set(fresh))
        assert len(built) == len(set(built))


class TestPlannerRungLadder:
    """``anticipated_worlds``/``CompileAheadService`` driven by the 2D
    replanner (docs/elastic_parallelism.md): entries are the Rungs each
    anticipated world would actually be replanned onto, not bare ints —
    the accum-only int ladder under-reports distinct programs once a
    shrink can trade DP for PP."""

    @staticmethod
    def _planner():
        from dlrover_tpu.parallel.replan import (
            CostModel,
            ElasticReplanner,
            Rung,
        )

        return ElasticReplanner(
            CostModel(
                param_bytes=1 << 20,
                opt_bytes=2 << 20,
                hbm_bytes_per_device=1_200_000,
                reference=Rung(dp=8),
                opt_dp_shard=True,
            ),
            full_dp=8,
            current=Rung(dp=8),
            max_pp=2,
        )

    def test_planner_ladder_is_the_planned_rungs(self):
        from dlrover_tpu.parallel.replan import Rung

        rungs = anticipated_worlds(
            8, max_workers=8, node_unit=4, planner=self._planner()
        )
        # one likely world (8 - 4 devices): under the HBM cap its PLAN
        # is the dp→pp trade, so the anticipation set carries the 2D
        # rung — the int ladder would have said "world 4" and the
        # compile-ahead cache would be warm for the wrong program
        assert rungs == [Rung(dp=2, pp=2, accum=4)]

    def test_int_ladder_unchanged_without_planner(self):
        assert anticipated_worlds(
            4, max_workers=8, node_unit=1, planner=None
        ) == anticipated_worlds(4, max_workers=8, node_unit=1)
        assert anticipated_worlds(0, planner=None) == []

    def test_service_compiles_rung_keys(self):
        from dlrover_tpu.parallel.replan import Rung

        built = []
        svc = CompileAheadService(
            lambda r: built.append(r),
            current_world=8,
            max_workers=8,
            node_unit=4,
            planner=self._planner(),
        )
        svc.start()
        assert svc.wait(timeout=10)
        svc.stop()
        assert built == [Rung(dp=2, pp=2, accum=4)]
        stats = svc.stats()
        assert set(stats["compiled"]) == {Rung(dp=2, pp=2, accum=4)}
        assert stats["errors"] == {}

    def test_stage_build_fn_compiles_per_stage_programs(self):
        from dlrover_tpu.parallel.replan import Rung
        from dlrover_tpu.trainer.precompile import make_stage_build_fn

        layers = {
            "w": jax.ShapeDtypeStruct((4, 8, 8), jnp.float32),
            "b": jax.ShapeDtypeStruct((4, 8), jnp.float32),
        }

        def stage_fn(params, x):
            def body(h, layer):
                return jnp.tanh(h @ layer["w"] + layer["b"]), None

            out, _ = jax.lax.scan(body, x, params)
            return out

        build = make_stage_build_fn(
            stage_fn, layers, np.zeros((2, 8), np.float32)
        )
        # a Rung's pp picks the stage depth; a bare int works too
        compiled = build(Rung(dp=2, pp=2, accum=4))
        assert compiled is not None
        assert build(1) is not None
        # depth that does not divide the layer count is a recorded error
        with pytest.raises(ValueError):
            build(3)


class TestCompileCachePlacement:
    """The whole placement rule (common/compile_cache.py): the caller's
    JAX_COMPILATION_CACHE_DIR untouched, else ONE fixed path in the
    checkout — never a temporary, pid or time-made path."""

    @pytest.fixture()
    def restore_jax_config(self):
        prev = (
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
        )
        yield
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev[1]
        )

    def test_env_set_is_left_untouched(
        self, tmp_path, monkeypatch, restore_jax_config
    ):
        import dlrover_tpu.common.compile_cache as cc

        theirs = str(tmp_path / "callers_cache")
        monkeypatch.setenv(cc.CACHE_DIR_ENV, theirs)
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert cc.resolve_cache_dir() == theirs
        assert cc.enable_compile_cache() == theirs
        # JAX read the variable itself; this code set no directory (and
        # made none)
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        assert not os.path.exists(theirs)

    def test_env_unset_goes_to_the_fixed_checkout_path(
        self, monkeypatch, restore_jax_config
    ):
        import dlrover_tpu
        import dlrover_tpu.common.compile_cache as cc

        monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
        checkout = os.path.dirname(os.path.dirname(dlrover_tpu.__file__))
        fixed = os.path.join(checkout, ".jax_compile_cache")
        assert cc.resolve_cache_dir() == cc.DEFAULT_CACHE_DIR == fixed
        assert cc.enable_compile_cache(min_compile_s=2.5) == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 2.5
        assert os.path.isdir(fixed)
        # idempotent
        assert cc.enable_compile_cache() == fixed

    def test_never_a_temporary_or_pid_path(self, monkeypatch):
        import tempfile

        import dlrover_tpu.common.compile_cache as cc

        monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
        seen = set()
        for tmp in ("/tmp/a", "/tmp/b"):
            monkeypatch.setenv("TMPDIR", tmp)
            tempfile.tempdir = None
            monkeypatch.setattr(os, "getpid", lambda t=tmp: hash(t) % 9999)
            seen.add(cc.resolve_cache_dir())
        tempfile.tempdir = None
        (path,) = seen  # the same path whatever TMPDIR and pid say
        assert "/tmp" not in path and not any(c.isdigit() for c in path)

    def test_the_chaos_harnesses_make_no_cache_dir_of_their_own(self):
        import inspect

        from dlrover_tpu.chaos import goodput_storm, master_kill

        for mod in (goodput_storm, master_kill):
            src = inspect.getsource(mod)
            assert "xla_cache" not in src, mod
            assert "jax_compilation_cache_dir" not in src, mod

    def test_context_env_wiring(self, monkeypatch):
        from dlrover_tpu.common.config import Context

        monkeypatch.setenv("DLROVER_COMPILE_CACHE_MIN_COMPILE_S", "2.5")
        monkeypatch.setenv("DLROVER_INPUT_PREFETCH", "0")
        monkeypatch.setenv("DLROVER_CKPT_PREFETCH_RESTORE", "false")
        ctx = Context()
        ctx.apply_env()
        assert ctx.compile_cache_min_compile_s == 2.5
        assert ctx.input_prefetch is False
        assert ctx.ckpt_prefetch_restore is False

    def test_agent_hands_the_same_directory_to_its_workers(
        self, tmp_path, monkeypatch
    ):
        import dlrover_tpu.common.compile_cache as cc
        from dlrover_tpu.launcher.elastic_run import (
            config_from_args,
            parse_args,
        )

        ns = parse_args(["--nnodes", "1", "--sync-input", "train.py"])
        theirs = str(tmp_path / "callers_cache")
        monkeypatch.setenv(cc.CACHE_DIR_ENV, theirs)
        env = config_from_args(ns).worker_env()
        assert env[cc.CACHE_DIR_ENV] == theirs
        assert env["DLROVER_INPUT_PREFETCH"] == "0"
        monkeypatch.delenv(cc.CACHE_DIR_ENV)
        ns2 = parse_args(["--nnodes", "1", "train.py"])
        env2 = config_from_args(ns2).worker_env()
        assert env2[cc.CACHE_DIR_ENV] == cc.DEFAULT_CACHE_DIR
        # default: prefetch on -> no override exported
        assert "DLROVER_INPUT_PREFETCH" not in env2


class TestWorkerPlatformPin:
    """No hidden CPU: the worker env contract pins ``tpu`` only when the
    job is a TPU job AND nothing else pinned a platform."""

    @pytest.mark.parametrize(
        "accelerator,caller,extra,want",
        [
            ("tpu", None, {}, "tpu"),  # nothing pinned -> pin the chip
            ("tpu", "cpu", {}, None),  # caller's pin is inherited as is
            ("tpu", None, {"JAX_PLATFORMS": "cpu"}, "cpu"),  # extra_env
            ("cpu", None, {}, None),  # a CPU job pins nothing
        ],
    )
    def test_worker_env_pin(self, monkeypatch, accelerator, caller, extra, want):
        from dlrover_tpu.agent.config import ElasticLaunchConfig

        if caller is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", caller)
        env = ElasticLaunchConfig(
            accelerator=accelerator, extra_env=dict(extra)
        ).worker_env()
        assert env.get("JAX_PLATFORMS") == want

    def test_pinned_worker_fails_instead_of_training_on_the_host(self):
        """With the pin, a process that cannot initialize the TPU raises
        at its first backend touch — here, where there is no chip."""
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="tpu", TPU_LOG_DIR="disabled")
        env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
        proc = subprocess.run(
            [sys.executable, "-c", "import jax; print(jax.devices())"],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0
        assert "CpuDevice" not in proc.stdout


# What TestWorkerAllocatorPolicy's child does: two rounds of allocate,
# write, free over 256 MB in pieces the size of a train state's leaves
# (one above the 32 MiB that glibc's mmap threshold cannot pass), and the
# pages it was given in each round.
_ALLOCATE_TWICE = """
import gc, resource
import numpy as np

def faults():
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt

for _ in range(2):
    before = faults()
    held = [np.empty(mb << 20, np.uint8) for mb in [48] + [8] * 26]
    for a in held:
        a.fill(1)
    print(faults() - before)
    del held, a
    gc.collect()
"""


class TestWorkerAllocatorPolicy:
    """A worker is born keeping what it frees (``GLIBC_TUNABLES``), unless
    the caller said how memory is to be allocated."""

    @staticmethod
    def clear(monkeypatch):
        for key in list(os.environ):
            if key.startswith("MALLOC_") or key in ("GLIBC_TUNABLES", "LD_PRELOAD"):
                monkeypatch.delenv(key)

    @pytest.mark.parametrize(
        "caller,extra,want",
        [
            ({}, {}, "policy"),  # nothing chosen -> the agent's policy
            ({"GLIBC_TUNABLES": "glibc.malloc.arena_max=2"}, {}, None),
            ({"MALLOC_TOP_PAD_": "1800000000"}, {}, None),
            ({"MALLOC_TRIM_THRESHOLD_": "2000000000"}, {}, None),
            ({"MALLOC_MMAP_THRESHOLD_": "33554432"}, {}, None),
            ({"MALLOC_ARENA_MAX": "1"}, {}, None),
            ({"LD_PRELOAD": "/usr/lib/libjemalloc.so.2"}, {}, None),
            # extra_env wins, whatever it says
            ({}, {"GLIBC_TUNABLES": "glibc.malloc.top_pad=1"}, "glibc.malloc.top_pad=1"),
            ({}, {"GLIBC_TUNABLES": "glibc.pthread.rseq=0"}, "glibc.pthread.rseq=0"),
            ({}, {"MALLOC_ARENA_MAX": "4"}, None),
            # a caller's other tunables are kept beside the policy
            ({"GLIBC_TUNABLES": "glibc.pthread.rseq=0"}, {}, "glibc.pthread.rseq=0:policy"),
        ],
    )
    def test_worker_env_policy(self, monkeypatch, caller, extra, want):
        from dlrover_tpu.agent.config import (
            WORKER_MALLOC_TUNABLES,
            ElasticLaunchConfig,
        )

        self.clear(monkeypatch)
        for key, value in caller.items():
            monkeypatch.setenv(key, value)
        env = ElasticLaunchConfig(extra_env=dict(extra)).worker_env()
        if want is not None:
            want = want.replace("policy", WORKER_MALLOC_TUNABLES)
        assert env.get("GLIBC_TUNABLES") == want

    @pytest.mark.parametrize("with_policy", [True, False])
    def test_a_second_round_lands_in_kept_pages(self, monkeypatch, with_policy):
        """Started with exactly the environment the agent writes, a
        process that frees 256 MB and allocates them again is given
        almost no new page; started without it, as many as at first."""
        import platform
        import subprocess
        import sys

        from dlrover_tpu.agent.config import ElasticLaunchConfig

        if platform.libc_ver()[0] != "glibc":
            pytest.skip(f"GLIBC_TUNABLES is glibc's: libc is {platform.libc_ver()}")
        self.clear(monkeypatch)
        env = dict(os.environ)
        policy = ElasticLaunchConfig().worker_env()["GLIBC_TUNABLES"]
        if with_policy:
            env["GLIBC_TUNABLES"] = policy
        proc = subprocess.run(
            [sys.executable, "-c", _ALLOCATE_TWICE],
            env=env, capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 0, proc.stderr
        first, second = (int(n) for n in proc.stdout.split())
        assert first >= 100  # 256 MB in pages of 2 MiB at the largest
        assert (second < first / 10) == with_policy, (first, second)


# ---------------------------------------------------------------------------
# MTTR phase attribution (attribution/recovery.py)
# ---------------------------------------------------------------------------


class TestRecoverySpool:
    def test_noop_without_env(self, monkeypatch):
        monkeypatch.delenv(recovery.RECOVERY_DIR_ENV, raising=False)
        assert recovery.record_phase_file("worker", {"x": 1}) is None

    def test_record_and_aggregate_excludes_first_boot(
        self, tmp_path, monkeypatch
    ):
        root = str(tmp_path / "spool")
        monkeypatch.setenv(recovery.RECOVERY_DIR_ENV, root)
        # round 0 = first boot: excluded from the rdzv mean
        recovery.record_phase_file("rdzv", {"rdzv_s": 9.0, "round": 0})
        recovery.record_phase_file("rdzv", {"rdzv_s": 2.0, "round": 1})
        recovery.record_phase_file("rdzv", {"rdzv_s": 4.0, "round": 2})
        # non-resumed worker = first boot: excluded from phase means
        recovery.record_phase_file(
            "worker",
            {"resumed": False, "restore_s": 0.1, "compile_s": 30.0,
             "first_step_s": 31.0},
        )
        recovery.record_phase_file(
            "worker",
            {"resumed": True, "restore_s": 0.4, "compile_s": 6.0,
             "first_step_s": 7.0},
        )
        recovery.record_phase_file(
            "worker",
            {"resumed": True, "restore_s": 0.6, "compile_s": 8.0,
             "first_step_s": 9.0},
        )
        agg = recovery.aggregate(root)
        assert agg["rdzv_s"] == 3.0
        assert agg["restore_s"] == 0.5
        assert agg["compile_s"] == 7.0
        assert agg["first_step_s"] == 8.0
        assert agg["recovery_samples"] == 2

    def test_aggregate_empty_and_torn_records(self, tmp_path):
        root = str(tmp_path / "spool2")
        agg = recovery.aggregate(root)  # missing dir
        assert agg["recovery_samples"] == 0
        os.makedirs(root)
        # a half-written temp file (dot-prefixed) and junk are ignored
        with open(os.path.join(root, ".worker_tmp.json"), "w") as f:
            f.write('{"resumed": true')
        with open(os.path.join(root, "worker_1_2.json"), "w") as f:
            f.write("not json")
        agg = recovery.aggregate(root)
        assert agg["recovery_samples"] == 0

    def test_loop_writes_worker_record(self, tmp_path, monkeypatch):
        spool = str(tmp_path / "rec")
        monkeypatch.setenv(recovery.RECOVERY_DIR_ENV, spool)

        @jax.jit
        def step(state, x):
            return {"v": state["v"] + jnp.asarray(x).sum()}, state["v"].sum()

        engine = CheckpointEngine(
            str(tmp_path / "ckpt"), standalone=True, replicate=False
        )
        try:
            loop = ElasticTrainLoop(
                engine, step, max_steps=3, storage_every=100
            )
            loop.run(
                {"v": jnp.zeros(3)},
                ((np.ones((2,), np.float32),) for _ in range(10)),
            )
        finally:
            engine.shm.unlink()
            engine.close()
        recs = [r for r in recovery.read_records(spool)
                if r["_kind"] == "worker"]
        assert len(recs) == 1
        rec = recs[0]
        assert rec["resumed"] is False  # first boot
        assert rec["first_step_s"] > 0
        assert "compile_s" in rec  # steady step observed -> split done


# ---------------------------------------------------------------------------
# Overlapped restore (checkpoint/engine.py + saver.py)
# ---------------------------------------------------------------------------


class TestOverlappedRestore:
    def _tree(self):
        return {
            "w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
            "step": np.int64(4),
        }

    def test_prefetched_restore_consumed(self, tmp_path):
        tree = self._tree()
        stage = CheckpointEngine(
            str(tmp_path / "ckpt"), standalone=True, replicate=False,
            prefetch_restore=False,
        )
        assert stage.save_to_memory(4, tree)
        stage.close()  # shm image survives the engine
        # a fresh engine (the restarted worker): its constructor starts
        # the host read in the background; load() consumes it
        engine = CheckpointEngine(
            str(tmp_path / "ckpt"), standalone=True, replicate=False,
            prefetch_restore=True,
        )
        try:
            step, restored = engine.load(
                jax.tree.map(jnp.zeros_like, tree)
            )
            assert step == 4
            assert engine.prefetch_used
            for a, b in zip(
                jax.tree.leaves(tree), jax.tree.leaves(restored)
            ):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)
                )
        finally:
            engine.shm.unlink()
            engine.close()

    def test_save_supersedes_prefetched_image(self, tmp_path):
        old = self._tree()
        stage = CheckpointEngine(
            str(tmp_path / "ckpt"), standalone=True, replicate=False,
            prefetch_restore=False,
        )
        assert stage.save_to_memory(4, old)
        stage.close()
        engine = CheckpointEngine(
            str(tmp_path / "ckpt"), standalone=True, replicate=False,
            prefetch_restore=True,
        )
        try:
            new = {"w": old["w"] * 2.0, "step": np.int64(9)}
            assert engine.save_to_memory(9, new)
            # the save invalidated the init-time prefetch: load must
            # see step 9, never the stale prefetched step 4
            step, restored = engine.load(
                jax.tree.map(jnp.zeros_like, new)
            )
            assert step == 9
            assert not engine.prefetch_used
            np.testing.assert_array_equal(
                np.asarray(restored["w"]), np.asarray(new["w"])
            )
        finally:
            engine.shm.unlink()
            engine.close()

    def test_saver_prefetch_restore_outcomes(self, tmp_path):
        # no saver instance yet: nothing to prefetch, never raises
        AsyncCheckpointSaver.reset()
        assert AsyncCheckpointSaver.prefetch_restore_async() is None
        engine = CheckpointEngine(
            str(tmp_path / "ckpt"), standalone=True, replicate=False,
            prefetch_restore=False,
        )
        try:
            # The engine ctor returns once the saver's shard-lock
            # server answers, but the runner thread assigns _instance
            # moments later — poll briefly on loaded boxes.
            deadline = time.time() + 10
            inst = AsyncCheckpointSaver._instance
            while inst is None and time.time() < deadline:
                time.sleep(0.05)
                inst = AsyncCheckpointSaver._instance
            assert inst is not None
            # no staged image, no replica manager -> unavailable
            assert inst.prefetch_restore() == "unavailable"
            assert engine.save_to_memory(2, self._tree())
            assert inst.prefetch_restore() == "staged"
            t = AsyncCheckpointSaver.prefetch_restore_async()
            assert t is not None
            t.join(10)
        finally:
            engine.shm.unlink()
            engine.close()


# ---------------------------------------------------------------------------
# Doc lint, folded into tpurun-lint (PR 6): the ad-hoc DLROVER_* doc
# test this file carried (its own exemption list + staleness check)
# now lives in the env-knobs pass of dlrover_tpu/analysis — one typed
# registry in common/constants.py (ENV_KNOBS) enforcing documented <=>
# registered <=> referenced. The assertions stay green through the
# pass; only the duplicate logic is gone.
# ---------------------------------------------------------------------------


def test_env_knob_registry_enforced_by_lint():
    """Every DLROVER_* knob is registered, documented (unless an
    internal process-contract var), still referenced, and every env
    access names a registered knob — via the env-knobs pass."""
    from dlrover_tpu.analysis import run_lint
    from dlrover_tpu.analysis.passes import env_knobs

    result = run_lint(
        [os.path.join(_REPO, "dlrover_tpu")],
        passes=[env_knobs],
        repo_root=_REPO,
    )
    assert result.clean, "\n".join(
        [v.render() for v in result.violations] + result.errors
    )


def test_recovery_doc_linked():
    assert os.path.exists(os.path.join(_REPO, "docs", "recovery.md"))
    for rel in ("README.md", "docs/chaos.md", "docs/deploy.md"):
        text = open(os.path.join(_REPO, rel)).read()
        assert "recovery.md" in text, f"{rel} does not link docs/recovery.md"


def test_storm_result_contract_mentions_phases():
    """The storm docstring/result contract carries the breakdown keys
    (the result dict itself is exercised by the slow storm tests and
    the smoke in test_zz_chaos_e2e)."""
    from dlrover_tpu.chaos import goodput_storm

    doc = goodput_storm.run_goodput_storm.__doc__
    for key in ("rdzv_s", "restore_s", "compile_s", "first_step_s"):
        assert key in doc
