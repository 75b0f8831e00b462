"""PJRT C-API interposer tests (VERDICT r2 #2).

The interposer is exercised exactly the way jax would use it — through
the PJRT plugin entry point ``GetPjrtApi`` — against the fake plugin
(``native/pjrt_interposer/fake_pjrt_plugin.cc``), with NO Python
annotations anywhere: the C test driver compiles, executes, and
transfers through the interposed table and the metrics must show up on
their own. Reference parity:
``xpu_timer/xpu_timer/nvidia/hook.cc:54,323`` (driver-boundary
interception), ``common/manager.cc:393-414`` (launch-vs-completion hang
split).
"""

import os
import subprocess
import sys
import time
import urllib.request

import pytest

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native",
    "pjrt_interposer",
)


@pytest.fixture(scope="module")
def built():
    r = subprocess.run(
        ["make", "-s"], cwd=NATIVE_DIR, capture_output=True, text=True,
        timeout=300,
    )
    assert r.returncode == 0, r.stderr
    return NATIVE_DIR


def _run_driver(built, mode, extra_env=None, port="0"):
    env = dict(
        os.environ,
        DLROVER_PJRT_REAL_PLUGIN=os.path.join(built, "libfake_pjrt_plugin.so"),
        DLROVER_TT_PORT=port,
    )
    env.update(extra_env or {})
    r = subprocess.run(
        ["./test_driver", "./libpjrt_interposer.so", mode],
        cwd=built, env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


class TestInterposition:
    def test_execute_and_transfers_recorded_without_annotations(self, built):
        """compile + 3 executes + H2D + D2H through the PJRT table only;
        every family must appear in the metrics text."""
        out = _run_driver(built, "basic")
        assert 'tpu_timer_count{kind="execute"} 3' in out
        assert 'tpu_timer_count{kind="compile"} 1' in out
        assert 'tpu_timer_count{kind="h2d"} 1' in out
        assert 'tpu_timer_count{kind="d2h"} 1' in out
        # completion events resolved: nothing left in flight
        assert "tpu_timer_device_launches_total 3" in out
        assert "tpu_timer_device_completes_total 3" in out
        assert out.strip().endswith("inflight=0")
        # the fake device delay (~5 ms) must be visible in the measured
        # execute latency — proof we timed the completion event, not
        # just the host-side call
        for line in out.splitlines():
            if line.startswith('tpu_timer_latency_us{kind="execute",agg="min"'):
                assert float(line.rsplit(" ", 1)[1]) >= 4000, line
                break
        else:
            pytest.fail("no execute latency line")

    def test_h2d_bytes_from_dims(self, built):
        """128x128 f32 = 64 KiB must yield a nonzero GB/s gauge."""
        out = _run_driver(built, "basic")
        assert 'tpu_timer_gbps{kind="h2d"}' in out

    def test_device_stall_verdict(self, built):
        """Execution launched, completion never fires -> DEVICE stall."""
        out = _run_driver(built, "devstall", {"FAKE_EXEC_HANG": "1"})
        assert "verdict=1" in out and "inflight=1" in out

    def test_host_stall_verdict(self, built):
        """Step open, nothing in flight -> HOST stall (dataloader/GC)."""
        out = _run_driver(built, "hoststall")
        assert "verdict=2" in out and "inflight=0" in out

    def test_metrics_served_over_http(self, built):
        """The interposer's tt core serves /metrics on the configured
        port inside the driven process; spot-check via a fixed port."""
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        # DRIVER_LINGER_MS holds the driver (and its HTTP server) open
        # after the measurements so polling can't race process exit.
        env = dict(
            os.environ,
            DLROVER_PJRT_REAL_PLUGIN=os.path.join(
                built, "libfake_pjrt_plugin.so"
            ),
            DLROVER_TT_PORT=str(port),
            DRIVER_LINGER_MS="5000",
        )
        proc = subprocess.Popen(
            ["./test_driver", "./libpjrt_interposer.so", "basic"],
            cwd=built, env=env, stdout=subprocess.PIPE, text=True,
        )
        try:
            body = None
            for _ in range(50):
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=1
                    ) as resp:
                        body = resp.read().decode()
                    if "tpu_timer_device_launches_total" in body:
                        break
                except OSError:
                    import time

                    time.sleep(0.05)
            assert body and "tpu_timer_device_launches_total" in body
        finally:
            proc.wait(timeout=60)


class TestPythonBindings:
    def test_parse_metrics(self):
        from dlrover_tpu.profiler.pjrt import parse_metrics

        text = 'tpu_timer_count{kind="execute"} 3\ntpu_timer_hang 0\nbad\n'
        m = parse_metrics(text)
        assert m['tpu_timer_count{kind="execute"}'] == 3.0
        assert m["tpu_timer_hang"] == 0.0

    def test_build_and_bind(self, built):
        """The ctypes bindings load the library and read live state."""
        from dlrover_tpu.profiler import pjrt

        # Fresh-process check: binding works without a prior GetPjrtApi
        # (tt core not initialized -> safe defaults, no crash).
        code = (
            "from dlrover_tpu.profiler import pjrt;"
            "assert pjrt.stall_verdict() == pjrt.STALL_NONE;"
            "assert pjrt.device_inflight() == 0;"
            "print('BIND_OK')"
        )
        r = subprocess.run(
            ["python", "-c", code],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert r.returncode == 0 and "BIND_OK" in r.stdout, r.stderr

    def test_enable_sets_env(self, built, monkeypatch, tmp_path):
        from dlrover_tpu.profiler import pjrt

        fake_real = tmp_path / "libtpu.so"
        fake_real.write_bytes(b"not really")
        for var in ("TPU_LIBRARY_PATH", "DLROVER_PJRT_REAL_PLUGIN"):
            monkeypatch.delenv(var, raising=False)
        lib = pjrt.enable_tpu_interposition(real_plugin=str(fake_real))
        assert os.environ["TPU_LIBRARY_PATH"] == lib
        assert os.environ["DLROVER_PJRT_REAL_PLUGIN"] == str(fake_real)
        monkeypatch.delenv("TPU_LIBRARY_PATH")
        monkeypatch.delenv("PJRT_TPU_LIBRARY_PATH")
        monkeypatch.delenv("DLROVER_PJRT_REAL_PLUGIN")
        monkeypatch.delenv("DLROVER_TT_PORT")


class TestProductWiring:
    """VERDICT r3 #2: the profiler must be ON in the product path — a
    tpurun-launched worker (fake plugin standing in for libtpu) produces
    pjrt execute counts in the MASTER's metric context and a
    stall-verdict gauge, with zero user profiling code. Reference: the
    agent auto-registers the collector (diagnosis_agent.py:85) and
    xpu_timer_launch preloads hooks into every trainer."""

    def test_tpurun_agent_wires_interposer_and_collector(
        self, built, tmp_path, monkeypatch
    ):
        import threading
        import urllib.request as _rq

        from dlrover_tpu.agent.config import ElasticLaunchConfig
        from dlrover_tpu.agent.training_agent import (
            AGENT_EXIT_OK,
            ElasticTrainingAgent,
        )
        from dlrover_tpu.master.local_master import LocalJobMaster
        from dlrover_tpu.master.monitor.metric_context import (
            get_metric_context,
        )
        from dlrover_tpu.rpc.client import MasterClient

        # The fake plugin IS the "real" plugin for this machine: on a TPU
        # host prepare_worker_profiling_env finds libtpu.so instead.
        monkeypatch.setenv(
            "DLROVER_PJRT_REAL_PLUGIN",
            os.path.join(built, "libfake_pjrt_plugin.so"),
        )
        # The worker stands in for "jax initializes the TPU backend": it
        # loads $TPU_LIBRARY_PATH (the interposer, injected by the AGENT
        # env contract — the script never mentions profiling) through the
        # PJRT entry point and runs a few executes, then lingers so the
        # agent's scraper can observe the live /metrics server.
        script = tmp_path / "train_tpu_sim.py"
        script.write_text(
            "import os, subprocess, time\n"
            "lib = os.environ['TPU_LIBRARY_PATH']\n"
            "assert os.environ['DLROVER_TT_PORT'] != '0'\n"
            "driver = os.environ['TEST_DRIVER']\n"
            "env = dict(os.environ, DRIVER_LINGER_MS='15000')\n"
            "p = subprocess.Popen([driver, lib, 'basic'], env=env,\n"
            "                     cwd=os.path.dirname(driver))\n"
            "time.sleep(8)\n"
            "p.terminate()\n"
            "print('sim worker done')\n"
        )

        master = LocalJobMaster(num_workers=1, fresh_context=True)
        master.prepare()
        try:
            client = MasterClient(
                master_addr=master.addr, node_id=0, service_type="grpc"
            )
            config = ElasticLaunchConfig(
                min_nodes=1,
                max_nodes=1,
                node_rank=0,
                entrypoint=str(script),
                master_addr=master.addr,
                profile="on",
                profiler_scrape_interval_s=0.5,
                monitor_interval=0.5,
                max_restarts=0,
                extra_env={"TEST_DRIVER": os.path.join(built, "test_driver")},
            )
            agent = ElasticTrainingAgent(
                config, client=client, start_ckpt_saver=False
            )
            rc = {}
            t = threading.Thread(target=lambda: rc.update(v=agent.run()))
            t.start()

            # Rank 0 must also serve the cluster profiler daemon, and the
            # master metric context must fill up — all with no user code.
            # Wait for the EXACT gauge the assertion needs: breaking on
            # any tpu_timer_count raced a scrape that caught compile
            # counts a beat before the first execute landed (flaked
            # once per ~3 full-suite runs under load).
            def has_execute(g):
                return any(
                    k.startswith("tpu_timer_count") and 'kind="execute"' in k
                    for k in g
                )

            deadline = time.time() + 60
            gauges = {}
            while time.time() < deadline:
                all_gauges = get_metric_context().all_gauges()
                gauges = all_gauges.get(0) or all_gauges.get("0") or {}
                if has_execute(gauges):
                    break
                time.sleep(0.25)
            assert has_execute(gauges), (
                f"no execute counts reached the master: {sorted(gauges)[:10]}"
            )
            assert "tpu_timer_stall_verdict" in gauges

            daemon = agent._profiler_daemon
            assert daemon is not None, "rank-0 agent did not start the daemon"
            with _rq.urlopen(
                f"http://127.0.0.1:{daemon.port}/metrics", timeout=10
            ) as resp:
                text = resp.read().decode()
            assert "tpu_timer_count" in text and 'node="0"' in text

            t.join(timeout=60)
            assert not t.is_alive(), "agent did not finish"
            assert rc.get("v") == AGENT_EXIT_OK
        finally:
            master.stop()


class TestStepMarks:
    def test_train_loop_marks_steps_in_native_lib(
        self, built, monkeypatch, tmp_path
    ):
        """With the agent's DLROVER_TT_PORT contract present, the train
        loop feeds step boundaries to the live tt core — the hang
        watchdog's host-progress signal (last_step stayed -1 in product
        runs before this wiring)."""
        import jax.numpy as jnp

        from dlrover_tpu.checkpoint.engine import CheckpointEngine
        from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
        from dlrover_tpu.profiler import pjrt
        from dlrover_tpu.trainer.loop import ElasticTrainLoop

        monkeypatch.setenv("DLROVER_TT_PORT", "0")
        monkeypatch.setenv("DLROVER_JOB_NAME", f"ttmarks_{os.getpid()}")
        AsyncCheckpointSaver.reset()
        engine = CheckpointEngine(str(tmp_path / "ckpt"), standalone=True)
        try:

            def step_fn(state, x):
                return {"w": state["w"] + x}, jnp.float32(0.0)

            def data():
                while True:
                    yield (jnp.ones(()),)

            loop = ElasticTrainLoop(
                engine, step_fn, max_steps=7, memory_every=100
            )
            loop.run({"w": jnp.zeros(())}, data())
            metrics = pjrt.parse_metrics(pjrt.metrics_text())
            assert metrics.get("tpu_timer_last_step") == 6.0
        finally:
            engine.shm.unlink()
            engine.close()
            AsyncCheckpointSaver.reset()


class TestRingDump:
    def test_ring_dump_request_roundtrip(self, built, monkeypatch, tmp_path):
        """Agent drops a request file; the worker's watcher thread dumps
        the live trace ring and acks with the event count; the timeline
        converts. (The thread design is deliberate: a Python signal
        handler would never run while the main thread is wedged in a
        blocked collective.)"""
        import ctypes

        from dlrover_tpu.profiler import pjrt, stack_dump
        from dlrover_tpu.profiler.timeline import convert

        monkeypatch.setenv("DLROVER_JOB_NAME", f"ring_{os.getpid()}")
        monkeypatch.setattr(
            stack_dump, "_DUMP_DIR", str(tmp_path / "dumps")
        )
        # Feed the live tt core a few events (stand-in for interposed
        # device executes on CPU CI).
        pjrt.ensure_core(0)
        lib = ctypes.CDLL(pjrt.build_interposer())
        lib.tt_intern_name.restype = ctypes.c_int32
        lib.tt_intern_name.argtypes = [ctypes.c_char_p]
        # Full 6-arg ABI (int32, int32, int64, int64, double, double):
        # calling with fewer/untyped args reads garbage registers.
        lib.tt_record.restype = None
        lib.tt_record.argtypes = [
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_double,
            ctypes.c_double,
        ]
        nid = lib.tt_intern_name(b"exec:test_kernel")
        for i in range(3):
            lib.tt_record(nid, 1, 1000 * i, 250, 0.0, 0.0)

        t = stack_dump.start_ring_dump_watcher(poll_s=0.1)
        assert t is not None
        out = stack_dump.request_ring_dump(timeout_s=10)
        assert out, "ring dump did not land"
        n = convert(out, out + ".json")
        assert n >= 3
        import json as _json

        evs = _json.load(open(out + ".json"))["traceEvents"]
        assert any(e.get("name") == "exec:test_kernel" for e in evs)
