"""Tests for dlrover_tpu.common: serialization, node model, config, events."""

import dataclasses
import os

import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.common.config import Context
from dlrover_tpu.common.constants import NodeExitReason, NodeStatus
from dlrover_tpu.common.events import (
    AsyncExporter,
    EventEmitter,
    Exporter,
)
from dlrover_tpu.common.node import Node, NodeResource, is_allowed_transition
from dlrover_tpu.common.serialize import dumps, loads, register_message


class TestSerialize:
    def test_roundtrip_simple(self):
        msg = comm.JoinRendezvousRequest(
            node_id=3, node_rank=1, local_world_size=4, rdzv_name="training"
        )
        assert loads(dumps(msg)) == msg

    def test_roundtrip_nested(self):
        world = {
            0: comm.NodeMeta(node_id=0, node_rank=0, addr="10.0.0.1"),
            1: comm.NodeMeta(node_id=1, node_rank=1, addr="10.0.0.2"),
        }
        msg = comm.CommWorldResponse(rdzv_name="training", round=2, world=world)
        out = loads(dumps(msg))
        assert out.world[1].addr == "10.0.0.2"
        assert isinstance(out.world[0], comm.NodeMeta)

    def test_roundtrip_bytes_and_lists(self):
        msg = comm.KeyValuePair(key="k", value=b"\x00\x01binary")
        assert loads(dumps(msg)).value == b"\x00\x01binary"
        msg2 = comm.FaultNodesResponse(fault_nodes=[1, 5, 9])
        assert loads(dumps(msg2)).fault_nodes == [1, 5, 9]

    def test_unknown_type_rejected(self):
        class NotRegistered:
            pass

        with pytest.raises(TypeError):
            dumps(NotRegistered())

    def test_register_duplicate_rejected(self):
        @register_message
        @dataclasses.dataclass
        class UniqueMsg1234:
            x: int = 0

        with pytest.raises(ValueError):

            @register_message
            @dataclasses.dataclass
            class UniqueMsg1234:  # noqa: F811
                y: int = 0

    def test_empty_payload(self):
        assert loads(b"") is None


class TestNode:
    def test_status_flow(self):
        node = Node(node_type="worker", node_id=0)
        assert node.update_status(NodeStatus.PENDING)
        assert node.update_status(NodeStatus.RUNNING)
        assert node.start_time is not None
        # Illegal transition back to pending
        assert not node.update_status(NodeStatus.PENDING)
        assert node.update_status(NodeStatus.FAILED)
        assert node.exited()

    def test_transition_table(self):
        assert is_allowed_transition(NodeStatus.RUNNING, NodeStatus.SUCCEEDED)
        assert not is_allowed_transition(NodeStatus.SUCCEEDED, NodeStatus.RUNNING)
        assert not is_allowed_transition(NodeStatus.RUNNING, NodeStatus.RUNNING)

    def test_should_relaunch_budget(self):
        node = Node(node_type="worker", node_id=0, max_relaunch_count=2)
        assert node.should_relaunch()
        node.relaunch_count = 2
        assert not node.should_relaunch()

    def test_fatal_error_not_relaunched(self):
        node = Node(node_type="worker", node_id=0)
        node.exit_reason = NodeExitReason.FATAL_ERROR
        assert not node.should_relaunch()

    def test_get_relaunch_node(self):
        node = Node(node_type="worker", node_id=0, rank_index=3)
        node.update_status(NodeStatus.RUNNING)
        new = node.get_relaunch_node(new_id=7)
        assert new.node_id == 7
        assert new.rank_index == 3
        assert new.status == NodeStatus.INITIAL
        assert new.relaunch_count == 1

    def test_resource_parse(self):
        res = NodeResource.resource_str_to_node_resource("cpu=4,memory=8192Mi,tpu=8")
        assert res.cpu == 4
        assert res.memory_mb == 8192
        assert res.device_count == 8


class TestConfig:
    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("DLROVER_MAX_RELAUNCH_COUNT", "7")
        monkeypatch.setenv("DLROVER_HANG_DETECTION_ENABLED", "false")
        ctx = Context()
        ctx.apply_env()
        assert ctx.max_relaunch_count == 7
        assert ctx.hang_detection_enabled is False

    def test_singleton(self):
        assert Context.singleton_instance() is Context.singleton_instance()


class _ListExporter(Exporter):
    def __init__(self):
        self.events = []

    def export(self, event):
        self.events.append(event)


class TestEvents:
    def test_instant_and_span(self):
        exp = _ListExporter()
        em = EventEmitter("test", exporter=exp)
        em.instant("hello", a=1)
        with em.duration("work", step=3):
            pass
        assert [e.name for e in exp.events] == ["hello", "work", "work"]
        end = exp.events[-1]
        assert end.event_type == "end"
        assert "duration_s" in end.content

    def test_span_failure(self):
        exp = _ListExporter()
        em = EventEmitter("test", exporter=exp)
        with pytest.raises(RuntimeError):
            with em.duration("work"):
                raise RuntimeError("boom")
        assert exp.events[-1].content["success"] is False

    def test_async_exporter_drains(self):
        exp = _ListExporter()
        async_exp = AsyncExporter(exp)
        em = EventEmitter("test", exporter=async_exp)
        for i in range(100):
            em.instant("e", i=i)
        async_exp.close()
        assert len(exp.events) == 100

    def test_async_exporter_counts_inner_export_failures(self):
        """PR 9 exception-swallow finding: a sink that throws silently
        ate events — now they count as dropped (the exporter still
        outlives the sink)."""

        class BoomExporter(_ListExporter):
            def export(self, event):
                if len(self.events) >= 2:
                    raise RuntimeError("sink died")
                super().export(event)

        exp = BoomExporter()
        async_exp = AsyncExporter(exp)
        em = EventEmitter("test", exporter=async_exp)
        for i in range(5):
            em.instant("e", i=i)
        async_exp.close()
        assert len(exp.events) == 2
        assert async_exp._dropped == 3


class TestSerializeEscaping:
    def test_plain_dict_with_reserved_key(self):
        msg = comm.ElasticRunConfigResponse(configs={"_t": "oops", "x": "1"})
        out = loads(dumps(msg))
        assert out.configs == {"_t": "oops", "x": "1"}

    def test_memory_units(self):
        res = NodeResource.resource_str_to_node_resource("memory=8Gi")
        assert res.memory_mb == 8192
        res = NodeResource.resource_str_to_node_resource("memory=2G")
        assert res.memory_mb == 2000


class TestErrorHandler:
    """Crash-safe event flushing (reference error_handler.py:26)."""

    def test_excepthook_flushes_and_chains(self):
        import sys

        from dlrover_tpu.common.error_handler import ErrorHandler

        handler = ErrorHandler()
        flushed = []
        chained = []
        handler.register_flushable("x", lambda: flushed.append(1))
        orig = sys.excepthook
        sys.excepthook = lambda *a: chained.append(a)
        try:
            handler.register()
            try:
                raise ValueError("boom")
            except ValueError:
                sys.excepthook(*sys.exc_info())
            assert flushed == [1]
            assert chained and chained[0][0] is ValueError
        finally:
            handler.unregister()
            sys.excepthook = orig

    def test_flush_failure_does_not_block_others(self):
        from dlrover_tpu.common.error_handler import ErrorHandler

        handler = ErrorHandler()
        ran = []
        handler.register_flushable("bad", lambda: 1 / 0)
        handler.register_flushable("good", lambda: ran.append(1))
        assert "good" in handler.flush_all()
        assert ran == [1]

    def test_fatal_signal_flushes_then_dies_with_signal(self, tmp_path):
        """SIGTERM: the flushable lands on disk, then the ORIGINAL
        disposition kills the process (exit -15), in a real child."""
        import signal
        import subprocess
        import sys
        import time

        marker = tmp_path / "flushed"
        child = subprocess.Popen(
            [
                sys.executable,
                "-c",
                (
                    "import sys, time, pathlib\n"
                    "sys.path.insert(0, %r)\n"
                    "from dlrover_tpu.common.error_handler import "
                    "init_error_handler\n"
                    "h = init_error_handler()\n"
                    "h.register_flushable('m', lambda: pathlib.Path(%r)"
                    ".write_text('flushed'))\n"
                    "print('READY', flush=True)\n"
                    "time.sleep(60)\n"
                )
                % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   str(marker)),
            ],
            stdout=subprocess.PIPE,
            text=True,
        )
        try:
            assert child.stdout.readline().strip() == "READY"
            child.send_signal(signal.SIGTERM)
            rc = child.wait(timeout=15)
            assert rc == -signal.SIGTERM  # true disposition preserved
            deadline = time.time() + 5
            while time.time() < deadline and not marker.exists():
                time.sleep(0.1)
            assert marker.read_text() == "flushed"
        finally:
            if child.poll() is None:
                child.kill()

    def test_crash_event_written_to_event_dir(self, tmp_path):
        """An unhandled exception leaves a 'crash' event on disk."""
        import subprocess
        import sys

        env = dict(os.environ, DLROVER_EVENT_DIR=str(tmp_path))
        code = (
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "from dlrover_tpu.common.error_handler import init_error_handler\n"
            "init_error_handler()\n"
            "raise RuntimeError('the crash reason')\n"
        ) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, "-c", code],
            env=env, capture_output=True, text=True, timeout=60,
        )
        assert r.returncode != 0
        assert "the crash reason" in r.stderr  # original hook chained
        contents = "".join(
            p.read_text() for p in tmp_path.glob("events*")
        )
        assert '"crash"' in contents and "the crash reason" in contents


class TestPinAccelerator:
    """No hidden CPU: a process started for the TPU pins it, so a failed
    TPU initialization raises; a caller's own pin is honoured."""

    def _run(self, env_value):
        import subprocess
        import sys

        env = dict(os.environ, TPU_LOG_DIR="disabled")
        env.pop("JAX_PLATFORMS", None)
        if env_value is not None:
            env["JAX_PLATFORMS"] = env_value
        code = (
            "import os, jax\n"
            "from dlrover_tpu.common.platform import pin_accelerator\n"
            "print('PIN', pin_accelerator(), os.environ['JAX_PLATFORMS'],"
            " jax.config.jax_platforms)\n"
            "print('DEV', jax.devices()[0].platform)\n"
        )
        return subprocess.run(
            [sys.executable, "-c", code],
            env=env, capture_output=True, text=True, timeout=120,
        )

    def test_nothing_pinned_pins_the_chip_and_fails_without_one(self):
        proc = self._run(None)
        assert "PIN tpu tpu tpu" in proc.stdout
        # no chip here: the first backend touch raises, no CPU answer
        assert proc.returncode != 0 and "DEV" not in proc.stdout

    def test_the_callers_pin_is_honoured(self):
        proc = self._run("cpu")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "PIN cpu cpu cpu" in proc.stdout and "DEV cpu" in proc.stdout
