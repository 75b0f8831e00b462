"""Test bootstrap: force a virtual 8-device CPU platform before jax imports.

Mirrors the reference's multi-node-without-cluster tricks (SURVEY.md §4):
control-plane tests run N simulated agents against an in-process master;
mesh/checkpoint tests run on 8 virtual CPU devices.
"""

import os

# Persistent XLA compile cache: the suite is compile-heavy (pipeline /
# MoE / sharded train steps) and repeated runs drop ~3x in wall time.
# Placement follows the program's own rule (common/compile_cache.py): a
# caller's JAX_COMPILATION_CACHE_DIR, else the fixed path in the
# checkout — exported here so every subprocess a test starts agrees.
from dlrover_tpu.common.compile_cache import CACHE_DIR_ENV, resolve_cache_dir

os.environ.setdefault(CACHE_DIR_ENV, resolve_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")

from dlrover_tpu.common.platform import force_virtual_cpu

force_virtual_cpu(8)
# One shm / socket namespace a test process. An xdist worker inherits the
# controller's environment, this name with it, and two workers on one name
# share a checkpoint saver's queue: one test's save lands in the other's
# directory (PR 56: test_lock_witness' drill restored test_zz_chaos_e2e's step).
_job = os.environ.get("DLROVER_JOB_NAME", f"test_{os.getpid()}")
_worker = os.environ.get("PYTEST_XDIST_WORKER")
os.environ["DLROVER_JOB_NAME"] = f"{_job}_{_worker}" if _worker else _job

import pytest  # noqa: E402


@pytest.fixture()
def tmp_ipc_dir(tmp_path, monkeypatch):
    import dlrover_tpu.common.multi_process as mp

    monkeypatch.setattr(mp, "SOCKET_TMP_DIR", str(tmp_path / "sockets"))
    return tmp_path
