"""PJRT C-API interposition — ground-truth device activity.

Python side of ``native/pjrt_interposer`` (see its README): the
interposer is a PJRT *plugin* whose ``GetPjrtApi()`` loads the real
plugin and patches Execute / H2D / D2H / Compile with timing wrappers
feeding the tpu_timer core. The reference gets the same ground truth by
LD_PRELOAD-ing CUDA symbol hooks (xpu_timer/nvidia/hook.cc:54,323);
on TPU the stable driver boundary is the PJRT function table.

Usage on real TPU — BEFORE the first ``import jax``::

    from dlrover_tpu.profiler import pjrt
    pjrt.enable_tpu_interposition()   # sets TPU_LIBRARY_PATH
    import jax                        # loads the interposer as libtpu

After that every jitted execution, transfer, and compile the process
performs shows up in the interposer's Prometheus ``/metrics`` and the
trace ring with no Python annotations, and
:func:`stall_verdict` distinguishes a wedged device program from a
stalled host loop (launch-vs-completion split).
"""

import ctypes
import os
import threading
from typing import Dict, Optional

from ..common.log import logger
from .native import build_native_lib

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "pjrt_interposer",
)
_LIB_NAME = "libpjrt_interposer.so"

_lib: Optional[ctypes.CDLL] = None
_lib_lock = threading.Lock()

# Verdicts from tt_stall_verdict (tpu_timer.h)
STALL_NONE = 0
STALL_DEVICE = 1
STALL_HOST = 2


def build_interposer() -> str:
    """Build (if stale) and return the interposer .so path."""
    tt_dir = os.path.join(os.path.dirname(_NATIVE_DIR), "tpu_timer")
    sources = [
        os.path.join(_NATIVE_DIR, "pjrt_interposer.cc"),
        os.path.join(_NATIVE_DIR, "pjrt_c_api.h"),
        os.path.join(tt_dir, "tpu_timer.cc"),
        os.path.join(tt_dir, "tpu_timer.h"),
    ]
    return build_native_lib(_NATIVE_DIR, _LIB_NAME, sources)


def find_real_libtpu() -> Optional[str]:
    try:
        import libtpu  # type: ignore

        path = os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so")
        if os.path.exists(path):
            return path
    except ImportError:
        pass
    # No importable package: scan the site dirs for the wheel's payload.
    import site

    site_dirs = list(getattr(site, "getsitepackages", lambda: [])())
    user_site = getattr(site, "getusersitepackages", lambda: None)()
    if user_site:
        site_dirs.append(user_site)
    for d in site_dirs:
        path = os.path.join(d, "libtpu", "libtpu.so")
        if os.path.exists(path):
            return path
    return None


def prepare_worker_profiling_env(
    real_plugin: Optional[str] = None, port: int = 0
) -> Optional[Dict[str, str]]:
    """Env contract that makes a CHILD process load the interposer.

    This is the agent-side product wiring (reference preloads hooks into
    every trainer via ``xpu_timer_launch`` and auto-registers the metric
    collector, ``diagnosis_agent.py:85``): the agent injects these vars
    into the worker env BEFORE spawning it, so the moment the worker's
    jax initializes the TPU backend it reads ``TPU_LIBRARY_PATH`` and
    loads the interposer — zero user code. The agent keeps the returned
    ``DLROVER_TT_PORT`` to scrape ``127.0.0.1:<port>/metrics``.

    Returns None (profiling unavailable) when no real plugin exists or
    the interposer does not build; both are logged, never raised — a
    missing profiler must not take down training.
    """
    explicit = real_plugin or os.environ.get("DLROVER_PJRT_REAL_PLUGIN")
    real = explicit or find_real_libtpu()
    if real is None:
        logger.warning(
            "profiling disabled: no libtpu.so found "
            "(set DLROVER_PJRT_REAL_PLUGIN to override)"
        )
        return None
    try:
        lib = build_interposer()
    except Exception as e:  # noqa: BLE001 — toolchain may be absent
        logger.warning("profiling disabled: interposer build failed: %s", e)
        return None
    if port <= 0:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
    return {
        "DLROVER_PJRT_REAL_PLUGIN": real,
        "DLROVER_TT_PORT": str(port),
        # Both spellings are honored across libtpu loaders.
        "TPU_LIBRARY_PATH": lib,
        "PJRT_TPU_LIBRARY_PATH": lib,
    }


def enable_tpu_interposition(
    real_plugin: Optional[str] = None, metrics_port: int = 0
) -> str:
    """Point the TPU runtime at the interposer. Call BEFORE importing
    jax — the plugin path is read at backend initialization.

    Returns the interposer path. Raises if no real plugin is found.
    """
    import sys

    if "jax" in sys.modules:
        logger.warning(
            "enable_tpu_interposition called after jax import; the TPU "
            "backend may already be initialized without the interposer"
        )
    real = real_plugin or find_real_libtpu()
    if real is None:
        raise FileNotFoundError(
            "no libtpu.so found; pass real_plugin= explicitly"
        )
    lib = build_interposer()
    os.environ["DLROVER_PJRT_REAL_PLUGIN"] = real
    os.environ["DLROVER_TT_PORT"] = str(metrics_port)
    # Both spellings are honored across libtpu loaders.
    os.environ["TPU_LIBRARY_PATH"] = lib
    os.environ["PJRT_TPU_LIBRARY_PATH"] = lib
    logger.info("TPU PJRT interposition enabled: %s -> %s", lib, real)
    return lib


def _load() -> ctypes.CDLL:
    """Bind to the interposer library. When jax already dlopened it as
    the TPU plugin, this returns the SAME loaded module (dlopen
    refcounts by path), so the tt_* state read here is the live one."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(build_interposer())
        lib.tt_http_port.restype = ctypes.c_int
        lib.tt_metrics_text.restype = ctypes.c_int64
        lib.tt_metrics_text.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.tt_stall_verdict.restype = ctypes.c_int
        lib.tt_device_inflight.restype = ctypes.c_int64
        lib.tt_last_device_complete_age_s.restype = ctypes.c_double
        lib.tt_step_begin.argtypes = [ctypes.c_int64]
        lib.tt_step_end.argtypes = [ctypes.c_int64]
        _lib = lib
        return _lib


def ensure_core(port: int = 0) -> int:
    """Initialize the tt core (metrics server) if nothing did yet —
    idempotent: in an interposed process the plugin already called
    tt_init at load and this returns the live port. Lets UNinterposed
    workers (CPU accelerator) still serve step progress for the agent's
    scraper. Returns the serving port (-1 on failure)."""
    lib = _load()
    lib.tt_init.argtypes = [ctypes.c_int]
    lib.tt_init.restype = ctypes.c_int
    return int(lib.tt_init(port))


def dump_timeline(path: str) -> int:
    """Dump the live trace ring (device executes/transfers/compiles the
    interposer recorded) to ``path`` in the compact binary format, with
    the interned-name sidecar at ``path + '.names'``. Returns the event
    count. Convert/merge with ``dlrover_tpu.profiler.timeline``."""
    lib = _load()
    lib.tt_dump_timeline.restype = ctypes.c_int64
    lib.tt_dump_timeline.argtypes = [ctypes.c_char_p]
    lib.tt_dump_names.restype = ctypes.c_int64
    lib.tt_dump_names.argtypes = [ctypes.c_char_p]
    n = int(lib.tt_dump_timeline(path.encode()))
    if n > 0:
        lib.tt_dump_names((path + ".names").encode())
    return n


def step_begin(step: int) -> None:
    """Mark a train-step boundary in the live interposer (feeds
    tpu_timer_last_step / step_open_seconds — the hang watchdog's
    host-progress signal)."""
    _load().tt_step_begin(step)


def step_end(step: int) -> None:
    _load().tt_step_end(step)


def metrics_text() -> str:
    buf = ctypes.create_string_buffer(1 << 20)
    n = _load().tt_metrics_text(buf, len(buf))
    return buf.raw[:n].decode(errors="replace")


def metrics_port() -> int:
    return int(_load().tt_http_port())


def stall_verdict() -> int:
    """STALL_NONE / STALL_DEVICE / STALL_HOST (see tpu_timer.h)."""
    return int(_load().tt_stall_verdict())


def device_inflight() -> int:
    return int(_load().tt_device_inflight())


def last_device_complete_age_s() -> float:
    return float(_load().tt_last_device_complete_age_s())


def parse_metrics(text: str) -> Dict[str, float]:
    """Flat {metric{labels}: value} map from Prometheus exposition text."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out
