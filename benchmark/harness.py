"""What every driver of the benchmark shares: the checkout's paths, the
children's environment, the event file, stopping a child by its saved
pid. Imports no JAX: the parent of a run never holds the chip.
"""

import glob
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WORK = os.path.join(ROOT, ".bench_work")  # git-ignored, made anew each run
KEEP = os.path.join(ROOT, "chiprun_out", "benchmark")  # what outlives a chip call


class RunFailed(Exception):
    """The run cannot give a result: no result line, exit code 1."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on it, nested groups merged key by key
    (a file's ``rehearsal`` override)."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def cache_dir() -> str:
    """The compile cache's one place (PR 21's rule): the caller's
    ``JAX_COMPILATION_CACHE_DIR``, else a fixed directory in the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_compile_cache"
    )


def child_env(platform: str, **extra) -> dict:
    """Environment of a child that may touch the chip. ``platform`` is
    pinned: a child that cannot reach it raises instead of carrying on
    on the host."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)  # the driver's own; nothing here reads it
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = platform
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir()
    env.setdefault("TPU_LOG_DIR", "disabled")
    env.update({k: str(v) for k, v in extra.items()})
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def stop(proc: subprocess.Popen, grace_s: float = 20.0) -> None:
    """Stop a child we started, its whole session, and wait for it."""
    if proc.poll() is not None:
        return
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(grace_s)
            return
        except subprocess.TimeoutExpired:
            continue
    proc.wait(grace_s)


def free_job_shm(job: str) -> None:
    """A flash checkpoint outlives its agent by design; nobody comes
    back for a benchmark run's."""
    for path in glob.glob(f"/dev/shm/dlrover_{job}_*"):
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass


def read_events(path: str) -> list:
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def tail(path: str, n: int = 40) -> str:
    if not os.path.exists(path):
        return ""
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def dump_logs(log_dir: str) -> None:
    for path in sorted(glob.glob(os.path.join(log_dir, "*"))):
        if os.path.isfile(path):
            sys.stderr.write(f"--- {path}\n{tail(path)}\n")


LOG_ENDINGS = (".log", ".jsonl")  # the drivers' own: tpurun.log, serve.log, events.jsonl, requests.jsonl


def failure_report(work: str, reason: str) -> str:
    """``<work>/FAILED.txt``: why the run gave no result, then the tail of
    every log under its work directory (a file with a log's ending, or
    anything under a ``logs`` directory): what ``keep`` takes to where the
    chip tool brings it back, since the work directory is removed."""
    parts = [f"FAILED: {reason}\n"]
    for top, dirs, files in os.walk(work):
        dirs.sort()
        for name in sorted(files):
            path = os.path.join(top, name)
            in_logs = "logs" in os.path.relpath(path, work).split(os.sep)[:-1]
            if name != "FAILED.txt" and (in_logs or name.endswith(LOG_ENDINGS)):
                parts.append(f"--- {os.path.relpath(path, work)}\n{tail(path)}\n")
    out = os.path.join(work, "FAILED.txt")
    with open(out, "w") as f:
        f.write("".join(parts))
    return out


def keep(paths, sub: str) -> None:
    """Copy small records of a run where the chip tool brings them back."""
    dst = os.path.join(KEEP, sub)
    os.makedirs(dst, exist_ok=True)
    for path in paths:
        if os.path.isfile(path) and os.path.getsize(path) < 48 << 20:
            shutil.copy(path, dst)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list (q in 0..100)."""
    s = sorted(values)
    return s[max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))]
